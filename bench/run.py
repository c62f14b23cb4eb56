"""Chip benchmark of the compressed-consensus train step: one run of a cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Fails (exit 1, no result) unless JAX's devices are TPUs of a kind listed in
bench/peaks.json, at least as many as the cell asks for. Earlier lines of
standard output say what happened; the last one is the result as one JSON
object. The numbers that decide `correct` are printed, each beside its
limit, as the last lines of standard error and under `checks` in the result.

--mode control runs the bfloat16 reference in the program's place, and
--mode fault:<name> plants a fault of bench/faults.py under the timed path:
both exist to show that `correct` rejects them, and the benchmark's own
runs use neither. A cell whose limits are null (not read yet) is refused
in a plain run; the two modes still print its numbers.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def fail(msg: str) -> int:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def finite(v):
    return v if not isinstance(v, float) or math.isfinite(v) else repr(v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--mode", default="run",
                    help="run | control | fault:<name> (see bench/faults.py)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler's .xplane.pb into this directory")
    args = ap.parse_args(argv)

    from bench import cells
    try:
        cell = cells.workload(args.workload)
        peaks = cells.peaks()
        bench = cells.benchmark()
    except FileNotFoundError as e:
        return fail(f"cannot read the benchmark's files: {e}")
    unset = sorted(k for k, v in cell["limits"].items() if v is None)
    if unset and args.mode == "run":
        return fail(f"cell {args.workload} has no limits set for {unset}: "
                    "its readings are not taken yet")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail("the program (src/repro) is not in this checkout")

    import jax
    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    print(f"devices: {found}", flush=True)
    if found["platform"] != "tpu":
        return fail(f"no TPU: JAX reports {found}")
    if found["kind"] not in peaks:
        return fail(f"device kind {found['kind']!r} is not in bench/peaks.json")
    if found["count"] < cell["chips"]:
        return fail(f"cell {args.workload} needs {cell['chips']} chips, "
                    f"JAX sees {found['count']}")

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compilation cache: {enable_compile_cache()}", flush=True)
    from bench import train_cell
    result = train_cell.run(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_start=T_START, mode=args.mode,
                            bench=bench, keep_trace=args.keep_trace)
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    order = ("correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks")
    line = {k: result[k] for k in order if k in result}
    line["checks"] = {k: {"value": finite(v["value"]), "limit": v["limit"]}
                      for k, v in line["checks"].items()}
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
