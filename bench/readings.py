"""Readings of `correct` over many seeds in one process, to set a cell's limits.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \
        [--mode run|control|fault:<name>] [--out DIR]

For each seed the program (with --mode fault:<name>, with that fault
planted) builds its state from the seed and takes the first three steps, as
a run's set-up does; the reference then follows them, and with --mode
control a second reference a precision below stands in the program's place.
Prints the numbers `correct` compares, and writes them with the per-leaf
readings they come from (norms of the first gradient, of the error feedback
after step 1 and of the change after three steps, and the norm of each
leaf's first-gradient difference) as one JSON line per seed to
DIR/<cell>.<mode>.jsonl. Only the work of setting a limit uses it; the
benchmark's runs never do.
"""
import argparse
import gc as gc_lib
import json
import os
import pathlib
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--mode", default="run")
    ap.add_argument("--out", default=os.path.join(ROOT, "out", "readings"))
    ap.add_argument("--root", default=None,
                    help="a benchmark directory other than bench/")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from bench import cells, train_cell, weights
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compilation cache: {enable_compile_cache()}", flush=True)
    root = pathlib.Path(args.root) if args.root else cells.BENCH
    cell = cells.workload(args.workload, root)
    cfg = cells.config(cell["config"], root)
    devices = jax.devices()[:cell["chips"]]
    fault = (args.mode.split(":", 1)[1] if args.mode.startswith("fault:")
             else None)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.workload}.{args.mode}.jsonl")
    prog = train_cell.Program(cell, cfg, devices, 0, fault)
    names = weights.names(prog.specs[0])
    used = list(prog.mesh.devices.flat)
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            prog.key = weights.seed_key(seed)
            prog.data_seed = np.int32(seed % (1 << 31))
            state, got, tokens, _ = train_cell.setup_steps(prog,
                                                           prog.init_state())
            del state
            gc_lib.collect()
            want = train_cell.reference_readings(cell, cfg, prog.specs[0],
                                                 prog.key, tokens, used)
            if args.mode == "control":
                got = train_cell.reference_readings(
                    cell, cfg, prog.specs[0], prog.key, tokens, used,
                    **train_cell.control_precision(cfg))
            checks = train_cell.compare(got, want, prog.gc.uses_ef)
            diff = [float(np.linalg.norm((np.asarray(a, np.float64)
                                          - np.asarray(b, np.float64)).ravel()))
                    for a, b in zip(got["g"], want["g"])]
            line = {"seed": seed, "mode": args.mode, "checks": checks,
                    "names": names, "grad_diff_norm": diff,
                    "seconds": time.perf_counter() - t0}
            for k in ("loss", "grad", "ef", "update"):
                line[k] = {"got": np.asarray(got[k], np.float64).tolist(),
                           "want": np.asarray(want[k], np.float64).tolist()}
            print(json.dumps({"seed": seed, "checks": checks,
                              "seconds": line["seconds"]}), flush=True)
            with open(path, "a") as f:
                f.write(json.dumps(line) + "\n")
            del got, want
            gc_lib.collect()
    finally:
        prog.undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
