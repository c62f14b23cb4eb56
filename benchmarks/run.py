"""Run every paper-table/figure benchmark (CPU-friendly sizes).

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig1b fig2 # subset
  PYTHONPATH=src python -m benchmarks.run fed table1 fig1c --tiny \
      --json BENCH_smoke.json                        # CI smoke lane

Each benchmark module is imported lazily when selected, so one broken module
can't kill the whole runner; failures are reported per benchmark and the run
continues (nonzero exit at the end if anything failed). `--tiny` substitutes
CPU-tiny kwargs for the CI smoke lane; `--json` writes per-benchmark
wall-time + the headline result for the perf-trajectory artifact.

Every benchmark executes inside its own `repro.obs` session, so the --json
payload carries a per-benchmark `obs` summary (span timings, dispatch
counters, recompile counts, and — new in schema v3 — the cost model's
per-program FLOPs/bytes plus per-span roofline attribution) next to the
headline metric, plus a top-level `schema_version` and `env` block
(jax/jaxlib versions, backend, devices, git SHA + dirty flag) that make
payloads comparable across commits and machines. `--obs DIR` additionally
writes `<name>.events.jsonl` and `<name>.trace.json` (Perfetto-loadable)
per benchmark into DIR.

Perf trajectory: `--append-history` folds the run into the append-only
`BENCH_history.jsonl` (see `repro.obs.history`), `--check-regressions`
gates the CURRENT run against the trailing baseline of comparable history
rows BEFORE anything is appended (exit code 2 on a regression;
`--regress-report-only` demotes it to a report, the PR-lane mode), and
`--bless` marks this run as an intentional perf change so the baseline
window restarts here. `--from-json PATH` re-checks/appends an existing
payload without re-running anything; `--repeats N` runs each benchmark N
times (median wall time as `seconds`, all N as `repeat_seconds` — the
sentinel's within-run noise floor).

The multi-pod dry-run HLO table is produced separately by
`python -m repro.launch.dryrun --sweep` (it needs a 512-device process) and
formatted by benchmarks.hlo_report (formerly misnamed benchmarks.roofline;
the measured kernel roofline is the `codec_roofline` benchmark below).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import platform
import sys
import time
import traceback

from repro.obs import core as obs_lib
from repro.obs import history as history_lib
from repro.obs import regress as regress_lib

# Version of the --json payload layout. Bump when records/env/obs keys
# change shape, so the perf-trajectory tooling can branch on it.
# v3: env gains git_sha/git_dirty; records gain repeat_seconds/directions;
# obs summaries gain costs + per-span attrib. Strictly additive over v2 —
# v2 readers (and history.records_from_payload) keep working.
SCHEMA_VERSION = 3

# benchmark name -> module under benchmarks/ exposing run(**kwargs)
ALL = {
    "fed": "fed_heterogeneous",
    "fed_agg": "fed_aggregate_scaling",
    "fed_cohort": "fed_cohort_scaling",
    "fed_mesh": "fed_mesh_scaling",
    "codec_roofline": "codec_roofline",
    "codec_frontier": "codec_frontier",
    "serve_load": "serve_load",
    "table1": "table1_compressors",
    "fig1a": "fig1a_compression_error",
    "fig1b": "fig1b_dgddef_rate",
    "fig1c": "fig1c_timing",
    "fig1d": "fig1d_sparsified_gd",
    "fig2": "fig2_svm",
    "fig3": "fig3_multiworker",
    "appJ": "appJ_frames",
    "appN": "appN_aspect_ratio",
    "lemma4": "lemma4_covering",
    "modelscale": "modelscale_ablation",
    "obs_overhead": "obs_overhead",
}

# --tiny kwargs: small enough for the CI smoke lane, large enough that each
# benchmark's internal assertions still hold
TINY = {
    "fed": dict(m=6, dim=96, rounds=30, chunk=32),
    "fed_agg": dict(m_values=(8, 64), dim=256, reps=3),
    "fed_cohort": dict(m_values=(8, 32), dim=48, per_client=16, rounds=3,
                       adaptive_m=8, adaptive_rounds=25),
    "fed_mesh": dict(m_values=(3, 8), dim=48, per_client=16, rounds=2,
                     chunk=32),
    "codec_roofline": dict(n_values=(128, 512), bits_values=(1, 4),
                           rows=16, reps=1),
    "codec_frontier": dict(n=512, m=160, chunk=32, trials=3, rounds=3,
                           steps=15),
    "serve_load": dict(slots=2, max_seq=64, prefix_len=24, n_requests=16,
                       base_rate=10.0, burst_rate=40.0, burst_period_s=1.0,
                       burst_len_s=0.3, prompt_len=(3, 6),
                       max_new_tokens=(3, 6)),
    "table1": dict(n=256, trials=5),
    "fig1c": dict(dims=(128, 256, 512)),
    "obs_overhead": dict(m=8, dim=48, per_client=16, rounds=30,
                         threshold=0.10),
}


def _git_info() -> tuple:
    """(sha, dirty) of the repo this file lives in; (None, None) when git
    is unavailable (tarball installs, sandboxed CI)."""
    import subprocess
    cwd = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.run(["git", "rev-parse", "HEAD"], cwd=cwd,
                             capture_output=True, text=True, timeout=10)
        if sha.returncode != 0:
            return None, None
        status = subprocess.run(["git", "status", "--porcelain"], cwd=cwd,
                                capture_output=True, text=True, timeout=10)
        dirty = bool(status.stdout.strip()) if status.returncode == 0 \
            else None
        return sha.stdout.strip(), dirty
    except Exception:                              # pragma: no cover
        return None, None


def env_info() -> dict:
    """The environment fingerprint embedded in every --json payload."""
    sha, dirty = _git_info()
    info = {
        "python": platform.python_version(),
        "platform": platform.platform(),
        "repro_force_pallas": os.environ.get("REPRO_FORCE_PALLAS"),
        "git_sha": sha,
        "git_dirty": dirty,
    }
    try:
        import jax
        import jaxlib
        info["jax"] = jax.__version__
        info["jaxlib"] = jaxlib.__version__
        info["backend"] = jax.default_backend()
        devs = jax.devices()
        info["device_kind"] = devs[0].device_kind if devs else None
        info["device_count"] = len(devs)
    except Exception as exc:                       # pragma: no cover
        info["jax"] = None
        info["error"] = repr(exc)
    return info


def _jsonable(obj, depth: int = 0):
    """Best-effort conversion of a benchmark's return value to JSON."""
    if depth > 8:       # deep enough for obs costs: summary → costs →
        return str(obj)  # programs → name → specializations → spec fields
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, dict):
        return {str(k): _jsonable(v, depth + 1) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v, depth + 1) for v in obj[:50]]
    if hasattr(obj, "item") and getattr(obj, "ndim", None) == 0:
        return obj.item()                       # numpy scalar
    if hasattr(obj, "tolist"):
        return _jsonable(obj.tolist(), depth + 1)
    return str(obj)


def run_one(name: str, tiny: bool = False, obs_dir: str = None,
            repeats: int = 1) -> dict:
    """Import + run one benchmark; never raises — failures land in the
    record (`ok`/`error`) so the rest of the run proceeds.

    Each benchmark gets its own obs session; its summary lands in the
    record under "obs". With `obs_dir` the raw events and a Perfetto trace
    are written there as `<name>.events.jsonl` / `<name>.trace.json`.
    `repeats > 1` re-runs the benchmark (same session): `seconds` is the
    median per-repeat wall time, `repeat_seconds` carries every repeat —
    the regression sentinel's within-run noise floor. The headline is the
    last repeat's. A module-level `DIRECTIONS` dict on the benchmark
    ({metric: "lower"|"higher"}) declares which headline metrics the
    sentinel may gate."""
    rec = {"name": name, "ok": False, "seconds": None, "headline": None,
           "error": None, "obs": None, "repeat_seconds": None,
           "directions": None}
    jsonl = trace = None
    if obs_dir is not None:
        os.makedirs(obs_dir, exist_ok=True)
        jsonl = os.path.join(obs_dir, f"{name}.events.jsonl")
        trace = os.path.join(obs_dir, f"{name}.trace.json")
    session = obs_lib.enable(jsonl=jsonl, trace=trace)
    times = []
    try:
        mod = importlib.import_module(f"benchmarks.{ALL[name]}")
        kwargs = TINY.get(name, {}) if tiny else {}
        directions = getattr(mod, "DIRECTIONS", None)
        if isinstance(directions, dict):
            rec["directions"] = dict(directions)
        for rep in range(max(1, repeats)):
            t0 = time.perf_counter()
            with obs_lib.span(f"bench.{name}", tiny=tiny, rep=rep):
                rec["headline"] = _jsonable(mod.run(**kwargs))
            times.append(round(time.perf_counter() - t0, 3))
        rec["ok"] = True
    except Exception:
        rec["error"] = traceback.format_exc(limit=8)
        if not times:
            times = [0.0]
    rec["seconds"] = sorted(times)[len(times) // 2]
    if len(times) > 1:
        rec["repeat_seconds"] = times
    obs_lib.disable()
    rec["obs"] = _jsonable(session.summary())
    return rec


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        prog="benchmarks.run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("names", nargs="*", default=[], metavar="name",
                        help=f"benchmarks to run (default: all) from "
                             f"{', '.join(ALL)}")
    parser.add_argument("--tiny", action="store_true",
                        help="CPU-tiny sizes for the CI smoke lane")
    parser.add_argument("--json", metavar="PATH", default=None,
                        help="write per-benchmark wall-time + headline "
                             "metric to PATH")
    parser.add_argument("--obs", metavar="DIR", default=None,
                        help="write per-benchmark obs artifacts "
                             "(<name>.events.jsonl, <name>.trace.json) "
                             "into DIR")
    parser.add_argument("--repeats", type=int, default=1, metavar="N",
                        help="run each benchmark N times (median seconds; "
                             "per-repeat times feed the sentinel's noise "
                             "floor)")
    parser.add_argument("--history", metavar="PATH",
                        default="BENCH_history.jsonl",
                        help="benchmark history file (default: "
                             "BENCH_history.jsonl)")
    parser.add_argument("--check-regressions", action="store_true",
                        help="gate this run against the trailing baseline "
                             "of comparable history rows (exit code 2 on "
                             "regression)")
    parser.add_argument("--regress-report-only", action="store_true",
                        help="with --check-regressions: print findings but "
                             "keep exit code 0 (PR-lane mode)")
    parser.add_argument("--append-history", action="store_true",
                        help="append this run's records to --history "
                             "(after any regression check)")
    parser.add_argument("--bless", action="store_true",
                        help="mark this run as an intentional perf change "
                             "and append it: the baseline window restarts "
                             "here (implies --append-history)")
    parser.add_argument("--regress-window", type=int, default=8,
                        metavar="K", help="baseline = trimmed mean of the "
                                          "last K comparable runs")
    parser.add_argument("--regress-threshold", type=float, default=0.35,
                        metavar="R", help="relative regression threshold "
                                          "(default 0.35 = 35%%)")
    parser.add_argument("--from-json", metavar="PATH", default=None,
                        help="load an existing --json payload instead of "
                             "running benchmarks (history/regression ops "
                             "only)")
    args = parser.parse_args(argv)
    unknown = [n for n in args.names if n not in ALL]
    if unknown:
        parser.error(f"unknown benchmark(s) {', '.join(unknown)}; "
                     f"choose from {', '.join(ALL)}")

    if args.from_json is not None:
        if args.names:
            parser.error("--from-json replaces running benchmarks; drop "
                         "the benchmark names")
        with open(args.from_json) as f:
            payload = json.load(f)
        records = payload.get("benchmarks", [])
        failed = payload.get("failed", [])
    else:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        names = args.names or list(ALL)
        records = []
        for name in names:
            rec = run_one(name, tiny=args.tiny, obs_dir=args.obs,
                          repeats=args.repeats)
            records.append(rec)
            if rec["ok"]:
                print(f"[{name} done in {rec['seconds']:.1f}s]")
            else:
                print(f"[{name} FAILED after {rec['seconds']:.1f}s]\n"
                      f"{rec['error']}", file=sys.stderr)
        failed = [r["name"] for r in records if not r["ok"]]
        payload = {
            "schema_version": SCHEMA_VERSION,
            "tiny": args.tiny,
            "env": env_info(),
            "total_seconds": round(sum(r["seconds"] for r in records), 3),
            "failed": failed,
            "benchmarks": records,
        }
        if args.json:
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=2)
            print(f"[wrote {args.json}]")

    exit_code = 0
    if failed:
        print(f"[{len(failed)}/{len(records)} benchmarks failed: "
              f"{', '.join(failed)}]", file=sys.stderr)
        exit_code = 1

    if args.check_regressions or args.append_history or args.bless:
        current = history_lib.records_from_payload(payload)
        if args.bless:
            for rec in current:
                rec["blessed"] = True
        if args.check_regressions:
            hist = history_lib.load(args.history)
            if hist.truncated:
                print(f"[warning: {args.history} ended mid-record; using "
                      f"the parsed prefix]", file=sys.stderr)
            result = regress_lib.check(
                hist, current, window=args.regress_window,
                rel_threshold=args.regress_threshold)
            print(regress_lib.render(result))
            if result["findings"] and not args.regress_report_only:
                exit_code = max(exit_code, 2)
        if args.append_history or args.bless:
            n = history_lib.append(args.history, current)
            print(f"[appended {n} record(s) to {args.history}]")
    if exit_code:
        sys.exit(exit_code)


if __name__ == "__main__":
    main()
