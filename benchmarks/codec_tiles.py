"""Device time of the codec kernels against their rows per grid step.

For one leaf shape (default: Yi-6B's MLP leaf, 4096 x 11008 entries in
chunks of 256, i.e. 176128 rows) and 4 bits, each of the fused encode +
error feedback (`_encode_call`), `unpack_dequant_pallas` and
`fwht_pallas` runs at each `block_rows`; the kernel body computes its
whole tile at once. The device milliseconds per call are the summed
durations of the device's ops in a profiler trace of `--calls` calls,
over the calls; `first_call_s` is the host time of the first call,
compilation included. Every point's outputs are checked against the
8-row tile's: words, scale and FWHT bitwise, the residual to the EF
contract's 4e-6. Against the jnp reference (`kernels.ref`) each point
records `ref_bitwise` (every output but the EF residual equal bit for
bit) and `ref_gap` (the largest difference of any output). `rule` marks
the tile `fwht.block_rows_for` picks. A row count that no tile divides
(`--rows 100003`) runs each kernel's partial last block.

Needs a TPU (it refuses the CPU: its numbers are device times):

  python -m benchmarks.codec_tiles --seed 1 --json out/tiles.json
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import fwht as fwht_kernel
from repro.kernels import quantencode
from repro.kernels import quantpack
from repro.kernels import ref

KERNELS = ("encode_ef", "unpack_dequant", "fwht")
DEVICE_PLANE = re.compile(r"^/device:TPU:0$")


def _inputs(rows: int, n: int, bits: int, seed: int) -> dict:
    kx, ks = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (rows, n), jnp.float32)
    signs = jnp.where(jax.random.bernoulli(ks, 0.5, (n,)), 1.0,
                      -1.0).astype(jnp.float32)
    words, scale = quantencode.encode_pallas(x, signs, bits, block_rows=8)
    return {"x": x, "signs": signs, "words": words, "scale": scale}


def _call(kernel: str, inp: dict, bits: int, n: int, block_rows: int | None):
    if kernel == "encode_ef":
        return lambda: quantencode.encode_ef_pallas(
            inp["x"], inp["signs"], bits, block_rows=block_rows)
    if kernel == "unpack_dequant":
        return lambda: quantpack.unpack_dequant_pallas(
            inp["words"], inp["scale"], bits, n, block_rows=block_rows)
    return lambda: fwht_kernel.fwht_pallas(inp["x"], block_rows=block_rows)


def _ref(kernel: str, inp: dict, bits: int, n: int):
    if kernel == "encode_ef":
        return jax.jit(lambda x, s: ref.encode_ef(x, s, bits))(inp["x"],
                                                              inp["signs"])
    if kernel == "unpack_dequant":
        return jax.jit(lambda w, s: ref.unpack_dequant(w, s, bits, n))(
            inp["words"], inp["scale"])
    return jax.jit(ref.fwht)(inp["x"])


def _against_ref(kernel: str, got, want) -> tuple[bool, float]:
    """(outputs but the EF residual bitwise equal, largest difference)."""
    got = [np.asarray(a) for a in jax.tree.leaves(got)]
    want = [np.asarray(a) for a in jax.tree.leaves(want)]
    exact = len(got) - 1 if kernel == "encode_ef" else len(got)
    bitwise = all(np.array_equal(g.view(np.int32), w.view(np.int32))
                  for g, w in zip(got[:exact], want[:exact]))
    gap = max(float(np.max(np.abs(g.astype(np.float64) - w)))
              for g, w in zip(got, want))
    return bitwise, gap


def _device_ms(fn, calls: int) -> float:
    """Summed device-op time per call, from a profiler trace."""
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory(dir=os.getcwd()) as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                out = fn()
            jax.block_until_ready(out)
        (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                            recursive=True)
        data = ProfileData.from_file(path)
    total = 0
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    total += sum(ev.duration_ns for ev in line.events)
    return total / calls * 1e-6


def _check(kernel: str, got, want) -> float:
    """Largest residual difference; raises if a payload or FWHT differs."""
    got = [np.asarray(a) for a in jax.tree.leaves(got)]
    want = [np.asarray(a) for a in jax.tree.leaves(want)]
    exact = got[:2] if kernel == "encode_ef" else got
    for g, w in zip(exact, want):
        if not np.array_equal(g.view(np.int32), w.view(np.int32)):
            raise AssertionError(f"{kernel}: output differs from 8-row tile")
    if kernel != "encode_ef":
        return 0.0
    gap = float(np.max(np.abs(got[2] - want[2])))
    if gap > 4e-6:
        raise AssertionError(f"encode_ef: residual differs by {gap}")
    return gap


BLOCK_ROWS = (8, 32, 128, 256, 512, 1024)


def run(rows: int = 176128, n: int = 256, bits: int = 4, seed: int = 1,
        block_rows=BLOCK_ROWS, calls: int = 10) -> dict:
    if jax.default_backend() != "tpu":
        raise SystemExit("codec_tiles measures device time: it needs a TPU")
    inp = _inputs(rows, n, bits, seed)
    rule = fwht_kernel.block_rows_for(n, rows)
    records = []
    for kernel in KERNELS:
        want = jax.block_until_ready(_call(kernel, inp, bits, n, 8)())
        oracle = jax.block_until_ready(_ref(kernel, inp, bits, n))
        for block in block_rows:
            fn = _call(kernel, inp, bits, n, block)
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn())
            first = time.perf_counter() - t0
            gap = _check(kernel, got, want)
            bitwise, ref_gap = _against_ref(kernel, got, oracle)
            rec = {"kernel": kernel, "block_rows": block, "rule": block == rule,
                   "device_ms": _device_ms(fn, calls), "first_call_s": first,
                   "residual_gap": gap, "ref_bitwise": bitwise,
                   "ref_gap": ref_gap}
            records.append(rec)
            print(json.dumps(rec), flush=True)
    dev = jax.devices()[0]
    return {"rows": rows, "n": n, "bits": bits, "seed": seed,
            "calls": calls, "rule_block_rows": rule,
            "device": {"platform": dev.platform,
                       "device_kind": dev.device_kind,
                       "count": jax.device_count()},
            "records": records}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=176128)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--block-rows", type=int, nargs="+",
                    default=list(BLOCK_ROWS))
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    result = run(rows=args.rows, n=args.n, bits=args.bits, seed=args.seed,
                 block_rows=args.block_rows, calls=args.calls)
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
