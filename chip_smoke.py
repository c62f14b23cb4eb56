"""Smoke test of the compressed-consensus train step on TPU chips.

    python chip_smoke.py              # one chip: phases (a)–(d)
    python chip_smoke.py --chips 4    # four chips: the multi-worker phase only

One chip:
  (a) fail unless JAX's first device is a TPU (no CPU fallback);
  (b) train xlstm-350m at its published widths through
      `repro.launch.train.train` — 2 warm-up steps, then 6 — with the
      packed all-gather consensus (`allgather_packed`, 4 bits, error
      feedback), and again with the exact `psum` all-reduce from the same
      params and batches; all losses finite, first-step losses equal;
  (c) fail on any codec kernel dispatch that took the jnp reference;
  (d) compare the Pallas encode payloads with the jnp reference, bitwise,
      at the embedding leaf and one block leaf, bits 1, 4 and 8.

Four chips: a ("data", "model") = (4, 1) mesh with 8 sequences per
worker; `allgather_packed` (4 bits, EF) against `psum` through the same
entry point, then 2 ZeRO-1 (`alltoall_zero1`) steps compared bitwise with
2 `allgather_packed` steps on the same per-worker gradients.

Everything runs in this one process. Details go to earlier lines; the last
line of stdout is one JSON object, {"ok": true, "device": {...}}, printed
only when every phase passed. Exits non-zero on any failure.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "xlstm-350m"
SEQ = 256
PER_WORKER_BATCH = 8
WARMUP, TIMED = 2, 6


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def count_cache_events() -> dict:
    """Count persistent-compilation-cache hits and misses from now on."""
    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listener(event, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listener)
    return counts


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def dispatch_counts(session) -> dict:
    """{"op/path": count} of the `kernels.dispatch` counters of a session."""
    out = {}
    for ev in session.memory_events():
        if ev.get("type") == "counter" and ev["name"] == "kernels.dispatch":
            key = f"{ev['attrs']['op']}/{ev['attrs']['path']}"
            out[key] = out.get(key, 0) + int(ev["value"])
    return out


def run_train(cfg, mesh, gc, batch, seed):
    from repro.launch.train import train
    params, losses, secs = train(cfg, steps=WARMUP + TIMED, batch_size=batch,
                                 seq_len=SEQ, gc=gc, mesh=mesh, seed=seed,
                                 log_every=1)
    check(all(math.isfinite(v) for v in losses),
          f"{gc.strategy}: non-finite loss in {losses}")
    timed = secs[WARMUP:]
    log(f"[{gc.strategy}] losses {losses}")
    log(f"[{gc.strategy}] first step (trace + compile + run) {secs[0]!r} s; "
        f"after warm-up {sum(timed) / len(timed)!r} s/step "
        f"(steps {timed!r})")
    return params, losses


def compare_first_loss(compressed, exact):
    """Same params and batch, so the same loss — up to the forward's own
    rounding: a TPU runs f32 matmuls as one bf16 pass, and the two train
    programs may fuse the forward differently. On a v5e this xlstm-350m
    loss moves by 1.2e-4 relative between default and highest matmul
    precision; the bound is twice that."""
    gap = abs(compressed[0] - exact[0])
    log(f"first-step loss: allgather_packed {compressed[0]!r} "
        f"psum {exact[0]!r} |gap| {gap!r}")
    check(gap <= 2.4e-4 * abs(exact[0]),
          f"first-step loss differs from psum by {gap}")


def phase_train_one_chip(cfg, mesh, seed):
    from repro import obs
    from repro.dist.gradcomp import GradCompConfig

    device = jax.devices()[0]
    gc = GradCompConfig(bits=4, strategy="allgather_packed")
    session = obs.enable(costs=False)
    try:
        params, compressed = run_train(cfg, mesh, gc, PER_WORKER_BATCH, seed)
    finally:
        obs.disable()
    n_params = sum(x.size for x in jax.tree.leaves(params))
    del params
    log(f"params {n_params}; peak_bytes_in_use after allgather_packed "
        f"{peak_bytes(device)}")

    counts = dispatch_counts(session)
    log(f"kernels.dispatch {json.dumps(counts, sort_keys=True)}")
    check(counts, "no codec kernel was dispatched")
    refs = {k: v for k, v in counts.items() if k.endswith("/ref")}
    check(not refs, f"reference dispatches on the chip: {refs}")

    _, exact = run_train(cfg, mesh, GradCompConfig(strategy="psum"),
                         PER_WORKER_BATCH, seed)
    log(f"peak_bytes_in_use after psum {peak_bytes(device)}")
    compare_first_loss(compressed, exact)


def phase_payloads(cfg, seed):
    """Pallas vs jnp reference encode payloads, bitwise, on the chip."""
    import jax.numpy as jnp
    from repro.dist import gradcomp as G
    from repro.kernels import quantencode, quantpack, ref
    from repro.models import model as model_lib

    shapes = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.key(0), cfg))
    leaves = [(jax.tree_util.keystr(p), x) for p, x
              in jax.tree_util.tree_leaves_with_path(shapes)]
    def size(i):
        return leaves[i][1].size

    in_blocks = [name.startswith("['blocks']") for name, _ in leaves]
    embed = max((i for i, b in enumerate(in_blocks) if not b), key=size)
    block = max((i for i, b in enumerate(in_blocks) if b), key=size)

    def bits_of(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32)

    def mismatches(a, b):
        return int(jnp.sum(bits_of(a) != bits_of(b)))

    ref_encode = jax.jit(ref.encode, static_argnames="bits")
    ref_unpack = jax.jit(ref.unpack_dequant, static_argnames=("bits", "n"))
    for i in (embed, block):
        name, shape = leaves[i]
        u = jax.random.normal(jax.random.fold_in(jax.random.key(seed), i),
                              shape.shape, jnp.float32)
        for bits in (1, 4, 8):
            gc = G.GradCompConfig(bits=bits)
            chunks = G._to_chunks(u, gc.chunk)
            signs = G._frame_signs(i, gc).astype(jnp.float32)
            want_w, want_s = ref_encode(chunks, signs, bits=bits)
            got_w, got_s = quantencode.encode_pallas(chunks, signs, bits)
            ef_w, ef_s, _ = quantencode.encode_ef_pallas(chunks, signs, bits)
            bad = {"encode.words": mismatches(got_w, want_w),
                   "encode.scale": mismatches(got_s, want_s),
                   "encode_ef.words": mismatches(ef_w, want_w),
                   "encode_ef.scale": mismatches(ef_s, want_s)}
            dec = quantpack.unpack_dequant_pallas(want_w, want_s, bits,
                                                  gc.chunk)
            dec_ref = ref_unpack(want_w, want_s, bits=bits, n=gc.chunk)
            dec_gap = float(jnp.max(jnp.abs(dec - dec_ref)))
            key = f"{name} rows={chunks.shape[0]} bits={bits}"
            log(f"payload {key}: mismatching elements {bad}; "
                f"unpack_dequant max |pallas - ref| {dec_gap!r}")
            check(not any(bad.values()),
                  f"payload {key} differs from ref.encode: {bad}")
            check(dec_gap <= 1e-6 * float(jnp.max(want_s)),
                  f"unpack_dequant {key} off by {dec_gap}")
            del chunks, want_w, want_s, got_w, got_s, ef_w, ef_s, dec, dec_ref
        del u


def phase_four_chips(cfg, seed):
    from repro.dist.gradcomp import GradCompConfig

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, have {len(devices)}")
    mesh = jax.sharding.Mesh(np.asarray(devices[:4]).reshape(4, 1),
                             ("data", "model"))
    batch = 4 * PER_WORKER_BATCH
    gc_a = GradCompConfig(bits=4, strategy="allgather_packed")

    params, compressed = run_train(cfg, mesh, gc_a, batch, seed)
    spans = {len(x.sharding.device_set) for x in jax.tree.leaves(params)}
    log(f"devices per trained param leaf: {sorted(spans)}")
    check(spans == {4}, f"params not replicated over the mesh: {spans}")
    del params
    _, exact = run_train(cfg, mesh, GradCompConfig(strategy="psum"),
                         batch, seed)
    compare_first_loss(compressed, exact)
    compare_zero1(cfg, mesh, gc_a, batch, seed)


def compare_zero1(cfg, mesh, gc_a, batch, seed):
    """ZeRO-1 (`alltoall_zero1`) against `allgather_packed`, bitwise, after
    2 steps from the same init. Both are fed the same per-worker gradients:
    xlstm-350m's own, taken at the initial params on the first two
    batches, through a loss whose gradient is exactly them. What is
    compared is what ZeRO-1 changes — the consensus, the error feedback,
    the sharded optimizer. The model's backward is left out: the TPU
    compiler narrows ZeRO-1's parameter all-gather to bf16 for the
    forward's matmuls and then tiles the fused backward differently from
    the replicated path's, which reorders e.g. the final norm's gradient
    sum over tokens."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.data import batch_for_shape
    from repro.dist import step as step_lib
    from repro.dist import zero as zero_lib
    from repro.dist.gradcomp import GradCompConfig
    from repro.models import model as model_lib
    from repro.optimizer import adamw

    opt = adamw(3e-4, weight_decay=0.1)
    gc_z = GradCompConfig(bits=4, strategy="alltoall_zero1")
    key = jax.random.key(seed)

    def local_grads(params, b):
        g = jax.grad(lambda p: model_lib.loss_fn(cfg, p, b))(params)
        return jax.tree.map(lambda x: x[None], g)

    grads_of = jax.jit(jax.shard_map(
        local_grads, mesh=mesh, in_specs=(P(), P("data")),
        out_specs=P("data"), check_vma=False))
    params = step_lib.init_train_state(cfg, opt, gc_a, mesh, key)[0]
    grads = [grads_of(params, batch_for_shape(cfg, batch, SEQ, s, seed))
             for s in range(2)]
    del params

    def fed_loss(params, g):
        return sum(jnp.sum(p * w[0]) for p, w
                   in zip(jax.tree.leaves(params), jax.tree.leaves(g)))

    astep = step_lib.make_train_step(cfg, opt, gc_a, mesh, loss_fn=fed_loss)
    state = step_lib.init_train_state(cfg, opt, gc_a, mesh, key)
    for g in grads:
        *state, _ = astep(*state, g)
    names = [jax.tree_util.keystr(p) for p, _
             in jax.tree_util.tree_leaves_with_path(state[0])]
    like = jax.eval_shape(lambda: state[0])
    want = [np.asarray(x) for x in jax.tree.leaves(state[0])]
    del state

    zstep = step_lib.make_zero_train_step(cfg, opt, gc_z, mesh,
                                          loss_fn=fed_loss)
    state = step_lib.init_zero_state(cfg, opt, gc_z, mesh, key)
    for g in grads:
        *state, _ = zstep(*state, g)
    treedef, infos = zero_lib.params_meta(like, gc_z, 4)
    got = [np.asarray(o).reshape(-1)[:size].reshape(shape)
           for o, (size, shape, _, _)
           in zip(treedef.flatten_up_to(state[0]), infos)]
    differ = {name: (int(np.sum(a != b)), float(np.max(np.abs(a - b))))
              for name, a, b in zip(names, got, want)
              if not np.array_equal(a, b)}
    log(f"ZeRO-1 vs allgather_packed after 2 steps on the same gradients: "
        f"{len(differ)} of {len(want)} param leaves differ "
        f"(leaf: elements, max |gap|): {differ}")
    check(not differ, f"ZeRO-1 params differ from allgather_packed: {differ}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-worker phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"devices: {device}")
    if device["platform"] != "tpu":
        print(f"FAIL (a): no TPU found, JAX reports {device}", file=sys.stderr)
        return 1

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = count_cache_events()
    log(f"compilation cache: {cache_dir}")
    cfg = configs.get(ARCH)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_four_chips(cfg, args.seed)
        else:
            mesh = jax.sharding.Mesh(np.asarray(devices[:1]).reshape(1, 1),
                                     ("data", "model"))
            phase_train_one_chip(cfg, mesh, args.seed)
            phase_payloads(cfg, args.seed)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    log(f"compilation cache events: {cache}; total {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
