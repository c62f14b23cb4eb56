"""Training driver: end-to-end LM training with compressed gradient consensus.

Runs on whatever devices exist, on a 1×1 mesh unless given one: on a TPU
the codec runs as compiled Pallas kernels; on a CPU the collectives
degenerate but the full codec path — FWHT embedding, R-bit pack, decode,
error feedback, optimizer — executes exactly through the jnp reference.

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
      --steps 50 --batch 8 --seq 128 --bits 4

For the ~100M-scale end-to-end deliverable see examples/train_lm.py, which
drives this module with a fixed recipe.
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax

from repro import configs, obs
from repro.checkpoint import save_checkpoint
from repro.data import batch_for_shape
from repro.dist import step as step_lib
from repro.dist.gradcomp import GradCompConfig, wire_bytes_tree
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optimizer import adamw, warmup_cosine


def _step_annotation(step: int):
    """Marks one loop step in a profiler trace while a `repro.obs` session
    is active, so each idle gap of the device falls under a step."""
    if not obs.enabled():
        return contextlib.nullcontext()
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


def train(cfg, *, steps: int, batch_size: int, seq_len: int,
          gc: GradCompConfig, lr: float = 3e-4, log_every: int = 10,
          ckpt_dir: str | None = None, mesh=None, seed: int = 0):
    """Train for `steps` steps on `mesh` (default: one device).

    Returns (params, per-step losses, per-step wall seconds); each step's
    time ends when its outputs are ready, and the first one includes the
    trace and compile. With a `repro.obs` session active, each step is a
    profiler step, with spans `train.batch`, `train.step` (the dispatch;
    `dist.step` nests inside it) and `train.wait` (until the outputs are
    ready), and the checkpoint is a `train.checkpoint` span."""
    mesh = mesh or make_host_mesh(data=1, model=1)
    opt = adamw(warmup_cosine(lr, max(steps // 20, 1), steps),
                weight_decay=0.1)
    tstep = step_lib.make_train_step(cfg, opt, gc, mesh, clip_norm=1.0)
    params, opt_state, ef = step_lib.init_train_state(
        cfg, opt, gc, mesh, jax.random.key(seed))

    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model={cfg.name} params={n_params/1e6:.1f}M "
          f"workers={step_lib.num_workers(mesh)} strategy={gc.strategy} "
          f"R={gc.effective_bits if gc.compresses else 32} bits/dim")
    if gc.compresses:
        audit = wire_bytes_tree(params, gc, step_lib.num_workers(mesh))
        print(f"wire audit: f32={audit['f32_bytes']/2**20:.1f}MiB → "
              f"payload={audit['payload_bytes']/2**20:.1f}MiB "
              f"({audit['compression_x']:.1f}× smaller)")
    else:
        print("wire audit: uncompressed f32 all-reduce (psum)")

    losses, step_seconds = [], []
    t0 = time.perf_counter()
    for step in range(steps):
        t_step = time.perf_counter()
        with _step_annotation(step):
            with obs.span("train.batch"):
                batch = batch_for_shape(cfg, batch_size, seq_len, step, seed)
            with obs.span("train.step"):
                params, opt_state, ef, metrics = tstep(params, opt_state, ef,
                                                       batch)
            with obs.span("train.wait"):
                jax.block_until_ready((params, opt_state, ef, metrics))
        step_seconds.append(time.perf_counter() - t_step)
        losses.append(float(metrics["loss"]))
        if step % log_every == 0 or step == steps - 1:
            dt = time.perf_counter() - t0
            print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"({dt:.1f}s)", flush=True)
    if ckpt_dir:
        with obs.span("train.checkpoint"):
            path = save_checkpoint(ckpt_dir, steps, {"params": params,
                                                     "opt_state": opt_state})
        print(f"checkpoint → {path}")
    return params, losses, step_seconds


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="yi-6b", choices=configs.ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--bits", type=int, default=4, choices=(1, 2, 4, 8))
    ap.add_argument("--strategy", default="allgather_packed",
                    choices=("psum", "psum_decoded", "allgather_packed"))
    ap.add_argument("--keep-fraction", type=float, default=1.0,
                    help="chunk keep rate: R_eff = bits × keep (< 1 is the "
                         "paper's sub-linear regime)")
    ap.add_argument("--dithered", action="store_true",
                    help="unbiased dithered codec — drops the params-sized "
                         "error-feedback state")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get(args.arch))
    gc = GradCompConfig(bits=args.bits, strategy=args.strategy,
                        keep_fraction=args.keep_fraction,
                        dithered=args.dithered,
                        error_feedback=not args.dithered)
    train(cfg, steps=args.steps, batch_size=args.batch, seq_len=args.seq,
          gc=gc, lr=args.lr, ckpt_dir=args.ckpt_dir)


if __name__ == "__main__":
    main()
