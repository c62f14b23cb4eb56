"""End-to-end driver: train an LM with democratically-compressed gradients.

    PYTHONPATH=src python examples/train_lm.py            # ~25M, 300 steps
    PYTHONPATH=src python examples/train_lm.py --full     # ~110M (slower)

This is the deliverable (b) end-to-end run: synthetic Markov token stream →
blockwise-attention transformer → shard_map train step whose gradient
consensus goes through the NDSC codec (FWHT embed → 4-bit pack → all-gather
of PACKED payloads → decode → mean → AdamW), with per-worker error feedback.
On the CPU container the mesh is 1×1; the identical code drives the 16×16 /
2×16×16 production meshes (see repro/launch/dryrun.py).
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse

from repro.dist.gradcomp import GradCompConfig
from repro.launch.train import train
from repro.models.model import ModelConfig, param_count


def small_lm() -> ModelConfig:
    """~25M params: CPU-friendly a-few-minutes run."""
    return ModelConfig(
        name="lm-25m", num_layers=6, d_model=384, num_heads=6,
        num_kv_heads=2, d_ff=1536, vocab_size=2048, block="attn_mlp",
        rope_theta=10000.0, remat=False)


def full_lm() -> ModelConfig:
    """~110M params: the deliverable-scale run (use on real hardware)."""
    return ModelConfig(
        name="lm-110m", num_layers=12, d_model=640, num_heads=10,
        num_kv_heads=2, d_ff=2560, vocab_size=50304, block="attn_mlp",
        rope_theta=10000.0, remat=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    cfg = full_lm() if args.full else small_lm()
    print(f"{cfg.name}: {param_count(cfg)/1e6:.1f}M params")
    gc = GradCompConfig(bits=args.bits, strategy="allgather_packed")
    _, losses, _ = train(cfg, steps=args.steps, batch_size=args.batch,
                         seq_len=args.seq, gc=gc, lr=3e-3, log_every=10,
                         ckpt_dir=args.ckpt_dir)
    print(f"\nloss: {losses[0]:.3f} → {losses[-1]:.3f} "
          f"over {len(losses)} steps (R={args.bits} bits/dim on the wire)")


if __name__ == "__main__":
    main()
