"""Serving driver: batched prefill + greedy decode against explicit caches.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.dist import step as step_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import decode as decode_lib
from repro.models import model as model_lib


def serve(cfg, *, batch: int, prompt_len: int, gen: int, seed: int = 0):
    if not cfg.decode_supported:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode")
    mesh = make_host_mesh(data=1, model=1)
    key = jax.random.key(seed)
    params = model_lib.init_params(key, cfg)
    prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                 (batch, prompt_len), 0, cfg.vocab_size,
                                 jnp.int32)
    max_seq = prompt_len + gen

    t0 = time.time()
    logits, state = jax.jit(
        lambda p, t: decode_lib.prefill(cfg, p, t, max_seq))(params, prompts)
    print(f"prefill[{batch}×{prompt_len}] {time.time()-t0:.2f}s "
          f"(cache_len={decode_lib.cache_len(cfg, max_seq)})")

    sstep = step_lib.make_serve_step(cfg, mesh)
    tok = decode_lib.greedy_token(logits)
    out = [tok]
    t0 = time.time()
    for _ in range(gen - 1):
        logits, state = sstep(params, state, tok)
        tok = decode_lib.greedy_token(logits)
        out.append(tok)
    dt = time.time() - t0
    seqs = jnp.concatenate(out, axis=1)
    print(f"decode {gen-1} steps in {dt:.2f}s "
          f"({(gen-1)*batch/max(dt,1e-9):.1f} tok/s)")
    for b in range(min(batch, 4)):
        print(f"  seq[{b}]: {seqs[b].tolist()}")
    return seqs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="yi-6b", choices=configs.ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)
    enable_compile_cache()
    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get(args.arch))
    serve(cfg, batch=args.batch, prompt_len=args.prompt_len, gen=args.gen)


if __name__ == "__main__":
    main()
