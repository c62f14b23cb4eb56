"""Pallas TPU kernel: fused dequantize + flash-decode attention.

Beyond-paper extension: the paper's NDSC codec applied to the KV cache.
Decode is bandwidth-bound on reading the cache; storing K/V packed at R bits
(per-position-per-head vectors, Hadamard-rotated then uniformly quantized —
the same democratic trick, so outlier channels don't blow the per-vector
scale) cuts that traffic R/32×. The catch: dequantize-then-attend at the XLA
level re-materializes the f32 cache in HBM and gives the win back. This
kernel fuses unpack→dequant→(FWHT⁻¹ rotation)→online-softmax attention in
VMEM: packed words stream HBM→VMEM once, f32 never touches HBM.

Layout per (batch, kv-head) grid cell, kv blocks iterated on the last grid
dim with VMEM scratch accumulators (classic flash-decode):

  q:       (B, K, G, dh) f32     — grouped queries (GQA-native)
  kw/vw:   (B, C, K, dh·R/32) i32 — packed cache
  ks/vs:   (B, C, K) f32          — per-vector ‖·‖∞ scales
  out:     (B, K, G, dh) f32

The kernel assumes the Hadamard rotation used a FIXED per-head sign vector
(passed in as ±1 f32 (K, dh)); scores against rotated queries are computed
directly in the rotated basis — ⟨q, k⟩ = ⟨Hq', Hk'⟩ = ⟨q', k'⟩, so K is
attended WITHOUT inverse-rotating (orthonormality of H). Only V needs the
inverse transform, applied to the (G, dh) accumulator ONCE at the end —
O(G·dh·log dh) instead of O(C·dh·log dh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fwht import fwht_tile
from repro.kernels.quantpack import dequantize_codes, unpack_tile


DEFAULT_BLOCK_C = 512


def _unpack_block(words: jax.Array, bits: int) -> jax.Array:
    """(bc, dh·bits/32) i32 planar words (see quantpack.py) → (bc, dh) f32
    in [-1, 1) mid-rise levels."""
    return dequantize_codes(unpack_tile(words, bits), bits)


def _qdecode_kernel(q_ref, kw_ref, ks_ref, vw_ref, vs_ref, len_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, bits: int, dh: int,
                    block_c: int, num_blocks: int, inv_rotate_v: bool):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                   # (G, dh) — pre-scaled
    kd = _unpack_block(kw_ref[0], bits) * ks_ref[0][:, None]  # (bc, dh)
    s = q @ kd.T                                      # (G, bc)
    pos = ic * block_c + jnp.arange(block_c, dtype=jnp.int32)
    valid = pos < len_ref[0]
    s = jnp.where(valid[None, :], s, -1e30)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])                   # (G, bc)
    corr = jnp.exp(m_prev - m_new)
    vd = _unpack_block(vw_ref[0], bits) * vs_ref[0][:, None]
    acc_ref[...] = acc_ref[...] * corr[:, None] + p @ vd
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)

    @pl.when(ic == num_blocks - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        if inv_rotate_v:
            out = fwht_tile(out, dh)                  # H is its own inverse
        o_ref[0, 0] = out


@functools.partial(jax.jit, static_argnames=("bits", "block_c", "interpret",
                                             "inv_rotate_v"))
def quant_decode_attention_pallas(q: jax.Array, kw: jax.Array, ks: jax.Array,
                                  vw: jax.Array, vs: jax.Array,
                                  kv_len: jax.Array, *, bits: int,
                                  block_c: int = DEFAULT_BLOCK_C,
                                  inv_rotate_v: bool = True,
                                  interpret: bool | None = None) -> jax.Array:
    """q: (B,K,G,dh) f32 (already ·dh^-1/4-scaled & rotated);
    kw/vw: (B,C,K,dh·bits/32) i32; ks/vs: (B,C,K) f32; kv_len: (B,) i32.
    Returns (B, K, G, dh) f32 attention output (V un-rotated).
    interpret=None infers from the backend (compiled on TPU)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, kh, g, dh = q.shape
    c = kw.shape[1]
    if c % block_c:
        raise ValueError(f"cache length {c} not divisible by {block_c}")
    nb = c // block_c
    wpv = kw.shape[-1]
    # (B, C, K, w) → (B, K, C, w) so the grid cell slices are contiguous
    kw_t = kw.transpose(0, 2, 1, 3)
    vw_t = vw.transpose(0, 2, 1, 3)
    ks_t = ks.transpose(0, 2, 1)
    vs_t = vs.transpose(0, 2, 1)

    kernel = functools.partial(
        _qdecode_kernel, bits=bits, dh=dh, block_c=block_c, num_blocks=nb,
        inv_rotate_v=inv_rotate_v)
    out = pl.pallas_call(
        kernel,
        grid=(b, kh, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda ib, ik, ic: (ib, ik, 0, 0)),
            pl.BlockSpec((1, block_c, wpv), lambda ib, ik, ic: (ib * kh + ik,
                                                                ic, 0)),
            pl.BlockSpec((1, block_c), lambda ib, ik, ic: (ib * kh + ik, ic)),
            pl.BlockSpec((1, block_c, wpv), lambda ib, ik, ic: (ib * kh + ik,
                                                                ic, 0)),
            pl.BlockSpec((1, block_c), lambda ib, ik, ic: (ib * kh + ik, ic)),
            pl.BlockSpec((1,), lambda ib, ik, ic: (ib,)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda ib, ik, ic: (ib, ik, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g, dh), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
        interpret=interpret,
    )(q, kw_t.reshape(b * kh, c, wpv), ks_t.reshape(b * kh, c),
      vw_t.reshape(b * kh, c, wpv), vs_t.reshape(b * kh, c), kv_len)
    return out
