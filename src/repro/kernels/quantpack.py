"""Pallas TPU kernels: fused quantize→bit-pack encoder and unpack→dequant decoder.

The second hot spot of the codec: after the FWHT produces the near-democratic
embedding, each chunk is scaled by 1/‖x‖∞, uniformly quantized to R bits and
bit-packed into int32 words — all inside one VMEM tile, so the intermediate
per-element integer codes never round-trip through HBM. The decoder fuses the
inverse. bits ∈ {1, 2, 4, 8} (packing factor k = 32/bits).

Word layout (planar): a row of N codes packs into W = N/k words, and slot i
(bits [i·bits, (i+1)·bits)) of word w holds the code of element i·W + w.
Packing is then lane-local: shift each code by its slot, OR-fold the row
onto its first W lanes with log₂k lane rolls, keep those lanes. Unpacking
tiles the W words k times along the lanes and shifts each slot back down.
No value ever splits the 128-lane dimension, and all integer work is int32
with logical right shifts (bits=1 uses bit 31). `ref.quantize_pack` /
`ref.unpack_dequant` define the same layout with the same float ops.

Tiling follows the FWHT's rule on the f32 side's length N:
`fwht.block_rows_for` rows per grid step (512 at N = 256, 680 at
N <= 128), each block computed whole in VMEM. Rows are independent, so
each is packed or unpacked exactly as in an 8-row tile. A leaf whose rows
are not a multiple of the block runs a partial last block; a leaf
smaller than one block is one block of all its rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fwht import block_rows_for


def quantize_tile(x: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """(rows, n) float, (rows, 1) scale → (rows, n) int32 codes in [0, 2^bits)."""
    m = 2 ** bits
    normalized = x / jnp.maximum(scale, jnp.finfo(x.dtype).tiny)
    idx = jnp.floor((jnp.clip(normalized, -1.0, 1.0) + 1.0) * (m / 2))
    return jnp.clip(idx, 0, m - 1).astype(jnp.int32)


def pack_tile(idx: jax.Array, bits: int) -> jax.Array:
    """(rows, n) int32 codes → (rows, n·bits/32) int32 planar words."""
    n = idx.shape[1]
    w = n * bits // 32
    lane = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 1)
    t = idx << ((lane // w) * bits)
    s = n // 2
    while s >= w:
        t = t | pltpu.roll(t, n - s, 1)     # t[j] |= t[j + s]
        s //= 2
    return t[:, :w]


def dequantize_codes(idx: jax.Array, bits: int) -> jax.Array:
    """int32 codes → f32 mid-rise levels v_i = -1 + (2i+1)/M (unscaled)."""
    m = 2 ** bits
    return -1.0 + (2.0 * idx.astype(jnp.float32) + 1.0) * (1.0 / m)


def unpack_tile(words: jax.Array, bits: int) -> jax.Array:
    """(rows, W) int32 planar words → (rows, W·32/bits) int32 codes."""
    k = 32 // bits
    w = words.shape[1]
    tiled = pltpu.repeat(words, k, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, tiled.shape, 1)
    return jax.lax.shift_right_logical(tiled, (lane // w) * bits) & (2 ** bits - 1)


def _quantpack_kernel(x_ref, scale_ref, o_ref, *, bits: int):
    o_ref[...] = pack_tile(quantize_tile(x_ref[...], scale_ref[...], bits),
                           bits)


def _unpackdequant_kernel(w_ref, scale_ref, o_ref, *, bits: int):
    values = dequantize_codes(unpack_tile(w_ref[...], bits), bits)
    o_ref[...] = values * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("bits", "block_rows", "interpret"))
def quantize_pack_pallas(x: jax.Array, scale: jax.Array, bits: int,
                         block_rows: int | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """x: (..., N) float, scale: (..., 1) → packed int32 (..., N*bits/32).

    block_rows=None takes `fwht.block_rows_for`; interpret=None infers
    from the backend (compiled on TPU)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if bits not in (1, 2, 4, 8):
        raise ValueError(f"bits must be in {{1,2,4,8}}, got {bits}")
    k = 32 // bits
    n = x.shape[-1]
    if n % k:
        raise ValueError(f"N={n} not divisible by packing factor {k}")
    lead = x.shape[:-1]
    flat_x = x.reshape((-1, n))
    flat_s = jnp.broadcast_to(scale, lead + (1,)).reshape((-1, 1))
    rows = flat_x.shape[0]
    block_rows = min(block_rows or block_rows_for(n, rows), rows)
    out = pl.pallas_call(
        functools.partial(_quantpack_kernel, bits=bits),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, n // k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n // k), jnp.int32),
        interpret=interpret,
    )(flat_x, flat_s)
    return out.reshape(lead + (n // k,))


@functools.partial(jax.jit, static_argnames=("bits", "n", "block_rows", "interpret"))
def unpack_dequant_pallas(words: jax.Array, scale: jax.Array, bits: int, n: int,
                          block_rows: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """words: (..., N*bits/32) int32, scale: (..., 1) → float (..., n).

    block_rows=None takes `fwht.block_rows_for`; interpret=None infers
    from the backend (compiled on TPU)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if bits not in (1, 2, 4, 8):
        raise ValueError(f"bits must be in {{1,2,4,8}}, got {bits}")
    k = 32 // bits
    if n % k:
        raise ValueError(f"N={n} not divisible by packing factor {k}")
    lead = words.shape[:-1]
    flat_w = words.reshape((-1, words.shape[-1]))
    flat_s = jnp.broadcast_to(scale, lead + (1,)).reshape((-1, 1)).astype(jnp.float32)
    rows = flat_w.shape[0]
    block_rows = min(block_rows or block_rows_for(n, rows), rows)
    out = pl.pallas_call(
        functools.partial(_unpackdequant_kernel, bits=bits),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[pl.BlockSpec((block_rows, n // k), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        interpret=interpret,
    )(flat_w, flat_s)
    return out.reshape(lead + (n,))
