"""Pallas TPU kernel: the FUSED codec encoder (the NDSC encode hot loop).

Every subsystem's encode path — gradcomp consensus, ZeRO-1, fed cohorts and
the mesh backend — runs sign-flip (D) → FWHT → ℓ∞ scale → (dither) →
uniform quantize → int32 bit-pack on each (C, chunk) block. Composed at the
XLA level those are separate programs with full-precision HBM round-trips
between every stage: the f32 embedding is written out after the FWHT, read
back for the scale reduction, written again after the dither… This kernel
does the whole chain inside one (block_rows, N) VMEM tile, so the f32
embedding NEVER touches HBM — HBM traffic drops to "read y once, write
N·bits/32 words + one f32 scale per row" (gated in
`benchmarks/codec_roofline.py`). Speed is another matter: on a v5e the
step's codec kernels ran at 2.04% of the time that minimum traffic needs
at peak bandwidth with 8-row tiles, and at 24% with the 512-row tiles of
`fwht.block_rows_for` (`codec_roofline`, Yi-6B stage, 4 bits).

A fused error-feedback variant (`encode_ef_pallas`) additionally
dequantizes its own codes in-tile, inverse-rotates, and emits the
EF residual u − D(E(u)) alongside — the DGD-DEF update without a second
pass over the leaf.

Semantics are defined by the composed jnp oracles `ref.encode` /
`ref.encode_ef`. The PAYLOAD contract is strict: (words, scale) are
BIT-EXACT with `ref.encode` (asserted in tests and by the roofline gate) —
deterministically, and on the dithered / sub-linear paths given the same
pre-drawn dither / keep-mask inputs. The stochastic draws happen OUTSIDE
the kernel (in `gradcomp.encode_leaf`, from the same `fold_in`-derived keys
as before), so forcing the Pallas path can never change a payload. The EF
residual is LOCAL state (never on the wire): it matches `ref.encode_ef` to
within a few f32 ulp of the embedding scale — the compiler may contract
the in-tile decode's multiply→add chains into fmas, which tests bound with
a tight tolerance rather than bitwise equality.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fwht import MAX_VMEM_N, block_rows_for, fwht_tile
from repro.kernels.quantpack import (dequantize_codes, pack_tile,
                                     quantize_tile)


def _encode_kernel(*refs, bits: int, n: int, dithered: bool, masked: bool,
                   ef: bool, rescale, residual_dtype):
    """One grid step: encode a (block_rows, n) tile fully in VMEM.

    Operand order (inputs): x, signs, [dither], [mask];
    (outputs): words, scale, [decoded]."""
    it = iter(refs)
    x_ref = next(it)
    signs_ref = next(it)
    dither_ref = next(it) if dithered else None
    mask_ref = next(it) if masked else None
    words_ref = next(it)
    scale_ref = next(it)
    residual_ref = next(it) if ef else None

    u = x_ref[...]                                    # (rows, n) f32 input
    signs = signs_ref[...]                            # (1, n) ±1 f32
    embedded = fwht_tile(u * signs, n)                # x = H·D·u
    scale = jnp.max(jnp.abs(embedded), axis=-1, keepdims=True)
    if dithered:
        embedded = embedded + dither_ref[...] * scale
    codes = quantize_tile(embedded, scale, bits)
    out_scale = scale
    if masked:
        mask = mask_ref[...]                          # (rows, 1) 0/1 f32
        codes = codes * mask.astype(jnp.int32)
        out_scale = scale * mask
    words_ref[...] = pack_tile(codes, bits)
    scale_ref[...] = out_scale

    if ef:
        # decode the tile's OWN (masked) payload in-tile, replaying
        # decode_leaf's op order exactly: dequant → mask → (1/keep rescale)
        # → FWHT → sign-flip → leaf-dtype rounding → subtract. The codes
        # are what unpacking the words would give back (a dropped row's
        # zero words unpack to zero codes), so no unpack is needed. The
        # residual never leaves VMEM un-reduced: u is already resident, so
        # the EF state costs no second pass over the leaf.
        x_hat = dequantize_codes(codes, bits) * out_scale
        if masked:
            x_hat = x_hat * mask_ref[...]
            if rescale is not None:
                x_hat = x_hat / rescale
        y_hat = fwht_tile(x_hat, n) * signs
        y_hat = y_hat.astype(residual_dtype).astype(jnp.float32)
        residual_ref[...] = u - y_hat


@functools.partial(
    jax.jit, static_argnames=("bits", "block_rows", "interpret", "ef",
                              "rescale", "residual_dtype"))
def _encode_call(x, signs, dither, mask, *, bits: int, block_rows: int | None,
                 interpret, ef: bool, rescale, residual_dtype):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = x.shape[-1]
    if n & (n - 1):
        raise ValueError(f"encode length {n} is not a power of 2")
    if n > MAX_VMEM_N:
        raise ValueError(f"N={n} exceeds single-tile VMEM budget {MAX_VMEM_N}")
    if bits not in (1, 2, 4, 8):
        raise ValueError(f"bits must be in {{1,2,4,8}}, got {bits}")
    k = 32 // bits
    if n % k:
        raise ValueError(f"N={n} not divisible by packing factor {k}")
    lead = x.shape[:-1]
    flat = x.astype(jnp.float32).reshape((-1, n))
    rows = flat.shape[0]
    block_rows = min(block_rows or block_rows_for(n, rows), rows)
    signs2d = signs.astype(jnp.float32).reshape((1, n))

    row_spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    inputs = [flat]
    if dither is not None:
        inputs.append(dither.astype(jnp.float32).reshape((-1, n)))
    if mask is not None:
        inputs.append(mask.astype(jnp.float32).reshape((-1, 1)))
    # signs go FIRST after x in the kernel's operand order
    inputs.insert(1, signs2d)
    in_specs = [row_spec, pl.BlockSpec((1, n), lambda i: (0, 0))]
    if dither is not None:
        in_specs.append(row_spec)
    if mask is not None:
        in_specs.append(pl.BlockSpec((block_rows, 1), lambda i: (i, 0)))

    out_shape = [jax.ShapeDtypeStruct((rows, n // k), jnp.int32),
                 jax.ShapeDtypeStruct((rows, 1), jnp.float32)]
    out_specs = [pl.BlockSpec((block_rows, n // k), lambda i: (i, 0)),
                 pl.BlockSpec((block_rows, 1), lambda i: (i, 0))]
    if ef:
        out_shape.append(jax.ShapeDtypeStruct((rows, n), jnp.float32))
        out_specs.append(row_spec)

    kernel = functools.partial(
        _encode_kernel, bits=bits, n=n, dithered=dither is not None,
        masked=mask is not None, ef=ef, rescale=rescale,
        residual_dtype=residual_dtype)
    outs = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)
    words = outs[0].reshape(lead + (n // k,))
    scale = outs[1].reshape(lead + (1,))
    if ef:
        return words, scale, outs[2].reshape(lead + (n,))
    return words, scale


def encode_pallas(chunks: jax.Array, signs: jax.Array, bits: int, *,
                  dither: jax.Array | None = None,
                  mask: jax.Array | None = None,
                  block_rows: int | None = None,
                  interpret: bool | None = None) -> tuple:
    """Fused codec encode — semantics of `ref.encode` in one VMEM pass.

    chunks: (..., N) float rows (N a power of 2, ≤ MAX_VMEM_N, divisible by
    the 32/bits packing factor); signs: (N,) ±1; dither/mask as in
    `ref.encode` (pre-drawn OUTSIDE the kernel). `block_rows=None` takes
    `fwht.block_rows_for`; `interpret=None` infers from the backend
    (compiled on TPU, interpreter elsewhere).
    Returns (words int32 (..., N·bits/32), scale f32 (..., 1)).
    """
    return _encode_call(chunks, signs, dither, mask, bits=bits,
                        block_rows=block_rows, interpret=interpret,
                        ef=False, rescale=None, residual_dtype=jnp.float32)


def encode_ef_pallas(chunks: jax.Array, signs: jax.Array, bits: int, *,
                     dither: jax.Array | None = None,
                     mask: jax.Array | None = None,
                     rescale: float | None = None,
                     residual_dtype=jnp.float32,
                     block_rows: int | None = None,
                     interpret: bool | None = None) -> tuple:
    """Fused encode + error-feedback residual — semantics of `ref.encode_ef`.

    Returns (words, scale, residual f32 (..., N)) where residual is
    u − D(E(u)) with the decode replayed and subtracted in-tile
    (`rescale` = keep_fraction for the dithered-unbiased path, None for
    the contractive EF path; `residual_dtype` = the leaf dtype the eager
    tree-level decode rounds through before the f32 subtract). (words,
    scale) keep the bitwise payload contract; the residual matches
    `ref.encode_ef` to a few f32 ulp of the embedding scale."""
    return _encode_call(chunks, signs, dither, mask, bits=bits,
                        block_rows=block_rows, interpret=interpret,
                        ef=True, rescale=rescale,
                        residual_dtype=jnp.dtype(residual_dtype))
