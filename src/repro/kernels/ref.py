"""Pure-jnp reference oracles for the Pallas kernels.

These define the semantics; the Pallas kernels in fwht.py / quantpack.py /
quantencode.py must match them — bitwise for integer wire payloads, to
tolerance for float outputs (tests sweep shapes/dtypes against these).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def fwht(x: jax.Array) -> jax.Array:
    """Normalized fast Walsh–Hadamard transform along the last axis.

    Computes H x with H the N×N Hadamard matrix with entries ±1/√N
    (H = Hᵀ, H Hᵀ = I). N = x.shape[-1] must be a power of 2.
    """
    n = x.shape[-1]
    if n & (n - 1):
        raise ValueError(f"FWHT length {n} is not a power of 2")
    # butterfly h pairs lane j with j ^ h via two rolls and a select — the
    # op sequence of kernels/fwht.fwht_tile, with no (…, 2, h) layout that
    # a TPU would pad out to 128 lanes
    lane = jnp.arange(n, dtype=jnp.int32)
    y = x
    h = 1
    while h < n:
        up = jnp.roll(y, -h, axis=-1)        # up[j] = y[j + h]
        down = jnp.roll(y, h, axis=-1)       # down[j] = y[j − h]
        y = jnp.where((lane & h) == 0, y + up, down - y)
        h *= 2
    scale = jnp.asarray(1.0 / math.sqrt(n), x.dtype)
    return y * scale


def quantize_pack(x: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """Uniform R-bit nearest-neighbour quantize + bit-pack into int32 words.

    x:     (..., N) float; values assumed (softly) within ±scale.
    scale: broadcastable to x[..., :1] — the per-row dynamic range (‖x‖∞).
    bits:  ∈ {1, 2, 4, 8} — levels M = 2^bits on [-1, 1], v_i = -1 + (2i+1)/M.

    Returns int32 words of shape (..., W) with W = N * bits / 32; N must be
    divisible by the packing factor k = 32 // bits. Planar layout: slot i
    (bits [i·bits, (i+1)·bits)) of word w holds the code of element i·W + w.
    """
    if bits not in (1, 2, 4, 8):
        raise ValueError(f"bits must be in {{1,2,4,8}}, got {bits}")
    k = 32 // bits
    n = x.shape[-1]
    if n % k:
        raise ValueError(f"N={n} not divisible by packing factor {k}")
    m = 2 ** bits
    w = n // k
    normalized = x / jnp.maximum(scale, jnp.finfo(x.dtype).tiny)
    # nearest-neighbour index of v_i = -1 + (2i+1)/M; ·M/2 is the exact
    # power-of-two form of ÷(2/M)
    idx = jnp.floor((jnp.clip(normalized, -1.0, 1.0) + 1.0) * (m / 2))
    idx = jnp.clip(idx, 0, m - 1).astype(jnp.int32)
    words = idx[..., :w]
    for i in range(1, k):
        words = words | (idx[..., i * w:(i + 1) * w] << (i * bits))
    return words


def encode(chunks: jax.Array, signs: jax.Array, bits: int, *,
           dither: jax.Array | None = None,
           mask: jax.Array | None = None) -> tuple:
    """Composed-reference codec encode: sign-flip → FWHT → ℓ∞ scale →
    (dither) → quantize+pack → (mask). The fused Pallas kernel in
    quantencode.py must match this BIT-EXACTLY.

    chunks: (..., N) float — the pre-embedding rows (one codec chunk each).
    signs:  (N,) ±1 float  — the diagonal D of the Hadamard frame S = D·H.
    dither: optional (..., N), pre-drawn uniform in [-Δ/2, Δ/2]; added as
            `dither · scale` AFTER the scale reduction (non-subtractive).
    mask:   optional (..., 1) 0/1 float — kept rows; dropped rows emit
            all-zero words and a zero scale (no ghost information).

    Returns (words int32 (..., N·bits/32), scale f32 (..., 1)).
    """
    embedded = fwht(chunks * signs)
    scale = jnp.max(jnp.abs(embedded), axis=-1, keepdims=True)
    if dither is not None:
        embedded = embedded + dither * scale
    words = quantize_pack(embedded, scale, bits)
    if mask is not None:
        words = words * mask.astype(words.dtype)
        scale = scale * mask
    return words, scale


def decode_embedded(words: jax.Array, scale: jax.Array, signs: jax.Array,
                    bits: int, n: int, *, mask: jax.Array | None = None,
                    rescale: float | None = None) -> jax.Array:
    """Composed-reference codec decode back to the ORIGINAL domain:
    unpack+dequant → (mask, 1/keep rescale) → FWHT → sign-flip. Mirrors
    `repro.dist.gradcomp.decode_leaf` on a single chunk block."""
    x_hat = unpack_dequant(words, scale, bits, n)
    if mask is not None:
        x_hat = x_hat * mask
        if rescale is not None:
            x_hat = x_hat / rescale
    return fwht(x_hat) * signs.astype(x_hat.dtype)


def encode_ef(chunks: jax.Array, signs: jax.Array, bits: int, *,
              dither: jax.Array | None = None,
              mask: jax.Array | None = None,
              rescale: float | None = None,
              residual_dtype=jnp.float32) -> tuple:
    """`encode` plus the error-feedback residual u − D(E(u)).

    The residual is what the EF update keeps: the encoder's own payload is
    decoded (through `residual_dtype`, the leaf dtype the eager tree-level
    decode would round through) and subtracted from the input rows.
    Returns (words, scale, residual f32 (..., N))."""
    words, scale = encode(chunks, signs, bits, dither=dither, mask=mask)
    y_hat = decode_embedded(words, scale, signs, bits, chunks.shape[-1],
                            mask=mask, rescale=rescale)
    y_hat = y_hat.astype(residual_dtype).astype(jnp.float32)
    # No fusion fence here: under an enclosing jit XLA may contract the
    # decode's multiply→add chains into the subtract (exactly as it could
    # in the pre-fused decode-then-subtract composition), so the residual
    # is bit-stable only eagerly — the EF contract is tolerance-based.
    return words, scale, chunks.astype(jnp.float32) - y_hat


def quant_decode_attention(q: jax.Array, kw: jax.Array, ks: jax.Array,
                           vw: jax.Array, vs: jax.Array, kv_len: jax.Array,
                           *, bits: int, inv_rotate_v: bool = True
                           ) -> jax.Array:
    """Oracle for kernels/quantdecode.py: dequantize the packed rotated KV
    cache and run exact softmax attention, inverse-rotating V at the end.

    q: (B,K,G,dh) f32 (pre-scaled, rotated basis); kw/vw: (B,C,K,dh·bits/32);
    ks/vs: (B,C,K); kv_len: (B,). Returns (B,K,G,dh)."""
    b, kh, g, dh = q.shape
    c = kw.shape[1]
    kd = unpack_dequant(kw, ks[..., None], bits, dh)      # (B,C,K,dh)
    vd = unpack_dequant(vw, vs[..., None], bits, dh)
    s = jnp.einsum("bkgd,bckd->bkgc", q, kd)
    pos = jnp.arange(c, dtype=jnp.int32)
    s = jnp.where((pos[None, :] < kv_len[:, None])[:, None, None, :],
                  s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgc,bckd->bkgd", p, vd)
    if inv_rotate_v:
        out = fwht(out)
    return out


def unpack_dequant(words: jax.Array, scale: jax.Array, bits: int, n: int,
                   dtype=jnp.float32) -> jax.Array:
    """Inverse of quantize_pack: int32 words → dequantized float (..., n)."""
    if bits not in (1, 2, 4, 8):
        raise ValueError(f"bits must be in {{1,2,4,8}}, got {bits}")
    k = 32 // bits
    m = 2 ** bits
    idx = jnp.concatenate(
        [jax.lax.shift_right_logical(words, i * bits) & (m - 1)
         for i in range(k)], axis=-1)[..., :n]
    values = -1.0 + (2.0 * idx.astype(dtype) + 1.0) * (1.0 / m)
    return values * scale
