"""jit'd public wrappers around the Pallas kernels, with pure-jnp fallback.

Dispatch policy:
  * On TPU: Pallas kernels (compiled).
  * On CPU (this container): the jnp reference — numerically identical and much
    faster than interpret-mode Pallas. Tests exercise the Pallas path explicitly
    with interpret=True to validate the kernels against the reference oracles.
Set REPRO_FORCE_PALLAS=1 to force the (interpret-mode on CPU) Pallas path.
On the forced path a kernel that CANNOT run (e.g. N over the single-tile
VMEM budget) raises instead of silently substituting the reference — a
silent fallback would make "forced Pallas" tests vacuous.

Observability: every dispatch decision increments the
`kernels.dispatch` counter (attrs: op, path "pallas"|"ref", N, forced,
and on the Pallas path block_rows, the rows per grid step that
`fwht.block_rows_for` gives the call's row count)
and a refused forced dispatch increments `kernels.forced_error` BEFORE
raising — so a CI run under REPRO_FORCE_PALLAS=1 can assert "zero
reference-fallback events" from the event stream instead of relying on
the raise alone. These fire at Python call time (i.e. once per trace /
compilation when called under jit, per call when eager), never inside
compiled code, and cost one global load when obs is disabled.

Cost model: each dispatch additionally records the resolved kernel
callable + abstract signature with the active session's cost capture
(`kernels.<op>.<path>` programs, `jit_wrap=True` — the session's
`costs()` snapshot lowers a FRESH never-called jit of the callable, so
the dispatch path itself never gains a jit wrapper or a compile).
Compile-time parameters (bits, n, …) are closed over with
`functools.partial` and keyed into the signature via `static=`.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from repro.kernels import fwht as _fwht_kernel
from repro.kernels import quantencode as _quantencode_kernel
from repro.kernels import quantpack as _quantpack_kernel
from repro.kernels import ref as _ref
from repro.obs import core as obs


def _use_pallas() -> bool:
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return True
    return jax.default_backend() == "tpu"


def _forced() -> bool:
    return os.environ.get("REPRO_FORCE_PALLAS") == "1"


def _rows(x: jax.Array) -> int:
    return math.prod(x.shape[:-1])


def _count_dispatch(op: str, path: str, n, rows: int | None = None) -> None:
    """`rows`: the call's chunk rows, given on the Pallas path only."""
    attrs = {} if rows is None else {
        "block_rows": _fwht_kernel.block_rows_for(int(n), rows)}
    obs.counter("kernels.dispatch", 1, op=op, path=path, n=int(n),
                forced=_forced(), **attrs)


def _count_forced_error(op: str, n) -> None:
    obs.counter("kernels.forced_error", 1, op=op, n=int(n))


def _observe(op: str, path: str, fn, args, kwargs=None, static=None) -> None:
    obs.observe_program_call(f"kernels.{op}.{path}", fn, args, kwargs,
                             static=static, jit_wrap=True)


def fwht(x: jax.Array) -> jax.Array:
    """Normalized Walsh–Hadamard transform along the last axis (power-of-2 len)."""
    if _use_pallas():
        if x.shape[-1] <= _fwht_kernel.MAX_VMEM_N:
            _count_dispatch("fwht", "pallas", x.shape[-1], _rows(x))
            _observe("fwht", "pallas", _fwht_kernel.fwht_pallas, (x,))
            return _fwht_kernel.fwht_pallas(x)
        if _forced():
            _count_forced_error("fwht", x.shape[-1])
            raise ValueError(
                f"REPRO_FORCE_PALLAS=1 but FWHT N={x.shape[-1]} exceeds the "
                f"single-tile VMEM budget {_fwht_kernel.MAX_VMEM_N}; the "
                "forced path refuses to silently fall back to the jnp "
                "reference")
    _count_dispatch("fwht", "ref", x.shape[-1])
    _observe("fwht", "ref", _ref.fwht, (x,))
    return _ref.fwht(x)


def rotate(chunks: jax.Array, signs: jax.Array) -> jax.Array:
    """Apply the randomized-Hadamard frame chunk-wise: H·(D·x) — the
    `transform` stage of `repro.codecs.stages`. Rides the `fwht` dispatch
    (Pallas on TPU, jnp reference on CPU, counters included)."""
    return fwht(chunks * signs)


def unrotate(x: jax.Array, signs: jax.Array) -> jax.Array:
    """Inverse of `rotate` (H orthonormal, D its own inverse): D·(H·x)."""
    return fwht(x) * signs


def quantize_pack(x: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """Fused uniform-quantize + bit-pack to int32 words (bits ∈ {1,2,4,8})."""
    if _use_pallas():
        _count_dispatch("quantize_pack", "pallas", x.shape[-1], _rows(x))
        _observe("quantize_pack", "pallas",
                 functools.partial(_quantpack_kernel.quantize_pack_pallas,
                                   bits=bits),
                 (x, scale), static=("bits", bits))
        return _quantpack_kernel.quantize_pack_pallas(x, scale, bits)
    _count_dispatch("quantize_pack", "ref", x.shape[-1])
    _observe("quantize_pack", "ref",
             functools.partial(_ref.quantize_pack, bits=bits),
             (x, scale), static=("bits", bits))
    return _ref.quantize_pack(x, scale, bits)


def unpack_dequant(words: jax.Array, scale: jax.Array, bits: int, n: int) -> jax.Array:
    """Fused unpack + dequantize (inverse of quantize_pack)."""
    if _use_pallas():
        _count_dispatch("unpack_dequant", "pallas", n, _rows(words))
        _observe("unpack_dequant", "pallas",
                 functools.partial(_quantpack_kernel.unpack_dequant_pallas,
                                   bits=bits, n=n),
                 (words, scale), static=("bits", bits, "n", n))
        return _quantpack_kernel.unpack_dequant_pallas(words, scale, bits, n)
    _count_dispatch("unpack_dequant", "ref", n)
    _observe("unpack_dequant", "ref",
             functools.partial(_ref.unpack_dequant, bits=bits, n=n),
             (words, scale), static=("bits", bits, "n", n))
    return _ref.unpack_dequant(words, scale, bits, n)


def encode(chunks: jax.Array, signs: jax.Array, bits: int, *,
           dither: jax.Array | None = None,
           mask: jax.Array | None = None) -> tuple:
    """Fused codec encode: sign-flip → FWHT → ℓ∞ scale → (dither) →
    quantize+pack → (mask), one VMEM pass on the Pallas path.

    The Pallas kernel's (words, scale) are bit-exact with the composed
    `ref.encode`, so dispatch never changes a wire payload. Falls back to
    the reference when N exceeds the single-tile budget (raising instead
    under REPRO_FORCE_PALLAS=1, like `fwht`)."""
    if _use_pallas():
        if chunks.shape[-1] <= _quantencode_kernel.MAX_VMEM_N:
            _count_dispatch("encode", "pallas", chunks.shape[-1],
                            _rows(chunks))
            _observe("encode", "pallas",
                     functools.partial(_quantencode_kernel.encode_pallas,
                                       bits=bits),
                     (chunks, signs), {"dither": dither, "mask": mask},
                     static=("bits", bits))
            return _quantencode_kernel.encode_pallas(
                chunks, signs, bits, dither=dither, mask=mask)
        if _forced():
            _count_forced_error("encode", chunks.shape[-1])
            raise ValueError(
                f"REPRO_FORCE_PALLAS=1 but encode N={chunks.shape[-1]} "
                f"exceeds the single-tile VMEM budget "
                f"{_quantencode_kernel.MAX_VMEM_N}")
    _count_dispatch("encode", "ref", chunks.shape[-1])
    _observe("encode", "ref",
             functools.partial(_ref.encode, bits=bits),
             (chunks, signs), {"dither": dither, "mask": mask},
             static=("bits", bits))
    return _ref.encode(chunks, signs, bits, dither=dither, mask=mask)


def encode_ef(chunks: jax.Array, signs: jax.Array, bits: int, *,
              dither: jax.Array | None = None,
              mask: jax.Array | None = None,
              rescale: float | None = None,
              residual_dtype=None) -> tuple:
    """`encode` plus the in-tile error-feedback residual u − D(E(u)).

    Same payload contract as `encode`; the residual (local EF state, never
    on the wire) matches `ref.encode_ef` to a few f32 ulp on the Pallas
    path. residual_dtype=None means f32 (no leaf-dtype rounding)."""
    rdt = jnp.float32 if residual_dtype is None else residual_dtype
    if _use_pallas():
        if chunks.shape[-1] <= _quantencode_kernel.MAX_VMEM_N:
            _count_dispatch("encode_ef", "pallas", chunks.shape[-1],
                            _rows(chunks))
            _observe("encode_ef", "pallas",
                     functools.partial(_quantencode_kernel.encode_ef_pallas,
                                       bits=bits, rescale=rescale,
                                       residual_dtype=rdt),
                     (chunks, signs), {"dither": dither, "mask": mask},
                     static=("bits", bits, "rescale", rescale,
                             "rdt", jnp.dtype(rdt).name))
            return _quantencode_kernel.encode_ef_pallas(
                chunks, signs, bits, dither=dither, mask=mask,
                rescale=rescale, residual_dtype=rdt)
        if _forced():
            _count_forced_error("encode_ef", chunks.shape[-1])
            raise ValueError(
                f"REPRO_FORCE_PALLAS=1 but encode N={chunks.shape[-1]} "
                f"exceeds the single-tile VMEM budget "
                f"{_quantencode_kernel.MAX_VMEM_N}")
    _count_dispatch("encode_ef", "ref", chunks.shape[-1])
    _observe("encode_ef", "ref",
             functools.partial(_ref.encode_ef, bits=bits, rescale=rescale,
                               residual_dtype=rdt),
             (chunks, signs), {"dither": dither, "mask": mask},
             static=("bits", bits, "rescale", rescale,
                     "rdt", jnp.dtype(rdt).name))
    return _ref.encode_ef(chunks, signs, bits, dither=dither, mask=mask,
                          rescale=rescale, residual_dtype=rdt)
