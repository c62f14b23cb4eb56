"""Chunked NDSC gradient codec — the fused stage implementation behind the
`repro.codecs` NDSC pipeline (paper §3 at model scale).

This module IS the `hadamard + chunk_drop + uniform/dithered + int32`
combination of `repro.codecs.stages`: the Pipeline delegates its leaf
encode/decode (and the fused encode+EF residual) here rather than
re-composing the stages, which is what keeps registry-built NDSC codecs
bit-identical with the historical gradcomp path and keeps the whole chain
on the single fused Pallas kernel.

Each parameter leaf is flattened, zero-padded to a multiple of `chunk`
(a power of two) and embedded chunk-wise with a randomized Hadamard frame
S = D·H from `core.frames` — the near-democratic embedding that flattens
the per-chunk dynamic range so a single ‖x‖∞ scale + uniform R-bit
quantization achieves the Thm. 1 error 2^(2−R)·√log(2·chunk) per chunk.
The whole encode chain runs as ONE fused Pallas kernel
(`kernels.quantencode` via `kernels.ops.encode`) — sign flip, FWHT, scale,
dither, quantize and int32 bit-pack in a single VMEM pass; its packed-word
output is also the exact wire format audited by `wire_bytes_tree`.

Shared randomness: the frame for leaf i is a pure function of
(cfg.seed, i) — every worker builds the same frame, so gathered payloads
decode identically everywhere (and the ZeRO-1 all-to-all path in
`repro.dist.zero` stays bit-exact with the all-gather consensus). The
stochastic parts (non-subtractive dither, sub-linear chunk keep-mask) fold
in `round_idx` so they refresh every step but still agree across workers.

Wire format per leaf (the payload dict):
  words  int32 (C, chunk·bits/32) — bit-packed codes
  scale  f32   (C, 1)             — per-chunk ‖x‖∞ (the paper's O(1) bits)
  mask   f32   (C, 1)             — only when keep_fraction < 1: which
                                    chunks made it onto the wire this round
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import frames as frames_lib
from repro.kernels import ops as kernel_ops

STRATEGIES = ("psum", "psum_decoded", "allgather_packed", "alltoall_zero1")


@dataclasses.dataclass(frozen=True)
class GradCompConfig:
    """Budget + consensus strategy for compressed gradient exchange.

    bits           R per kept coordinate; {1, 2, 4, 8} (int32 packing).
    chunk          FWHT/frame length; power of two ≥ 32.
    strategy       psum            — exact f32 all-reduce (no compression),
                   psum_decoded    — compress→decode locally, f32 all-reduce
                                     (isolates codec error from wire savings),
                   allgather_packed— all-gather the PACKED payloads, decode
                                     all m, mean (paper's consensus, Alg. 3),
                   alltoall_zero1  — ZeRO-1: compressed reduce-scatter via
                                     all-to-all, owner-sharded optimizer.
    error_feedback per-worker EF state e ← u − D(E(u)) (DGD-DEF path).
    dithered       non-subtractive uniform dither → unbiased codec (Alg. 2 /
                   DQ-PSGD path; lets training drop the params-sized EF).
    keep_fraction  chunk-level subsampling for the sub-linear regime
                   (R_eff = bits·keep_fraction < 1, App. E.2).
    exact_keep     keep EXACTLY ⌈keep_fraction·C⌉ chunks per leaf (a shared
                   random subset of fixed size) instead of i.i.d. Bernoulli —
                   the realized bytes-on-wire then equal the analytic audit
                   every round, which the repro.fed ledger relies on.
    """

    bits: int = 4
    chunk: int = 256
    strategy: str = "allgather_packed"
    error_feedback: bool = True
    dithered: bool = False
    keep_fraction: float = 1.0
    exact_keep: bool = False
    seed: int = 0

    def __post_init__(self):
        if self.bits not in (1, 2, 4, 8):
            raise ValueError(f"bits must be in {{1,2,4,8}}, got {self.bits}")
        if self.chunk < 32 or (self.chunk & (self.chunk - 1)):
            raise ValueError(
                f"chunk must be a power of two ≥ 32, got {self.chunk}")
        if self.strategy not in STRATEGIES:
            raise ValueError(f"strategy must be one of {STRATEGIES}, "
                             f"got {self.strategy!r}")
        if not 0.0 < self.keep_fraction <= 1.0:
            raise ValueError(
                f"keep_fraction must be in (0, 1], got {self.keep_fraction}")

    @property
    def effective_bits(self) -> float:
        """Bits per original dimension actually spent on the wire."""
        return self.bits * self.keep_fraction

    @property
    def words_per_chunk(self) -> int:
        return self.chunk * self.bits // 32

    def kept_chunks(self, c: int) -> int:
        """Chunks on the wire for a leaf of c chunks under exact_keep."""
        if self.keep_fraction >= 1.0:
            return c
        return max(1, int(round(self.keep_fraction * c)))

    @property
    def compresses(self) -> bool:
        return self.strategy != "psum"

    @property
    def uses_ef(self) -> bool:
        return self.compresses and self.error_feedback


# ---------------------------------------------------------------------------
# Deterministic per-leaf randomness (shared across workers)
# ---------------------------------------------------------------------------
def _frame_signs(leaf_idx: int, cfg: GradCompConfig) -> jax.Array:
    """±1 diagonal of the leaf's Hadamard frame S = D·H (P = identity at
    n = N = chunk). Pure function of (cfg.seed, leaf_idx)."""
    key = jax.random.fold_in(jax.random.key(cfg.seed), leaf_idx)
    frame = frames_lib.hadamard_frame(key, cfg.chunk, cfg.chunk)
    return frame.signs


def _stoch_key(leaf_idx, round_idx, cfg: GradCompConfig) -> jax.Array:
    """Key for the per-round stochastic parts (dither / keep-mask)."""
    base = jax.random.fold_in(jax.random.key(cfg.seed), 0x5eed)
    return jax.random.fold_in(jax.random.fold_in(base, leaf_idx), round_idx)


# ---------------------------------------------------------------------------
# Leaf codec
# ---------------------------------------------------------------------------
def _to_chunks(x: jax.Array, chunk: int) -> jax.Array:
    flat = x.astype(jnp.float32).reshape(-1)
    c = -(-flat.size // chunk)
    flat = jnp.pad(flat, (0, c * chunk - flat.size))
    return flat.reshape(c, chunk)


def _pad_rows(t: jax.Array, rows: int) -> jax.Array:
    """Zero-pad the leading axis of t up to `rows`."""
    if t.shape[0] == rows:
        return t
    return jnp.pad(t, ((0, rows - t.shape[0]),) + ((0, 0),) * (t.ndim - 1))


def _exact_keep_mask(draw: jax.Array, k: int) -> jax.Array:
    """Keep EXACTLY the k smallest of the (C, 1) uniform draws.

    A `draw <= kth-smallest` threshold keeps MORE than k chunks when draws
    tie, breaking the ledger == analytic-audit byte contract; double-argsort
    ranking (stable, ties broken by chunk index — identical on every worker)
    keeps exactly k always."""
    rank = jnp.argsort(jnp.argsort(draw[:, 0]))
    return (rank < k)[:, None]


def _leaf_draws(leaf_idx: int, lc: int, rows: int, cfg: GradCompConfig,
                round_idx, key: jax.Array | None) -> tuple:
    """Pre-draw the per-round stochastic kernel inputs for one leaf.

    Returns (dither (rows, chunk) | None, mask f32 (rows, 1) | None). The
    draws happen at the LOGICAL chunk count `lc` from the same
    `fold_in`-derived keys as always, then zero-extend over padding — they
    are handed to the fused kernel as plain inputs, so forcing the Pallas
    path can never change a payload."""
    if key is None and (cfg.dithered or cfg.keep_fraction < 1.0):
        key = _stoch_key(leaf_idx, round_idx, cfg)
    dither = None
    if cfg.dithered:
        delta = 2.0 / (2 ** cfg.bits)
        dither = _pad_rows(jax.random.uniform(
            jax.random.fold_in(key, 1), (lc, cfg.chunk),
            minval=-delta / 2, maxval=delta / 2), rows)
    mask = None
    if cfg.keep_fraction < 1.0:
        draw = jax.random.uniform(jax.random.fold_in(key, 2), (lc, 1))
        if cfg.exact_keep:
            # fixed-size random subset: the k smallest draws stay on the wire
            keep = _exact_keep_mask(draw, cfg.kept_chunks(lc))
        else:
            keep = draw < cfg.keep_fraction
        mask = _pad_rows(keep.astype(jnp.float32), rows)
    return dither, mask


def encode_leaf(x: jax.Array, leaf_idx: int, cfg: GradCompConfig,
                round_idx=0, key: jax.Array | None = None,
                logical_chunks: int | None = None) -> dict:
    """Encode one leaf → payload dict (see module docstring for the format).

    `key` overrides the derived stochastic key (benchmarks that want
    per-worker independent dither); frames are never affected by it.

    `logical_chunks` is the PRE-PAD chunk count ⌈size/chunk⌉ of the leaf;
    pass it when `x` arrives already padded to extra all-zero chunks (the
    ZeRO-1 owned layout pads to a multiple of the worker count). The
    stochastic draws (dither, keep-mask) happen at the logical count and are
    zero-extended over the padding, so the payload of the padded layout is
    bit-exact with the un-padded all-gather encode on the real chunks.

    The whole chain (sign-flip → FWHT → scale → dither → quantize+pack →
    mask) runs in `kernel_ops.encode` — one fused VMEM pass on the Pallas
    path, the composed jnp reference otherwise, bit-identical payloads
    either way (dropped chunks emit all-zero words + zero scale, so the
    wire carries no ghost information)."""
    chunks = _to_chunks(x, cfg.chunk)
    lc = chunks.shape[0] if logical_chunks is None else logical_chunks
    signs = _frame_signs(leaf_idx, cfg).astype(jnp.float32)
    dither, mask = _leaf_draws(leaf_idx, lc, chunks.shape[0], cfg,
                               round_idx, key)
    words, scale = kernel_ops.encode(chunks, signs, cfg.bits,
                                     dither=dither, mask=mask)
    payload = {"words": words, "scale": scale}
    if mask is not None:
        payload["mask"] = mask
    return payload


def encode_leaf_ef(x: jax.Array, leaf_idx: int, cfg: GradCompConfig,
                   round_idx=0, key: jax.Array | None = None,
                   logical_chunks: int | None = None,
                   residual_dtype=None) -> tuple:
    """`encode_leaf` plus the error-feedback residual u − D(E(u)).

    Returns (payload, residual) with residual of x's shape/dtype — what
    the DGD-DEF update stores as the next round's EF state. On the Pallas
    path the kernel decodes its own payload in-tile and emits the residual
    without a second pass over the leaf; on the reference path the composed
    decode replays `decode_leaf`'s op order exactly (including the
    1/keep_fraction rescale only on the dithered-unbiased path and the
    decode-dtype rounding before the subtract). `residual_dtype` is the
    dtype the eager path would decode to (defaults to x's dtype); the fed
    engine passes the PARAM dtype so u − D(E(u)) rounds where a real
    decode would."""
    chunks = _to_chunks(x, cfg.chunk)
    lc = chunks.shape[0] if logical_chunks is None else logical_chunks
    signs = _frame_signs(leaf_idx, cfg).astype(jnp.float32)
    dither, mask = _leaf_draws(leaf_idx, lc, chunks.shape[0], cfg,
                               round_idx, key)
    rescale = (cfg.keep_fraction
               if (mask is not None and cfg.dithered
                   and not cfg.error_feedback) else None)
    rdt = x.dtype if residual_dtype is None else residual_dtype
    words, scale, resid = kernel_ops.encode_ef(
        chunks, signs, cfg.bits, dither=dither, mask=mask,
        rescale=rescale, residual_dtype=rdt)
    payload = {"words": words, "scale": scale}
    if mask is not None:
        payload["mask"] = mask
    residual = resid.reshape(-1)[:x.size].reshape(x.shape).astype(x.dtype)
    return payload, residual


def decode_leaf(payload: dict, leaf_idx: int, size: int, shape, dtype,
                cfg: GradCompConfig, extra_lead: int = 0) -> jax.Array:
    """Decode a payload back to a leaf of `shape`.

    With `extra_lead` = k the payload carries k leading stacked axes (e.g.
    the all-gathered worker axis) and the result is lead + shape.
    """
    words, scale = payload["words"], payload["scale"]
    x_hat = kernel_ops.unpack_dequant(words, scale, cfg.bits, cfg.chunk)
    mask = payload.get("mask")
    if mask is not None:
        x_hat = x_hat * mask
        if cfg.dithered and not cfg.error_feedback:
            # unbiased 1/keep rescale (DQ-PSGD); the EF path must stay
            # contractive, so it never rescales (see core.coding).
            x_hat = x_hat / cfg.keep_fraction
    signs = _frame_signs(leaf_idx, cfg).astype(x_hat.dtype)
    y = kernel_ops.unrotate(x_hat, signs)                    # y = D·H·x̂
    lead = tuple(words.shape[:extra_lead])
    flat = y.reshape(lead + (-1,))[..., :size]
    return flat.reshape(lead + tuple(shape)).astype(dtype)


def worker_mean(stacked: jax.Array) -> jax.Array:
    """Mean over the leading worker axis of decoded payloads, summed left
    to right. A reduce would pick its summation order from the array's
    layout; the all-gather path decodes whole leaves and ZeRO-1 only its
    owned rows, and both must round alike."""
    total = stacked[0]
    for w in range(1, stacked.shape[0]):
        total = total + stacked[w]
    return total / stacked.shape[0]


# ---------------------------------------------------------------------------
# Tree codec (what the consensus strategies move around)
# ---------------------------------------------------------------------------
def compress_tree(tree, cfg: GradCompConfig, round_idx=0):
    """Encode every leaf. Returns (payload tree, (treedef, leaf infos))."""
    leaves, treedef = jax.tree.flatten(tree)
    payloads = [encode_leaf(x, i, cfg, round_idx)
                for i, x in enumerate(leaves)]
    meta = (treedef, [(x.size, tuple(x.shape), x.dtype) for x in leaves])
    return jax.tree.unflatten(treedef, payloads), meta


def decode_payload(payloads, meta, cfg: GradCompConfig, extra_lead: int = 0):
    """Inverse of compress_tree; `extra_lead` as in decode_leaf."""
    treedef, infos = meta
    plist = treedef.flatten_up_to(payloads)
    outs = [decode_leaf(p, i, size, shape, dtype, cfg, extra_lead=extra_lead)
            for i, (p, (size, shape, dtype)) in enumerate(zip(plist, infos))]
    return jax.tree.unflatten(treedef, outs)


# ---------------------------------------------------------------------------
# Wire audit — the analytic bytes-on-wire formula
# ---------------------------------------------------------------------------
def wire_bytes_tree(tree, cfg: GradCompConfig, num_workers: int = 1) -> dict:
    """Exact bytes a worker puts on the wire per step, vs f32 all-reduce.

    Per leaf with C = ⌈size/chunk⌉ chunks, each kept chunk costs
    chunk·bits/8 payload bytes + 4 bytes for its f32 scale; in the
    sub-linear regime (keep_fraction < 1) the kept count is exactly
    `cfg.kept_chunks(C)` under exact_keep (else C·keep_fraction in
    expectation) and a 1-bit-per-chunk keep mask rides along.
    """
    f32_bytes = 0
    payload_bytes = 0.0
    for leaf in jax.tree.leaves(tree):
        size = int(leaf.size)
        f32_bytes += size * jnp.dtype(jnp.float32).itemsize
        c = -(-size // cfg.chunk)
        per_chunk = cfg.chunk * cfg.bits // 8 + 4
        if cfg.keep_fraction < 1.0:
            kept = (cfg.kept_chunks(c) if cfg.exact_keep
                    else cfg.keep_fraction * c)
            payload_bytes += kept * per_chunk + (c + 7) // 8
        else:
            payload_bytes += c * per_chunk
    if cfg.keep_fraction >= 1.0 or cfg.exact_keep:
        payload_bytes = int(payload_bytes)
    return {
        "f32_bytes": f32_bytes,
        "payload_bytes": payload_bytes,
        "compression_x": f32_bytes / payload_bytes,
        "num_workers": num_workers,
        # allgather_packed: each worker sends its payload and receives m−1
        "allgather_rx_bytes": payload_bytes * max(num_workers - 1, 0),
    }


def _payload_leaves(payloads) -> list:
    """Flatten a payload tree to its per-leaf {"words", "scale", ...} dicts."""
    return jax.tree.leaves(
        payloads, is_leaf=lambda d: isinstance(d, dict) and "words" in d)


def wire_bytes_payload(payloads, cfg: GradCompConfig) -> float:
    """Bytes a CONCRETE encoded tree actually puts on the wire.

    Counts only kept chunks (per the realized keep mask) at the packed-words
    + f32-scale cost, plus the 1-bit-per-chunk mask when present — the
    realized counterpart of `wire_bytes_tree`. Under `exact_keep` the two
    agree to the byte every round (the repro.fed ledger asserts this).
    """
    per_chunk = cfg.chunk * cfg.bits // 8 + 4
    total = 0.0
    for p in _payload_leaves(payloads):
        c = p["scale"].shape[-2]
        mask = p.get("mask")
        if mask is None:
            total += c * per_chunk
        else:
            total += float(jnp.sum(mask)) * per_chunk + (c + 7) // 8
    return total
