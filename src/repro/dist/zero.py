"""ZeRO-1 layout + compressed reduce-scatter (all-to-all of packed payloads).

Owned layout: each leaf is flattened, zero-padded so its chunk count is a
multiple of the worker count m, and reshaped (padded_chunks, chunk). Worker
w owns the contiguous row block [w·rows, (w+1)·rows) — its optimizer state
exists only for those rows (the ZeRO-1 memory saving). Reconstruction is
`owned.reshape(-1)[:size].reshape(shape)`.

Consensus: every worker encodes ALL its gradient chunks with the shared
per-leaf frame (repro.dist.gradcomp), then an all-to-all routes each row
block's m payloads to its owner, who decodes the stacked payloads and takes
the mean. Because the frames, quantizer and mean order are identical to the
all-gather consensus, the updated owned shards are BIT-EXACT with the
replicated `allgather_packed` path (tests/test_zero.py asserts this at m=4).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import gradcomp as G
from repro.dist import scopes


def leaf_layout(shape, chunk: int, num_workers: int) -> tuple:
    """(padded_chunks, rows_per_owner) for a leaf of `shape`."""
    size = int(np.prod(shape)) if shape else 1
    c = -(-size // chunk)
    padded = -(-c // num_workers) * num_workers
    return padded, padded // num_workers


def params_meta(params, gc: G.GradCompConfig, num_workers: int):
    """(treedef, [(size, shape, dtype, (padded_chunks, rows)), ...]).

    `params` may hold arrays or ShapeDtypeStructs (jax.eval_shape output).
    """
    leaves, treedef = jax.tree.flatten(params)
    infos = []
    for x in leaves:
        shape = tuple(x.shape)
        size = int(np.prod(shape)) if shape else 1
        infos.append((size, shape, x.dtype,
                      leaf_layout(shape, gc.chunk, num_workers)))
    return treedef, infos


def to_owned(leaf: jax.Array, chunk: int, num_workers: int) -> jax.Array:
    """Full leaf → f32 (padded_chunks, chunk) owned layout (global view)."""
    padded, _ = leaf_layout(leaf.shape, chunk, num_workers)
    flat = leaf.astype(jnp.float32).reshape(-1)
    flat = jnp.pad(flat, (0, padded * chunk - flat.size))
    return flat.reshape(padded, chunk)


def from_owned(owned: jax.Array, size: int, shape, dtype) -> jax.Array:
    """Inverse of to_owned (drops the zero padding)."""
    return owned.reshape(-1)[:size].reshape(shape).astype(dtype)


def valid_mask(size: int, padded_chunks: int, chunk: int) -> jax.Array:
    """f32 (padded_chunks, chunk): 1 on real coordinates, 0 on padding."""
    pos = (jnp.arange(padded_chunks)[:, None] * chunk
           + jnp.arange(chunk)[None, :])
    return (pos < size).astype(jnp.float32)


def compressed_reduce_scatter(u: jax.Array, leaf_idx: int,
                              gc: G.GradCompConfig, axes, num_workers: int,
                              round_idx=0, logical_chunks: int | None = None):
    """One leaf's ZeRO-1 consensus step, inside shard_map (manual `axes`).

    u: worker-local (padded_chunks, chunk) gradient(+EF) chunks.
    `logical_chunks` is the leaf's PRE-PAD chunk count ⌈size/chunk⌉ — the
    codec draws its stochastic parts (keep-mask, dither) at that count so the
    payload stays bit-exact with the un-padded all-gather encode even at
    keep_fraction < 1 (the padded chunks are always dropped).
    Returns (owned_mean (rows, chunk), residual) — the owner-side consensus
    mean for this worker's rows, and, when `gc` uses error feedback, the
    fused encoder's residual u − D(E(u)) of the worker's OWN payload
    (padded_chunks, chunk), else None. The residual comes from the same
    fused `encode_ef` the all-gather path uses, row for row, so the EF state
    of the two paths stays bit-identical.
    """
    rows = u.shape[0] // num_workers
    residual = None
    with jax.named_scope(scopes.ENCODE):
        if gc.uses_ef:
            payload, residual = G.encode_leaf_ef(
                u, leaf_idx, gc, round_idx, logical_chunks=logical_chunks)
        else:
            payload = G.encode_leaf(u, leaf_idx, gc, round_idx,
                                    logical_chunks=logical_chunks)

    def route(t):
        tm = t.reshape((num_workers, rows) + t.shape[1:])
        if num_workers == 1:
            return tm
        return jax.lax.all_to_all(tm, axes, split_axis=0, concat_axis=0,
                                  tiled=False)

    with jax.named_scope(scopes.EXCHANGE):
        gathered = jax.tree.map(route, payload)  # (m, rows, …) per wire leaf
    with jax.named_scope(scopes.DECODE):
        stacked = G.decode_leaf(gathered, leaf_idx, rows * gc.chunk,
                                (rows, gc.chunk), jnp.float32, gc,
                                extra_lead=1)
    with jax.named_scope(scopes.MEAN):
        return G.worker_mean(stacked), residual
