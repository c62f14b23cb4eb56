"""shard_map federation backend: cohort lanes placed on mesh devices.

This is the fed ∘ dist composition the ROADMAP tracked: the cohort engine
(PR 3/4) already lays every cohort out as ONE stacked pytree with a leading
lane axis, but executes all lanes on a single device under `jax.vmap`. Here
the same stacked trees are sharded over the mesh data axes — the axes
`repro.dist.step` runs its consensus workers on — so each device runs its
slice of client lanes (local SGD → encode → decode → per-lane norms) fully
manually inside one `shard_map` program, consistent with the all-manual
pattern of `repro.dist.step`.

Lane placement contract:

  * a cohort of n lanes is padded to `padded_lanes(n, axis_size)` by
    repeating lane 0 (`clients.stack_padded`), so the stack shards evenly;
    real lanes keep positions 0..n−1 and padded lanes carry weight 0
    downstream — `server._check_weights` explicitly admits exact zeros.
  * per-lane numerics are IDENTICAL to the vmap cohort engine: shard_map
    merely splits the lane axis across devices, and the round body is the
    same `clients._round_body` vmapped per shard — including the fused
    `codec.encode_ef` path (one `kernels.quantencode` pass per leaf emits
    wire + EF residual together) — so wires, EF states, decoded deltas and
    norms agree bit for bit (regression-tested). Any `repro.codecs`
    TreeCodec rides this path, including the sub-linear R < 1 regime
    (exact-keep chunk drop), whose realized ledger the mesh round reports
    byte-equal to the analytic audit.

Server reduce contract (`ServerConfig.sum_mode`, same words as PR 4):

  "sequential"  every device all-gathers the decoded lane stack (tiled over
                the data axes, so lanes land in global participant order),
                slices off the padding, and replays EXACTLY the
                `server._sequential_weighted_sum` fold of the single-device
                path — one collective, then the reference's float-op order,
                so params / opt_state / EF stay bit-exact with the vmap
                cohort engine (and hence with the PR-2 list reference).
  "pairwise"    each device pairwise-folds its own weighted lanes and the
                partial sums meet in a `psum` over the data axes — the
                truly distributed O(m/devices + log devices) reduce, equal
                to the reference only to float tolerance (padding lanes are
                killed by their zero weights before the psum).

fedmem is not a lane fold (its direction reduces over ALL m_total memory
slots), so the mesh backend gathers the decoded stack and reuses
`server.aggregate_stacked` unchanged — same compiled program, bit-exact by
construction.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.obs import recompile as recompile_lib
from repro.dist.sharding import (data_axis_names, lane_pspec, num_workers,
                                 padded_lanes)
from repro.fed import clients as clients_lib
from repro.fed import server as server_lib
from repro.launch.mesh import make_host_mesh


def default_mesh() -> jax.sharding.Mesh:
    """All visible devices on the "data" axis — the lane-placement mesh a
    `Federation(backend="mesh")` builds when none is passed."""
    return make_host_mesh(data=jax.device_count(), model=1)


def lane_axis_size(mesh) -> int:
    """Devices the lane axis shards over (≥ 1 even on a degenerate mesh)."""
    return max(num_workers(mesh), 1)


# ---------------------------------------------------------------------------
# Client side: one cohort round, lanes sharded over the data axes
# ---------------------------------------------------------------------------
def make_mesh_cohort_round(loss_fn, codec, client_cfg, params_template,
                           mesh) -> callable:
    """jit'd (params, stacked data, stacked states, round_idx) →
    (stacked wires, stacked states, stacked decoded deltas, per-lane norms).

    All stacked arguments/results carry a leading lane axis padded to a
    multiple of the mesh's data-axis size and sharded over it; params and
    round_idx are replicated. Each device vmaps `clients._round_body` over
    its own lane slice AND decodes its lanes' payloads locally — embed →
    quantize → decode runs where the lane lives, nothing m-sized crosses
    devices before the reduce. Per-lane outputs are bitwise identical to
    `clients.make_cohort_round` + the driver's cohort decode (vmap lanes are
    independent, so splitting the lane axis cannot change them)."""
    meta = codec.meta(params_template)
    body = clients_lib._round_body(loss_fn, codec, client_cfg, meta)
    lane = lane_pspec(mesh)

    def local_lanes(params, data, state, round_idx):
        wires, new_state = jax.vmap(body, in_axes=(None, 0, 0, None))(
            params, data, state, round_idx)
        decoded = jax.vmap(lambda w: codec.decode(w, meta))(wires)
        return wires, new_state, decoded, server_lib.stacked_norms(decoded)

    fn = jax.shard_map(local_lanes, mesh=mesh,
                       in_specs=(P(), lane, lane, P()),
                       out_specs=(lane, lane, lane, lane), check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Server side: the lane fold as a collective over the data axes
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _mesh_mean_fn(mesh, sum_mode: str, lanes: int):
    """Compiled `(padded stacked, weights) → Σ (w/Σw)_l · lane_l` with the
    lane axis sharded over `mesh`'s data axes. `lanes` is the REAL lane
    count (static); padding lanes beyond it never enter the arithmetic in
    "sequential" mode and are zero-weighted in "pairwise" mode."""
    axes = data_axis_names(mesh)
    lane = lane_pspec(mesh)

    if sum_mode == "sequential":
        # one tiled all_gather puts the full stack (global lane order) on
        # every device; the fold is then literally the single-device
        # reference: same normalize, same materialized weighted lanes, same
        # pure-add fori_loop — bit-exact with server._stacked_mean_fn.
        def fold(stacked, w):
            full = jax.tree.map(
                lambda x: jax.lax.all_gather(x, axes, axis=0, tiled=True),
                stacked)
            real = jax.tree.map(lambda x: x[:lanes], full)
            return server_lib._sequential_weighted_sum(real, w / jnp.sum(w))

        in_specs = (lane, P())
    else:
        # distributed pairwise: local weighted fold per device, partial sums
        # psum'd over the data axes. Padding lanes multiply by weight 0, so
        # they vanish before the collective. Summation order differs from
        # BOTH the sequential reference and the single-device pairwise fold
        # — float-tolerance territory, exactly like sum_mode="pairwise"
        # already is on one device.
        def fold(stacked, w_local):
            total = jax.lax.psum(jnp.sum(w_local), axes)
            partial = server_lib._pairwise_weighted_sum(stacked,
                                                        w_local / total)
            return jax.tree.map(lambda x: jax.lax.psum(x, axes), partial)

        in_specs = (lane, lane)

    return recompile_lib.register(
        "fed.aggregate.mesh",
        jax.jit(jax.shard_map(fold, mesh=mesh, in_specs=in_specs,
                              out_specs=P(), check_vma=False)))


def _place_lanes(tree, mesh):
    """Pad a stacked tree's lane axis to the axis size and shard it over the
    mesh data axes. A tree that already carries its padding (the round
    program's own output, in the single-cohort fast path) passes through —
    the device_put is a no-op when the sharding already matches. Added
    padding lanes are zeros; pre-existing ones are lane-0 copies — either
    way "sequential" never reads them and "pairwise" multiplies them by
    weight exactly 0."""
    lanes = jax.tree.leaves(tree)[0].shape[0]
    total = padded_lanes(lanes, lane_axis_size(mesh))
    if total != lanes:
        tree = jax.tree.map(
            lambda x: jnp.concatenate(
                [x, jnp.zeros((total - lanes,) + x.shape[1:], x.dtype)],
                axis=0), tree)
    spec = lane_pspec(mesh)
    return jax.device_put(tree, NamedSharding(mesh, spec)), total


def mesh_weighted_mean(stacked, weights, mesh, sum_mode: str = "sequential",
                       lanes: Optional[int] = None):
    """Σ (w/Σw)_l · lane_l over the first `lanes` lanes, reduced across the
    mesh.

    `lanes` is the REAL lane count (default: the stack's leading axis);
    lanes past it are padding and contribute nothing. Lane placement (and
    any padding still missing) happens here, so callers may pass either a
    real-lanes-only stack or the round program's already-padded output.
    With `sum_mode="sequential"` the result is bit-exact with
    `server._stacked_mean_fn("sequential")` on the real lanes."""
    if lanes is None:
        lanes = jax.tree.leaves(stacked)[0].shape[0]
    placed, total = _place_lanes(stacked, mesh)
    if sum_mode == "sequential":
        w = jnp.asarray(np.asarray(weights), jnp.float32)
    else:
        w_pad = np.zeros(total, np.float32)
        w_pad[:lanes] = np.asarray(weights, np.float64)
        w = jax.device_put(jnp.asarray(w_pad),
                           NamedSharding(mesh, lane_pspec(mesh)))
    return _mesh_mean_fn(mesh, sum_mode, lanes)(placed, w)


def aggregate_stacked_mesh(state, cfg, stacked, weights, mesh,
                           participant_ids: Optional[Sequence[int]] = None,
                           slot_weights=None, lanes: Optional[int] = None):
    """`server.aggregate_stacked` semantics with the lane fold distributed
    over the mesh data axes.

    Same signature modulo `mesh` and `lanes`; `stacked` carries the
    participant lanes in the same order as `weights` / `participant_ids`,
    optionally followed by padding lanes (`lanes` = real count — the
    single-cohort fast path feeds the round program's padded output
    straight through, so the m×L-sized stack never reshards between decode
    and the fold). The m-independent tail — η_s step, fedopt optimizer —
    replays the reference's eager helpers, so with
    `cfg.sum_mode == "sequential"` the whole step is bit-exact with the
    single-device stacked path (regression-tested)."""
    have = jax.tree.leaves(stacked)[0].shape[0]
    lanes = have if lanes is None else lanes
    if lanes == 0:
        return state
    if np.asarray(weights).shape[0] != lanes:
        raise ValueError(f"{np.asarray(weights).shape[0]} weights for "
                         f"{lanes} stacked lanes")

    if cfg.aggregator in ("fedavg", "fedopt"):
        server_lib._check_weights(weights)
        mean = mesh_weighted_mean(stacked, weights, mesh, cfg.sum_mode,
                                  lanes=lanes)
        if cfg.aggregator == "fedopt":
            return server_lib._fedopt_tail(state, cfg, mean)
        return server_lib.ServerState(
            server_lib._apply_delta(state.params, mean, cfg.server_lr),
            state.opt_state, state.memory)

    # fedmem: the direction is a reduction over ALL m_total memory slots,
    # not a participant-lane fold — replicate the (small-m) decoded stack
    # and reuse the single-device program wholesale, which keeps the slot
    # scatter + slot mean bit-exact with the vmap backend for free.
    if lanes != have:
        stacked = jax.tree.map(lambda a: a[:lanes], stacked)
    replicated = jax.device_put(stacked, NamedSharding(mesh, P()))
    return server_lib.aggregate_stacked(state, cfg, replicated, weights,
                                        participant_ids,
                                        slot_weights=slot_weights)
