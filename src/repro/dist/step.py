"""Train / serve step factories on a ("data","model") mesh.

Training runs as a fully-manual shard_map: gradients cross the data axes
("pod","data") only through the chosen consensus strategy, and params are
replicated over "model" inside the step (partial-auto — manual data axes
over a GSPMD-sharded model axis — is ROADMAP Reach 2). The tensor-parallel
sharding from repro.dist.sharding drives the pure-jit serve / prefill
paths.

Consensus strategies (GradCompConfig.strategy):

  psum             exact f32 all-reduce (the uncompressed baseline).
  psum_decoded     every worker round-trips its own gradients through the
                   chunked NDSC codec, then f32 all-reduce of the DECODED
                   gradients — codec error without the wire savings.
  allgather_packed the paper's consensus: all-gather the PACKED int32
                   payloads (bits/32 of the f32 bytes), decode all m on every
                   worker (stacked decode), take the mean. Shared per-leaf
                   frames make the decode identical everywhere.
  alltoall_zero1   ZeRO-1 (make_zero_train_step): compressed reduce-scatter
                   via all-to-all; each worker updates only its owned shard
                   and the optimizer state is 1/m per worker. Bit-exact with
                   allgather_packed under shared randomness.

Error feedback is per-worker: e ← (g + e) − D(E(g + e)), decoded from the
worker's OWN payload, so EF never needs extra communication.

Observability: the step's phases run under the named scopes of
`repro.dist.scopes` (forward, consensus with encode / exchange / decode /
mean, optimizer), which reach every device op's metadata and leave the
compiled instructions as they are. With a `repro.obs` session active,
each call of the returned step runs under a host span ("dist.step",
"dist.step.zero1"); disabled, the wrapper is one global load per call.
The underlying jit program, its `lower` method and its compile cache are
reachable via the wrapper (`_jitted`), and the program registers with
`obs.recompile` so compile counts are attributable. Numerics are
untouched either way (bit-exactness regression-tested).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.codecs import stages as codec_stages
from repro.dist import gradcomp as G
from repro.dist import scopes
from repro.dist import zero as zero_lib
from repro.dist.sharding import (data_axes_for, data_axis_names, num_workers,
                                 param_specs)
from repro.models import decode as decode_lib
from repro.models import model as model_lib
from repro.obs import core as obs_lib
from repro.obs import recompile as recompile_lib
from repro.optimizer.optim import (apply_updates, clip_by_global_norm,
                                   global_norm)


def _model_axis(mesh) -> int:
    return mesh.shape.get("model", 1)


def _round_idx(opt_state):
    """Per-step salt for the codec's stochastic parts (dither / keep-mask)."""
    if isinstance(opt_state, dict) and "step" in opt_state:
        return opt_state["step"]
    return 0


def _worker_index(axes, mesh):
    """Row-major worker index over the data axes (matches the stacking order
    of all_gather / all_to_all over the same axis tuple)."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _lead_axes(axes):
    """Leading PartitionSpec entry for a dim sharded over the data axes:
    the tuple for several, the bare name for one, None for none."""
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def _analytic_payload_bytes(cfg, gc: G.GradCompConfig, mesh):
    """Per-worker bytes-on-wire per step, from the analytic audit over the
    model's parameter template (None when the template can't be built, e.g.
    a custom loss over non-model params)."""
    try:
        p_shapes = jax.eval_shape(
            lambda: model_lib.init_params(jax.random.key(0), cfg))
        wire = G.wire_bytes_tree(p_shapes, gc, num_workers(mesh))
        if gc.strategy == "psum":
            return float(wire["f32_bytes"])
        return float(wire["payload_bytes"])
    except Exception:
        return None


def _with_obs(fn, name: str, gc: G.GradCompConfig, payload_bytes):
    """Host-side span around a jit'd train step. The wrapper is
    call-transparent (same signature, same outputs); `lower` and the
    compile cache stay reachable for the dry-run launcher and the tests."""
    recompile_lib.register(name, fn, wire_bytes_per_call=payload_bytes)

    def stepper(params, opt_state, ef, batch):
        if not obs_lib.enabled():
            return fn(params, opt_state, ef, batch)
        with obs_lib.span(name, strategy=gc.strategy):
            return fn(params, opt_state, ef, batch)

    stepper.lower = fn.lower
    stepper._jitted = fn
    return stepper


def _loss_and_grad(loss_of, params, batch):
    """`jax.value_and_grad(loss_of)` under the forward scope. JAX names
    the ops of the forward pass `forward/jvp(...)/...` and those of the
    backward pass, rematerialized forward work included,
    `forward/transpose(jvp(...))/...`. Inside the transform the scope
    would rename the functions JAX inlines into the step; outside it, it
    does not."""
    with jax.named_scope(scopes.FORWARD):
        return jax.value_and_grad(loss_of)(params, batch)


# ---------------------------------------------------------------------------
# Consensus
# ---------------------------------------------------------------------------
def _pin(g):
    """Materialize a gradient leaf, in its own shape, before the codec
    touches it. Without the barrier XLA may fuse g's producer (a reduction,
    a scatter-add) with the reshape, pad or EF add that follows — e.g. seed
    the embedding's scatter-add with e instead of zeros — and sum in an
    order that depends on that consumer, which differs between the
    replicated and the ZeRO-1 layouts. Those must stay bit-identical."""
    return jax.lax.optimization_barrier(g)


def _consensus(grads, ef, gc: G.GradCompConfig, axes, round_idx):
    """Returns (consensus grads, new EF state); the caller runs it under
    the consensus scope.

    The per-leaf encode/decode routes through the NDSC stage codec from
    `repro.codecs.stages` — the same fused-kernel gradcomp implementation
    the fed engine and the registry use, so wire payloads here stay
    bit-identical with every other consumer of the codec stack."""
    if gc.strategy == "psum":
        with jax.named_scope(scopes.EXCHANGE):
            return jax.tree.map(lambda g: jax.lax.pmean(g, axes), grads), ef

    leaf_codec = codec_stages.ndsc_leaf(gc)
    leaves, treedef = jax.tree.flatten(grads)
    e_leaves = treedef.flatten_up_to(ef) if gc.uses_ef else [None] * len(leaves)
    outs, new_e = [], []
    for i, (g, e) in enumerate(zip(leaves, e_leaves)):
        with jax.named_scope(scopes.ENCODE):
            u = _pin(g).astype(jnp.float32)
            if e is not None:
                u = u + e
            resid = None
            if gc.strategy == "allgather_packed" and gc.uses_ef:
                # fused encode + EF: the kernel decodes its own payload
                # in-tile and emits u − D(E(u)) alongside — no second
                # decode pass
                payload, resid = leaf_codec.encode_ef(u, i, round_idx)
            else:
                payload = leaf_codec.encode(u, i, round_idx)
        if gc.strategy == "psum_decoded":
            # the consensus itself needs the decoded leaf here, so EF
            # reuses it (u − (u − d) ≠ d in floats, so the fused residual
            # can't substitute)
            with jax.named_scope(scopes.DECODE):
                d_own = leaf_codec.decode(payload, i, u.size, u.shape,
                                          jnp.float32)
            with jax.named_scope(scopes.EXCHANGE):
                cons = jax.lax.pmean(d_own, axes)
            if gc.uses_ef:
                with jax.named_scope(scopes.DECODE):
                    resid = u - d_own
        else:  # allgather_packed
            with jax.named_scope(scopes.EXCHANGE):
                gathered = jax.tree.map(
                    lambda t: jax.lax.all_gather(t, axes, axis=0), payload)
            with jax.named_scope(scopes.DECODE):
                stacked = leaf_codec.decode(gathered, i, u.size, u.shape,
                                            jnp.float32, extra_lead=1)
            with jax.named_scope(scopes.MEAN):
                cons = G.worker_mean(stacked)
        outs.append(cons.astype(g.dtype))
        if gc.uses_ef:
            new_e.append(resid)
    grads = jax.tree.unflatten(treedef, outs)
    return grads, (jax.tree.unflatten(treedef, new_e) if gc.uses_ef else ef)


# ---------------------------------------------------------------------------
# Replicated-parameter train step (psum / psum_decoded / allgather_packed)
# ---------------------------------------------------------------------------
def make_train_step(cfg, opt, gc: G.GradCompConfig, mesh, clip_norm=None,
                    loss_fn=None):
    """jit'd (params, opt_state, ef, batch) → (params, opt_state, ef, metrics).

    Params / optimizer / EF are replicated across ALL mesh axes inside the
    step (see the NOTE at the shard_map below); the batch is sharded over
    the data axes on dim 0.
    """
    if gc.strategy == "alltoall_zero1":
        raise ValueError("strategy 'alltoall_zero1' needs make_zero_train_step")
    axes = data_axis_names(mesh)
    first = _lead_axes(axes)
    loss_of = loss_fn or (lambda p, b: model_lib.loss_fn(cfg, p, b))

    def local_step(params, opt_state, ef, batch):
        loss, grads = _loss_and_grad(loss_of, params, batch)
        loss = jax.lax.pmean(loss, axes)
        # EF leaves carry a leading per-worker axis (m, …); local view (1, …)
        ef_local = jax.tree.map(lambda e: e[0], ef)
        with jax.named_scope(scopes.CONSENSUS):
            grads, ef_local = _consensus(grads, ef_local, gc, axes,
                                         _round_idx(opt_state))
        ef = jax.tree.map(lambda e: e[None], ef_local)
        with jax.named_scope(scopes.OPTIMIZER):
            if clip_norm is not None:
                grads, grad_norm = clip_by_global_norm(grads, clip_norm)
            else:
                grad_norm = global_norm(grads)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
        return params, opt_state, ef, {"loss": loss, "grad_norm": grad_norm}

    batch_spec = P(first)
    ef_spec = P(first) if gc.uses_ef else P()
    # ALL mesh axes are manual here — params enter with in_specs=P() and
    # are therefore fully replicated (incl. over "model") inside the train
    # step; tensor-parallel param sharding drives only the pure-jit
    # serve/prefill paths.
    fn = jax.shard_map(local_step, mesh=mesh,
                       in_specs=(P(), P(), ef_spec, batch_spec),
                       out_specs=(P(), P(), ef_spec, P()), check_vma=False)
    return _with_obs(jax.jit(fn), "dist.step", gc,
                     _analytic_payload_bytes(cfg, gc, mesh))


def _ef_shapes(params_shapes, gc: G.GradCompConfig, m: int):
    """Per-worker error feedback: (m, *param shape) f32 leaves."""
    if not gc.uses_ef:
        return {}
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((m,) + tuple(x.shape), jnp.float32),
        params_shapes)


def _state_specs_like(state_shapes, params_shapes, pspecs):
    """Optimizer-state PartitionSpecs: subtrees structured like the params
    (mu / nu / vel) inherit the param specs; everything else is replicated."""
    pdef = jax.tree.structure(params_shapes)
    if not isinstance(state_shapes, dict):
        return jax.tree.map(lambda _: P(), state_shapes)
    return {k: (pspecs if jax.tree.structure(v) == pdef
                else jax.tree.map(lambda _: P(), v))
            for k, v in state_shapes.items()}


def _with_shardings(shapes, specs, mesh):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
        shapes, specs)


def train_state_specs(cfg, opt, gc: G.GradCompConfig, mesh):
    """Sharded ShapeDtypeStruct stand-ins for (params, opt_state, ef)."""
    p_shapes = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.key(0), cfg))
    pspecs = param_specs(p_shapes, _model_axis(mesh))
    o_shapes = jax.eval_shape(opt.init, p_shapes)
    e_shapes = _ef_shapes(p_shapes, gc, num_workers(mesh))
    axes = data_axis_names(mesh)
    first = _lead_axes(axes)
    e_specs = (jax.tree.map(lambda x: P(first, *([None] * (len(x.shape) - 1))),
                            e_shapes) if gc.uses_ef else {})
    return (_with_shardings(p_shapes, pspecs, mesh),
            _with_shardings(o_shapes,
                            _state_specs_like(o_shapes, p_shapes, pspecs),
                            mesh),
            _with_shardings(e_shapes, e_specs, mesh))


def init_train_state(cfg, opt, gc: G.GradCompConfig, mesh, key=None):
    """Materialized (params, opt_state, ef) placed per train_state_specs."""
    key = jax.random.key(0) if key is None else key
    params = model_lib.init_params(key, cfg)
    opt_state = opt.init(params)
    m = num_workers(mesh)
    ef = (jax.tree.map(
        lambda p: jnp.zeros((m,) + tuple(p.shape), jnp.float32), params)
        if gc.uses_ef else {})
    specs = train_state_specs(cfg, opt, gc, mesh)
    return tuple(
        jax.device_put(v, jax.tree.map(lambda s: s.sharding, spec))
        for v, spec in zip((params, opt_state, ef), specs))


# ---------------------------------------------------------------------------
# ZeRO-1 train step (alltoall_zero1)
# ---------------------------------------------------------------------------
def make_zero_train_step(cfg, opt, gc: G.GradCompConfig, mesh,
                         gather_dtype=None, clip_norm=None, loss_fn=None):
    """jit'd ZeRO-1 step over OWNED-layout state (see repro.dist.zero).

    State leaves are (padded_chunks, chunk) f32 sharded over the data axes on
    dim 0 — each worker holds and updates only its row block; `gather_dtype`
    optionally down-casts the forward all-gather of the parameters (set None
    for bit-exactness with the replicated path).
    """
    axes = data_axis_names(mesh)
    m = num_workers(mesh)
    loss_of = loss_fn or (lambda p, b: model_lib.loss_fn(cfg, p, b))
    p_shapes = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.key(0), cfg))
    treedef, infos = zero_lib.params_meta(p_shapes, gc, m)

    def local_step(owned_params, opt_state, ef, batch):
        owned_leaves = treedef.flatten_up_to(owned_params)
        full = []
        for owned, (size, shape, dtype, _) in zip(owned_leaves, infos):
            g = owned if gather_dtype is None else owned.astype(gather_dtype)
            if m > 1:
                g = jax.lax.all_gather(g, axes, axis=0, tiled=True)
            full.append(zero_lib.from_owned(g.astype(jnp.float32),
                                            size, shape, dtype))
        params = jax.tree.unflatten(treedef, full)
        loss, grads = _loss_and_grad(loss_of, params, batch)
        loss = jax.lax.pmean(loss, axes)
        round_idx = _round_idx(opt_state)

        g_leaves = treedef.flatten_up_to(grads)
        e_leaves = (treedef.flatten_up_to(ef) if gc.uses_ef
                    else [None] * len(g_leaves))
        owned_grads, new_e = [], []
        sq_sum = jnp.zeros((), jnp.float32)
        for i, (g, e, (size, shape, dtype, (padded, rows))) in enumerate(
                zip(g_leaves, e_leaves, infos)):
            with jax.named_scope(scopes.CONSENSUS):
                with jax.named_scope(scopes.ENCODE):
                    u = zero_lib.to_owned(_pin(g), gc.chunk, m)
                    if e is not None:
                        u = u + e[0]
                mean_own, resid = zero_lib.compressed_reduce_scatter(
                    u, i, gc, axes, m, round_idx,
                    logical_chunks=-(-size // gc.chunk))
                # zero the padding coords so optimizer state / EF stay clean
                # and the norms match the replicated path exactly
                widx = _worker_index(axes, mesh) if m > 1 else 0
                row0 = widx * rows
                pos = ((row0 + jnp.arange(rows))[:, None] * gc.chunk
                       + jnp.arange(gc.chunk)[None, :])
                mean_own = mean_own * (pos < size).astype(jnp.float32)
                owned_grads.append(mean_own)
            with jax.named_scope(scopes.OPTIMIZER):
                sq_sum = sq_sum + jnp.sum(jnp.square(mean_own))
            if e is not None:
                with jax.named_scope(scopes.CONSENSUS), \
                        jax.named_scope(scopes.ENCODE):
                    new_e.append((resid
                                  * zero_lib.valid_mask(size, padded,
                                                        gc.chunk))[None])
        with jax.named_scope(scopes.OPTIMIZER):
            grad_norm = jnp.sqrt(jax.lax.psum(sq_sum, axes))
            owned_grads = jax.tree.unflatten(treedef, owned_grads)
            if clip_norm is not None:
                scale = jnp.minimum(1.0,
                                    clip_norm / jnp.maximum(grad_norm, 1e-12))
                owned_grads = jax.tree.map(lambda x: x * scale, owned_grads)
            updates, opt_state = opt.update(owned_grads, opt_state,
                                            owned_params)
            owned_params = apply_updates(owned_params, updates)
        ef = jax.tree.unflatten(treedef, new_e) if gc.uses_ef else ef
        return owned_params, opt_state, ef, {"loss": loss,
                                             "grad_norm": grad_norm}

    owned_spec = jax.tree.map(
        lambda _: P(_lead_axes(axes)), p_shapes)
    o_shapes = jax.eval_shape(
        opt.init, jax.tree.unflatten(treedef, [
            jax.ShapeDtypeStruct((pc, gc.chunk), jnp.float32)
            for (_, _, _, (pc, _)) in infos]))
    opt_spec = _state_specs_like(
        o_shapes, p_shapes, owned_spec)
    ef_spec = jax.tree.map(
        lambda _: P(_lead_axes(axes)),
        p_shapes) if gc.uses_ef else {}
    batch_spec = P(_lead_axes(axes))
    fn = jax.shard_map(local_step, mesh=mesh,
                       in_specs=(owned_spec, opt_spec, ef_spec, batch_spec),
                       out_specs=(owned_spec, opt_spec, ef_spec, P()),
                       check_vma=False)
    return _with_obs(jax.jit(fn), "dist.step.zero1", gc,
                     _analytic_payload_bytes(cfg, gc, mesh))


def zero_state_specs(cfg, opt, gc: G.GradCompConfig, mesh):
    """Sharded ShapeDtypeStructs for the owned-layout ZeRO-1 state."""
    m = num_workers(mesh)
    axes = data_axis_names(mesh)
    first = _lead_axes(axes)
    p_shapes = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.key(0), cfg))
    treedef, infos = zero_lib.params_meta(p_shapes, gc, m)
    owned = jax.tree.unflatten(treedef, [
        jax.ShapeDtypeStruct((pc, gc.chunk), jnp.float32)
        for (_, _, _, (pc, _)) in infos])
    owned_spec = jax.tree.map(lambda _: P(first, None), owned)
    o_shapes = jax.eval_shape(opt.init, owned)
    o_spec = _state_specs_like(o_shapes, owned, owned_spec)
    ef = (jax.tree.unflatten(treedef, [
        jax.ShapeDtypeStruct((m, pc, gc.chunk), jnp.float32)
        for (_, _, _, (pc, _)) in infos]) if gc.uses_ef else {})
    ef_spec = jax.tree.map(lambda _: P(first, None, None), ef)
    return (_with_shardings(owned, owned_spec, mesh),
            _with_shardings(o_shapes, o_spec, mesh),
            _with_shardings(ef, ef_spec, mesh))


def init_zero_state(cfg, opt, gc: G.GradCompConfig, mesh, key=None):
    """Materialized owned-layout (params, opt_state, ef), sharded over data.

    Uses the same init key as init_train_state so the two paths start from
    identical parameters (the bit-exactness test relies on this).
    """
    m = num_workers(mesh)
    key = jax.random.key(0) if key is None else key
    params = model_lib.init_params(key, cfg)
    owned = jax.tree.map(lambda p: zero_lib.to_owned(p, gc.chunk, m), params)
    opt_state = opt.init(owned)
    ef = (jax.tree.map(
        lambda o: jnp.zeros((m,) + o.shape, jnp.float32), owned)
        if gc.uses_ef else {})
    specs = zero_state_specs(cfg, opt, gc, mesh)
    return tuple(
        jax.device_put(v, jax.tree.map(lambda s: s.sharding, spec))
        for v, spec in zip((owned, opt_state, ef), specs))


# ---------------------------------------------------------------------------
# Serve step
# ---------------------------------------------------------------------------
def make_serve_step(cfg, mesh):
    """jit'd (params, DecodeState, tokens (B,1)) → (logits (B,V), state)."""
    return recompile_lib.register(
        "dist.serve_step",
        jax.jit(functools.partial(decode_lib.decode_step, cfg)))


def serve_state_specs(cfg, mesh, global_batch: int, seq_len: int):
    """Sharded ShapeDtypeStructs for (params, decode state, tokens)."""
    p_shapes = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.key(0), cfg))
    pspecs = param_specs(p_shapes, _model_axis(mesh))
    params = _with_shardings(p_shapes, pspecs, mesh)

    axes = data_axes_for(global_batch, mesh)
    first = _lead_axes(axes)
    state_shapes = decode_lib.decode_state_specs(cfg, global_batch, seq_len)

    def cache_spec(name, leaf):
        if name == "signs" or not axes:          # per-layer constants
            return P(*([None] * len(leaf.shape)))
        return P(None, first, *([None] * (len(leaf.shape) - 2)))

    caches = {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype,
        sharding=NamedSharding(mesh, cache_spec(k, v)))
        for k, v in state_shapes.caches.items()}
    pos = jax.ShapeDtypeStruct(
        state_shapes.pos.shape, state_shapes.pos.dtype,
        sharding=NamedSharding(mesh, P(first) if axes else P(None)))
    state = decode_lib.DecodeState(caches=caches, pos=pos)
    tokens = jax.ShapeDtypeStruct(
        (global_batch, 1), jnp.int32,
        sharding=NamedSharding(mesh, P(first, None) if axes else P(None, None)))
    return params, state, tokens
