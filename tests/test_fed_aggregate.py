"""Stacked server aggregation: bit-exactness with the list reference,
sum modes, weight guards, codec-spec canonicalization, audit caching."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fed import (AdaptiveConfig, ClientConfig, FedConfig, Federation,
                       ServerConfig, server as server_lib)
from repro import codecs as registry
from repro.optimizer import sgd


def _random_tree(key, lanes=None):
    ks = jax.random.split(key, 3)
    shape = lambda s: ((lanes,) + s) if lanes is not None else s
    return {"w": jax.random.normal(ks[0], shape((13, 5)), jnp.float32),
            "b": jax.random.normal(ks[1], shape((29,)), jnp.float32)}


def _server_cfgs(sum_mode="sequential"):
    return [
        ServerConfig(sum_mode=sum_mode),
        ServerConfig(aggregator="fedopt", optimizer=sgd(1.0, momentum=0.5),
                     sum_mode=sum_mode),
        ServerConfig(aggregator="fedmem", server_lr=0.7, sum_mode=sum_mode),
    ]


# ---------------------------------------------------------------------------
# aggregate_stacked vs the list reference, unit level
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("lanes", [1, 3, 6])
@pytest.mark.parametrize("slot_weighted", [False, True])
def test_stacked_sequential_bitwise_matches_list_reference(lanes,
                                                           slot_weighted):
    """Every aggregator, every piece of server state, bit for bit."""
    m_total = 8
    key = jax.random.key(0)
    params = _random_tree(jax.random.fold_in(key, 99))
    stacked = _random_tree(jax.random.fold_in(key, 1), lanes=lanes)
    deltas = [jax.tree.map(lambda x, i=i: x[i], stacked)
              for i in range(lanes)]
    rng = np.random.default_rng(3)
    weights = rng.uniform(0.5, 2.0, lanes)
    ids = sorted(rng.choice(m_total, size=lanes, replace=False).tolist())
    slot_w = rng.uniform(0.5, 2.0, m_total) if slot_weighted else None
    for cfg in _server_cfgs():
        state = server_lib.init_server(params, cfg, m_total)
        ref = server_lib.aggregate(
            state, cfg, deltas, weights, ids,
            slot_weights=slot_w if cfg.aggregator == "fedmem" else None)
        got = server_lib.aggregate_stacked(
            state, cfg, stacked, weights, ids,
            slot_weights=slot_w if cfg.aggregator == "fedmem" else None)
        for name, r, g in (("params", ref.params, got.params),
                           ("opt_state", ref.opt_state, got.opt_state),
                           ("memory", ref.memory, got.memory)):
            for rl, gl in zip(jax.tree.leaves(r), jax.tree.leaves(g)):
                np.testing.assert_array_equal(
                    np.asarray(rl), np.asarray(gl),
                    err_msg=f"{cfg.aggregator}/{name} diverged")


def test_stacked_pairwise_matches_to_tolerance():
    """sum_mode='pairwise' reduces in a different order: equal to the
    sequential reference only to float tolerance (and for 1-2 lanes, where
    the orders coincide, exactly)."""
    key = jax.random.key(7)
    params = _random_tree(jax.random.fold_in(key, 99))
    for lanes in (1, 2, 5, 9):
        stacked = _random_tree(jax.random.fold_in(key, lanes), lanes=lanes)
        weights = np.random.default_rng(lanes).uniform(0.5, 2.0, lanes)
        seq = server_lib.aggregate_stacked(
            server_lib.init_server(params, ServerConfig(), 4),
            ServerConfig(sum_mode="sequential"), stacked, weights)
        pw = server_lib.aggregate_stacked(
            server_lib.init_server(params, ServerConfig(), 4),
            ServerConfig(sum_mode="pairwise"), stacked, weights)
        for s, p in zip(jax.tree.leaves(seq.params),
                        jax.tree.leaves(pw.params)):
            np.testing.assert_allclose(np.asarray(s), np.asarray(p),
                                       rtol=1e-5, atol=1e-6)
        if lanes <= 2:
            for s, p in zip(jax.tree.leaves(seq.params),
                            jax.tree.leaves(pw.params)):
                np.testing.assert_array_equal(np.asarray(s), np.asarray(p))


def test_sum_mode_validation():
    with pytest.raises(ValueError, match="sum_mode"):
        ServerConfig(sum_mode="bogus")


def test_stacked_weight_arity_checked():
    params = _random_tree(jax.random.key(0))
    stacked = _random_tree(jax.random.key(1), lanes=3)
    state = server_lib.init_server(params, ServerConfig(), 3)
    with pytest.raises(ValueError, match="weights"):
        server_lib.aggregate_stacked(state, ServerConfig(), stacked,
                                     np.ones(2))


def test_stacked_norms_match_host_reference():
    """Device-side per-lane norms (what the decode programs emit) agree with
    the float64 host oracle to f32 precision."""
    stacked = _random_tree(jax.random.key(4), lanes=5)
    lanes = [jax.tree.map(lambda x, i=i: x[i], stacked) for i in range(5)]
    dev = np.asarray(server_lib.stacked_norms(stacked))
    host = server_lib.delta_norms(lanes)
    np.testing.assert_allclose(dev, host, rtol=1e-6)


# ---------------------------------------------------------------------------
# non-positive weight sums must fail loudly, not NaN-poison the params
# ---------------------------------------------------------------------------
def test_zero_weight_sum_raises():
    deltas = [{"x": jnp.ones(4)}, {"x": jnp.ones(4)}]
    with pytest.raises(ValueError, match="positive"):
        server_lib.weighted_mean(deltas, np.zeros(2))


def test_nan_inf_and_negative_weight_sums_raise():
    deltas = [{"x": jnp.ones(4)}]
    for bad in (np.array([np.nan]), np.array([-1.0]), np.array([np.inf])):
        with pytest.raises(ValueError, match="positive"):
            server_lib.weighted_mean(deltas, bad)


def test_stacked_and_fedmem_slot_weight_guards():
    params = {"x": jnp.ones(4)}
    stacked = {"x": jnp.ones((2, 4))}
    cfg = ServerConfig(aggregator="fedmem")
    state = server_lib.init_server(params, cfg, 3)
    avg = ServerConfig()
    with pytest.raises(ValueError, match="positive"):
        server_lib.aggregate_stacked(server_lib.init_server(params, avg, 3),
                                     avg, stacked, np.zeros(2))
    with pytest.raises(ValueError, match="slot_weights"):
        server_lib.aggregate_stacked(state, cfg, stacked, np.ones(2), [0, 1],
                                     slot_weights=np.zeros(3))
    deltas = [{"x": jnp.ones(4)}, {"x": jnp.ones(4)}]
    with pytest.raises(ValueError, match="slot_weights"):
        server_lib.aggregate(state, cfg, deltas, np.ones(2), [0, 1],
                             slot_weights=np.zeros(3))
    # fedmem NEVER reads the participant weights (its direction comes from
    # the slots) — both layouts must accept a zero weight sum there, like
    # the list reference always has
    ref = server_lib.aggregate(state, cfg, deltas, np.zeros(2), [0, 1])
    got = server_lib.aggregate_stacked(state, cfg, stacked, np.zeros(2),
                                       [0, 1])
    np.testing.assert_array_equal(np.asarray(ref.params["x"]),
                                  np.asarray(got.params["x"]))


# ---------------------------------------------------------------------------
# the full driver: stacked pipeline ≡ PR-2 sequential reference, bit for bit
# ---------------------------------------------------------------------------
def _mixed_population(seed=0):
    """m=6: three ndsc R=2 clients with equal specs, two sub-linear ndsc
    R=0.75, one identity; one client has a different shard shape."""
    ka, kx = jax.random.split(jax.random.key(seed))
    m, dim, n = 6, 48, 64
    a = jax.random.normal(ka, (m, n, dim)) / jnp.sqrt(n)
    x_true = jax.random.normal(kx, (dim,))
    shards = [{"a": a[i], "b": a[i] @ x_true} for i in range(m)]
    shards[5] = {"a": a[5][:32], "b": (a[5] @ x_true)[:32]}

    def loss_fn(p, batch):
        r = batch["a"] @ p["x"] - batch["b"]
        return 0.5 * jnp.mean(r * r)

    codecs = ([registry.make("ndsc", budget=2.0, chunk=32) for _ in range(3)]
              + [registry.make("ndsc", budget=0.75, chunk=32)
                 for _ in range(2)]
              + [registry.make("identity")])
    return loss_fn, {"x": jnp.zeros(dim)}, shards, codecs


@pytest.mark.parametrize("agg", ["fedavg", "fedopt", "fedmem"])
def test_driver_stacked_bit_exact_with_sequential_reference(agg):
    """The stacked on-device pipeline (cohort decode → concat →
    aggregate_stacked, sum_mode='sequential') reproduces the PR-2 list-
    reference driver bit for bit — params, fedmem memory, fedopt optimizer
    state — on a mixed population with partial participation, stragglers
    and data_size weighting."""
    loss_fn, params, shards, codecs = _mixed_population()
    scfg = {"fedavg": ServerConfig(),
            "fedopt": ServerConfig(aggregator="fedopt",
                                   optimizer=sgd(1.0, momentum=0.5)),
            "fedmem": ServerConfig(aggregator="fedmem")}[agg]
    ccfg = ClientConfig(local_steps=2, lr=0.3)
    out = {}
    for use_cohorts in (True, False):
        fed = Federation(loss_fn, params, shards, list(codecs), ccfg, scfg,
                         seed=3, use_cohorts=use_cohorts)
        hist = fed.run(FedConfig(num_rounds=6, participation=0.8, dropout=0.2,
                                 seed=9, weighting="data_size"))
        out[use_cohorts] = (fed, hist)
    fed_c, hist_c = out[True]
    fed_s, hist_s = out[False]
    assert hist_c["participants"] == hist_s["participants"]
    assert hist_c["wire_bytes"] == hist_s["wire_bytes"]
    np.testing.assert_array_equal(np.asarray(fed_c.server.params["x"]),
                                  np.asarray(fed_s.server.params["x"]))
    for c, s in zip(jax.tree.leaves(fed_c.server.opt_state),
                    jax.tree.leaves(fed_s.server.opt_state)):
        np.testing.assert_array_equal(np.asarray(c), np.asarray(s))
    for c, s in zip(jax.tree.leaves(fed_c.server.memory),
                    jax.tree.leaves(fed_s.server.memory)):
        np.testing.assert_array_equal(np.asarray(c), np.asarray(s))


def test_driver_ema_norms_bit_exact_across_paths():
    """The decode-program-emitted norms feed the allocator EMA identically
    on the stacked and reference paths (the adaptive trajectories can only
    be regression-tested if the EMA state matches bitwise)."""
    loss_fn, params, shards, _ = _mixed_population()
    factory = lambda r: registry.make("ndsc", budget=float(r), chunk=32)
    acfg = AdaptiveConfig(total_rate=8.0, realloc_every=2, grid=0.25,
                          hysteresis=0.25, min_rate=0.25)
    ema, rates = {}, {}
    for use_cohorts in (True, False):
        fed = Federation(loss_fn, params, shards[:4], [factory(2.0)] * 4,
                         ClientConfig(local_steps=1, lr=0.3), ServerConfig(),
                         seed=1, use_cohorts=use_cohorts, adaptive=acfg,
                         codec_factory=factory)
        hist = fed.run(FedConfig(num_rounds=6, participation=0.8, seed=5))
        ema[use_cohorts] = fed._ema.norms.copy()
        rates[use_cohorts] = hist["rates"]
    np.testing.assert_array_equal(ema[True], ema[False])
    assert rates[True] == rates[False]


# ---------------------------------------------------------------------------
# codec_spec canonicalization: factory defaults must not split cohorts
# ---------------------------------------------------------------------------
def test_codec_spec_binds_factory_defaults():
    """make('ndsc', 1.5) and make('ndsc', 1.5, chunk=128) build identical
    codecs — their specs must compare equal (chunk=128 IS the default)."""
    a = registry.make("ndsc", budget=1.5)
    b = registry.make("ndsc", budget=1.5, chunk=128)
    c = registry.make("ndsc", budget=1.5, chunk=128, exact_keep=True, seed=0)
    d = registry.make("ndsc", budget=1.5, chunk=64)
    assert a.spec == b.spec == c.spec
    assert a.spec != d.spec
    # kwarg ORDER never mattered; defaults now don't either, across backends
    assert (registry.make("dsc", budget=2.0).spec
            == registry.make("dsc", budget=2.0, dithered=False).spec)
    assert (registry.make("topk", budget=2.0).spec
            == registry.make("topk", budget=2.0, quant_levels=256).spec)


def test_codec_spec_unknown_name_raises():
    with pytest.raises(ValueError, match="unknown"):
        registry.codec_spec("nope", 2.0, {})


def test_make_accepts_spec_tuple():
    """make(spec) rebuilds a codec from its canonical identity —
    make(c.spec).spec == c.spec — so checkpoints and benchmarks can
    round-trip codecs without re-plumbing the original kwargs."""
    for args in (("ndsc", 1.5, {"chunk": 64}),
                 ("ndsc", [1.0, 2.0], {"chunk": 32}),   # per-leaf budgets
                 ("dsc", 2.0, {"dithered": True}),
                 ("qsgd", 4.0, {}),
                 ("topk", 2.0, {"quant_levels": 64})):
        name, budget, kwargs = args
        direct = registry.make(name, budget, **kwargs)
        rebuilt = registry.make(direct.spec)
        assert rebuilt.spec == direct.spec, args
        assert rebuilt.name == direct.name
        # and the spec constructor alone agrees with codec_spec
        assert registry.make(
            registry.codec_spec(name, budget, kwargs)).spec == direct.spec
    # spec-form rejects extra arguments and malformed tuples
    c = registry.make("ndsc", 1.5)
    with pytest.raises(ValueError, match="no extra"):
        registry.make(c.spec, 2.0)
    with pytest.raises(ValueError, match="no extra"):
        registry.make(c.spec, chunk=32)
    with pytest.raises(ValueError, match="malformed"):
        registry.make(("ndsc", 1.5))
    # a spec-rebuilt codec encodes/decodes identically to the original
    key = jax.random.key(0)
    tree = {"w": jax.random.normal(jax.random.key(1), (96,))}
    wire_a = c.encode(key, tree)
    wire_b = registry.make(c.spec).encode(key, tree)
    for xa, xb in zip(jax.tree.leaves(wire_a), jax.tree.leaves(wire_b)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def test_equivalent_make_calls_share_one_cohort_and_compile():
    """Clients built with and without the factory-default kwargs land in ONE
    cohort: a single vmapped round/decode program is compiled, not two."""
    loss_fn, params, shards, _ = _mixed_population()
    codecs = [registry.make("ndsc", budget=1.5),
              registry.make("ndsc", budget=1.5, chunk=128),
              registry.make("ndsc", budget=1.5, chunk=128, seed=0)]
    fed = Federation(loss_fn, params, shards[:3], codecs,
                     ClientConfig(local_steps=1, lr=0.2), ServerConfig(),
                     seed=0)
    fed.run(FedConfig(num_rounds=2))
    assert len(fed._cohort_fns) == 1
    assert len(fed._cohort_decode_fns) == 1
    assert len(fed._round_fns) == 1


# ---------------------------------------------------------------------------
# analytic-audit caching: computed once per spec, ledger unchanged
# ---------------------------------------------------------------------------
def test_audit_cache_one_entry_per_spec_and_ledger_unchanged():
    loss_fn, params, shards, codecs = _mixed_population()
    fed = Federation(loss_fn, params, shards, list(codecs),
                     ClientConfig(local_steps=1, lr=0.2), ServerConfig(),
                     seed=0)
    # 3 distinct specs (ndsc R=2, ndsc R=0.75, identity) → 3 cached audits
    assert len(fed._audit_bits) == 3
    hist = fed.run(FedConfig(num_rounds=3, participation=0.8, seed=2))
    for ana, parts in zip(hist["analytic_bytes"], hist["participants"]):
        direct = sum(codecs[i].wire_bits(params) / 8.0 for i in parts)
        assert ana == direct
    assert hist["wire_bytes"] == hist["analytic_bytes"]


def test_audit_cache_survives_rate_reallocation():
    """set_rates reuses cached audits for previously seen specs and the
    ledger stays byte-exact across the rebuild."""
    loss_fn, params, shards, _ = _mixed_population()
    factory = lambda r: registry.make("ndsc", budget=float(r), chunk=32)
    acfg = AdaptiveConfig(total_rate=8.0, realloc_every=2, hysteresis=0.0,
                          grid=0.25, min_rate=0.25)
    fed = Federation(loss_fn, params, shards[:4], [factory(2.0)] * 4,
                     ClientConfig(local_steps=1, lr=0.3), ServerConfig(),
                     seed=0, adaptive=acfg, codec_factory=factory)
    hist = fed.run(FedConfig(num_rounds=8, seed=1))
    assert any(hist["realloc"])
    assert hist["wire_bytes"] == hist["analytic_bytes"]
    # one audit entry per distinct spec ever installed
    specs = {registry.make("ndsc", budget=float(r), chunk=32).spec
             for rates in hist["rates"] for r in rates}
    assert len(fed._audit_bits) == len(specs)


# ---------------------------------------------------------------------------
# spec-less codecs still work end to end (object-keyed caches)
# ---------------------------------------------------------------------------
def test_specless_codec_round_trip():
    loss_fn, params, shards, _ = _mixed_population()
    bare = dataclasses.replace(registry.make("ndsc", budget=2.0, chunk=32),
                               spec=None)
    fed = Federation(loss_fn, params, shards[:2], bare,
                     ClientConfig(local_steps=1, lr=0.2), ServerConfig(),
                     seed=0)
    hist = fed.run(FedConfig(num_rounds=2))
    assert hist["wire_bytes"] == hist["analytic_bytes"]
    assert len(fed._audit_bits) == 1       # keyed by the codec object


# ---------------------------------------------------------------------------
# the mesh padding contract: zero-weight lanes are admitted and inert
# ---------------------------------------------------------------------------
def test_zero_weight_padding_lanes_are_inert():
    """aggregate_stacked with trailing zero-weight lanes (the mesh backend's
    padding layout) passes the weight guard and produces the SAME result as
    the unpadded stack — sequential mode bitwise, pairwise to tolerance."""
    key = jax.random.key(4)
    lanes, pads = 5, 3
    real = _random_tree(key, lanes=lanes)
    junk = _random_tree(jax.random.fold_in(key, 1), lanes=pads)
    padded = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), real, junk)
    w = np.random.default_rng(7).uniform(0.5, 2.0, lanes)
    w_padded = np.concatenate([w, np.zeros(pads)])
    params = _random_tree(jax.random.fold_in(key, 2))
    for sum_mode in ("sequential", "pairwise"):
        cfg = ServerConfig(sum_mode=sum_mode)
        state = server_lib.init_server(params, cfg, lanes + pads)
        ref = server_lib.aggregate_stacked(state, cfg, real, w)
        got = server_lib.aggregate_stacked(state, cfg, padded, w_padded)
        for rl, gl, pl, xl in zip(jax.tree.leaves(ref.params),
                                  jax.tree.leaves(got.params),
                                  jax.tree.leaves(params),
                                  jax.tree.leaves(real)):
            if sum_mode == "sequential":
                np.testing.assert_array_equal(np.asarray(rl), np.asarray(gl))
            else:
                # a different fold order rounds differently: bound the gap
                # in f32 ulps of the operands, not of the (possibly
                # cancelled) result — log2(8) levels per fold, the weight
                # normalization and the params add
                w_bar = (w / w.sum()).reshape((-1,) + (1,) * (xl.ndim - 1))
                magnitude = (np.abs(np.asarray(pl))
                             + np.sum(w_bar * np.abs(np.asarray(xl)), axis=0))
                gap = np.abs(np.asarray(rl) - np.asarray(gl))
                bound = 8 * np.finfo(np.float32).eps * magnitude
                assert np.all(gap <= bound), np.max(gap / bound)


def test_weight_guard_rejects_negative_and_nonfinite_entries():
    """Exact zeros pass (padding lanes); anything negative or non-finite is
    poison even when the SUM still looks positive."""
    deltas = [{"x": jnp.ones(4)}, {"x": jnp.ones(4)}]
    server_lib._check_weights(np.array([1.0, 0.0]))            # zeros OK
    for bad in (np.array([2.0, -1.0]),       # positive sum, negative entry
                np.array([1.0, np.nan]),
                np.array([1.0, np.inf])):
        with pytest.raises(ValueError, match="non-negative|positive"):
            server_lib.weighted_mean(deltas, bad)


def test_concat_stacks_perm_drops_padded_lanes():
    """concat_stacks' gather permutation can SELECT lanes, not just reorder
    them: stacks with trailing padding join into a real-lanes-only result.
    (The driver's mesh join slices padding off before concat — this pins
    down that the perm itself is also a safe way to drop lanes, so zero
    lanes can never leak into an aggregate through it.)"""
    import repro.fed.clients as clients_lib

    def tree(v, lanes):
        return {"x": jnp.full((lanes, 3), float(v))}

    # cohort A: lanes 0..2 real (clients 4,1,2), one pad; cohort B: lanes
    # 0..1 real (clients 3,0), two pads
    a = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                     tree(4, 1), tree(1, 1), tree(2, 1), tree(-99, 1))
    b = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                     tree(3, 1), tree(0, 1), tree(-77, 2))
    # participant order 0..4; global lane layout [A(4 lanes), B(4 lanes)]
    perm = [5, 1, 2, 4, 0]     # client i at global lane perm[i]
    joined = clients_lib.concat_stacks([a, b], perm)
    np.testing.assert_array_equal(np.asarray(joined["x"][:, 0]),
                                  [0.0, 1.0, 2.0, 3.0, 4.0])
    assert joined["x"].shape[0] == 5       # pads dropped by the gather
