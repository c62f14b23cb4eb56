"""The obs hard contract: enabling observability changes NOTHING.

Params, client EF states, the wire ledger/history and the compiled-program
cache sizes must be identical between an instrumented and an
uninstrumented run — obs is observe-only, host-side, outside jit. Checked
on both Federation backends (vmap cohorts and mesh lane placement) and on
the dist consensus train step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data import batch_for_shape
from repro.dist import step as step_lib
from repro.dist.gradcomp import GradCompConfig
from repro.fed import (ClientConfig, FedConfig, Federation, ServerConfig)
from repro import codecs as registry
from repro.models import model as model_lib
from repro.obs import core as obs
from repro.obs import recompile
from repro.obs.sinks import MemorySink
from repro.optimizer import sgd
from repro.serve import Engine, Request, ServeConfig


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _problem(m=4, dim=24, n=16, seed=3):
    ka, kx = jax.random.split(jax.random.key(seed))
    a = jax.random.normal(ka, (m, n, dim)) / jnp.sqrt(n)
    x_true = jax.random.normal(kx, (dim,))
    shards = [{"a": a[i], "b": a[i] @ x_true} for i in range(m)]

    def loss_fn(p, batch):
        r = batch["a"] @ p["x"] - batch["b"]
        return 0.5 * jnp.mean(r * r)

    return shards, loss_fn, {"x": jnp.zeros(dim)}


@pytest.mark.parametrize("backend", ["vmap", "mesh"])
def test_federation_bit_exact_and_no_extra_recompiles(backend):
    shards, loss_fn, params = _problem()

    def build():
        return Federation(loss_fn, params, shards,
                          registry.make("ndsc", 4.0, chunk=32),
                          ClientConfig(local_steps=2, lr=0.2),
                          ServerConfig(aggregator="fedavg"), seed=5,
                          backend=backend)

    cfg = FedConfig(num_rounds=4, participation=0.9, dropout=0.2, seed=11)

    # warm the process-wide lru-cached programs (the server aggregate folds,
    # keyed on participant-lane count): they compile once per process, so
    # whichever arm ran first would otherwise be charged for them — an order
    # artifact, not an obs effect. Same cfg ⇒ same participant draws ⇒ same
    # lane counts as both measured arms.
    build().run(cfg)

    base = recompile.counts()
    fed_off = build()
    hist_off = fed_off.run(cfg)
    compiles_off = recompile.delta(base, recompile.counts())

    base = recompile.counts()
    o = obs.enable()
    fed_on = build()
    hist_on = fed_on.run(cfg)
    obs.disable()
    compiles_on = recompile.delta(base, recompile.counts())

    assert _tree_equal(fed_off.server.params, fed_on.server.params)
    assert _tree_equal([s.ef for s in fed_off.states],
                       [s.ef for s in fed_on.states])
    assert hist_off == hist_on                    # ledger + history exact
    # same programs, same number of compiled specializations: obs added none
    assert compiles_on == compiles_off
    # and the session actually observed the run
    s = o.summary()
    assert s["counters"]["fed.rounds"]["total"] == 4.0
    assert s["counters"]["fed.wire_bytes"]["total"] == sum(
        hist_off["wire_bytes"])
    assert "fed.round" in s["spans"]

    # PR-10 contract: the cost model captured the round program, and
    # reading the snapshot touches no jit cache (counts pinned around it)
    base = recompile.counts()
    snap = o.costs()
    assert recompile.counts() == base
    prog_name = "fed.round.cohort" if backend == "vmap" else "fed.round.mesh"
    prog = snap["programs"][prog_name]
    assert prog["calls"] > 0 and prog["wire_bytes"] > 0
    for spec in prog["specializations"]:      # cost analysis may degrade
        assert spec["available"] or spec["reason"]   # ... but never crash
    attrib = s["spans"]["fed.clients.compute"]["attrib"]
    assert attrib["calls_observed"] >= prog["calls"]
    assert attrib["wire_min_bytes"] >= prog["wire_bytes"]


def test_federation_run_obs_argument_scopes_session():
    """`Federation.run(obs=...)` instruments exactly that run, without a
    globally-enabled session."""
    shards, loss_fn, params = _problem()
    fed = Federation(loss_fn, params, shards,
                     registry.make("ndsc", 4.0, chunk=32),
                     ClientConfig(local_steps=1, lr=0.2),
                     ServerConfig(), seed=5)
    session = obs.Obs(sinks=(MemorySink(),))
    fed.run(FedConfig(num_rounds=2), obs=session)
    assert not obs.enabled()                      # run() released it
    session.close()
    s = session.summary()
    assert s["counters"]["fed.rounds"]["total"] == 2.0
    metas = [e for e in session.memory_events()
             if e["type"] == "meta" and e["name"] == "fed.run.summary"]
    assert len(metas) == 1 and metas[0]["data"]["rounds"] == 2


def test_serve_engine_bit_exact_and_no_extra_recompiles():
    """The serve engine under obs: token streams, admissions and the final
    decode state are bitwise identical with observability on or off, and
    obs adds zero compiled specializations (the engine's jitted programs
    are shared process-wide per (config, max_seq))."""
    cfg = configs.get_reduced("yi-6b")
    params = model_lib.init_params(jax.random.key(0), cfg)
    prefix = np.arange(9, dtype=np.int32) + 2
    prompts = [jnp.arange(3 + i, dtype=jnp.int32) for i in range(4)]

    def run():
        eng = Engine(cfg, params, ServeConfig(slots=2, max_seq=48))
        eng.register_prefix("sys", prefix, prefill=True)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=4,
                               prefix_id="sys" if i % 2 else None))
        finished = eng.run_to_completion()
        return ([(r.rid, r.admission, r.tokens_out) for r in finished],
                eng.state)

    run()    # warm the process-shared jitted programs + specializations

    base = recompile.counts()
    out_off, state_off = run()
    compiles_off = recompile.delta(base, recompile.counts())

    base = recompile.counts()
    o = obs.enable()
    out_on, state_on = run()
    obs.disable()
    compiles_on = recompile.delta(base, recompile.counts())

    assert out_off == out_on                      # streams + admissions
    assert _tree_equal((state_off.caches, state_off.pos),
                       (state_on.caches, state_on.pos))
    assert compiles_on == compiles_off
    s = o.summary()
    assert s["counters"]["serve.submitted"]["count"] == 4
    assert s["counters"]["serve.requests"]["count"] == 4
    assert s["counters"]["serve.prefix.hit"]["count"] == 2
    assert s["counters"]["serve.prefill_bytes_saved"]["total"] > 0
    assert s["hists"]["serve.ttft_s"]["count"] == 4
    assert "serve.decode_step" in s["spans"]
    assert "serve.admit_prefix" in s["spans"]

    base = recompile.counts()
    snap = o.costs()
    assert recompile.counts() == base
    decode = snap["programs"]["serve.decode_step"]
    assert decode["calls"] > 0
    for spec in decode["specializations"]:
        assert spec["available"] or spec["reason"]
    assert {"serve.prefill", "serve.admit_prefix",
            "serve.admit_cold"} <= set(snap["programs"])


def test_dist_step_bit_exact_and_no_extra_recompiles(mesh):
    cfg = configs.get_reduced("llama3.2-3b")
    gc = GradCompConfig(bits=4, chunk=256, strategy="allgather_packed")
    opt = sgd(1e-2, momentum=0.9)
    batch = batch_for_shape(cfg, 2, 16)

    def run_steps():
        tstep = step_lib.make_train_step(cfg, opt, gc, mesh)
        params, opt_state, ef = step_lib.init_train_state(cfg, opt, gc, mesh)
        for _ in range(2):
            params, opt_state, ef, metrics = tstep(params, opt_state, ef,
                                                   batch)
        # the caller holds tstep so recompile.counts() can still read its
        # cache size after this returns
        return params, ef, metrics, tstep

    base = recompile.counts()
    p_off, ef_off, m_off, step_off = run_steps()
    compiles_off = recompile.delta(base, recompile.counts())

    base = recompile.counts()
    o = obs.enable()
    p_on, ef_on, m_on, step_on = run_steps()
    obs.disable()
    compiles_on = recompile.delta(base, recompile.counts())

    assert _tree_equal(p_off, p_on)
    assert _tree_equal(ef_off, ef_on)
    assert float(m_off["loss"]) == float(m_on["loss"])
    assert compiles_on == compiles_off
    s = o.summary()
    # the step emits one span per call and nothing else: no per-step
    # counters, no cost-model capture of the train step
    span = s["spans"]["dist.step"]
    assert span["count"] == 2
    steps = [e for e in o.memory_events()
             if e["type"] == "span" and e["name"] == "dist.step"]
    assert [e["attrs"] for e in steps] == [{"strategy": gc.strategy}] * 2
    assert not [n for n in s["counters"] if n.startswith("dist.")]

    base = recompile.counts()
    snap = o.costs()
    assert recompile.counts() == base
    assert "dist.step" not in snap["programs"]
    assert "attrib" not in span
