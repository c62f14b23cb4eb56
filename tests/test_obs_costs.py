"""The device cost model: capture, compile-free extraction, attribution.

The load-bearing guarantee: `costs.snapshot()` NEVER triggers an XLA
backend compile and never touches any program's jit cache — proven here by
monkeypatching the compiler entry point to raise, not just by counting.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.obs import core as obs
from repro.obs import costs, recompile, report


def _toy():
    return recompile.register("t.costs.toy", jax.jit(lambda x, y: x @ y))


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------
def test_capture_dedupes_specializations_and_accumulates():
    fn = _toy()
    store = {}
    x = jnp.ones((8, 8))
    costs.record_call(store, "t.costs.toy", fn, (x, x), wire_bytes=10.0)
    costs.record_call(store, "t.costs.toy", fn, (x, x), wire_bytes=10.0)
    y = jnp.ones((16, 16))
    costs.record_call(store, "t.costs.toy", fn, (y, y))
    assert len(store) == 2                      # one record per signature
    rec = next(r for r in store.values() if r["args"][0].shape == (8, 8))
    assert rec["calls"] == 2 and rec["wire_bytes"] == 20.0
    # captured args are abstract — no live arrays (or tracers) retained
    assert all(isinstance(a, jax.ShapeDtypeStruct) for a in rec["args"])


def test_python_scalars_do_not_mint_specializations():
    """A jitted program traced once covers every value of a dynamic python
    int (e.g. the round index) — the capture must key by type, not value."""
    fn = jax.jit(lambda x, i: x + i)
    store = {}
    x = jnp.ones(4)
    for i in range(5):
        costs.record_call(store, "t.costs.scalar", fn, (x, i))
    assert len(store) == 1
    assert next(iter(store.values()))["calls"] == 5


def test_static_tag_separates_closures():
    store = {}
    x = jnp.ones(8)
    for bits in (1, 4):
        fn = functools.partial(lambda v, bits: v * bits, bits=bits)
        costs.record_call(store, "t.costs.bits", fn, (x,), jit_wrap=True,
                          static=("bits", bits))
    assert len(store) == 2


# ---------------------------------------------------------------------------
# extraction: compile-free by construction
# ---------------------------------------------------------------------------
def test_snapshot_never_backend_compiles(monkeypatch):
    """The hard proof: with the XLA compile entry point booby-trapped,
    the default snapshot still extracts FLOPs/bytes."""
    fn = _toy()
    x = jnp.ones((32, 32))
    fn(x, x)                                    # the real compile, up front
    store = {}
    costs.record_call(store, "t.costs.toy", fn, (x, x))

    import jax._src.compiler as compiler

    def boom(*a, **k):
        raise AssertionError("cost extraction triggered a backend compile")

    monkeypatch.setattr(compiler, "backend_compile", boom)
    snap = costs.snapshot(store)
    spec = snap["programs"]["t.costs.toy"]["specializations"][0]
    assert spec["available"] and spec["source"] == "lowered"
    assert spec["flops"] and spec["flops"] > 0
    assert spec["bytes_accessed"] and spec["bytes_accessed"] > 0
    assert spec["argument_bytes"] == 2 * 32 * 32 * 4


def test_snapshot_leaves_jit_cache_and_registry_untouched():
    fn = _toy()
    x = jnp.ones((8, 8))
    fn(x, x)
    store = {}
    costs.record_call(store, "t.costs.toy", fn, (x, x))
    before_cache = fn._cache_size()
    before_counts = recompile.counts()
    costs.snapshot(store)
    costs.snapshot(store, compile_ok=True)      # AOT path: also outside jit
    assert fn._cache_size() == before_cache
    assert recompile.counts() == before_counts


def test_compile_ok_adds_memory_analysis():
    fn = _toy()
    x = jnp.ones((16, 16))
    fn(x, x)
    store = {}
    costs.record_call(store, "t.costs.toy", fn, (x, x))
    spec = costs.snapshot(store, compile_ok=True)[
        "programs"]["t.costs.toy"]["specializations"][0]
    assert spec["source"] == "compiled" and spec["available"]
    assert spec["peak_bytes"] and spec["peak_bytes"] > 0
    assert spec["output_bytes"] == 16 * 16 * 4


def test_unavailable_backend_degrades_with_reason():
    """A program that refuses to re-lower must yield available=False with
    the reason recorded — never an exception out of snapshot()."""
    def broken(*args):
        raise RuntimeError("this backend has no cost analysis")

    store = {}
    costs.record_call(store, "t.costs.broken", broken, (jnp.ones(4),),
                      jit_wrap=True)
    # force the failure through the real lower() path
    snap = costs.snapshot(store)
    spec = snap["programs"]["t.costs.broken"]["specializations"][0]
    assert spec["available"] is False
    assert "no cost analysis" in spec["reason"]
    assert spec["flops"] is None and spec["bytes_accessed"] is None
    assert snap["programs"]["t.costs.broken"]["cost_coverage"] == 0.0


def test_jit_wrap_capture_never_registers_or_compiles():
    """Kernel-style capture: snapshot jits a FRESH wrapper for lowering
    only — the recompile registry must not grow a new program for it."""
    store = {}
    costs.record_call(store, "t.costs.plain", lambda x: x * 2.0,
                      (jnp.ones(16),), jit_wrap=True)
    names_before = set(recompile.counts())
    spec = costs.snapshot(store)["programs"]["t.costs.plain"][
        "specializations"][0]
    assert spec["available"] and spec["flops"] is not None
    assert set(recompile.counts()) == names_before


# ---------------------------------------------------------------------------
# peaks + attribution
# ---------------------------------------------------------------------------
def test_peaks_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PEAK_FLOPS", "2e12")
    monkeypatch.setenv("REPRO_PEAK_BYTES", "1e11")
    pk = costs.peaks()
    assert pk == {"flops_per_s": 2e12, "bytes_per_s": 1e11,
                  "backend": pk["backend"], "device_kind": pk["device_kind"],
                  "source": "env"}


def test_peaks_device_table_prefix_match():
    pk = costs.peaks(backend="tpu", device_kind="TPU v4 (chip)")
    assert pk["source"] == "device_table"
    assert pk["flops_per_s"] == 275e12


def test_peaks_v5e_reports_as_v5_lite():
    """A v5e chip reports device_kind "TPU v5 lite": it gets the published
    v5e peaks (197 TFLOP/s bf16, 819 GB/s), not another chip's."""
    pk = costs.peaks(backend="tpu", device_kind="TPU v5 lite")
    assert pk["source"] == "device_table"
    assert (pk["flops_per_s"], pk["bytes_per_s"]) == (197e12, 8.19e11)


def test_peaks_unknown_tpu_kind_is_refused(monkeypatch):
    monkeypatch.delenv("REPRO_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("REPRO_PEAK_BYTES", raising=False)
    pk = costs.peaks(backend="tpu", device_kind="TPU v99")
    assert pk["source"] == "unavailable"
    assert pk["flops_per_s"] is None and pk["bytes_per_s"] is None
    assert "TPU v99" in pk["reason"]
    summary = {"spans": {"work": {"count": 1, "total_s": 2.0}}}
    snap = {"peaks": pk,
            "programs": {"prog": {"span": "work", "calls": 1,
                                  "wire_bytes": 0.0, "flops_total": 100.0,
                                  "bytes_total": 5.0, "cost_coverage": 1.0,
                                  "specializations": []}}}
    at = costs.attach_attrib(summary, snap)["spans"]["work"]["attrib"]
    assert at["roofline_frac"] is None and at["t_model_s"] is None
    assert at["unavailable"] == pk["reason"]


def test_attach_attrib_roofline_math():
    summary = {"spans": {"work": {"count": 1, "total_s": 2.0, "mean_s": 2.0,
                                  "max_s": 2.0}}}
    snap = {"peaks": {"flops_per_s": 100.0, "bytes_per_s": 10.0},
            "programs": {"prog": {"span": "work", "calls": 4,
                                  "wire_bytes": 40.0, "flops_total": 100.0,
                                  "bytes_total": 5.0, "cost_coverage": 1.0,
                                  "specializations": []}}}
    costs.attach_attrib(summary, snap)
    at = summary["spans"]["work"]["attrib"]
    assert at["t_flops_s"] == 1.0                # 100 FLOP / 100 FLOP/s
    assert at["t_bytes_s"] == 0.5
    assert at["t_model_s"] == 1.0 and at["bound"] == "flops"
    assert at["roofline_frac"] == 0.5            # 1.0 model / 2.0 measured
    assert at["wire_min_bytes_per_s"] == 20.0
    assert at["flops_per_s_achieved"] == 50.0


def test_attrib_skips_spans_without_programs():
    summary = {"spans": {"lonely": {"count": 1, "total_s": 1.0}}}
    costs.attach_attrib(summary, {"peaks": costs.peaks(), "programs": {}})
    assert "attrib" not in summary["spans"]["lonely"]


# ---------------------------------------------------------------------------
# session integration
# ---------------------------------------------------------------------------
def test_session_costs_and_summary_attrib():
    fn = _toy()
    x = jnp.ones((8, 8))
    fn(x, x)                                    # compile outside the session
    o = obs.enable()
    with obs.span("t.costs.work"):
        obs.observe_program_call("t.costs.toy", fn, (x, x),
                                 span="t.costs.work", wire_bytes=64.0)
        fn(x, x)
    obs.disable()
    s = o.summary()
    prog = s["costs"]["programs"]["t.costs.toy"]
    assert prog["calls"] == 1 and prog["wire_bytes"] == 64.0
    at = s["spans"]["t.costs.work"]["attrib"]
    assert at["roofline_frac"] is not None and at["cost_coverage"] == 1.0
    rendered = report.render(s)
    assert "attrib (roofline)" in rendered and "t.costs.toy" in rendered
    # attribution surfaces as counter tracks for the Chrome trace
    gauge_names = {e["name"] for e in o.memory_events()
                   if e["type"] == "gauge"}
    assert "attrib.t.costs.work.roofline_frac" in gauge_names


def test_costs_false_disables_capture():
    fn = _toy()
    x = jnp.ones((4, 4))
    o = obs.enable(costs=False)
    obs.observe_program_call("t.costs.toy", fn, (x, x))
    obs.disable()
    s = o.summary()
    assert "costs" not in s
    assert o._cost_captures == {}


def test_kernel_dispatch_is_captured(monkeypatch):
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    o = obs.enable()
    ops.encode(jnp.ones((2, 64)), jnp.ones((2, 64)), 4)
    obs.disable()
    snap = o.costs()
    names = [n for n in snap["programs"] if n.startswith("kernels.encode")]
    assert len(names) == 1
    prog = snap["programs"][names[0]]
    spec = prog["specializations"][0]
    assert "static=('bits', 4)" in spec["sig"]
    assert spec["available"] or spec["reason"]   # degrade allowed, crash not


def test_disabled_observe_is_noop():
    assert not obs.enabled()
    obs.observe_program_call("t.costs.toy", _toy(), (jnp.ones(4),))


@pytest.mark.parametrize("bad", [object(), {"weird": object()}])
def test_capture_never_raises_from_odd_args(bad):
    o = obs.enable()
    try:
        o.observe_call("t.costs.odd", lambda x: x, (bad,))
    finally:
        obs.disable()
