"""repro.obs: session stack, sinks, trace export, recompile tracking, and
the instrumentation hooks in kernels.ops and serve.scheduler."""
import json
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.kernels import fwht as fwht_kernel
from repro.kernels import ops
from repro.models import model as model_lib
from repro.obs import core as obs
from repro.obs import recompile, report, trace as trace_lib
from repro.obs.sinks import EventList, MemorySink, load_jsonl
from repro.serve import Engine, Request, ServeConfig


# ---------------------------------------------------------------------------
# disabled path
# ---------------------------------------------------------------------------
def test_disabled_is_noop():
    assert not obs.enabled()
    assert obs.get() is None
    assert obs.span("x") is obs.NOOP_SPAN        # shared singleton, no alloc
    with obs.span("x", k=1):
        pass
    obs.counter("c", 1, k=2)
    obs.gauge("g", 3.0)
    obs.histogram("h", 0.5)


def test_traced_decorator_passthrough_when_disabled():
    calls = []

    @obs.traced("my.fn", tag="t")
    def fn(a, b=2):
        calls.append((a, b))
        return a + b

    assert fn(1, b=3) == 4                        # disabled: plain call
    o = obs.enable()
    assert fn(5) == 7
    obs.disable()
    assert calls == [(1, 3), (5, 2)]
    spans = [e for e in o.memory_events() if e["type"] == "span"]
    assert [s["name"] for s in spans] == ["my.fn"]
    assert spans[0]["attrs"] == {"tag": "t"}


# ---------------------------------------------------------------------------
# sessions, sinks, summary
# ---------------------------------------------------------------------------
def test_enable_disable_stack_and_events():
    o1 = obs.enable()
    assert obs.get() is o1
    o2 = obs.enable()                             # nested: innermost wins
    assert obs.get() is o2
    obs.counter("inner", 1)
    obs.disable()
    assert obs.get() is o1
    obs.counter("outer", 1)
    obs.disable()
    assert not obs.enabled()
    assert [e["name"] for e in o2.memory_events()
            if e["type"] == "counter"] == ["inner"]
    assert [e["name"] for e in o1.memory_events()
            if e["type"] == "counter"] == ["outer"]


def test_use_and_suspended():
    session = obs.Obs(sinks=(MemorySink(),))
    with obs.use(session):
        obs.counter("a", 1)
        with obs.suspended():
            assert not obs.enabled()
            obs.counter("ghost", 1)               # must vanish
        obs.counter("b", 1)
    assert not obs.enabled()
    names = [e["name"] for e in session.memory_events()]
    assert names == ["a", "b"]
    session.close()


def test_span_nesting_depth_and_duration():
    o = obs.enable()
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    obs.disable()
    spans = {e["name"]: e for e in o.memory_events() if e["type"] == "span"}
    assert spans["inner"]["depth"] == 2
    assert spans["outer"]["depth"] == 1
    assert spans["outer"]["dur"] >= spans["inner"]["dur"] >= 0.0
    tid = threading.get_ident() & 0x7FFFFFFF
    assert spans["outer"]["tid"] == tid


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "sub" / "events.jsonl")  # parent dir auto-created
    obs.enable(jsonl=path)
    obs.counter("c", 2, op="fwht")
    with obs.span("s", k=1):
        pass
    obs.disable()
    events = load_jsonl(path)
    assert [e["type"] for e in events] == ["counter", "span", "meta"]
    assert events[0]["value"] == 2.0 and events[0]["attrs"]["op"] == "fwht"
    assert events[-1]["name"] == "obs.summary"    # emitted by close()


def test_summary_aggregates_and_survives_disable():
    o = obs.enable()
    for v in (1.0, 3.0):
        obs.counter("c", v)
        obs.histogram("h", v)
    obs.gauge("g", 7.0)
    with obs.span("s"):
        pass
    obs.disable()
    s = o.summary()
    assert s["counters"]["c"] == {"total": 4.0, "count": 2}
    assert s["hists"]["h"]["count"] == 2 and s["hists"]["h"]["max"] == 3.0
    assert s["gauges"]["g"]["last"] == 7.0
    assert s["spans"]["s"]["count"] == 1
    assert s is o.summary()                       # frozen after close
    rendered = report.render(s)
    assert isinstance(rendered, str) and "s" in rendered


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------
def test_chrome_trace_sink_writes_valid_trace(tmp_path):
    path = str(tmp_path / "trace.json")
    obs.enable(trace=path)
    with obs.span("work", k=1):
        obs.counter("bytes", 10)
    obs.disable()
    n = trace_lib.validate_trace(path)
    doc = json.load(open(path))
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"M", "X", "C"} <= phases              # metadata, span, counter
    assert n == len(doc["traceEvents"]) >= 4
    span = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
    assert span["name"] == "work" and span["dur"] >= 0
    assert span["args"] == {"k": 1}


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError, match="bad phase"):
        trace_lib.validate_trace([{"ph": "Z", "name": "x"}])
    with pytest.raises(ValueError, match="dur"):
        trace_lib.validate_trace(
            [{"ph": "X", "name": "x", "ts": 0.0, "pid": 0}])
    with pytest.raises(ValueError, match="traceEvents"):
        trace_lib.validate_trace({})


def test_jax_profiler_unavailable_is_recorded_not_raised(tmp_path, monkeypatch):
    """Satellite: a missing/broken jax.profiler must degrade to a no-op
    session with a recorded reason, never an exception."""
    import jax as jax_mod

    class Broken:
        def start_trace(self, d):
            raise RuntimeError("no profiler build")

        def stop_trace(self):
            raise RuntimeError("no profiler build")

    monkeypatch.setattr(jax_mod, "profiler", Broken())
    o = obs.enable(jax_trace_dir=str(tmp_path / "jaxtrace"))
    obs.counter("still.works", 1)
    obs.disable()
    s = o.summary()
    assert s["jax_trace"]["active"] is False
    assert "no profiler build" in s["jax_trace"]["error"]
    assert s["counters"]["still.works"]["total"] == 1.0


# ---------------------------------------------------------------------------
# recompile tracker
# ---------------------------------------------------------------------------
def test_recompile_registry_counts_and_delta():
    fn = recompile.register("t.obs.toy", jax.jit(lambda x: x * 2))
    before = recompile.counts()
    fn(jnp.ones(4))
    fn(jnp.ones(8))                               # new shape -> new compile
    fn(jnp.ones(8))                               # cached -> no compile
    after = recompile.counts()
    assert recompile.delta(before, after)["t.obs.toy"] == 2


def test_recompile_counts_survive_gc():
    """An active session pins programs registered during its window, so the
    summary still reports them after the owner (e.g. a benchmark's
    Federation) is garbage-collected."""
    o = obs.enable()
    fn = recompile.register("t.obs.dying", jax.jit(lambda x: x + 1))
    fn(jnp.ones(4))
    del fn
    import gc
    gc.collect()
    obs.disable()
    assert o.summary()["recompiles"]["t.obs.dying"] == 1


# ---------------------------------------------------------------------------
# kernels.ops dispatch counters
# ---------------------------------------------------------------------------
def test_kernel_dispatch_counter(monkeypatch):
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    o = obs.enable()
    ops.fwht(jnp.ones((2, 64)))
    obs.disable()
    events = [e for e in o.memory_events()
              if e["name"] == "kernels.dispatch"]
    assert len(events) == 1
    attrs = events[0]["attrs"]
    assert attrs["op"] == "fwht" and attrs["n"] == 64
    assert attrs["path"] in ("pallas", "ref") and attrs["forced"] is False


def test_kernel_dispatch_counter_block_rows(monkeypatch):
    """On the Pallas path the counter names the tile the call took: the
    rows per grid step `block_rows_for` gives its row count."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    o = obs.enable()
    ops.fwht(jnp.ones((3, 13, 256)))
    obs.disable()
    (event,) = [e for e in o.memory_events()
                if e["name"] == "kernels.dispatch"]
    assert event["attrs"]["path"] == "pallas"
    assert event["attrs"]["block_rows"] == fwht_kernel.block_rows_for(256, 39)
    assert event["attrs"]["block_rows"] == 39


def test_forced_dispatch_error_counts_and_raises(monkeypatch):
    """Satellite: the forced-pallas refusal must BOTH report through the obs
    counter and keep raising."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    n = fwht_kernel.MAX_VMEM_N * 2
    o = obs.enable()
    with pytest.raises(ValueError, match="REPRO_FORCE_PALLAS"):
        ops.fwht(jnp.ones((1, n)))
    obs.disable()
    errs = [e for e in o.memory_events()
            if e["name"] == "kernels.forced_error"]
    assert len(errs) == 1
    assert errs[0]["attrs"] == {"op": "fwht", "n": n}


# ---------------------------------------------------------------------------
# scheduler instrumentation
# ---------------------------------------------------------------------------
def test_scheduler_tokens_identical_and_metrics_present():
    cfg = configs.get_reduced("phi3-mini-3.8b")
    params = model_lib.init_params(jax.random.key(0), cfg)
    prompts = [jax.random.randint(jax.random.key(40 + i), (4 + i,), 0,
                                  cfg.vocab_size, jnp.int32)
               for i in range(3)]

    def generate():
        sched = Engine(cfg, params, ServeConfig(slots=2, max_seq=32))
        for i, p in enumerate(prompts):
            sched.submit(Request(rid=i, prompt=p, max_new_tokens=4))
        done = sched.run_to_completion()
        return {r.rid: r.tokens_out for r in done}, done

    ref, _ = generate()
    o = obs.enable()
    instrumented, done = generate()
    obs.disable()
    assert instrumented == ref                    # tokens identical with obs
    assert all(r.submit_time is not None and r.finish_time is not None
               and r.finish_time >= r.submit_time for r in done)
    s = o.summary()
    assert s["counters"]["serve.submitted"]["total"] == 3.0
    assert s["counters"]["serve.requests"]["total"] == 3.0
    assert s["hists"]["serve.request_latency_s"]["count"] == 3
    assert s["gauges"]["serve.queue_depth"]["last"] == 0.0
    assert {"serve.admit_cold", "serve.decode_step"} <= set(s["spans"])
    reasons = {e["attrs"]["reason"] for e in o.memory_events()
               if e["name"] == "serve.requests"}
    assert reasons <= {"eos", "budget", "max_seq"} and reasons


# ---------------------------------------------------------------------------
# truncated JSONL tolerance
# ---------------------------------------------------------------------------
def _write_events_jsonl(path, n=3):
    with open(path, "w") as f:
        for i in range(n):
            f.write(json.dumps({"type": "counter", "name": f"c{i}",
                                "ts": float(i), "value": 1.0}) + "\n")


def test_load_jsonl_truncated_final_line(tmp_path):
    """A writer that died mid-write leaves a torn last record: the parsed
    prefix comes back with truncated=True instead of an exception."""
    path = str(tmp_path / "torn.jsonl")
    _write_events_jsonl(path)
    with open(path, "a") as f:
        f.write('{"type": "counter", "name": "c3", "ts": 3.0, "val')
    events = load_jsonl(path)
    assert isinstance(events, EventList) and events.truncated is True
    assert [e["name"] for e in events] == ["c0", "c1", "c2"]
    with pytest.raises(json.JSONDecodeError):
        load_jsonl(path, strict=True)


def test_load_jsonl_midfile_corruption_still_raises(tmp_path):
    """A bad record with valid records AFTER it is corruption, not a torn
    tail — that must keep raising."""
    path = str(tmp_path / "corrupt.jsonl")
    _write_events_jsonl(path, n=1)
    with open(path, "a") as f:
        f.write('{"broken": \n')
        f.write(json.dumps({"type": "counter", "name": "after",
                            "ts": 9.0, "value": 1.0}) + "\n")
    with pytest.raises(json.JSONDecodeError):
        load_jsonl(path)


def test_load_jsonl_intact_file_not_truncated(tmp_path):
    path = str(tmp_path / "ok.jsonl")
    _write_events_jsonl(path)
    events = load_jsonl(path)
    assert events.truncated is False and len(events) == 3


# ---------------------------------------------------------------------------
# Chrome trace under interleaved spans + multiple counter tracks
# ---------------------------------------------------------------------------
def test_chrome_trace_interleaved_spans_and_counter_tracks(tmp_path):
    path = str(tmp_path / "trace.json")
    o = obs.enable(trace=path)
    with obs.span("outer"):
        # interleaved (not properly nested) spans: enter a, enter b,
        # exit a, exit b — the exporter must still produce a valid trace
        a = o.span("stream.a")
        b = o.span("stream.b")
        a.__enter__()
        b.__enter__()
        for i in range(4):
            obs.counter("track.bytes", 128 * (i + 1))
            obs.gauge("track.depth", i)
        a.__exit__(None, None, None)
        b.__exit__(None, None, None)
    obs.disable()

    assert trace_lib.validate_trace(path) > 0
    doc = json.load(open(path))
    events = doc["traceEvents"]
    span_names = {e["name"] for e in events if e["ph"] == "X"}
    assert {"outer", "stream.a", "stream.b"} <= span_names
    # each metric name is its own counter track; samples must be
    # monotonically timestamped within a track (Perfetto requirement)
    tracks: dict = {}
    for e in events:
        if e["ph"] == "C":
            tracks.setdefault(e["name"], []).append(e["ts"])
    assert {"track.bytes", "track.depth"} <= set(tracks)
    for name, tss in tracks.items():
        assert tss == sorted(tss), f"counter track {name} not monotonic"
    assert len(tracks["track.bytes"]) == 4


# ---------------------------------------------------------------------------
# summary: p99 + deterministic ordering
# ---------------------------------------------------------------------------
def test_hist_summary_includes_p99():
    o = obs.enable()
    for v in range(101):                         # 0..100: ranks land exactly
        obs.histogram("lat", float(v))
    obs.disable()
    h = o.summary()["hists"]["lat"]
    assert h["p50"] == 50.0
    assert h["p95"] == 95.0
    assert h["p99"] == 99.0
    assert h["max"] == 100.0


def test_summary_ordering_is_deterministic():
    """Every per-name table in the summary is key-sorted, so JSON payloads
    diff cleanly run to run regardless of emission order."""
    o = obs.enable()
    for name in ("zeta", "alpha", "mid"):
        obs.counter(name, 1)
        obs.gauge("g." + name, 1.0)
        obs.histogram("h." + name, 1.0)
        with obs.span("s." + name):
            pass
    obs.disable()
    s = o.summary()
    for table in ("counters", "gauges", "hists", "spans", "recompiles"):
        keys = list(s[table])
        assert keys == sorted(keys), f"{table} not sorted"
