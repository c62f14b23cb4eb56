"""bench/trace_reduce.py on hand-made intervals, and on a small trace
recorded on a TPU v5e (bench/testdata)."""
import pathlib

import pytest

import bench_tiny  # noqa: F401  (puts the repo on sys.path)

from bench import trace_reduce as tr

TESTDATA = pathlib.Path(bench_tiny.ROOT) / "bench" / "testdata"


def _ops(*spans, name="op"):
    return [tr.Op(f"{name}.{i}", s, e) for i, (s, e) in enumerate(spans)]


def test_busy_union_and_idle_share():
    ops = _ops((0, 10), (5, 20), (30, 40), (45, 70))
    assert tr.union(((o.start, o.end) for o in ops), 0, 50) == \
        [[0, 20], [30, 40], [45, 50]]
    assert tr.busy_ns(ops, 0, 50) == 35
    assert tr.idle_share(ops, 0, 50) == pytest.approx(0.3)


def test_gaps_are_named_after_the_host_span_that_overlaps_most():
    ops = _ops((0, 10), (5, 20), (30, 40))
    host = [("bench.batch", 0, 18), ("bench.dispatch", 18, 28),
            ("bench.wait", 28, 45)]
    assert tr.gaps(ops, 0, 50) == [(20, 30), (40, 50)]
    got = tr.idle_gaps(ops, host, 0, 50)
    assert [g[0] for g in got] == ["bench.dispatch", "bench.wait"]
    assert got[0][1] == pytest.approx(10e-9)
    assert tr.idle_gaps(ops, [], 0, 50)[0][0] == "other"


def test_top_ops_and_matching():
    ops = (_ops((0, 10), (20, 25), name="fusion")
           + _ops((10, 22), name="all-gather")
           + [tr.Op("_encode_call.3", 30, 34)])
    top = tr.top_ops(ops, 0, 100, k=2)
    assert [t[0] for t in top] == ["all-gather.0", "fusion.0"]
    assert [t[1] for t in top] == pytest.approx([12e-9, 10e-9])
    assert [o.name for o in tr.matching(ops, [r"_encode_call\b"])] == \
        ["_encode_call.3"]
    assert tr.op_name("%fusion.137 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.137"


def test_containers_are_kept_out_of_the_busy_union():
    """A `while` spans its body; the idle time between body ops is idle."""
    ops = [tr.Op("while.4", 0, 100), tr.Op("fusion.0", 0, 30),
           tr.Op("custom-call.1", 60, 100), tr.Op("conditional", 100, 120),
           tr.Op("call.2", 100, 120), tr.Op("fusion.1", 110, 120)]
    leaves, containers = tr.split_containers(ops)
    assert [o.name for o in containers] == ["while.4", "conditional",
                                            "call.2"]
    assert [o.name for o in leaves] == ["fusion.0", "custom-call.1",
                                        "fusion.1"]
    assert tr.busy_ns(leaves, 0, 120) == 80
    assert tr.gaps(leaves, 0, 120) == [(30, 60), (100, 110)]


def test_window_and_steps():
    t = tr.Trace({0: []}, [("bench.batch", 5, 6), ("bench.dispatch", 6, 7),
                           ("bench.wait", 7, 20), ("bench.batch", 20, 21),
                           ("bench.dispatch", 21, 22), ("bench.wait", 22, 40)])
    assert t.window() == (5, 40)
    assert t.steps() == 2


RECORDED = sorted(TESTDATA.glob("*.xplane.pb.gz"))


def _ctx(trace):
    import types
    return types.SimpleNamespace(trace=trace, reduce=tr, device_ids=[0])


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace_reduces(path):
    t = tr.load(str(path))
    lo, hi = t.window()
    ops = t.devices[0]
    assert ops and t.steps() >= 1
    assert all(o.end >= o.start for o in ops)
    busy = tr.busy_ns(ops, lo, hi)
    assert 0 < busy <= hi - lo
    assert busy <= sum(min(o.end, hi) - max(o.start, lo) for o in ops
                       if o.end > lo and o.start < hi)
    assert 0 <= tr.idle_share(ops, lo, hi) < 1
    gaps = tr.idle_gaps(ops, t.host, lo, hi)
    assert gaps and {g[0] for g in gaps} <= set(tr.HOST_SPANS) | {"other"}
    assert sum(g[1] for g in tr.idle_gaps(ops, t.host, lo, hi, k=10 ** 9)) \
        == pytest.approx((hi - lo - busy) * 1e-9)


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_loops_count_their_idle_time(path):
    """The recorded trace's loops are containers; inside the longest one
    the leaf ops leave idle time, which the idle share counts."""
    t = tr.load(str(path))
    ops, loops = t.devices[0], t.containers[0]
    assert loops and not any(tr.CONTAINER.match(o.name) for o in ops)
    lo, hi = t.window()
    assert not any(tr.CONTAINER.match(name) for name, _ in
                   tr.top_ops(ops, lo, hi, k=10 ** 9))
    w = max(loops, key=lambda o: o.end - o.start)
    inside = tr.busy_ns(ops, w.start, w.end)
    assert 0 < inside < w.end - w.start
    assert tr.busy_ns(ops + loops, lo, hi) > tr.busy_ns(ops, lo, hi)


@pytest.mark.parametrize("path", [p for p in RECORDED if "ag4ef" in p.name],
                         ids=lambda p: p.name)
def test_codec_reader_finds_the_codec_ops(path):
    from bench import cells
    ctx = _ctx(tr.load(str(path)))
    codec_ms = cells.metric("codec_ms").read(ctx)
    assert codec_ms is not None and codec_ms > 0
    found = tr.matching(ctx.trace.devices[0], cells.metric("codec_ms").PATTERNS)
    assert {o.name.split(".")[0] for o in found} >= {"_encode_call",
                                                     "fwht_pallas"}
    idle = cells.metric("device_idle_share").read(ctx)
    assert 0 <= idle < 100


def test_every_reader_reads_a_recorded_trace_or_returns_none():
    """Each per-layer reader on the recorded one-chip trace reads a share
    or a time within its range."""
    from bench import cells, codec_bytes
    path = next(p for p in RECORDED if "ag4ef" in p.name)
    cfg = {"d_model": 256, "num_heads": 4, "num_kv_heads": 2, "head_dim": 64,
           "d_ff": 512, "vocab_size": 500, "num_layers": 2,
           "block": "attn_mlp"}
    ctx = _ctx(tr.load(str(path)))
    ctx.cell = {"seq_len": 256, "chips": 1}
    ctx.config = cfg
    ctx.flops = cells.flops("attn_mlp")
    ctx.peak = cells.peaks()["TPU v5 lite"]
    ctx.tokens_per_s = 1e4
    ctx.codec_minimum = codec_bytes.minimum([2 ** 20], 4, 256, 1, True)
    ctx.metric = lambda name: cells.metric(name).read(ctx)
    got = {m: cells.metric(m).read(ctx) for m in
           ("device_idle_share", "mfu", "codec_ms", "codec_roofline")}
    for name in ("device_idle_share", "mfu", "codec_ms", "codec_roofline"):
        assert got[name] is not None and 0 < got[name] < 100, (name, got)
