"""bench/scopes.py: the XSpace reader and the phase split, on hand-made
inputs and on small traces recorded on a TPU v5e (bench/testdata)."""
import pathlib
import types

import pytest

import bench_tiny

from bench import cells, codec_bytes, scopes
from bench import trace_reduce as tr

TESTDATA = pathlib.Path(bench_tiny.ROOT) / "bench" / "testdata"
# the parent program's trace: no phase scopes
UNSCOPED = TESTDATA / "yi-tiny.ag4ef.1chip.xplane.pb.gz"
# the harness's run of a tiny cell, and train() under an obs session
SCOPED = sorted((TESTDATA / "scoped").glob("*.ag4ef.*.xplane.pb.gz"))
TRAIN = TESTDATA / "scoped" / "yi-tiny.train.xplane.pb.gz"


def test_scope_names_are_the_programs():
    from repro.dist import scopes as program
    assert scopes.SCOPES == program.ALL
    assert (scopes.FORWARD, scopes.CONSENSUS, scopes.ENCODE, scopes.EXCHANGE,
            scopes.DECODE, scopes.MEAN, scopes.OPTIMIZER) == (
        program.FORWARD, program.CONSENSUS, program.ENCODE, program.EXCHANGE,
        program.DECODE, program.MEAN, program.OPTIMIZER)


@pytest.mark.parametrize("path,want", [
    ("jit(local_step)/shard_map/forward/jvp()/while/body/dot_general",
     ("forward", None)),
    ("forward/transpose(jvp())/while/body/closed_call/mul", ("backward", None)),
    ("jit(local_step)/shard_map/forward/transpose(jvp(jit(_take)))/scatter-add",
     ("backward", None)),
    ("jit(local_step)/shard_map/consensus/encode/jit(_encode_call)/"
     "pallas_call:", ("consensus", "encode")),
    ("jit(local_step)/shard_map/consensus/exchange/all_gather:",
     ("consensus", "exchange")),
    ("jit(local_step)/shard_map/consensus/decode/mul", ("consensus", "decode")),
    ("jit(local_step)/shard_map/consensus/mean/add", ("consensus", "mean")),
    ("jit(local_step)/shard_map/consensus/convert_element_type",
     ("consensus", None)),
    ("jit(local_step)/shard_map/optimizer/mul:", ("optimizer", None)),
    ("jit(local_step)/jit(_encode_call)/pallas_call:", ("unscoped", None)),
    ("jit(<lambda>)/jit(forward_hidden)/mul", ("unscoped", None)),
    ("", ("unscoped", None)),
    ("jit(local_step)/shard_map/broadcast_in_dim;consensus/encode/reshape;"
     "consensus/encode/reshape:", ("consensus", "encode")),
    ("jit(local_step)/shard_map/optimizer/mul;forward/jvp()/add",
     ("optimizer", None)),
])
def test_phase_of_a_scope_path(path, want):
    assert scopes.phase_of(path) == want


def _op(name, start, end, path):
    return scopes.ScopedOp(name, start, end, path)


def test_device_split_sums_phases_and_exposed_exchange():
    ops = [_op("fusion.1", 0, 10, "a/forward/jvp()/mul"),
           _op("fusion.2", 10, 30, "a/forward/transpose(jvp())/mul"),
           _op("all-gather-start.1", 30, 40, "a/consensus/exchange/all_gather"),
           _op("fusion.3", 32, 36, "a/consensus/decode/mul"),
           _op("fusion.4", 40, 45, "a/consensus/mean/add"),
           _op("fusion.5", 45, 48, "a/optimizer/sub"),
           _op("copy.1", 48, 50, "")]
    got = scopes.device_split(ops, 0, 50)
    assert (got["forward"], got["backward"], got["consensus"],
            got["optimizer"], got["unscoped"]) == (10, 20, 19, 3, 2)
    assert (got["exchange"], got["decode"], got["mean"]) == (10, 4, 5)
    assert got["exposed_collective"] == 6          # 10 less the 4 overlapped
    assert got["busy"] == 50
    clipped = scopes.device_split(ops, 5, 35)
    assert clipped["forward"] == 5 and clipped["exposed_collective"] == 2


def test_window_and_steps_follow_the_loop_the_trace_holds():
    bench = scopes.Scoped({}, [("bench.batch", 5, 6), ("bench.dispatch", 6, 7),
                               ("dist.step", 6, 7), ("bench.wait", 7, 20)])
    assert (bench.window(), bench.steps()) == ((5, 20), 1)
    train = scopes.Scoped({}, [("train.batch", 1, 2), ("train.step", 2, 3),
                               ("dist.step", 2, 3), ("train.wait", 3, 9),
                               ("train.batch", 9, 10), ("train.step", 10, 11),
                               ("train.wait", 11, 15)])
    assert (train.window(), train.steps()) == ((1, 15), 2)
    with pytest.raises(ValueError):
        scopes.Scoped({}, [("dist.step", 2, 3)]).window()


def test_the_reader_gives_what_profile_data_gives():
    """Every XLA-Ops event of the recorded trace, joined with its metadata
    by id, starts and ends where `ProfileData` puts it, under the same
    name; the host spans and the window agree too."""
    got = scopes.load(str(UNSCOPED))
    want = tr.load(str(UNSCOPED))
    assert sorted(got.devices) == sorted(want.devices)
    for dev, ops in want.devices.items():
        assert [(o.name, o.start, o.end) for o in got.devices[dev]] == \
            [(o.name, o.start, o.end) for o in ops]
    assert [h for h in got.host if h[0] in tr.HOST_SPANS] == want.host
    assert got.window() == want.window()
    assert got.steps() == want.steps()


def test_scope_paths_are_taken_by_metadata_id_not_by_name():
    """Bare op names repeat across programs (a `broadcast_add_fusion.5` of
    the feed, `jit(<lambda>)`, and one of the step, `jit(local_step)`):
    each event keeps its own program's path."""
    got = scopes.load(str(UNSCOPED))
    programs = {}
    for o in got.devices[0]:
        programs.setdefault(o.name, set()).add(o.path.split("/")[0])
    shared = {n for n, p in programs.items() if len(p) > 1}
    assert "broadcast_add_fusion.5" in shared
    assert all(programs[n] == {"jit(<lambda>)", "jit(local_step)"}
               for n in shared)


def test_the_unscoped_parent_trace_reads_as_unscoped():
    split = scopes.split(scopes.load(str(UNSCOPED)))
    assert split["unscoped_ms"] > 0 and split["scoped_share"] == 0
    t = tr.load(str(UNSCOPED))
    assert split["busy_ms"] == pytest.approx(
        tr.busy_ns(t.devices[0], *t.window()) * 1e-6)


def _ctx(trace):
    ctx = types.SimpleNamespace(trace=trace, reduce=tr, device_ids=[0])
    ctx.cell = {"seq_len": 256, "chips": 1}
    ctx.config = {"d_model": 256, "num_heads": 4, "num_kv_heads": 2,
                  "head_dim": 64, "d_ff": 512, "vocab_size": 500,
                  "num_layers": 2, "block": "attn_mlp"}
    ctx.flops = cells.flops("attn_mlp")
    ctx.peak = cells.peaks()["TPU v5 lite"]
    ctx.tokens_per_s = 1e4
    ctx.codec_minimum = codec_bytes.minimum([2 ** 20], 4, 256, 1, True)
    ctx.metric = lambda name: cells.metric(name).read(ctx)
    return ctx


def test_the_accepted_readings_of_the_recorded_trace_are_pinned():
    """The loader and the accepted readers read the recorded parent trace
    as they did when the benchmark was accepted."""
    t = tr.load(str(UNSCOPED))
    lo, hi = t.window()
    ops = t.devices[0]
    assert (len(ops), len(t.containers[0]), len(t.host)) == (3475, 3, 3)
    assert (lo, hi, t.steps()) == (40965760.0, 47921740.0, 1)
    assert tr.busy_ns(ops, lo, hi) == 1949555.0
    assert tr.top_ops(ops, lo, hi, k=3) == [
        ["_encode_call.14", 0.000162398],
        ["_encode_call.16", 0.00016172700000000002],
        ["_encode_call.15", 0.00016172200000000002]]
    assert tr.idle_gaps(ops, t.host, lo, hi, k=2) == [
        ["bench.dispatch", 0.0028864480000000002],
        ["bench.wait", 0.002035514]]
    ctx = _ctx(t)
    got = {m: cells.metric(m).read(ctx) for m in
           ("device_idle_share", "mfu", "codec_ms", "codec_roofline")}
    assert got == {"device_idle_share": 71.9729642695925,
                   "mfu": 0.043928040609137055,
                   "codec_ms": 1.3743649999999998,
                   "codec_roofline": 1.5865744227569507}


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.name)
def test_every_phase_reads_its_ops_from_a_scoped_trace(path):
    """On one worker the all-gather and the mean over one payload compile
    to nothing; on four, every phase and every consensus child reads."""
    got = scopes.load(str(path))
    split = scopes.split(got)
    names = ["forward_ms", "backward_ms", "consensus_ms", "optimizer_ms",
             "encode_ms", "decode_ms"]
    if len(split["devices"]) > 1:
        names += ["exchange_ms", "mean_ms"]
        # the exchange is the step's all-gathers, synchronous or the done
        # of an asynchronous one, and only they
        exchange = scopes.top_ops(got, split["devices"][0], scopes.CONSENSUS,
                                  scopes.EXCHANGE, k=1000)
        assert exchange and all(
            n.startswith(("all-gather", "async-collective-done"))
            for n, _, _ in exchange)
    for name in names:
        assert split[name] > 0, name
    # the step's own ops are scoped; the feed program (`jit(<lambda>)`) is
    # the unscoped rest
    lo, hi = got.window()
    for dev in split["devices"]:
        step = [(min(o.end, hi) - max(o.start, lo), o.path)
                for o in got.devices[dev] if o.end > lo and o.start < hi
                and o.path.startswith("jit(local_step)")]
        scoped = sum(d for d, p in step
                     if scopes.phase_of(p)[0] != "unscoped")
        assert scoped >= 0.99 * sum(d for d, _ in step), dev
    assert (split["encode_ms"] + split["exchange_ms"] + split["decode_ms"]
            + split["mean_ms"]) <= split["consensus_ms"] + 1e-9
    assert 0 <= split["exposed_collective_ms"] <= split["exchange_ms"] + 1e-9
    # the phases and the unscoped rest add up to the leaf ops' time
    total = sum(min(o.end, hi) - max(o.start, lo)
                for o in got.devices[split["devices"][0]]
                if o.end > lo and o.start < hi)
    if len(split["devices"]) == 1:
        assert sum(split[f"{p}_ms"] for p in scopes.PHASES + ("unscoped",)) \
            == pytest.approx(total / split["steps"] * 1e-6)
    # the codec's kernels sit under the consensus scope
    codec = tr.matching(got.devices[split["devices"][0]],
                        cells.metric("codec_ms").PATTERNS)
    assert codec and all(scopes.phase_of(o.path)[0] == "consensus"
                         for o in codec)


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.name)
def test_a_scoped_trace_still_reads_with_the_accepted_loader(path):
    t = tr.load(str(path))
    got = scopes.load(str(path))
    for dev, ops in t.devices.items():
        assert [(o.name, o.start, o.end) for o in got.devices[dev]] == \
            [(o.name, o.start, o.end) for o in ops]
    assert cells.metric("codec_ms").read(_ctx(t)) > 0


def test_the_training_loop_spans_are_on_the_device_clock():
    """train() under an obs session: each step's batch, dispatch and wait
    are host spans of the trace, `dist.step` inside `train.step`, and the
    device ops of the step fall inside the loop's window."""
    got = scopes.load(str(TRAIN))
    names = [h[0] for h in got.host]
    for name in ("train.batch", "train.step", "train.wait", "dist.step"):
        assert names.count(name) == got.steps(), name
    assert names.count("train.checkpoint") == 1
    steps = [h for h in got.host if h[0] == "train.step"]
    for _, s, e in (h for h in got.host if h[0] == "dist.step"):
        assert any(s0 <= s <= e <= e0 for _, s0, e0 in steps)
    lo, hi = got.window()
    split = scopes.split(got)
    assert split["forward_ms"] > 0 and split["backward_ms"] > 0
    assert all(lo <= o.start for o in got.devices[0]
               if scopes.phase_of(o.path)[0] == "optimizer")
