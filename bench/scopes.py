"""Split a profiler trace's device time by the train step's phase scopes.

    python3 bench/scopes.py <trace.xplane.pb[.gz]> [--devices 0,1,2,3]

The program runs its train step under named scopes (`forward`, `consensus`
with `encode` / `exchange` / `decode` / `mean`, `optimizer`; defined in
src/repro/dist/scopes.py and copied here, so that the benchmark imports
nothing of the program to read a trace). A scope reaches each device op's
metadata: in the XSpace, every "XLA Ops" event points by metadata id at an
event metadata whose `tf_op` stat is the op's scope path, for example
`jit(local_step)/shard_map/consensus/encode/jit(_encode_call)/pallas_call:`.
A fusion carries the path of its root instruction. Bare op names repeat
across programs ("fusion.137" of the feed and of the step), so the path is
taken by metadata id, never by name.

`jax.profiler.ProfileData` does not expose event metadata stats, so this
module reads the XSpace itself: a varint and length-delimited reader of
the protobuf wire format, which decodes each device plane's event and stat
metadata and its "XLA Ops" line (metadata id, offset, duration), skips
every other line by its length, and on host planes keeps the spans of the
benchmark (`bench.*`) and of the program (`train.*`, `dist.step*`). Times
are those `ProfileData` gives: line timestamp_ns + offset_ps // 1000, and
duration_ps // 1000, in ns.

Phases, by the path's components: under `forward`, an op is backward work
when a later component starts with `transpose(` (JAX's name for the
transposed forward, where rematerialized forward work runs too), else
forward work; under `consensus` it is consensus work, split by its child
scope; under `optimizer`, optimizer work; anything else is unscoped. Each
phase's time is the sum of its leaf ops' durations within the window, per
step, averaged over the devices read. `exposed_collective_ms` is the union
of the `consensus/exchange` ops' intervals less the part of it that any
other leaf op overlaps. On a v5e the "XLA Ops" line is one TensorCore's
op stream, whose ops never overlap, so it equals `exchange_ms`: the time
the core spends in the exchange's ops (a synchronous collective whole, an
asynchronous one's start and done). That is a lower bound on the wire's
cost; what an asynchronous one hides runs between its start and its done,
and is not read.
"""
from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import os
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))

from bench import trace_reduce  # noqa: E402

# the program's scope names (src/repro/dist/scopes.py)
FORWARD = "forward"
CONSENSUS = "consensus"
ENCODE = "encode"
EXCHANGE = "exchange"
DECODE = "decode"
MEAN = "mean"
OPTIMIZER = "optimizer"
SCOPES = (FORWARD, CONSENSUS, ENCODE, EXCHANGE, DECODE, MEAN, OPTIMIZER)
CHILDREN = (ENCODE, EXCHANGE, DECODE, MEAN)
PHASES = ("forward", "backward", "consensus", "optimizer")

DEVICE_PLANE = trace_reduce.DEVICE_PLANE
OPS_LINE = trace_reduce.OPS_LINE
HOST_SPAN = re.compile(r"^(?:%s|train\.\w+|dist\.step[\w.]*)$" % "|".join(
    map(re.escape, trace_reduce.HOST_SPANS)))
TF_OP = "tf_op"


@dataclasses.dataclass
class ScopedOp(trace_reduce.Op):
    path: str = ""   # the op's scope path (its `tf_op` stat), "" if none


@dataclasses.dataclass
class Scoped:
    devices: dict    # device id -> [ScopedOp] of leaf ops, sorted by start
    host: list       # [(span name, start_ns, end_ns)], sorted by start

    def _loop(self) -> str:
        """The loop the trace holds: the benchmark's or `train()`'s."""
        for prefix in ("bench.", "train."):
            if any(h[0].startswith(prefix) for h in self.host):
                return prefix
        raise ValueError("trace holds no bench.* or train.* host spans")

    def window(self) -> tuple:
        """From the loop's first span's start to its last one's end."""
        spans = [h for h in self.host if h[0].startswith(self._loop())]
        return spans[0][1], max(e for _, _, e in spans)

    def steps(self) -> int:
        name = {"bench.": "bench.dispatch", "train.": "train.step"}
        return sum(1 for h in self.host if h[0] == name[self._loop()])


# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------
def _varint(buf, i: int):
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    value, shift = b & 0x7F, 7
    i += 1
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of one message: an int for a varint, (start,
    end) of the bytes for a length-delimited field; fixed-width fields give
    (start, end) of their bytes too."""
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 1:
            value, i = (i, i + 8), i + 8
        elif kind == 5:
            value, i = (i, i + 4), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """(key, (start, end) of the value message) of a map<int64, message>."""
    key, value = 0, (span[0], span[0])
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat_metadata(buf, span) -> str:
    for f, v in _fields(buf, *span):
        if f == 2:
            return _text(buf, v)
    return ""


def _event_metadata(buf, span, stat_names: dict, tf_op_ids: set):
    """(name, tf_op path or None) of one XEventMetadata; a `ref_value`
    stat names its string by stat metadata id."""
    name, path = "", None
    stats = []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 5:
            stats.append(v)
    for s in stats:
        sid, value = None, None
        for f, v in _fields(buf, *s):
            if f == 1:
                sid = v
            elif f == 5:
                value = _text(buf, v)
            elif f == 7:
                value = stat_names.get(v, "")
        if sid in tf_op_ids and value is not None:
            path = value
    return name, path


def _event(buf, i: int, end: int) -> tuple:
    """(metadata id, offset_ps, duration_ps) of one XEvent; its stats are
    skipped by length."""
    mid = off = dur = 0
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            f = key >> 3
            if f == 1:
                mid = value
            elif f == 2:
                off = value
            elif f == 3:
                dur = value
        elif kind == 2:
            n, i = _varint(buf, i)
            i += n
        else:
            i += 8 if kind == 1 else 4
    return mid, off, dur


def _line(buf, span, only=None) -> list:
    """[(metadata id, start_ns, end_ns)] of the events of one XLine; a line
    not named `only` (when given) is left unread."""
    ts, name, events = 0, "", []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
            if only is not None and name != only:
                return []
        elif f == 3:
            ts = v
        elif f == 4:
            events.append(v)
    if only is not None and name != only:
        return []
    out = []
    for s, e in events:
        mid, off, dur = _event(buf, s, e)
        start = float(ts + off // 1000)
        out.append((mid, start, start + float(dur // 1000)))
    return out


def _plane(buf, span, host: list, device_ids):
    """(device id, [ScopedOp]) of a device plane in `device_ids` (all if
    None), None for another plane (a host plane's program spans go to
    `host`)."""
    name, lines, events_md, stats_md = "", [], [], []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            events_md.append(v)
        elif f == 5:
            stats_md.append(v)
    m = DEVICE_PLANE.match(name)
    if m is None and not name.startswith("/host:"):
        return None
    if m is not None and device_ids is not None \
            and int(m.group(1)) not in device_ids:
        return None
    stat_names = {}
    for entry in stats_md:
        key, value = _map_entry(buf, entry)
        stat_names[key] = _stat_metadata(buf, value)
    if m is None:
        names = {}
        for entry in events_md:
            key, value = _map_entry(buf, entry)
            ev_name, _ = _event_metadata(buf, value, {}, set())
            if HOST_SPAN.match(ev_name):
                names[key] = ev_name
        for line in lines:
            host.extend((names[mid], s, e) for mid, s, e in _line(buf, line)
                        if mid in names)
        return None
    tf_op_ids = {k for k, v in stat_names.items() if v == TF_OP}
    meta = {}
    for entry in events_md:
        key, value = _map_entry(buf, entry)
        full, path = _event_metadata(buf, value, stat_names, tf_op_ids)
        meta[key] = (trace_reduce.op_name(full), path or "")
    raw = [ev for line in lines for ev in _line(buf, line, OPS_LINE)]
    ops = [ScopedOp(meta.get(mid, ("", ""))[0], s, e,
                    meta.get(mid, ("", ""))[1]) for mid, s, e in raw]
    ops.sort(key=lambda o: o.start)
    return int(m.group(1)), ops


def read_xspace(data: bytes, device_ids=None) -> Scoped:
    """The leaf ops of each TPU plane in `device_ids` (all if None) with
    their scope paths, and the program's and the benchmark's host spans."""
    buf = memoryview(data)
    devices, host = {}, []
    for f, v in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        got = _plane(buf, v, host, device_ids)
        if got is not None:
            dev, ops = got
            devices[dev] = [o for o in ops
                            if not trace_reduce.CONTAINER.match(o.name)]
    host.sort(key=lambda h: h[1])
    return Scoped(devices, host)


def load(path: str, device_ids=None) -> Scoped:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return read_xspace(f.read(), device_ids)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_of(path: str) -> tuple:
    """(phase, consensus child or None) of a scope path; phase is one of
    PHASES or "unscoped". An op XLA merged from several carries their
    paths joined by ";" (the later ones relative): the first that names a
    phase gives it."""
    for part in path.rstrip(":").split(";"):
        got = _phase(part.split("/"))
        if got[0] != "unscoped":
            return got
    return "unscoped", None


def _phase(parts: list) -> tuple:
    if FORWARD in parts:
        after = parts[parts.index(FORWARD) + 1:]
        if any(p.startswith("transpose(") for p in after):
            return "backward", None
        return "forward", None
    if CONSENSUS in parts:
        i = parts.index(CONSENSUS)
        child = parts[i + 1] if i + 1 < len(parts) else None
        return "consensus", child if child in CHILDREN else None
    if OPTIMIZER in parts:
        return "optimizer", None
    return "unscoped", None


def _clipped(o, lo, hi) -> float:
    return max(0.0, min(o.end, hi) - max(o.start, lo))


def _overlap(a: list, b: list) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def device_split(ops, lo: float, hi: float) -> dict:
    """ns of one device's leaf ops within [lo, hi), by phase and consensus
    child, with the exposed exchange time and the busy union."""
    out = {p: 0.0 for p in PHASES + ("unscoped",)}
    out.update({c: 0.0 for c in CHILDREN})
    exchange, other = [], []
    for o in ops:
        d = _clipped(o, lo, hi)
        if d <= 0:
            continue
        phase, child = phase_of(o.path)
        out[phase] += d
        if child is not None:
            out[child] += d
        (exchange if child == EXCHANGE else other).append((o.start, o.end))
    ex = trace_reduce.union(exchange, lo, hi)
    out["exposed_collective"] = (sum(e - s for s, e in ex)
                                 - _overlap(ex, trace_reduce.union(other, lo,
                                                                   hi)))
    out["busy"] = trace_reduce.busy_ns(ops, lo, hi)
    return out


def split(scoped: Scoped, device_ids=None) -> dict:
    """Device ms per step of each phase, averaged over `device_ids` (all
    devices of the trace by default): forward_ms, backward_ms,
    consensus_ms, optimizer_ms, unscoped_ms, the consensus children
    (encode_ms, exchange_ms, decode_ms, mean_ms), exposed_collective_ms and
    busy_ms, with `scoped_share`, the share of the leaf ops' time under
    the four phases."""
    ids = sorted(scoped.devices) if device_ids is None else list(device_ids)
    if not ids:
        raise ValueError("the trace holds no TPU plane")
    lo, hi = scoped.window()
    per = [device_split(scoped.devices.get(d, []), lo, hi) for d in ids]
    steps = scoped.steps()
    out = {f"{k}_ms": sum(p[k] for p in per) / len(per) / steps * 1e-6
           for k in per[0]}
    total = sum(out[f"{p}_ms"] for p in PHASES + ("unscoped",))
    out["scoped_share"] = (sum(out[f"{p}_ms"] for p in PHASES) / total
                           if total else None)
    out["steps"] = steps
    out["devices"] = ids
    return out


def top_ops(scoped: Scoped, device_id: int, phase: str = "unscoped",
            child: str | None = None, k: int = 10) -> list:
    """[(op name, scope path, ms per step)] of the ops of one phase (and
    consensus child, when given) with most time on one device: with the
    defaults, what the phases leave out."""
    lo, hi = scoped.window()
    total = {}
    for o in scoped.devices.get(device_id, []):
        d = _clipped(o, lo, hi)
        got = phase_of(o.path)
        if d > 0 and got[0] == phase and (child is None or got[1] == child):
            key = (o.name, o.path)
            total[key] = total.get(key, 0.0) + d
    steps = scoped.steps()
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, path, ns / steps * 1e-6] for (name, path), ns in ranked]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--devices", default=None,
                    help="comma-separated device ids (default: all)")
    args = ap.parse_args(argv)
    ids = ([int(d) for d in args.devices.split(",")] if args.devices
           else None)
    scoped = load(args.trace, ids)
    out = split(scoped, ids)
    out["top_unscoped"] = top_ops(scoped, out["devices"][0])
    out["top_exchange"] = top_ops(scoped, out["devices"][0], CONSENSUS,
                                  EXCHANGE)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
