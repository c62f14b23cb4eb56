"""Reduce a JAX profiler trace (.xplane.pb) to per-op device intervals.

What is kept: for each device plane (`/device:TPU:<n>`), the events of its
"XLA Ops" line as (HLO instruction name, start_ns, end_ns); and the
harness's own host spans (`bench.batch` around the batch, `bench.dispatch`
around the step call, `bench.wait` around `block_until_ready`), on the
same clock. On a v5e an event's name is the instruction's HLO text
("%fusion.137 = f32[...] fusion(...)"); the name kept is "fusion.137". A
Pallas kernel's instruction is named after its kernel ("_encode_call.25").
A container (`while`, `conditional`, `call`) is one event spanning its
body's ops, which appear too as events of their own. Containers are kept
apart: the busy union, the top ops and the idle gaps count leaf ops only,
so idle time inside a loop body shows as idle.

From those: the busy union of a device over a window, the idle share, the
ops that took most time, and the longest idle gaps, each labelled with the
host span that overlaps it most.
"""
from __future__ import annotations

import dataclasses
import gzip
import re

HOST_SPANS = ("bench.batch", "bench.dispatch", "bench.wait")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINER = re.compile(r"^(?:while|conditional|call)(?:\.\d+)?$")


@dataclasses.dataclass
class Op:
    name: str
    start: float     # ns
    end: float       # ns


@dataclasses.dataclass
class Trace:
    devices: dict            # device id -> [Op] of leaf ops, sorted by start
    host: list               # [(span name, start_ns, end_ns)], sorted
    containers: dict = dataclasses.field(default_factory=dict)
    # device id -> [Op] of `while` / `conditional` / `call` events

    def window(self) -> tuple:
        """From the first harness span's start to the last one's end."""
        if not self.host:
            raise ValueError("trace holds no bench.* host spans")
        return self.host[0][1], max(end for _, _, end in self.host)

    def steps(self) -> int:
        return sum(1 for name, _, _ in self.host if name == "bench.dispatch")


def op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def split_containers(ops) -> tuple:
    """(leaf ops, container ops) of a device's ops, each in input order."""
    leaves, containers = [], []
    for o in ops:
        (containers if CONTAINER.match(o.name) else leaves).append(o)
    return leaves, containers


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, containers, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, names = [], {}
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    full = ev.name
                    if full not in names:
                        names[full] = op_name(full)
                    start = ev.start_ns
                    ops.append(Op(names[full], start, start + ev.duration_ns))
            ops.sort(key=lambda o: o.start)
            dev = int(m.group(1))
            devices[dev], containers[dev] = split_containers(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    host.sort(key=lambda h: h[1])
    return Trace(devices, host, containers)


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(((o.start, o.end) for o in ops), lo, hi))


def idle_share(ops, lo: float, hi: float) -> float:
    """1 - busy union over the window [lo, hi)."""
    return 1.0 - busy_ns(ops, lo, hi) / (hi - lo)


def top_ops(ops, lo: float, hi: float, k: int = 10) -> list:
    """[(op name, seconds)] of the k names with most device time."""
    total = {}
    for o in ops:
        d = min(o.end, hi) - max(o.start, lo)
        if d > 0:
            total[o.name] = total.get(o.name, 0.0) + d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in ranked]


def gaps(ops, lo: float, hi: float) -> list:
    """Idle [start, end) intervals of the device within [lo, hi)."""
    out, t = [], lo
    for s, e in union(((o.start, o.end) for o in ops), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def idle_gaps(ops, host, lo: float, hi: float, k: int = 10) -> list:
    """[(host span name, seconds)] of the k longest idle gaps, each named
    after the harness span that overlaps it most ('other' if none)."""
    named = []
    for s, e in gaps(ops, lo, hi):
        best, label = 0.0, "other"
        for name, hs, he in host:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, label = ov, name
        named.append([label, (e - s) * 1e-9])
    return sorted(named, key=lambda g: -g[1])[:k]


def matching(ops, patterns) -> list:
    """The ops whose name matches any of the regex `patterns`."""
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    return [o for o in ops if rx.match(o.name)]

