"""Per-program device cost model + roofline-fraction attribution.

The paper's claims are *cost* claims — O(n²) multiplications for the exact
embedding, O(n log n) additions for the Hadamard relaxation, R bits per
dimension on the wire — so a measured span is only half a result; this
module supplies the analytic half. Every named jitted program (the ones
`repro.obs.recompile` tracks and whose call site captures it: fed.round.*,
fed.aggregate.*, serve.{prefill,decode_step}, the kernel dispatch wrappers)
can be asked, per compiled specialization it was actually called
with, what the compiler says it does: FLOPs and bytes accessed from XLA's
HLO cost analysis, argument/output byte footprints, plus the analytic
wire-bytes the codec audit charges per call. A per-backend peak table then
turns (measured seconds, modeled FLOPs/bytes) into a roofline fraction per
instrumented span.

THE HARD CONSTRAINT, inherited from the PR-7 obs contract: cost extraction
must never trigger a compile. Two mechanisms enforce it:

  * Capture observes calls the instrumented layers already make — it
    records an abstract (shape/dtype/sharding) signature per distinct
    specialization, one cheap dict hit per call, only while an obs session
    with `costs=True` is active. Nothing is ever re-executed.
  * Extraction uses `fn.lower(*abstract_args).cost_analysis()` — a trace +
    HLO analysis with NO backend compile and NO effect on the program's
    jit cache (`_cache_size()` pinned before/after `snapshot()` in the
    regression tests; `tests/test_obs_costs.py` additionally monkeypatches
    the XLA compile entry point to raise). `memory_analysis()` (peak /
    temp device bytes) genuinely needs a compiled executable, so it is
    behind an explicit `snapshot(compile_ok=True)` opt-in that performs an
    AOT compile OUTSIDE every jit cache — never on by default.

Backends whose cost analysis is unavailable (or whose programs refuse to
re-lower) degrade per specialization to `available: False` with the
recorded reason — a cost model must never crash a benchmark.
"""
from __future__ import annotations

import os
from typing import Optional

# ---------------------------------------------------------------------------
# Peak table, keyed by the prefix of `device.device_kind`: dense bf16
# FLOP/s and HBM bytes/s per chip, from Google Cloud's TPU documentation.
# "TPU v5 lite" is what a v5e chip reports; its row is the "TPU v5e" page:
# 197 TFLOP/s bf16, 819 GB/s. A TPU whose kind is not here gets no peak
# (attribution unavailable, with the reason) — never another chip's
# figures. Non-TPU backends keep a nominal row, and REPRO_PEAK_FLOPS /
# REPRO_PEAK_BYTES (floats) override everything for calibrated hardware.
# ---------------------------------------------------------------------------
DEVICE_PEAKS = (
    ("TPU v5 lite", 197e12, 8.19e11),
    ("TPU v5p", 459e12, 2.77e12),
    ("TPU v4", 275e12, 1.2e12),
    ("TPU v3", 123e12, 9.0e11),
    ("TPU v2", 46e12, 7.0e11),
)
BACKEND_PEAKS = {
    "gpu": (1.0e14, 2.0e12),
    "cpu": (1.0e11, 5.0e10),   # one AVX-ish core complex + DDR stream
}


def peaks(backend: Optional[str] = None,
          device_kind: Optional[str] = None) -> dict:
    """{"flops_per_s", "bytes_per_s", "backend", "device_kind", "source"}.

    Resolution order: env override → device-kind prefix in DEVICE_PEAKS →
    backend default → cpu default. A TPU kind missing from DEVICE_PEAKS
    resolves to None peaks with `source="unavailable"` and a `reason`.
    Never raises (jax probing is guarded): a missing accelerator yields
    the cpu row, with the source recorded.
    """
    if backend is None or device_kind is None:
        try:
            import jax                                  # noqa: PLC0415
            backend = backend or jax.default_backend()
            if device_kind is None:
                devs = jax.devices()
                device_kind = devs[0].device_kind if devs else None
        except Exception:
            pass
    env_f = os.environ.get("REPRO_PEAK_FLOPS")
    env_b = os.environ.get("REPRO_PEAK_BYTES")
    if env_f is not None and env_b is not None:
        return {"flops_per_s": float(env_f), "bytes_per_s": float(env_b),
                "backend": backend, "device_kind": device_kind,
                "source": "env"}
    if device_kind:
        for prefix, fl, by in DEVICE_PEAKS:
            if str(device_kind).startswith(prefix):
                return {"flops_per_s": fl, "bytes_per_s": by,
                        "backend": backend, "device_kind": device_kind,
                        "source": "device_table"}
    if backend == "tpu":
        return {"flops_per_s": None, "bytes_per_s": None, "backend": backend,
                "device_kind": device_kind, "source": "unavailable",
                "reason": f"TPU device_kind {device_kind!r} has no row in "
                          "DEVICE_PEAKS"}
    fl, by = BACKEND_PEAKS.get(backend or "cpu", BACKEND_PEAKS["cpu"])
    return {"flops_per_s": fl, "bytes_per_s": by, "backend": backend,
            "device_kind": device_kind, "source": "backend_default"}


# ---------------------------------------------------------------------------
# Call capture: one record per (program name, abstract signature, statics)
# ---------------------------------------------------------------------------
def _leaf_sig(x):
    """Hashable per-leaf signature component. Arrays (incl. tracers) key by
    shape/dtype; python scalars key by TYPE only — jit traces them as weak
    dynamic scalars, so e.g. a round index must not mint a new
    specialization per value."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return ("a", tuple(shape), str(dtype))
    if isinstance(x, (bool, int, float)):
        return (type(x).__name__,)
    return ("other", type(x).__qualname__)


def _abstractify(x):
    """Array-likes → ShapeDtypeStruct (keeping a NamedSharding so the
    re-lowered program matches the sharded one that actually ran); python
    scalars pass through to `lower()` unchanged. Tracers are reduced to
    their shape/dtype — capture never retains a live tracer."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return x
    import jax                                          # noqa: PLC0415
    from jax.sharding import NamedSharding              # noqa: PLC0415
    try:
        sharding = getattr(x, "sharding", None)
    except Exception:
        sharding = None
    if isinstance(sharding, NamedSharding):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def record_call(store: dict, name: str, fn, args, kwargs=None, *,
                static=None, jit_wrap: bool = False,
                span: Optional[str] = None, wire_bytes=None) -> None:
    """Observe one call of `fn` (a jitted program, or with `jit_wrap=True`
    a plain traceable callable) under program `name`.

    `store` is the owning Obs session's capture dict. First sighting of a
    signature abstracts and stores the args; every sighting bumps the call
    count and accumulates `wire_bytes` (the analytic minimum-traffic bytes
    this call puts on the wire, from the codec audit). `static` is a
    hashable tag for compile-time parameters closed over by `fn` (e.g.
    quantizer bits) so differently-specialized closures don't collide.
    `span` names the host-side obs span whose measured time this program
    should be attributed to (default: the program name itself).
    """
    import jax                                          # noqa: PLC0415
    kwargs = kwargs or {}
    leaves, treedef = jax.tree.flatten((args, kwargs))
    sig = (name, treedef, tuple(_leaf_sig(x) for x in leaves), static)
    rec = store.get(sig)
    if rec is None:
        a_args, a_kwargs = jax.tree.map(_abstractify, (args, kwargs))
        store[sig] = rec = {
            "name": name, "fn": fn, "args": a_args, "kwargs": a_kwargs,
            "static": static, "jit_wrap": jit_wrap, "span": span,
            "calls": 0, "wire_bytes": 0.0, "cost": None, "cost_mem": None,
        }
    rec["calls"] += 1
    if wire_bytes:
        rec["wire_bytes"] += float(wire_bytes)


# ---------------------------------------------------------------------------
# Extraction (cached per capture record)
# ---------------------------------------------------------------------------
def _normalize_cost(ca) -> dict:
    """XLA returns a dict (Lowered) or a per-partition list (Compiled)."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def _leaf_bytes(tree) -> float:
    import jax                                          # noqa: PLC0415
    import numpy as np                                  # noqa: PLC0415
    total = 0.0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        try:
            itemsize = np.dtype(dtype).itemsize
        except Exception:
            # extended dtypes (typed PRNG keys: 'key<fry>') aren't numpy
            # dtypes; their itemsize attribute covers the wire footprint
            itemsize = getattr(dtype, "itemsize", None)
            if itemsize is None:
                continue
        total += float(np.prod(shape, dtype=np.float64) * itemsize)
    return total


def _extract(rec: dict, compile_ok: bool) -> dict:
    """Cost-analyze one captured specialization. `lower()` only (trace +
    HLO analysis; no backend compile, no jit-cache effect) unless
    `compile_ok`, which additionally AOT-compiles for `memory_analysis()`.
    Any failure degrades to available=False with the reason recorded."""
    cached = rec["cost_mem"] if compile_ok else rec["cost"]
    if cached is not None:
        return cached
    out = {"sig": _sig_str(rec), "calls": 0, "available": False,
           "reason": None, "source": None, "flops": None,
           "bytes_accessed": None, "argument_bytes": None,
           "output_bytes": None, "temp_bytes": None, "peak_bytes": None}
    try:
        import jax                                      # noqa: PLC0415
        fn = jax.jit(rec["fn"]) if rec["jit_wrap"] else rec["fn"]
        lowered = fn.lower(*rec["args"], **rec["kwargs"])
        out["argument_bytes"] = _leaf_bytes((rec["args"], rec["kwargs"]))
        if compile_ok:
            compiled = lowered.compile()
            ca = _normalize_cost(compiled.cost_analysis())
            out["source"] = "compiled"
            try:
                mem = compiled.memory_analysis()
                arg = float(mem.argument_size_in_bytes)
                outb = float(mem.output_size_in_bytes)
                tmp = float(mem.temp_size_in_bytes)
                out.update(argument_bytes=arg, output_bytes=outb,
                           temp_bytes=tmp, peak_bytes=arg + outb + tmp)
            except Exception as e:                      # pragma: no cover
                out["reason"] = f"memory_analysis: {type(e).__name__}: {e}"
        else:
            ca = _normalize_cost(lowered.cost_analysis())
            out["source"] = "lowered"
        flops = ca.get("flops")
        accessed = ca.get("bytes accessed")
        out["flops"] = float(flops) if flops is not None else None
        out["bytes_accessed"] = (float(accessed)
                                 if accessed is not None else None)
        if out["flops"] is None and out["bytes_accessed"] is None:
            out["reason"] = ("cost analysis reported neither flops nor "
                             "bytes accessed on this backend")
        else:
            out["available"] = True
    except Exception as e:
        out["reason"] = f"{type(e).__name__}: {e}"
    if compile_ok:
        rec["cost_mem"] = out
    else:
        rec["cost"] = out
    return out


def _sig_str(rec: dict) -> str:
    import jax                                          # noqa: PLC0415
    parts = []
    for leaf in jax.tree.leaves((rec["args"], rec["kwargs"])):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}[{','.join(map(str, shape))}]")
        else:
            parts.append(type(leaf).__name__)
    tail = f" static={rec['static']!r}" if rec["static"] is not None else ""
    return f"({', '.join(parts)}){tail}"


def snapshot(captures: dict, *, compile_ok: bool = False,
             peak_info: Optional[dict] = None) -> dict:
    """Fold a session's captures into the per-program cost table.

    {"peaks": {...}, "programs": {name: {"span", "calls", "wire_bytes",
    "flops_total", "bytes_total", "cost_coverage", "specializations":
    [...]}}}. Totals weight each specialization's analysis by its observed
    call count; `cost_coverage` is the fraction of observed calls whose
    specialization produced an analysis (1.0 = fully modeled). Extraction
    is cached per specialization, so repeated snapshots are cheap.
    """
    from repro.obs import recompile as recompile_lib    # noqa: PLC0415
    annotations = recompile_lib.annotations_by_name()
    programs: dict = {}
    for rec in captures.values():
        name = rec["name"]
        ann = annotations.get(name, {})
        prog = programs.setdefault(name, {
            "span": rec["span"] or ann.get("span") or name,
            "calls": 0, "wire_bytes": 0.0, "flops_total": 0.0,
            "bytes_total": 0.0, "covered_calls": 0,
            "annotations": {k: v for k, v in ann.items() if k != "span"},
            "specializations": []})
        spec = dict(_extract(rec, compile_ok))
        spec["calls"] = rec["calls"]
        prog["specializations"].append(spec)
        prog["calls"] += rec["calls"]
        prog["wire_bytes"] += rec["wire_bytes"]
        if spec["available"]:
            prog["covered_calls"] += rec["calls"]
            if spec["flops"] is not None:
                prog["flops_total"] += spec["flops"] * rec["calls"]
            if spec["bytes_accessed"] is not None:
                prog["bytes_total"] += spec["bytes_accessed"] * rec["calls"]
    for prog in programs.values():
        prog["specializations"].sort(key=lambda s: s["sig"])
        prog["cost_coverage"] = (prog.pop("covered_calls") / prog["calls"]
                                 if prog["calls"] else 0.0)
    return {"peaks": peak_info or peaks(),
            "programs": {k: programs[k] for k in sorted(programs)}}


# ---------------------------------------------------------------------------
# Roofline-fraction attribution onto measured spans
# ---------------------------------------------------------------------------
def attach_attrib(summary: dict, snap: dict) -> dict:
    """Mutate `summary` (a `report.summarize` result): every span that a
    cost-modeled program attributes to gains an `attrib` block — measured
    seconds vs the model-predicted FLOP time and byte time from the peak
    table, the achieved roofline fraction, which roof binds, and achieved
    wire-bytes/s against the analytic R·n minimum-traffic bytes."""
    spans = summary.get("spans", {})
    pk = snap.get("peaks", {})
    by_span: dict = {}
    for name, prog in snap.get("programs", {}).items():
        by_span.setdefault(prog.get("span") or name, []).append((name, prog))
    for span_name in sorted(by_span):
        sp = spans.get(span_name)
        if sp is None:
            continue
        group = by_span[span_name]
        flops = sum(p["flops_total"] for _, p in group)
        nbytes = sum(p["bytes_total"] for _, p in group)
        wire = sum(p["wire_bytes"] for _, p in group)
        calls = sum(p["calls"] for _, p in group)
        covered = sum(p["cost_coverage"] * p["calls"] for _, p in group)
        measured = sp.get("total_s", 0.0)
        t_flops = flops / pk["flops_per_s"] if pk.get("flops_per_s") else None
        t_bytes = nbytes / pk["bytes_per_s"] if pk.get("bytes_per_s") else None
        t_model = max(t_flops or 0.0, t_bytes or 0.0) or None
        attrib = {
            "programs": sorted(n for n, _ in group),
            "calls_observed": calls,
            "cost_coverage": (covered / calls) if calls else 0.0,
            "flops_total": flops or None,
            "bytes_total": nbytes or None,
            "measured_s": measured,
            "t_flops_s": t_flops if flops else None,
            "t_bytes_s": t_bytes if nbytes else None,
            "t_model_s": t_model if (flops or nbytes) else None,
            "roofline_frac": None, "bound": None,
            "flops_per_s_achieved": (flops / measured
                                     if flops and measured > 0 else None),
            "bytes_per_s_achieved": (nbytes / measured
                                     if nbytes and measured > 0 else None),
            "wire_min_bytes": wire or None,
            "wire_min_bytes_per_s": (wire / measured
                                     if wire and measured > 0 else None),
        }
        if attrib["t_model_s"] and measured > 0:
            attrib["roofline_frac"] = attrib["t_model_s"] / measured
            attrib["bound"] = ("flops" if (t_flops or 0.0) >= (t_bytes or 0.0)
                               else "bytes")
        if pk.get("reason"):
            attrib["unavailable"] = pk["reason"]
        sp["attrib"] = attrib
    return summary
