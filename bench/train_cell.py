"""One run of a training cell.

Set-up builds one object, the compiled train step of `repro.dist.step`
with its state, and drives it from the seed through its first three steps
(the readings `correct` compares) and the cell's warm-up steps. The window
then calls the same step on fresh batches until `seconds` have passed;
with `trace` a few more steps run under the profiler. Once the window has
closed and the peak memory is read, the program's state is freed and the
plain reference (bench/reference.py) follows the first three steps.
"""
from __future__ import annotations

import contextlib
import gc as gc_lib
import glob
import math
import os
import shutil
import statistics
import tempfile
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import cells, codec_bytes, faults, reference, trace_reduce, weights

SETUP_STEPS = 3


def log(msg: str) -> None:
    print(msg, flush=True)


class Compiles:
    """Counts XLA compilations and persistent-cache hits from now on."""

    def __init__(self):
        self.backend = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def total(self) -> int:
        return self.backend + self.cache_hits


def _ann(name):
    return jax.profiler.TraceAnnotation(name)


def _leaf_norms(tree, scale=1.0):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)]) * scale


def _dispatches(session) -> dict:
    out = {}
    for ev in session.memory_events():
        if ev.get("type") == "counter" and ev["name"] == "kernels.dispatch":
            key = f"{ev['attrs']['op']}/{ev['attrs']['path']}"
            out[key] = out.get(key, 0) + int(ev["value"])
    return out


def matmul_precision(cfg: dict) -> str:
    """The float32 matrix-product precision the configuration states:
    "default" (one bfloat16 pass on a TPU) or "highest"."""
    return cfg.get("matmul_precision", "default")


class Program:
    """The train step, its state and its feed, as the training loop of
    `repro.launch.train` builds them, with the benchmark's weights. Every
    program call runs under the configuration's matmul precision (JAX's
    `default_matmul_precision`), which the compiled programs take in."""

    def __init__(self, cell: dict, cfg: dict, devices, seed: int,
                 fault: str | None = None):
        from repro.data import batch_for_shape
        from repro.dist import step as step_lib
        from repro.dist.gradcomp import GradCompConfig
        from repro.optimizer import adamw, warmup_cosine

        self.cell, self.cfg = cell, cfg
        self.model = cells.model_config(cfg)
        self.precision = matmul_precision(cfg)
        rows, cols = cell["mesh"]
        self.mesh = Mesh(np.asarray(devices[:rows * cols]).reshape(rows, cols),
                         ("data", "model"))
        self.workers = rows
        o = cell["optimizer"]
        self.opt = adamw(warmup_cosine(o["lr"], o["warmup"], o["total"]),
                         b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"])
        self.gc = GradCompConfig(
            bits=cell["bits"], chunk=cell["chunk"], strategy=cell["strategy"],
            error_feedback=cell["error_feedback"],
            keep_fraction=cell["keep_fraction"])
        self.undo = faults.plant(fault)
        step = step_lib.make_train_step(self.model, self.opt, self.gc,
                                        self.mesh, clip_norm=o["clip_norm"])
        self.step = faults.wrap_step(fault, step)
        self.specs = step_lib.train_state_specs(self.model, self.opt, self.gc,
                                                self.mesh)
        self.key = weights.seed_key(seed)
        self.data_seed = np.int32(seed % (1 << 31))
        self.batch = cell["per_worker_batch"] * self.workers
        seq = cell["seq_len"]
        model = self.model
        self.feed = jax.jit(
            lambda i, s: batch_for_shape(model, self.batch, seq, i, s),
            out_shardings=NamedSharding(self.mesh, P("data")))

    def init_state(self):
        with self.precise():
            return self._init_state()

    def _init_state(self):
        p, o, e = self.specs
        shard = lambda t: jax.tree.map(lambda s: s.sharding, t)
        params = weights.make(p, self.key, out_shardings=shard(p))
        opt_state = jax.jit(self.opt.init, out_shardings=shard(o))(params)
        ef = jax.jit(lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), e),
            out_shardings=shard(e))()
        return params, opt_state, ef

    def precise(self):
        if self.precision == "default":
            return contextlib.nullcontext()
        return jax.default_matmul_precision(self.precision)

    def advance(self, state, i: int):
        """One step of the loop: batch, step call, wait."""
        with _ann("bench.batch"), self.precise():
            batch = self.feed(np.int32(i), self.data_seed)
        with _ann("bench.dispatch"), self.precise():
            out = self.step(*state, batch)
        with _ann("bench.wait"):
            jax.block_until_ready(out)
        return out[:3], out[3]["loss"], batch

    def update_norms(self, params):
        shapes = self.specs[0]
        key = self.key

        def norms(p, k):
            p0 = weights.make(shapes, k)
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a - b)))
                              for a, b in zip(jax.tree.leaves(p),
                                              jax.tree.leaves(p0))])
        with self.precise():
            return np.asarray(jax.jit(norms)(params, key))


def setup_steps(prog: Program, state):
    """The first three steps, with the readings the reference follows."""
    from repro import obs
    b1 = prog.cell["optimizer"]["b1"]
    losses, tokens = [], []
    session = obs.enable(costs=False)
    try:
        state, loss, batch = prog.advance(state, 0)
    finally:
        obs.disable()
    losses.append(float(loss))
    tokens.append(np.asarray(batch["tokens"]))
    grad = np.asarray(jax.jit(lambda mu: _leaf_norms(mu, 1.0 / (1 - b1)))(
        state[1]["mu"]))
    g = [np.asarray(x) / np.float32(1 - b1) for x in jax.tree.leaves(state[1]["mu"])]
    ef = (np.asarray(jax.jit(_leaf_norms)(state[2])) if prog.gc.uses_ef
          else np.zeros_like(grad))
    for i in range(1, SETUP_STEPS):
        state, loss, batch = prog.advance(state, i)
        losses.append(float(loss))
        tokens.append(np.asarray(batch["tokens"]))
    readings = {"loss": np.asarray(losses), "grad": grad, "ef": ef,
                "update": prog.update_norms(state[0]), "g": g}
    m = prog.workers
    toks = np.stack([t.reshape((m, t.shape[0] // m) + t.shape[1:])
                     for t in tokens])
    return state, readings, toks, _dispatches(session)


def reference_readings(cell: dict, cfg: dict, shapes, key, tokens, devices,
                       dtype=jnp.float32, precision=None) -> dict:
    """The reference's readings over the first three steps.

    tokens: (3, m, B, S+1); worker w runs on device w."""
    names = weights.names(shapes)
    trainer = reference.Trainer(cfg, cell, names, devices, dtype, precision)
    plain = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                         shapes)
    tree0 = weights.make(plain, key, out_shardings=jax.tree.map(
        lambda _: trainer.rep, plain))
    params0 = dict(zip(names, jax.tree.leaves(tree0)))
    state = trainer.init_state(params0)
    losses = []
    for t in range(SETUP_STEPS):
        state, r = trainer.step(state, tokens[t], t + 1, keep_grad=t == 0)
        losses.append(r["loss"])
        if t == 0:
            grad, ef_norms, g = np.asarray(r["grad"]), np.asarray(r["ef"]), r["g"]
    update = np.asarray(jax.jit(reference.update_norms, static_argnums=2)(
        state[0], params0, tuple(names)))
    return {"loss": np.asarray(losses), "grad": grad, "ef": ef_norms,
            "update": update, "g": g}


def control_precision(cfg: dict) -> dict:
    """The control's arithmetic: the nearest precision below the one the
    configuration states (bench/reference.py)."""
    if matmul_precision(cfg) == "highest":
        return {"dtype": jnp.float32, "precision": "high"}
    return {"dtype": jnp.bfloat16}


def leaf_gap(got, want, keep=None) -> float:
    """Worst leaf of |got - want| / max(want, median leaf of want)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if keep is not None:
        got, want = got[keep], want[keep]
    med = np.median(want)
    return float(np.max(np.abs(got - want) / np.maximum(want, med)))


def median_leaf_gap(got, want) -> float:
    """The median leaf's |got - want| / max(want, median leaf of want): the
    gap of a typical leaf, which one leaf's rounding does not move."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.median(np.abs(got - want)
                           / np.maximum(want, np.median(want))))


def diff_gap(got, want) -> float:
    """Worst leaf of |got - want| / max(|want|, median leaf's |want|), the
    norms of the leaves' difference and of the reference's leaves."""
    diff = np.asarray([np.linalg.norm((np.asarray(a, np.float64)
                                       - np.asarray(b, np.float64)).ravel())
                       for a, b in zip(got, want)])
    norm = np.asarray([np.linalg.norm(np.asarray(b, np.float64).ravel())
                       for b in want])
    return float(np.max(diff / np.maximum(norm, np.median(norm))))


def compare(got: dict, want: dict, use_ef: bool) -> dict:
    """The numbers `correct` compares, from the program's (or the
    control's) readings and the reference's. Leaves whose reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone and are left out of the update comparison. `grad_diff` compares
    the first gradient itself, not its norm: rounding that averages out of
    a leaf's norm shows in the norm of the difference. `ef_gap_median`
    takes the error feedback's gap at the median leaf: in the xLSTM the
    worst leaf is a gate projection whose gradient passes the whole
    recurrence, and its gap reads alike at `highest` and a precision
    below."""
    loss = float(np.max(np.abs(got["loss"] - want["loss"])
                        / np.abs(want["loss"])))
    keep = want["grad"] >= 1e-3 * np.median(want["grad"])
    out = {"loss_gap": loss,
           "grad_gap": leaf_gap(got["grad"], want["grad"]),
           "update_gap": leaf_gap(got["update"], want["update"], keep)}
    if use_ef:
        out["ef_gap"] = leaf_gap(got["ef"], want["ef"])
        out["ef_gap_median"] = median_leaf_gap(got["ef"], want["ef"])
    out["grad_diff"] = diff_gap(got["g"], want["g"])
    return out


def payload_checks(prog: Program, seed: int) -> dict:
    """The program's codec against the reference's on the timed leaf
    shapes: bitwise payloads of the largest leaf and the largest block leaf
    through the program's own encode + EF entry, and the payload bytes
    against the R-bit budget."""
    from repro.dist import gradcomp as G
    shapes = jax.tree.leaves(prog.specs[0])
    names = weights.names(prog.specs[0])
    sizes = [int(math.prod(s.shape)) for s in shapes]
    gc = prog.gc
    budget = codec_bytes.payload_bytes(sizes, gc.bits, gc.chunk)
    audit = G.wire_bytes_tree(shapes, gc, prog.workers)
    actual = 0
    for i, s in enumerate(shapes):
        out = jax.eval_shape(
            lambda x, i=i: G.encode_leaf_ef(x, i, gc)[0],
            jax.ShapeDtypeStruct(s.shape, jnp.float32))
        actual += sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(out))
    log(f"wire audit: f32 {audit['f32_bytes']} B -> payload "
        f"{audit['payload_bytes']} B per worker per step "
        f"({audit['compression_x']!r}x); encoded {actual} B; "
        f"{gc.bits}-bit budget {budget} B")
    in_blocks = [n.startswith("blocks/") for n in names]
    picks = {max(range(len(sizes)), key=lambda i: sizes[i]),
             max((i for i in range(len(sizes)) if in_blocks[i]),
                 key=lambda i: sizes[i])}
    mismatch = 0
    for i in sorted(picks):
        u = jax.random.normal(jax.random.fold_in(weights.seed_key(seed), i),
                              shapes[i].shape, jnp.float32)
        got = jax.jit(lambda x, i=i: G.encode_leaf_ef(x, i, gc)[0])(u)
        want = jax.jit(lambda x, i=i: reference.encode(
            reference.to_chunks(x, gc.chunk),
            reference.frame_signs(i, gc.chunk), gc.bits))(u)
        bad = {"words": int(jnp.sum(got["words"] != want[0])),
               "scale": int(jnp.sum(jax.lax.bitcast_convert_type(
                   got["scale"], jnp.int32) != jax.lax.bitcast_convert_type(
                       want[1], jnp.int32)))}
        log(f"payload {names[i]} ({want[0].shape[0]} chunks): mismatching "
            f"entries {bad}")
        mismatch += sum(bad.values())
        del u, got, want
    return {"payload_mismatch": mismatch,
            "payload_bytes_gap": abs(actual - budget)
            + abs(audit["payload_bytes"] - budget)}


def _window(prog: Program, state, first: int, seconds: float):
    """Steps until `seconds` would pass; returns (state, step times,
    losses, window seconds, next step, device bytes in use after each
    step)."""
    times, losses, in_use = [], [], []
    dev = prog.mesh.devices.flat[0]
    i = first
    t_start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        state, loss, _ = prog.advance(state, i)
        t1 = time.perf_counter()
        times.append(t1 - t0)
        losses.append(float(loss))
        in_use.append((dev.memory_stats() or {}).get("bytes_in_use"))
        i += 1
        if t1 - t_start + statistics.median(times) > seconds:
            break
    return state, times, losses, t1 - t_start, i, in_use


def _trace(prog: Program, state, first: int, keep_trace: str | None):
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            for i in range(first, first + prog.cell["trace_steps"]):
                state, _, _ = prog.advance(state, i)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, os.path.join(keep_trace, os.path.basename(path)))
        t0 = time.perf_counter()
        tr = trace_reduce.load(path)
        log(f"trace: {os.path.getsize(path)} B, "
            f"{sum(len(v) for v in tr.devices.values())} device ops, reduced "
            f"in {time.perf_counter() - t0!r} s")
        return state, tr
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, mode: str = "run", root=cells.BENCH,
        bench: dict | None = None, keep_trace: str | None = None) -> dict:
    """Everything of one run after the look for a chip. Returns the result
    line's fields (`run.py` orders them)."""
    cell = cells.workload(cell_name, root)
    cfg = cells.config(cell["config"], root)
    devices = jax.devices()[:cell["chips"]]
    fault = mode.split(":", 1)[1] if mode.startswith("fault:") else None
    compiles = Compiles()
    prog = Program(cell, cfg, devices, seed, fault)
    used = list(prog.mesh.devices.flat)
    state, prog_read, tokens, dispatches = setup_steps(prog,
                                                       prog.init_state())
    log(f"set-up steps: losses {prog_read['loss'].tolist()}")
    log(f"kernels.dispatch {dispatches}")
    first = SETUP_STEPS
    for i in range(first, first + cell["warmup_steps"]):
        state, _, _ = prog.advance(state, i)
    first += cell["warmup_steps"]
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s!r} s; compiles {compiles.backend}, cache hits "
        f"{compiles.cache_hits}, misses {compiles.cache_misses}")

    from repro.obs import recompile
    before, counted = compiles.total(), recompile.counts()
    state, times, losses, window_s, nxt, in_use = _window(prog, state, first,
                                                           seconds)
    in_window = compiles.total() - before
    step_programs = {k: v - counted.get(k, 0)
                     for k, v in recompile.counts().items()
                     if v != counted.get(k, 0)}
    tokens_per_s = len(times) * prog.batch * cell["seq_len"] / window_s
    log(f"window: {len(times)} steps in {window_s!r} s, {tokens_per_s!r} "
        f"tokens/s; step seconds {times!r}")
    log(f"compiles in the window: {in_window} (new programs {step_programs})")
    log(f"device bytes in use after each step: {in_use}")

    tr = None
    if trace:
        state, tr = _trace(prog, state, nxt, keep_trace)
    memory = peak_bytes(used)
    failed = sum(1 for v in losses if not math.isfinite(v))
    del state
    prog.undo()

    checks = {"nonfinite_steps": failed,
              "ref_dispatches": sum(v for k, v in dispatches.items()
                                    if k.endswith("/ref"))}
    if prog.gc.compresses:
        checks.update(payload_checks(prog, seed))
    shapes = prog.specs[0]
    gc_lib.collect()
    t_ref = time.perf_counter()
    want = reference_readings(cell, cfg, shapes, prog.key, tokens, used)
    if mode == "control":
        got = reference_readings(cell, cfg, shapes, prog.key, tokens, used,
                                 **control_precision(cfg))
    else:
        got = prog_read
    log(f"reference followed {SETUP_STEPS} steps in "
        f"{time.perf_counter() - t_ref!r} s; losses {want['loss'].tolist()}")
    checks.update(compare(got, want, prog.gc.uses_ef))
    limits = cell["limits"]

    kind = used[0].device_kind
    device = {"platform": used[0].platform, "kind": kind, "count": len(used),
              "memory_peak_bytes": memory}
    result = {"attempted": len(times), "failed": failed}
    if trace:
        ctx = _context(cell, cfg, prog, tr, tokens_per_s, root)
        result["metrics"] = _per_layer(cell_name, ctx, bench)
        lo, hi = tr.window()
        busy = [trace_reduce.busy_ns(tr.devices.get(d.id, []), lo, hi)
                for d in used]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        dev0 = tr.devices.get(used[0].id, [])
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(dev0, lo, hi),
            "idle_gaps": trace_reduce.idle_gaps(dev0, tr.host, lo, hi)}
    else:
        result["metrics"] = {
            "tokens_per_s": {"value": tokens_per_s, "unit": "tokens/s"},
            "peak_hbm_gib": {"value": memory / 2 ** 30, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["device"] = device
    log("readings not compared: " + repr({k: v for k, v in checks.items()
                                           if k not in limits}))
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in checks.items() if k in limits}
    result["correct"] = all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in result["checks"].values())
    return result


def _context(cell, cfg, prog, tr, tokens_per_s, root):
    shapes = jax.tree.leaves(prog.specs[0])
    sizes = [int(math.prod(s.shape)) for s in shapes]
    ctx = types.SimpleNamespace(
        cell=cell, config=cfg, trace=tr, reduce=trace_reduce,
        device_ids=[d.id for d in prog.mesh.devices.flat],
        tokens_per_s=tokens_per_s,
        flops=cells.flops(cfg["block"], root),
        peak=cells.peaks(root)[prog.mesh.devices.flat[0].device_kind],
        codec_minimum=codec_bytes.minimum(
            sizes, prog.gc.bits, prog.gc.chunk, prog.workers,
            prog.gc.uses_ef),
        root=root, values={})

    def metric(name):
        if name not in ctx.values:
            ctx.values[name] = cells.metric(name, root).read(ctx)
        return ctx.values[name]

    ctx.metric = metric
    return ctx


def _per_layer(cell_name, ctx, bench) -> dict:
    out = {}
    for m in cells.per_layer_for(cell_name, bench):
        value = ctx.metric(m["name"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
