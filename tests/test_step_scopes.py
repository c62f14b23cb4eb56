"""The train step's phase scopes and the host spans around it.

The scopes of `repro.dist.scopes` must reach the compiled program's op
metadata for every consensus strategy and change nothing else in it; an
active obs span must land in a `jax.profiler` trace under its own name,
and `train()` must mark its loop phases there.
"""
import contextlib
import functools
import glob
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.data import batch_for_shape
from repro.dist import scopes
from repro.dist import step as step_lib
from repro.dist.gradcomp import GradCompConfig
from repro.models.model import ModelConfig
from repro.obs import core as obs
from repro.optimizer import adamw

CFG = ModelConfig(name="scopes-tiny", num_layers=1, d_model=64, num_heads=2,
                  num_kv_heads=1, d_ff=128, vocab_size=256, head_dim=32)
STRATEGIES = ("psum", "psum_decoded", "allgather_packed", "alltoall_zero1")
# the consensus children each strategy runs
CHILDREN = {"psum": {scopes.EXCHANGE},
            "psum_decoded": {scopes.ENCODE, scopes.DECODE, scopes.EXCHANGE},
            "allgather_packed": {scopes.ENCODE, scopes.EXCHANGE,
                                 scopes.DECODE, scopes.MEAN},
            "alltoall_zero1": {scopes.ENCODE, scopes.EXCHANGE, scopes.DECODE,
                               scopes.MEAN}}
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _mesh():
    if jax.device_count() < 4:
        pytest.skip("needs 4 (virtual) devices")
    return Mesh(np.asarray(jax.devices()[:4]).reshape(4, 1),
                ("data", "model"))


@functools.lru_cache(maxsize=None)
def _compiled_text(strategy: str, scoped: bool = True) -> str:
    mesh = _mesh()
    opt = adamw(1e-3)
    gc = GradCompConfig(bits=4, chunk=256, strategy=strategy)
    if strategy == "alltoall_zero1":
        make, specs = step_lib.make_zero_train_step, step_lib.zero_state_specs
    else:
        make, specs = step_lib.make_train_step, step_lib.train_state_specs
    named_scope = jax.named_scope
    if not scoped:
        jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        step = make(CFG, opt, gc, mesh, clip_norm=1.0)
        return step.lower(*specs(CFG, opt, gc, mesh),
                          batch_for_shape(CFG, 4, 16)).compile().as_text()
    finally:
        jax.named_scope = named_scope


def _phases(op_names) -> set:
    """The scope paths the op names reach: 'forward', 'backward',
    'consensus/<child>', 'optimizer'."""
    found = set()
    for name in op_names:
        parts = name.split("/")
        if scopes.FORWARD in parts:
            after = parts[parts.index(scopes.FORWARD) + 1:]
            found.add("backward" if any(p.startswith("transpose(")
                                        for p in after) else "forward")
        if scopes.CONSENSUS in parts:
            i = parts.index(scopes.CONSENSUS)
            found.add("/".join(parts[i:i + 2]))
        if scopes.OPTIMIZER in parts:
            found.add(scopes.OPTIMIZER)
    return found


def _structure(hlo: str) -> list:
    """The compiled module's instructions without metadata, each
    instruction and computation name replaced by the order in which it
    first appears: two programs that differ only in op metadata and in
    the numeric suffixes of names read alike."""
    lines = [re.sub(r", metadata=\{[^}]*\}", "", ln)
             for ln in hlo.splitlines()
             if " = " in ln or ln.startswith(("ENTRY", "%"))]
    ids: dict = {}
    return [re.sub(r"%([\w.-]+)",
                   lambda m: "%" + str(ids.setdefault(m.group(1), len(ids))),
                   ln) for ln in lines]


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_compiled_step_carries_every_phase_scope(strategy):
    got = _phases(OP_NAME.findall(_compiled_text(strategy)))
    want = ({"forward", "backward", scopes.OPTIMIZER}
            | {f"{scopes.CONSENSUS}/{c}" for c in CHILDREN[strategy]})
    assert want <= got, sorted(want - got)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_scopes_change_the_compiled_step_in_metadata_only(strategy):
    scoped = _compiled_text(strategy)
    bare = _compiled_text(strategy, scoped=False)
    assert not _phases(OP_NAME.findall(bare))
    assert _structure(scoped) == _structure(bare)


def test_scope_names_are_distinct_path_components():
    assert len(set(scopes.ALL)) == len(scopes.ALL)
    assert all(re.fullmatch(r"[a-z]+", s) for s in scopes.ALL)


def _host_events(trace_dir) -> list:
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    data = ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_active_span_is_a_profiler_host_event(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        obs.enable()
        with obs.span("t.scopes.outer"):
            with obs.span("t.scopes.inner"):
                jax.numpy.ones(4).block_until_ready()
        obs.disable()
        with obs.span("t.scopes.disabled"):
            pass
    finally:
        jax.profiler.stop_trace()
    events = {name: (s, e) for name, s, e in _host_events(tmp_path)}
    assert "t.scopes.disabled" not in events
    (o0, o1), (i0, i1) = events["t.scopes.outer"], events["t.scopes.inner"]
    assert o0 <= i0 <= i1 <= o1


def test_disabled_span_is_the_shared_noop():
    assert not obs.enabled()
    assert obs.span("t.scopes.off") is obs.NOOP_SPAN


def test_obs_imports_and_spans_without_jax():
    """With jax unimportable, the package imports and an active span still
    records (its profiler annotation is the no-op)."""
    import os
    import subprocess
    import sys
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro.obs import core as obs\n"
            "o = obs.enable()\n"
            "with obs.span('t.nojax'):\n"
            "    pass\n"
            "obs.disable()\n"
            "assert o.summary()['spans']['t.nojax']['count'] == 1\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=src))
    assert r.returncode == 0, r.stderr


def test_train_loop_marks_its_phases_in_the_profiler_trace(tmp_path):
    from repro.launch.train import train
    gc = GradCompConfig(strategy="psum", error_feedback=False)
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        session = obs.enable()
        train(CFG, steps=2, batch_size=2, seq_len=16, gc=gc, log_every=10,
              ckpt_dir=str(tmp_path / "ckpt"))
        obs.disable()
    finally:
        jax.profiler.stop_trace()
    spans = session.summary()["spans"]
    for name in ("train.batch", "train.step", "train.wait", "dist.step"):
        assert spans[name]["count"] == 2, name
    assert spans["train.checkpoint"]["count"] == 1
    events = _host_events(tmp_path / "prof")
    names = [n for n, _, _ in events]
    for name in ("train.batch", "train.step", "train.wait", "dist.step",
                 "train.checkpoint"):
        assert name in names, name
    steps = [(s, e) for n, s, e in events if n == "train.step"]
    dist = [(s, e) for n, s, e in events if n == "dist.step"]
    assert all(any(s0 <= s <= e <= e0 for s0, e0 in steps) for s, e in dist)
    assert sum(1 for n in names if n == "train") == 2
