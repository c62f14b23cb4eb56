"""BENCHMARK.json and the files the harness finds by name agree, and the
harness refuses to run anywhere but on a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import bench_tiny

from bench import cells

ROOT = bench_tiny.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_names_and_units_use_the_allowed_characters():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("name", WORKLOADS)
def test_each_cell_resolves_to_its_files(name):
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    cell = cells.workload(name)
    for key in ("config", "traffic", "chips", "why"):
        assert cell[key] == entry[key], key
    assert cell["mesh"][0] * cell["mesh"][1] == cell["chips"]
    cfg = cells.config(cell["config"])
    conf = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert conf["file"] == f"bench/configs/{cell['config']}.json"
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert hasattr(cells.flops(cfg["block"]), "flops_per_token")
    for m in cells.per_layer_for(name, BENCH):
        reader = cells.metric(m["name"])
        assert reader.UNIT == m["unit"] and reader.MOVES == m["moves"]
        assert callable(reader.read)


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", WORKLOADS):
            assert cell in WORKLOADS
            assert cell in moved.get("workloads", WORKLOADS)
    assert {"tokens_per_s", "setup_s"} <= set(e2e)


def test_a_cell_added_as_a_file_is_found_without_an_edit(tmp_path):
    new = bench_tiny.cell("yi-tiny", "psum")
    root = bench_tiny.make_root(tmp_path, {"yi-tiny.psum.1chip": new})
    assert "yi-tiny.psum.1chip" in cells.workload_names(root)
    assert cells.workload("yi-tiny.psum.1chip", root)["strategy"] == "psum"
    assert cells.config("yi-tiny", root)["block"] == "attn_mlp"
    assert set(cells.workload_names()) <= set(cells.workload_names(root))


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith('{"correct"')]


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    r = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert not _result_lines(r.stdout)
    assert "no TPU" in r.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert not _result_lines(r.stdout)
