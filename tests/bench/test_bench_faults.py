"""Faults planted under the timed path make `correct` false.

Each test drives the whole run except the look for a chip, with the step
broken underneath: the state returned unchanged, half of each worker's
rows left out of the loss (half of the sequence where a worker has one
row), and (on four virtual devices) the exchange between workers left
out.
"""
import time

import jax
import pytest

import bench_tiny

from bench import train_cell

CELLS = {"xlstm-tiny.ag4ef.1chip": bench_tiny.cell("xlstm-tiny",
                                                   "allgather_packed"),
         "xlstm-tiny.ag4ef.4chip": bench_tiny.cell("xlstm-tiny",
                                                   "allgather_packed", 4),
         "yi-tiny.psum.1chip": bench_tiny.cell("yi-tiny", "psum",
                                               per_worker_batch=1)}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("b"), CELLS)


@pytest.mark.parametrize("cell,fault", [
    ("xlstm-tiny.ag4ef.1chip", "unchanged"),
    ("xlstm-tiny.ag4ef.1chip", "half_batch"),
    ("xlstm-tiny.ag4ef.4chip", "no_exchange"),
    ("yi-tiny.psum.1chip", "unchanged"),
    ("yi-tiny.psum.1chip", "half_batch"),
])
def test_fault_is_caught(root, cell, fault, monkeypatch):
    if jax.device_count() < CELLS[cell]["chips"]:
        pytest.skip("needs 4 (virtual) devices")
    bench_tiny.force_pallas(monkeypatch)
    r = train_cell.run(cell, 7, 0.5, False, t_start=time.perf_counter(),
                       mode=f"fault:{fault}", root=root,
                       bench={"per_layer": []})
    checks = {k: v["value"] for k, v in r["checks"].items()}
    assert not r["correct"], checks


def test_sound_four_worker_run_is_correct(root, monkeypatch):
    if jax.device_count() < 4:
        pytest.skip("needs 4 (virtual) devices")
    bench_tiny.force_pallas(monkeypatch)
    r = train_cell.run("xlstm-tiny.ag4ef.4chip", 7, 0.5, False,
                       t_start=time.perf_counter(), root=root,
                       bench={"per_layer": []})
    checks = {k: v["value"] for k, v in r["checks"].items()}
    assert r["correct"], checks
