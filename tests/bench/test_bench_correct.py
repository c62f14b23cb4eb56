"""`correct` at a test size on the CPU: the program agrees with the plain
reference, and the bfloat16 control does not.

The same comparison decides `correct` in every run on the chip, at the
cell's own sizes; these cells are the xLSTM and Yi block families at small
widths, so a fault in the comparison shows here first.
"""
import time

import pytest

import bench_tiny

from bench import train_cell

CELLS = {"xlstm-tiny.ag4ef.1chip": bench_tiny.cell("xlstm-tiny",
                                                   "allgather_packed"),
         "yi-tiny.ag4ef.1chip": bench_tiny.cell("yi-tiny", "allgather_packed"),
         "yi-tiny.psum.1chip": bench_tiny.cell("yi-tiny", "psum")}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("b"), CELLS)


def _run(root, name, mode="run"):
    return train_cell.run(name, 2 ** 31 + 12345, 0.5, False,
                          t_start=time.perf_counter(), mode=mode, root=root,
                          bench={"per_layer": []})


@pytest.mark.parametrize("name", sorted(CELLS))
def test_program_matches_reference(root, name, monkeypatch):
    bench_tiny.force_pallas(monkeypatch)
    r = _run(root, name)
    checks = {k: v["value"] for k, v in r["checks"].items()}
    assert r["correct"], checks
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "peak_hbm_gib", "setup_s"}
    assert set(r["checks"]) <= set(bench_tiny.LIMITS)


def test_bfloat16_control_is_rejected(root, monkeypatch):
    bench_tiny.force_pallas(monkeypatch)
    r = _run(root, "xlstm-tiny.ag4ef.1chip", mode="control")
    checks = {k: v["value"] for k, v in r["checks"].items()}
    assert not r["correct"], checks
    assert checks["grad_gap"] > 3 * bench_tiny.LIMITS["grad_gap"]
