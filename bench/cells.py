"""Finds a cell's files by name.

  bench/workloads/<cell>.json   the cell: config, chips, mesh, strategy,
                                traffic sizes, optimizer, limits of `correct`
  bench/configs/<config>.json   the model configuration as it is run
  bench/flops/<block>.py        model FLOPs per token of one block family
  bench/metrics/<metric>.py     one reader per per-layer metric
  bench/peaks.json              peak FLOP/s and bytes/s by device kind

Adding a cell, a configuration, a block family or a metric adds files;
nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def workload(name: str, root: pathlib.Path = BENCH) -> dict:
    cell = _json(root / "workloads" / f"{name}.json")
    cell["name"] = name
    return cell


def config(name: str, root: pathlib.Path = BENCH) -> dict:
    return _json(root / "configs" / f"{name}.json")


def workload_names(root: pathlib.Path = BENCH) -> list[str]:
    return sorted(p.stem for p in (root / "workloads").glob("*.json"))


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def flops(block: str, root: pathlib.Path = BENCH):
    return _module(root / "flops" / f"{block}.py", f"bench_flops_{block}")


def metric(name: str, root: pathlib.Path = BENCH):
    return _module(root / "metrics" / f"{name}.py", f"bench_metric_{name}")


def peaks(root: pathlib.Path = BENCH) -> dict:
    return _json(root / "peaks.json")


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def per_layer_for(cell: str, bench: dict) -> list[dict]:
    """The per-layer metrics BENCHMARK.json expects from `cell`."""
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]


MODEL_KEYS = ("name", "num_layers", "d_model", "num_heads", "num_kv_heads",
              "d_ff", "vocab_size", "head_dim", "block", "rope_theta",
              "norm_eps", "dtype", "vocab_pad_multiple", "remat")


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models.model import ModelConfig
    return ModelConfig(**{k: cfg[k] for k in MODEL_KEYS if k in cfg})
