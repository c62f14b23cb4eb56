"""Tiny cells for the benchmark's CPU tests, written into a copy of bench/.

The copy is a benchmark directory of its own: the harness finds the tiny
cells there by name, with no edit of bench/ (the discovery test relies on
it). Limits are for float32 on the CPU, where the program and the
reference agree to ~1e-5 and the bfloat16 control does not.
"""
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-4, "update_gap": 1e-3,
          "ef_gap": 1e-4, "payload_mismatch": 0, "payload_bytes_gap": 0,
          "ref_dispatches": 0, "nonfinite_steps": 0}

CONFIGS = {
    "xlstm-tiny": {"num_layers": 2, "d_model": 256, "num_heads": 2,
                   "num_kv_heads": 2, "d_ff": 0, "vocab_size": 500,
                   "block": "xlstm_pair"},
    "yi-tiny": {"num_layers": 2, "d_model": 256, "num_heads": 4,
                "num_kv_heads": 2, "head_dim": 64, "d_ff": 512,
                "vocab_size": 500, "block": "attn_mlp",
                "rope_theta": 10000.0},
}


def cell(config: str, strategy: str, chips: int = 1, seq_len: int = 32,
         per_worker_batch: int = 2):
    return {
        "config": config, "traffic": f"{strategy}.{chips}chip",
        "chips": chips, "mesh": [chips, 1], "strategy": strategy,
        "bits": 4, "chunk": 256, "error_feedback": strategy != "psum",
        "keep_fraction": 1.0, "seq_len": seq_len, "per_worker_batch": per_worker_batch,
        "warmup_steps": 1, "trace_steps": 1,
        "optimizer": {"lr": 3e-4, "warmup": 50, "total": 1000, "b1": 0.9,
                      "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
                      "clip_norm": 1.0},
        "why": "CPU test cell", "limits": dict(LIMITS)}


def make_root(tmp: pathlib.Path, cells: dict) -> pathlib.Path:
    """A copy of bench/ plus the tiny configurations and `cells`
    ({name: workload dict})."""
    root = tmp / "bench"
    shutil.copytree(ROOT / "bench", root,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    for name, cfg in CONFIGS.items():
        full = dict(cfg, name=name, norm_eps=1e-5, dtype="float32",
                    vocab_pad_multiple=256, remat=True)
        (root / "configs" / f"{name}.json").write_text(json.dumps(full))
    for name, c in cells.items():
        (root / "workloads" / f"{name}.json").write_text(json.dumps(c))
    return root


def force_pallas(monkeypatch):
    """The codec's Pallas kernels (interpreted on the CPU), so that a sound
    run dispatches no reference kernel."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
