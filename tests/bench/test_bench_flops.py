"""The benchmark's FLOP and byte counts against hand counts at small shapes.

The hand counts walk the program's own parameter tree: every matrix a
token passes through costs 2 FLOPs per entry forward, to which each block
family adds the work that holds no weight (the mLSTM's matrix memory,
causal attention); training is three forwards.
"""
import math

import jax

import bench_tiny  # noqa: F401  (puts the repo on sys.path)

from bench import cells, codec_bytes
from repro.dist.gradcomp import GradCompConfig, wire_bytes_tree
from repro.models import model as model_lib


def _matrix_params(cfg: dict) -> int:
    shapes = jax.eval_shape(lambda: model_lib.init_params(
        jax.random.key(0), cells.model_config(cfg)))
    flat = jax.tree_util.tree_leaves_with_path(shapes)
    return sum(math.prod(s.shape) for p, s in flat
               if not jax.tree_util.keystr(p).endswith("norm']")
               and "embed" not in jax.tree_util.keystr(p))


def _cfg(**kw):
    base = {"name": "t", "norm_eps": 1e-5, "dtype": "float32",
            "vocab_pad_multiple": 256, "remat": False}
    return dict(base, **kw)


def test_xlstm_pair_flops_per_token():
    cfg = _cfg(num_layers=4, d_model=64, num_heads=2, num_kv_heads=2,
               d_ff=0, vocab_size=300, block="xlstm_pair")
    heads, dh, pairs = 2, 32, 2
    forward = 2 * _matrix_params(cfg) + pairs * heads * 4 * dh * dh
    got = cells.flops("xlstm_pair").flops_per_token(cfg, 128)
    assert got == 3 * forward


def test_attn_mlp_flops_per_token():
    cfg = _cfg(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
               head_dim=16, d_ff=96, vocab_size=300, block="attn_mlp",
               rope_theta=1e4)
    seq = 128
    q = 4 * 16
    attention = 2 * q * (seq + 1)       # QK^T and PV over (S+1)/2 keys
    forward = 2 * _matrix_params(cfg) + 2 * attention
    got = cells.flops("attn_mlp").flops_per_token(cfg, seq)
    assert got == 3 * forward


def test_codec_minimum_by_hand():
    sizes = [300, 256]                  # 2 + 1 chunks of 256
    m = codec_bytes.minimum(sizes, bits=4, chunk=256, workers=4, ef=True)
    payload = 3 * (256 * 4 // 8 + 4)
    n = 556
    assert m["payload_bytes"] == payload
    assert m["bytes"] == 4 * n * 4 + payload * 5
    assert m["ops"] == (1 + 1 + 4) * 3 * 256 * 8
    plain = codec_bytes.minimum(sizes, bits=4, chunk=256, workers=1, ef=False)
    assert plain["bytes"] == 4 * n * 2 + payload * 2


def test_payload_budget_matches_the_program_audit():
    shapes = [jax.ShapeDtypeStruct(s, "float32")
              for s in [(1000, 7), (256,), (3, 5, 64)]]
    sizes = [math.prod(s.shape) for s in shapes]
    for bits in (1, 4, 8):
        audit = wire_bytes_tree(shapes, GradCompConfig(bits=bits))
        assert codec_bytes.payload_bytes(sizes, bits, 256) == \
            audit["payload_bytes"]
