"""Model FLOPs per trained token of a dense decoder (GQA attention + SwiGLU).

2 FLOPs per multiply-add; training is 3x the forward; recomputation not
counted. Per layer, forward, with d the width, q = H*dh, kv = K*dh, f the
SwiGLU width and S the sequence length:
  projections      2 (d q + 2 d kv + q d)
  SwiGLU           2 * 3 d f
  causal attention 2 * 2 q (S + 1) / 2   (scores and values over the
                                          (S+1)/2 positions a token sees
                                          on average)
Head: 2 d V over the padded vocabulary V; the embedding lookup is free.
"""


def padded_vocab(cfg: dict) -> int:
    m = cfg.get("vocab_pad_multiple", 256)
    return -(-cfg["vocab_size"] // m) * m


def flops_per_token(cfg: dict, seq_len: int) -> float:
    d = cfg["d_model"]
    dh = cfg.get("head_dim") or d // cfg["num_heads"]
    q, kv, f = cfg["num_heads"] * dh, cfg["num_kv_heads"] * dh, cfg["d_ff"]
    layer = 2 * (d * q + 2 * d * kv + q * d) + 6 * d * f + 2 * q * (seq_len + 1)
    forward = cfg["num_layers"] * layer + 2 * d * padded_vocab(cfg)
    return 3.0 * forward
