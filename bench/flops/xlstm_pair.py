"""Model FLOPs per trained token of an xLSTM stack of (mLSTM, sLSTM) pairs.

2 FLOPs per multiply-add; training is 3x the forward (forward, and the
backward's two products per forward product); recomputation not counted.
Per pair, forward, with d the width, H heads of dh = d/H:
  mLSTM   q, k, v, output-gate and out projections  2 * 5 d^2
          input and forget gate projections         2 * 2 d H
          matrix memory: C += i v k^T and C q       H * 4 dh^2
  sLSTM   input (d x 4d) and out (d x d)            2 * 5 d^2
          per-head recurrence (dh x 4dh)            H * 8 dh^2
Head: 2 d V over the padded vocabulary V; the embedding lookup is free.
"""


def padded_vocab(cfg: dict) -> int:
    m = cfg.get("vocab_pad_multiple", 256)
    return -(-cfg["vocab_size"] // m) * m


def flops_per_token(cfg: dict, seq_len: int) -> float:
    d, heads = cfg["d_model"], cfg["num_heads"]
    dh = d // heads
    mlstm = 10 * d * d + 4 * d * heads + 4 * heads * dh * dh
    slstm = 10 * d * d + 8 * heads * dh * dh
    forward = (cfg["num_layers"] // 2) * (mlstm + slstm) + 2 * d * padded_vocab(cfg)
    return 3.0 * forward
