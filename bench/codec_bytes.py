"""The codec's minimum device traffic and work per step, per worker.

Counted from the leaf sizes and the bit budget, not from what a kernel
does, so the count holds whatever implements the codec. Per worker and
step, for n gradient entries in leaves of C_i chunks of `chunk` entries:
  payload P = sum_i C_i * (chunk * bits / 8 + 4)     (codes + f32 scale)
  bytes     = 4n  read the gradient
            + 4n  read the error feedback,  4n  write it   (with EF)
            + P   write the own payload
            + m P read the m gathered payloads
            + 4n  write the decoded mean
  ops       = (1 + e + m) * C * chunk * log2(chunk)   FWHT additions:
              the encode, the error feedback's own decode (e = 1 with
              EF), the m decodes
"""
from __future__ import annotations

import math


def payload_bytes(sizes, bits: int, chunk: int) -> int:
    return sum(-(-n // chunk) * (chunk * bits // 8 + 4) for n in sizes)


def minimum(sizes, bits: int, chunk: int, workers: int, ef: bool) -> dict:
    n = sum(sizes)
    chunks = sum(-(-s // chunk) for s in sizes)
    p = payload_bytes(sizes, bits, chunk)
    traffic = 4 * n * (2 + (2 if ef else 0)) + p * (1 + workers)
    ops = (1 + int(ef) + workers) * chunks * chunk * math.log2(chunk)
    return {"bytes": traffic, "ops": ops, "payload_bytes": p}
