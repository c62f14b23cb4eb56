"""Pallas TPU kernel: fast Walsh–Hadamard transform (the NDSC hot spot).

The Hadamard transform is the compute core of near-democratic source coding
(x_nd = Sᵀy = H D Pᵀ y). On TPU we tile the batch of gradient chunks into
VMEM-resident (block_rows, N) tiles and run the radix-2 butterfly in-register:
log₂N add/sub sweeps — the paper's "O(n log n) additions, no multiplies",
mapped onto the VPU. N ≤ 8192 keeps a (8, 8192) f32 tile at 256 KiB << VMEM.

Every value keeps the full (rows, N) shape: butterfly h pairs lane j with
lane j ^ h through two lane rolls and a select on (j & h) == 0 —
`x + x[j+h]` on the low half of each 2h block, `x[j-h] − x` on the high
half. A reshape to (…, 2, h) would split the 128-lane dimension for
h < 128, which the TPU compiler refuses; a roll by a multiple of 128 is a
plain vreg move. `fwht_tile` is shared with the fused encoder
(`quantencode.py`), and `ref.fwht` runs the same adds and subtracts in the
same order, so all three agree bitwise.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_ROWS = 8
MAX_VMEM_N = 8192


def fwht_tile(x: jax.Array, n: int) -> jax.Array:
    """Normalized FWHT of a resident (rows, n) tile along its lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    h = 1
    while h < n:
        up = pltpu.roll(x, n - h, 1)        # up[j] = x[j + h]
        down = pltpu.roll(x, h, 1)          # down[j] = x[j − h]
        x = jnp.where((lane & h) == 0, x + up, down - x)
        h *= 2
    return x * (1.0 / math.sqrt(n))


def _fwht_kernel(x_ref, o_ref, *, n: int):
    o_ref[...] = fwht_tile(x_ref[...], n)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fwht_pallas(x: jax.Array, block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool | None = None) -> jax.Array:
    """Normalized FWHT along the last axis via pl.pallas_call.

    x: (..., N) with N a power of 2, N ≤ MAX_VMEM_N. interpret=None infers
    from the backend: compiled on TPU, interpreter elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = x.shape[-1]
    if n & (n - 1):
        raise ValueError(f"FWHT length {n} is not a power of 2")
    if n > MAX_VMEM_N:
        raise ValueError(f"N={n} exceeds single-tile VMEM budget {MAX_VMEM_N}")
    orig_shape = x.shape
    flat = x.reshape((-1, n))
    rows = flat.shape[0]
    padded_rows = -(-rows // block_rows) * block_rows
    if padded_rows != rows:
        flat = jnp.pad(flat, ((0, padded_rows - rows), (0, 0)))
    grid = (padded_rows // block_rows,)
    out = pl.pallas_call(
        functools.partial(_fwht_kernel, n=n),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_rows, n), flat.dtype),
        interpret=interpret,
    )(flat)
    return out[:rows].reshape(orig_shape)
