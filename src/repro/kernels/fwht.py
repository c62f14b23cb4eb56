"""Pallas TPU kernel: fast Walsh–Hadamard transform (the NDSC hot spot).

The Hadamard transform is the compute core of near-democratic source coding
(x_nd = Sᵀy = H D Pᵀ y). On TPU we tile the batch of gradient chunks into
VMEM-resident (block_rows, N) tiles and run the radix-2 butterfly in-register:
log₂N add/sub sweeps — the paper's "O(n log n) additions, no multiplies",
mapped onto the VPU.

The tile rule (`block_rows_for`, shared by every codec kernel) sets the
rows of a grid step from the row length N: about TILE_BYTES of VMEM per
block, counting each row's f32 values, packed words and scale at their
128-lane padded widths, so 512 rows at N = 256, 680 at N <= 128 and 24
at N = 8192. The body computes its whole tile at once. The height of
that arithmetic is what set the pace on a v5e: an 8-row tile of N = 256
is two vregs per value, too short a chain of rolls and selects to fill
the pipeline. At Yi-6B's 176128 x 256 MLP leaf the fused encode with EF
took 28.2 ms with 8-row tiles and 2.5 ms with 512-row ones, while
512-row grid steps that looped over 8-row slabs (not kept) took 28.1 ms
(`benchmarks/codec_tiles.py`). Rows are independent: a leaf whose rows
are not a multiple of the block runs a partial last block (its
out-of-range rows are computed and dropped), and a leaf smaller than one
block is one block of all its rows, so nothing is padded.

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


MAX_VMEM_N = 8192
TILE_BYTES = 1024 * 1024


def _lanes(width: int) -> int:
    """VMEM words a row of `width` takes: rounded up to 128 lanes."""
    return -(-width // 128) * 128


def block_rows_for(n: int, rows: int) -> int:
    """Rows per grid step for `rows` chunk rows of length n.

    A row's VMEM footprint is its f32 row, its packed words (at most n/4)
    and its scale, each padded to 128 lanes: 2 KiB at n = 256, 1.5 KiB at
    any n <= 128. The block holds about TILE_BYTES of it, a multiple of 8
    rows (512 at n = 256, 680 at n <= 128, 24 at n = 8192); a leaf of fewer
    rows is one block of all of them."""
    row_bytes = 4 * (_lanes(n) + _lanes(n // 4) + _lanes(1))
    return min(max(8, TILE_BYTES // row_bytes // 8 * 8), rows)


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
def fwht_pallas(x: jax.Array, block_rows: int | None = None,
                interpret: bool | None = None) -> jax.Array:
    """Normalized FWHT along the last axis via pl.pallas_call.

    x: (..., N) with N a power of 2, N ≤ MAX_VMEM_N. block_rows=None takes
    `block_rows_for`. interpret=None infers from the backend: compiled on
    TPU, interpreter elsewhere.
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
    block_rows = min(block_rows or block_rows_for(n, rows), rows)
    out = pl.pallas_call(
        functools.partial(_fwht_kernel, n=n),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), flat.dtype),
        interpret=interpret,
    )(flat)
    return out.reshape(orig_shape)
