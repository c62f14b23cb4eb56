"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import fwht as fwht_kernel
from repro.kernels import ops as kernel_ops
from repro.kernels import quantencode as qe_kernel
from repro.kernels import quantpack as qp_kernel
from repro.kernels import ref


# ---------------------------------------------------------------------------
# FWHT
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [2, 8, 64, 128, 1024])
@pytest.mark.parametrize("lead,block_rows", [
    pytest.param((), None, id="lead0"), pytest.param((1,), None, id="lead1"),
    pytest.param((5,), None, id="lead2"),
    pytest.param((3, 4), None, id="lead3"),
    # an explicit 16-row block over 13 rows: one block of all of them
    pytest.param((13,), 16, id="lead13-block16")])
def test_fwht_pallas_matches_ref(n, lead, block_rows):
    x = jax.random.normal(jax.random.key(0), lead + (n,))
    got = fwht_kernel.fwht_pallas(x, block_rows=block_rows, interpret=True)
    np.testing.assert_allclose(got, ref.fwht(x), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwht_dtypes(dtype):
    x = jax.random.normal(jax.random.key(1), (4, 256)).astype(dtype)
    got = fwht_kernel.fwht_pallas(x, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref.fwht(x), np.float32),
                               rtol=tol, atol=tol)


def test_fwht_orthonormal_involution():
    """H·H = I (normalized Hadamard is its own inverse)."""
    x = jax.random.normal(jax.random.key(2), (3, 512))
    np.testing.assert_allclose(ref.fwht(ref.fwht(x)), x, atol=1e-4)
    np.testing.assert_allclose(
        fwht_kernel.fwht_pallas(fwht_kernel.fwht_pallas(x, interpret=True),
                                interpret=True), x, atol=1e-4)


def test_fwht_matches_hadamard_matrix():
    n = 16
    h = np.array([[1.0]])
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    h /= np.sqrt(n)
    x = np.random.RandomState(0).randn(4, n).astype(np.float32)
    np.testing.assert_allclose(ref.fwht(jnp.asarray(x)), x @ h, atol=1e-5)


@pytest.mark.parametrize("n,rows,want", [
    (256, 176128, 512),     # Yi-6B's MLP leaf: 2 KiB of VMEM a row
    (256, 201728, 512),     # the xlstm-350m embedding leaf
    (8192, 64, 24),         # the single-tile limit N
    (128, 100003, 680),     # n <= 128: each row padded to 128 lanes
    (64, 100003, 680),
    (32, 100003, 680),      # the fed quickstart's chunk
    (256, 13, 13),          # a leaf under one block: one block of it all
    (256, 1, 1),
    (8192, 3, 3),
])
def test_block_rows_for(n, rows, want):
    """About TILE_BYTES of VMEM per block, each row's f32 values, words
    and scale counted at their 128-lane padded widths: a multiple of 8,
    at least 8, or the leaf's rows when it has fewer than one block."""
    got = fwht_kernel.block_rows_for(n, rows)
    assert got == want
    if got < rows:
        assert got % 8 == 0 and got >= 8
    lanes = lambda w: -(-w // 128) * 128
    row_bytes = 4 * (lanes(n) + lanes(n // 4) + 128)
    assert got * row_bytes <= max(fwht_kernel.TILE_BYTES, 8 * row_bytes)


def test_fwht_rejects_non_pow2():
    with pytest.raises(ValueError):
        fwht_kernel.fwht_pallas(jnp.zeros((2, 48)), interpret=True)


# ---------------------------------------------------------------------------
# quantize-pack / unpack-dequant
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bits", [1, 2, 4, 8])
@pytest.mark.parametrize("rows,n,block_rows", [
    pytest.param(1, 32, None, id="1-32"), pytest.param(7, 128, None, id="7-128"),
    pytest.param(16, 1024, None, id="16-1024"),
    # an explicit 16-row block over a single row: one 1-row block
    pytest.param(1, 128, 16, id="1-128-block16")])
def test_quantpack_pallas_matches_ref(bits, rows, n, block_rows):
    x = jax.random.normal(jax.random.key(3), (rows, n))
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    got = qp_kernel.quantize_pack_pallas(x, scale, bits,
                                         block_rows=block_rows,
                                         interpret=True)
    want = ref.quantize_pack(x, scale, bits)
    np.testing.assert_array_equal(got, want)
    back = qp_kernel.unpack_dequant_pallas(got, scale, bits, n,
                                           block_rows=block_rows,
                                           interpret=True)
    np.testing.assert_allclose(back, ref.unpack_dequant(want, scale, bits, n),
                               atol=1e-6)


@given(bits=st.sampled_from([1, 2, 4, 8]), seed=st.integers(0, 500))
@settings(max_examples=20, deadline=None)
def test_quantpack_roundtrip_error_property(bits, seed):
    """|x − unpack(pack(x))| ≤ scale/2^bits per coordinate."""
    n = 128
    x = jax.random.normal(jax.random.key(seed), (4, n))
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    words = ref.quantize_pack(x, scale, bits)
    back = ref.unpack_dequant(words, scale, bits, n)
    max_err = float(jnp.max(jnp.abs(back - x) / scale))
    assert max_err <= 1.0 / (2 ** bits) + 1e-6


def test_quantpack_rejects_bad_bits():
    x = jnp.zeros((2, 32))
    s = jnp.ones((2, 1))
    with pytest.raises(ValueError):
        ref.quantize_pack(x, s, 3)
    with pytest.raises(ValueError):
        qp_kernel.quantize_pack_pallas(x, s, 5, interpret=True)


@given(bits=st.sampled_from([2, 4, 8]), rows=st.integers(1, 21),
       seed=st.integers(0, 500))
@settings(max_examples=15, deadline=None)
def test_quantpack_pallas_odd_shapes_match_ref(bits, rows, seed):
    """Row counts off the 8-row tile grid and odd (non-power-of-two) lengths:
    the Pallas encode→decode roundtrip must match the jnp reference exactly
    (these are the ragged tail shapes the gradient codec produces)."""
    n = (32 // bits) * 13                   # divisible by the packing factor
    x = jax.random.normal(jax.random.key(seed), (rows, n))
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) + 1e-6
    words = qp_kernel.quantize_pack_pallas(x, scale, bits, interpret=True)
    np.testing.assert_array_equal(words, ref.quantize_pack(x, scale, bits))
    back = qp_kernel.unpack_dequant_pallas(words, scale, bits, n,
                                           interpret=True)
    np.testing.assert_allclose(back, ref.unpack_dequant(words, scale, bits, n),
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(back - x) / scale)) <= 1.0 / 2 ** bits + 1e-6


def test_packed_size():
    """Wire-format audit: 4-bit pack is exactly 8 values per int32 word."""
    x = jnp.ones((2, 64))
    s = jnp.ones((2, 1))
    assert ref.quantize_pack(x, s, 4).shape == (2, 8)
    assert ref.quantize_pack(x, s, 1).shape == (2, 2)
    assert ref.quantize_pack(x, s, 8).shape == (2, 16)


# ---------------------------------------------------------------------------
# fused encode (sign-flip → FWHT → scale → quantize → pack) vs composed ref
# ---------------------------------------------------------------------------
def _signs(n, seed=7):
    b = jax.random.bernoulli(jax.random.key(seed), 0.5, (n,))
    return jnp.where(b, 1.0, -1.0).astype(jnp.float32)


def _draws(rows, n, bits, seed=11):
    kd, km = jax.random.split(jax.random.key(seed))
    delta = 2.0 / (2 ** bits)
    dither = jax.random.uniform(kd, (rows, n), jnp.float32,
                                -delta / 2, delta / 2)
    mask = (jax.random.uniform(km, (rows, 1)) < 0.6).astype(jnp.float32)
    return dither, mask


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
@pytest.mark.parametrize("rows,n,block_rows", [
    pytest.param(1, 32, None, id="1-32"), pytest.param(8, 128, None, id="8-128"),
    pytest.param(13, 256, None, id="13-256"),
    # two 16-row blocks and a partial third of 5 rows
    pytest.param(37, 128, 16, id="37-128-block16")])
@pytest.mark.parametrize("mode", ["det", "dither", "mask", "dither_mask"])
def test_fused_encode_payload_bitexact(bits, rows, n, block_rows, mode):
    """The PAYLOAD contract: fused-kernel (words, scale) == composed ref,
    bit for bit — deterministically and with shared pre-drawn draws."""
    x = jax.random.normal(jax.random.key(bits * 100 + rows), (rows, n))
    signs = _signs(n)
    dither, mask = _draws(rows, n, bits)
    dth = dither if "dither" in mode else None
    msk = mask if "mask" in mode else None
    kw, ks = qe_kernel.encode_pallas(x, signs, bits, dither=dth, mask=msk,
                                     block_rows=block_rows, interpret=True)
    rw, rs = ref.encode(x, signs, bits, dither=dth, mask=msk)
    np.testing.assert_array_equal(kw, rw)
    np.testing.assert_array_equal(np.asarray(ks).view(np.int32),
                                  np.asarray(rs).view(np.int32))


@pytest.mark.parametrize("bits", [1, 4])
@pytest.mark.parametrize("rows,n,block_rows", [
    pytest.param(5, 128, None, id="5-128"), pytest.param(13, 64, None, id="13-64"),
    # two 16-row blocks and a partial third of 8 rows
    pytest.param(40, 64, 16, id="40-64-block16")])
@pytest.mark.parametrize("mode", ["det", "dither_mask", "rescale"])
def test_fused_encode_ef_residual(bits, rows, n, block_rows, mode):
    """The EF contract: payload stays bitwise; the in-tile residual matches
    the composed eager reference u − D(E(u)) to a few f32 ulp of the
    embedding scale (fma contraction in the in-tile decode is allowed)."""
    x = jax.random.normal(jax.random.key(bits * 10 + rows), (rows, n))
    signs = _signs(n)
    dither, mask = _draws(rows, n, bits)
    dth = dither if mode != "det" else None
    msk = mask if mode != "det" else None
    rescale = 0.6 if mode == "rescale" else None
    kw, ks, kr = qe_kernel.encode_ef_pallas(
        x, signs, bits, dither=dth, mask=msk, rescale=rescale,
        block_rows=block_rows, interpret=True)
    rw, rs, rr = ref.encode_ef(x, signs, bits, dither=dth, mask=msk,
                               rescale=rescale)
    np.testing.assert_array_equal(kw, rw)
    np.testing.assert_array_equal(np.asarray(ks).view(np.int32),
                                  np.asarray(rs).view(np.int32))
    np.testing.assert_allclose(kr, rr, atol=4e-6, rtol=0)
    # composed end-to-end: residual really is u − decode(encode(u))
    y_hat = ref.decode_embedded(rw, rs, signs, bits, n, mask=msk,
                                rescale=rescale)
    np.testing.assert_allclose(kr, x - y_hat, atol=4e-6, rtol=0)


def test_fused_encode_ef_residual_dtype_rounding():
    """residual_dtype=bf16 rounds ŷ where a bf16 tree decode would; the
    residual then matches the reference to bf16 resolution."""
    rows, n, bits = 6, 128, 4
    x = jax.random.normal(jax.random.key(3), (rows, n))
    signs = _signs(n)
    _, _, kr = qe_kernel.encode_ef_pallas(
        x, signs, bits, residual_dtype=jnp.bfloat16, interpret=True)
    _, _, rr = ref.encode_ef(x, signs, bits, residual_dtype=jnp.bfloat16)
    np.testing.assert_allclose(kr, rr, atol=4e-3, rtol=0)


def test_fused_encode_interpret_inferred_on_cpu():
    """interpret=None must infer interpreter mode off-TPU (satellite #2):
    the call below would crash trying to compile a TPU kernel otherwise."""
    x = jax.random.normal(jax.random.key(4), (4, 64))
    kw, ks = qe_kernel.encode_pallas(x, _signs(64), 2)
    rw, rs = ref.encode(x, _signs(64), 2)
    np.testing.assert_array_equal(kw, rw)
    got = fwht_kernel.fwht_pallas(x)
    np.testing.assert_allclose(got, ref.fwht(x), rtol=1e-5, atol=1e-5)


def test_forced_pallas_refuses_silent_fallback(monkeypatch):
    """REPRO_FORCE_PALLAS=1 + N over the VMEM budget must raise, not
    silently hand back the jnp reference (satellite #1)."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    big = fwht_kernel.MAX_VMEM_N * 2
    x = jnp.zeros((2, big))
    with pytest.raises(ValueError, match="VMEM"):
        kernel_ops.fwht(x)
    with pytest.raises(ValueError, match="VMEM"):
        kernel_ops.encode(x, jnp.ones((big,)), 2)
    with pytest.raises(ValueError, match="VMEM"):
        kernel_ops.encode_ef(x, jnp.ones((big,)), 2)
    # under the budget the forced path still dispatches to the kernel
    small = jax.random.normal(jax.random.key(5), (3, 64))
    kw, _ = kernel_ops.encode(small, _signs(64), 4)
    rw, _ = ref.encode(small, _signs(64), 4)
    np.testing.assert_array_equal(kw, rw)


def test_unforced_large_n_falls_back_to_ref(monkeypatch):
    """Without the force flag, over-budget N quietly uses the reference."""
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    x = jax.random.normal(jax.random.key(6), (1, fwht_kernel.MAX_VMEM_N * 2))
    np.testing.assert_allclose(kernel_ops.fwht(x), ref.fwht(x),
                               rtol=1e-5, atol=1e-5)
