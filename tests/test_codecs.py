"""repro.codecs package: stage pipelines, NDSC bit-exactness with the
gradcomp path, the new ratq / sparsify_then_embed codecs, registry
diagnostics, and the fed.registry / benchmarks.roofline deprecation shims."""
import importlib
import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs
from repro.codecs import stages
from repro.dist import gradcomp as G

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _heavy(key, shape):
    return jax.random.normal(key, shape) ** 3


def _bitwise_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(a.view(np.uint8), b.view(np.uint8)))


# ---------------------------------------------------------------------------
# NDSC through repro.codecs is BIT-EXACT with the direct gradcomp path
# ---------------------------------------------------------------------------
def _assert_ndsc_bitexact(bits, keep, dithered, n=256, chunk=32,
                          round_idx=3):
    key = jax.random.key(11)
    tree = {"w": _heavy(jax.random.fold_in(key, 0), (n,)),
            "b": _heavy(jax.random.fold_in(key, 1), (5, 9))}
    leaves, _ = jax.tree.flatten(tree)
    drop = keep < 1.0
    cfg = G.GradCompConfig(bits=bits, chunk=chunk, keep_fraction=keep,
                           exact_keep=drop, dithered=dithered,
                           error_feedback=True, seed=0)
    pipeline = stages.Pipeline(
        transform=stages.Transform("hadamard", seed=0),
        sparsify=(stages.Sparsify("chunk_drop", fraction=keep)
                  if drop else stages.Sparsify()),
        quantize=stages.Quantize("dithered" if dithered else "uniform",
                                 bits=bits),
        chunk=chunk)
    codec = pipeline.tree_codec("under-test")
    meta = codec.meta(tree)
    ekey = jax.random.fold_in(key, 7)

    wire = codec.encode(ekey, tree, round_idx)
    plist = meta.treedef.flatten_up_to(wire)
    direct = [G.encode_leaf(x, i, cfg, round_idx,
                            key=jax.random.fold_in(ekey, i))
              for i, x in enumerate(leaves)]
    for p, d in zip(plist, direct):
        assert set(p) == set(d)
        for field in p:
            assert _bitwise_equal(p[field], d[field]), field

    dec = jax.tree.leaves(codec.decode(wire, meta))
    for i, (d, (size, shape, dtype)) in enumerate(zip(direct, meta.infos)):
        assert _bitwise_equal(dec[i],
                              G.decode_leaf(d, i, size, shape, dtype, cfg))

    wire_ef, resid = codec.encode_ef(ekey, tree, meta, round_idx)
    for i, (x, p, r, info) in enumerate(zip(
            leaves, meta.treedef.flatten_up_to(wire_ef),
            jax.tree.leaves(resid), meta.infos)):
        dp, dr = G.encode_leaf_ef(x, i, cfg, round_idx,
                                  key=jax.random.fold_in(ekey, i),
                                  residual_dtype=info[2])
        for field in p:
            assert _bitwise_equal(p[field], dp[field]), f"EF {field}"
        assert _bitwise_equal(r, dr)

    assert abs(codec.wire_bytes(wire, meta)
               - sum(G.wire_bytes_payload(d, cfg) for d in direct)) < 1e-9
    assert abs(codec.wire_bits(tree)
               - G.wire_bytes_tree(leaves, cfg)["payload_bytes"] * 8.0) < 1e-6


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
@pytest.mark.parametrize("keep", [0.25, 1.0])
@pytest.mark.parametrize("dithered", [False, True])
def test_ndsc_pipeline_bitexact_with_gradcomp(bits, keep, dithered):
    _assert_ndsc_bitexact(bits, keep, dithered)


@pytest.mark.parametrize("bits,keep", [(1, 1.0), (4, 0.25), (8, 1.0)])
def test_ndsc_pipeline_bitexact_forced_pallas(monkeypatch, bits, keep):
    """Same contract with the (interpret-mode) Pallas kernels forced: the
    dispatch layer may never change a wire payload. Reduced grid — the
    interpreter is slow; CI sweeps the full grid via codec_frontier under
    REPRO_FORCE_PALLAS=1."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    _assert_ndsc_bitexact(bits, keep, dithered=False, n=128, chunk=32)


def test_make_ndsc_matches_explicit_pipeline():
    tree = {"w": _heavy(jax.random.key(0), (200,))}
    made = codecs.make("ndsc", budget=4.0, chunk=32)
    cfg = codecs.gradcomp_config_for_budget(4.0, 32)
    assert made.rate == cfg.effective_bits
    key = jax.random.key(5)
    wire = made.encode(key, tree, 0)
    direct = G.encode_leaf(tree["w"], 0, cfg, 0,
                           key=jax.random.fold_in(key, 0))
    for field in wire["w"]:
        assert _bitwise_equal(wire["w"][field], direct[field])


# ---------------------------------------------------------------------------
# ratq: roundtrip quality, audit == ledger, static shapes across rounds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("budget", [0.5, 1.0, 4.0])
def test_ratq_roundtrip_and_ledger(budget):
    n = 256
    tree = {"y": _heavy(jax.random.key(3), (n,))}
    codec = codecs.make("ratq", budget=budget, chunk=32)
    meta = codec.meta(tree)
    wire = codec.encode(jax.random.key(4), tree, 0)
    assert ("mask" in wire["y"]) == (budget < 1.0)
    out = codec.decode(wire, meta)["y"]
    assert out.shape == (n,) and out.dtype == jnp.float32
    # below 1 bit/dim the keep-mask is drawn from the encode key, so one
    # draw's error is a sample of the codec's error: bound its RMS over
    # eight draws. (Since jax_threefry_partitionable became the default,
    # the same keys give other bits: this input and its masks changed, and
    # a single draw sits anywhere in 0.81–1.08.)
    sq_errs = []
    for k in range(4, 12):
        dec = codec.decode(codec.encode(jax.random.key(k), tree, 0),
                           meta)["y"]
        sq_errs.append(float(jnp.sum(jnp.square(dec - tree["y"]))
                             / jnp.sum(jnp.square(tree["y"]))))
    err = float(np.sqrt(np.mean(sq_errs)))
    assert err < (1.05 if budget < 4 else 0.3)
    # fixed-length wire: realized ledger equals the analytic audit exactly
    assert abs(codec.wire_bytes(wire, meta)
               - codec.wire_bits(tree) / 8.0) < 1e-6
    # the rung index is the cheap side channel: ⌈log2 16⌉ = 4 bits/chunk
    # beats ndsc's 32-bit f32 scale at every budget
    ndsc = codecs.make("ndsc", budget=budget, chunk=32)
    assert codec.wire_bits(tree) < ndsc.wire_bits(tree)


def test_ratq_no_recompile_across_rounds():
    n = 256
    y = _heavy(jax.random.key(6), (n,))
    for budget in (0.5, 2.0):
        codec = codecs.make("ratq", budget=budget, chunk=32)
        meta = codec.meta({"y": y})
        fn = jax.jit(lambda k, t, r: codec.decode(codec.encode(k, t, r),
                                                  meta))
        for r in range(4):
            jax.block_until_ready(
                fn(jax.random.fold_in(jax.random.key(0), r), {"y": y},
                   jnp.uint32(r)))
        assert fn._cache_size() == 1, \
            f"ratq(R={budget}) recompiled across rounds"


def test_ratq_ladder_scales_cover_dynamic_range():
    """Chunks with very different norms land on different rungs, and every
    chunk's chosen scale bounds its own ℓ∞ norm (no clipping)."""
    n, chunk = 128, 32
    y = jnp.concatenate([100.0 * _heavy(jax.random.key(1), (chunk,)),
                         _heavy(jax.random.key(2), (n - chunk,)) * 0.01])
    codec = codecs.make("ratq", budget=4.0, chunk=chunk, ladder=16)
    wire = codec.encode(jax.random.key(0), {"y": y}, 0)
    ridx = np.asarray(wire["y"]["ridx"]).reshape(-1)
    assert ridx.max() > ridx.min()           # the ladder is actually used
    leaf = codec.meta({"y": y}).extra[0]
    scales = np.asarray(leaf._scales(wire["y"]["ridx"], wire["y"]["gain"]))
    import repro.kernels.ops as kernel_ops
    rot = np.asarray(kernel_ops.rotate(
        G._to_chunks(y, chunk), G._frame_signs(0, leaf.cfg).astype(
            jnp.float32)))
    assert (np.abs(rot).max(axis=-1, keepdims=True)
            <= scales + 1e-6).all()


# ---------------------------------------------------------------------------
# sparsify_then_embed: selection, reconstruction support, audit == ledger
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["topk", "randk"])
def test_sparsify_then_embed_roundtrip(mode):
    n, k = 300, 60
    y = _heavy(jax.random.key(8), (n,))
    codec = codecs.make("sparsify_then_embed", budget=1.0, mode=mode,
                        bits=8, chunk=32, k_fraction=k / n)
    meta = codec.meta({"y": y})
    wire = codec.encode(jax.random.key(9), {"y": y}, 0)
    idx = np.asarray(wire["y"]["indices"])
    assert idx.shape == (k,) and (np.diff(idx) > 0).all()
    if mode == "topk":
        expect = np.sort(np.argsort(-np.abs(np.asarray(y)))[:k])
        np.testing.assert_array_equal(idx, expect)
    out = np.asarray(codec.decode(wire, meta)["y"])
    # reconstruction lives exactly on the selected support
    assert (out[np.setdiff1d(np.arange(n), idx)] == 0.0).all()
    kept = np.asarray(y)[idx]
    err = np.linalg.norm(out[idx] - kept) / np.linalg.norm(kept)
    assert err < 0.05                        # 8-bit embedded quantization
    assert abs(codec.wire_bytes(wire, meta)
               - codec.wire_bits({"y": y}) / 8.0) < 1e-9


def test_sparsify_then_embed_audit_charges_indices():
    """The audit is C·(chunk·bits + 32) + log2 C(n,k) — the identical
    index-cost convention as the plain topk/randk baselines."""
    import math
    n, k, bits, chunk = 512, 64, 4, 32
    codec = codecs.make("sparsify_then_embed", budget=1.0, bits=bits,
                        chunk=chunk, k_fraction=k / n)
    tmpl = {"y": jax.ShapeDtypeStruct((n,), jnp.float32)}
    c = -(-k // chunk)
    expect = c * (chunk * bits + 32) + math.log2(math.comb(n, k))
    assert abs(codec.wire_bits(tmpl) - expect) < 1e-9


def test_randk_selection_is_key_deterministic():
    n = 200
    y = _heavy(jax.random.key(1), (n,))
    codec = codecs.make("sparsify_then_embed", budget=1.0, mode="randk",
                        bits=4, chunk=32, k_fraction=0.2)
    w1 = codec.encode(jax.random.key(2), {"y": y}, 0)
    w2 = codec.encode(jax.random.key(2), {"y": y}, 0)
    w3 = codec.encode(jax.random.key(3), {"y": y}, 0)
    np.testing.assert_array_equal(np.asarray(w1["y"]["indices"]),
                                  np.asarray(w2["y"]["indices"]))
    assert not np.array_equal(np.asarray(w1["y"]["indices"]),
                              np.asarray(w3["y"]["indices"]))


# ---------------------------------------------------------------------------
# stage validation + registry diagnostics
# ---------------------------------------------------------------------------
def test_stage_validation_errors():
    with pytest.raises(ValueError, match="transform"):
        stages.Transform("fourier")
    with pytest.raises(ValueError, match="sparsify"):
        stages.Sparsify("bottomk")
    with pytest.raises(ValueError, match="fraction"):
        stages.Sparsify("chunk_drop", fraction=0.0)
    with pytest.raises(ValueError, match="bits"):
        stages.Quantize(bits=3)
    with pytest.raises(ValueError, match="ladder"):
        stages.Quantize("ratq", ladder=1)
    with pytest.raises(ValueError, match="pack"):
        stages.Pack("zip")
    # unsupported stage combination: ratq after topk selection
    with pytest.raises(ValueError, match="topk/randk"):
        stages.Pipeline(sparsify=stages.Sparsify("topk", fraction=0.1),
                        quantize=stages.Quantize("ratq")).leaf()
    with pytest.raises(ValueError, match="hadamard"):
        stages.Pipeline(transform=stages.Transform("identity")).leaf()


def test_equal_pipelines_share_a_leaf_codec():
    a = stages.Pipeline(quantize=stages.Quantize(bits=4), chunk=64)
    b = stages.Pipeline(quantize=stages.Quantize(bits=4), chunk=64)
    assert a == b and hash(a) == hash(b)
    assert a.leaf() is b.leaf()              # lru-cached dispatch


def test_registry_unknown_name_suggests_nearest():
    with pytest.raises(ValueError) as e:
        codecs.make("ndcs", budget=1.0)
    msg = str(e.value)
    assert "unknown codec 'ndcs'" in msg
    assert "did you mean 'ndsc'?" in msg
    assert "available:" in msg
    with pytest.raises(ValueError, match="available:"):
        codecs.make("no_such_codec_at_all")


def test_registry_lists_new_codecs():
    names = codecs.available()
    assert "ratq" in names and "sparsify_then_embed" in names
    assert "ndsc" in names and "identity" in names


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------
def test_fed_registry_shim_import_is_warning_free():
    """`import repro.fed.registry` must NOT warn (CI imports it with
    -W error::DeprecationWarning); only calling make() through it warns."""
    sys.modules.pop("repro.fed.registry", None)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("error", DeprecationWarning)
        shim = importlib.import_module("repro.fed.registry")
    assert not caught
    for name in ("TreeCodec", "available", "codec_spec",
                 "gradcomp_config_for_budget", "register"):
        assert getattr(shim, name) is getattr(codecs, name)


def test_fed_registry_shim_make_warns_and_forwards():
    from repro.fed import registry as shim
    with pytest.warns(DeprecationWarning, match="repro.codecs"):
        codec = shim.make("identity")
    assert codec.name == codecs.make("identity").name


def test_roofline_shim_warns_and_forwards():
    sys.modules.pop("benchmarks.roofline", None)
    with pytest.warns(DeprecationWarning, match="hlo_report"):
        roofline = importlib.import_module("benchmarks.roofline")
    hlo_report = importlib.import_module("benchmarks.hlo_report")
    assert roofline.main is hlo_report.main
    assert roofline.table_rows is hlo_report.table_rows
    assert roofline.markdown is hlo_report.markdown
