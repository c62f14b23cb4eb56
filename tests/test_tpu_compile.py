"""The main path's Pallas kernels compile for a TPU v5e.

Each test lowers one kernel with `interpret=False` for a described (not
attached) v5e chip and compiles it with the TPU compiler, which refuses
lane-splitting reshapes, unsupported casts and VMEM overruns that interpret
mode lets through. Shapes are the codec's real ones: chunk N=256 at the row
counts of the xlstm-350m embedding leaf and of Yi-6B's MLP leaf, the
single-tile limit N=8192, the narrow chunks of the federated codecs and
the KV-cache quantizer (N=32, 64, 128, whose rows VMEM pads to 128 lanes),
ragged row counts that end in a partial block, and a leaf smaller than one
block, each at the tile `fwht.block_rows_for` gives it.
The topology is described inside a fixture, never at import, so every
pytest worker collects the same tests.
"""
import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import fwht as fwht_kernel
from repro.kernels import quantencode
from repro.kernels import quantpack

EMBED_ROWS = 201728          # chunks of 256 in the xlstm-350m embedding
YI_MLP_ROWS = 176128         # chunks of 256 in a Yi-6B MLP leaf (4096 x 11008)
RAGGED_ROWS = 100003         # a multiple of no tile: a partial last block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


SHAPES = [pytest.param(256, EMBED_ROWS, id="256"),
          pytest.param(8192, 64, id="8192"),
          pytest.param(256, YI_MLP_ROWS, id="256-yi_mlp"),
          pytest.param(32, RAGGED_ROWS, id="32-ragged"),
          pytest.param(64, RAGGED_ROWS, id="64-ragged"),
          pytest.param(128, RAGGED_ROWS, id="128-ragged"),
          pytest.param(256, 1000, id="256-1000"),
          pytest.param(256, EMBED_ROWS + 5, id="256-embed_ragged"),
          pytest.param(256, 13, id="256-13")]


@pytest.mark.parametrize("n,rows", SHAPES)
@pytest.mark.parametrize("bits", [1, 4, 8])
@pytest.mark.parametrize("op", ["encode", "encode_ef"])
def test_encode_compiles(one_chip, op, bits, n, rows):
    f32 = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    x = f32((rows, n), jnp.float32)
    signs = f32((n,), jnp.float32)
    dither = f32((rows, n), jnp.float32)
    mask = f32((rows, 1), jnp.float32)
    kernel = (quantencode.encode_pallas if op == "encode"
              else quantencode.encode_ef_pallas)

    def plain(x, s):
        return kernel(x, s, bits, interpret=False)

    def dithered_masked(x, s, d, m):
        return kernel(x, s, bits, dither=d, mask=m, interpret=False)

    assert "tpu_custom_call" in _compile_text(plain, x, signs)
    assert "tpu_custom_call" in _compile_text(dithered_masked, x, signs,
                                              dither, mask)


@pytest.mark.parametrize("bits,n,rows", [
    pytest.param(1, 256, EMBED_ROWS, id="1"),
    pytest.param(4, 256, EMBED_ROWS, id="4"),
    pytest.param(8, 256, EMBED_ROWS, id="8"),
    pytest.param(4, 256, YI_MLP_ROWS, id="4-yi_mlp"),
    pytest.param(4, 8192, 64, id="4-8192"),
    pytest.param(1, 32, RAGGED_ROWS, id="1-32-ragged"),
    pytest.param(8, 32, RAGGED_ROWS, id="8-32-ragged"),
    pytest.param(4, 64, RAGGED_ROWS, id="4-64-ragged"),
    pytest.param(4, 128, RAGGED_ROWS, id="4-128-ragged"),
    pytest.param(4, 256, 1000, id="4-1000"),
    pytest.param(4, 256, EMBED_ROWS + 5, id="4-embed_ragged"),
    pytest.param(4, 256, 13, id="4-13")])
def test_quantpack_compiles(one_chip, bits, n, rows):
    x = jax.ShapeDtypeStruct((rows, n), jnp.float32, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((rows, 1), jnp.float32, sharding=one_chip)
    words = jax.ShapeDtypeStruct((rows, n * bits // 32), jnp.int32,
                                 sharding=one_chip)
    pack = functools.partial(quantpack.quantize_pack_pallas, bits=bits,
                             interpret=False)
    unpack = functools.partial(quantpack.unpack_dequant_pallas, bits=bits,
                               n=n, interpret=False)
    assert "tpu_custom_call" in _compile_text(pack, x, scale)
    assert "tpu_custom_call" in _compile_text(unpack, words, scale)


@pytest.mark.parametrize("n,rows", SHAPES)
def test_fwht_compiles(one_chip, n, rows):
    x = jax.ShapeDtypeStruct((rows, n), jnp.float32, sharding=one_chip)
    fn = functools.partial(fwht_kernel.fwht_pallas, interpret=False)
    assert "tpu_custom_call" in _compile_text(fn, x)
