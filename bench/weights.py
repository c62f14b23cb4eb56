"""A cell's initial weights, made on the device from the seed.

The benchmark makes the weights itself, so the program under test and the
plain reference start from the same numbers without the reference taking
anything the program made. Leaves follow the program's parameter tree (its
structure only): norm weights are ones, every matrix is N(0, 0.02²).

The mLSTM forget-gate projection `wf` is drawn like every other matrix.
The program's own init adds 3 to each of its entries, which makes the
gate's pre-activation 3·sum(x) (about ±96 at d 1024) rather than a bias of
3: gates shut or open by the sign of that sum, and a leaf's first
gradient norm then moves by up to 2% when the weights move by a relative
1e-6 (4 of the 12 pairs, 2048 tokens, float32 on a CPU), so two float32
implementations of the same step disagree by seed. Drawn without the
shift, the same change moves it by under 2e-5 on each of three seeds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def leaf_name(path) -> str:
    """'blocks/mlstm/wq' for a pytree key path."""
    parts = []
    for k in path:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
    return "/".join(parts)


def names(tree) -> list[str]:
    return [leaf_name(p) for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


def _leaf(key, name: str, shape, dtype):
    last = name.rsplit("/", 1)[-1]
    if last.endswith("norm"):
        return jnp.ones(shape, dtype)
    x = jax.random.normal(key, shape, jnp.float32) * 0.02
    return x.astype(dtype)


def seed_key(seed: int) -> jax.Array:
    """A key for any whole-number seed (64-bit seeds included)."""
    return jax.random.key(int(seed) % (1 << 63))


def make(shapes, key: jax.Array, out_shardings=None):
    """Weights shaped like `shapes` (a ShapeDtypeStruct tree), in one jitted
    call from `key`. Leaf i uses fold_in(key, i)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(k):
        leaves = [_leaf(jax.random.fold_in(k, i), leaf_name(p), s.shape,
                        s.dtype) for i, (p, s) in enumerate(flat)]
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=out_shardings)(key)
