"""Pieces of the plain reference against textbook forms."""
import jax
import jax.numpy as jnp
import numpy as np

import bench_tiny  # noqa: F401  (puts the repo on sys.path)

from bench import reference


def test_scan_in_blocks_is_the_plain_scan_with_its_gradient():
    xs = jax.random.normal(jax.random.key(0), (96, 3))

    def step(c, x):
        c = jnp.tanh(c * 0.9 + x)
        return c, c * 2.0

    def total(fn, xs):
        _, ys = fn(step, jnp.zeros(3), xs)
        return jnp.sum(ys ** 2)

    want = jax.value_and_grad(lambda a: total(jax.lax.scan, a))(xs)
    got = jax.value_and_grad(
        lambda a: total(lambda f, c, x: reference.scan_in_blocks(f, c, x, 32),
                        a))(xs)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)


def test_fwht_is_the_normalized_hadamard_matrix():
    n = 16
    h = np.array([[1.0]])
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    x = np.asarray(jax.random.normal(jax.random.key(1), (5, n)))
    np.testing.assert_allclose(reference.fwht(jnp.asarray(x)),
                               x @ h.T / np.sqrt(n), rtol=1e-5, atol=1e-6)


def test_roundtrip_error_is_within_half_a_level():
    signs = reference.frame_signs(3, 256)
    u = jax.random.normal(jax.random.key(2), (1024,))
    dec = reference.roundtrip(u, signs, 4, 256)
    # in the embedded domain each entry is off by at most half a level
    emb = lambda v: reference.fwht(reference.to_chunks(v, 256) * signs)
    y = emb(u)
    scale = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    assert bool(jnp.all(jnp.abs(emb(dec) - y) <= scale / 16 + 1e-5))
    assert dec.shape == u.shape


def test_learning_rate_warms_up_then_decays():
    opt = {"lr": 1.0, "warmup": 10, "total": 110}
    assert reference.learning_rate(opt, 5) == 0.5
    assert reference.learning_rate(opt, 10) == 1.0
    assert abs(reference.learning_rate(opt, 60) - 0.5) < 1e-12
    assert reference.learning_rate(opt, 110) < 1e-12


def test_control_is_the_precision_below_the_configurations():
    from bench import train_cell
    assert train_cell.matmul_precision({}) == "default"
    assert train_cell.control_precision({}) == {"dtype": jnp.bfloat16}
    assert train_cell.control_precision({"matmul_precision": "highest"}) == \
        {"dtype": jnp.float32, "precision": "high"}
    names = ["embed"]
    cell = {"mesh": [1, 1], "strategy": "psum", "error_feedback": False,
            "bits": 4, "chunk": 256,
            "optimizer": {"b1": 0.9, "b2": 0.95, "eps": 1e-8,
                          "weight_decay": 0.1}}
    devices = jax.devices()[:1]
    assert reference.Trainer({}, cell, names, devices).precision == "highest"
    assert reference.Trainer({}, cell, names, devices, jnp.bfloat16
                             ).precision == "default"
    assert reference.Trainer({}, cell, names, devices, jnp.float32,
                             "high").precision == "high"


def test_diff_gap_sees_what_the_norms_average_out():
    """Two gradients with equal norms but different entries: the gap of
    the norms is 0, the gap of the difference is not."""
    from bench import train_cell
    a = [np.array([3.0, 4.0]), np.array([1.0, 0.0])]
    b = [np.array([4.0, 3.0]), np.array([1.0, 0.0])]
    assert train_cell.leaf_gap([5.0, 1.0], [5.0, 1.0]) == 0.0
    assert train_cell.diff_gap(a, b) == np.sqrt(2.0) / 5.0
    assert train_cell.diff_gap(b, b) == 0.0


def test_median_leaf_gap_is_not_moved_by_one_leaf():
    """One leaf far off moves the worst leaf's gap, not the median's."""
    from bench import train_cell
    want = [1.0, 2.0, 3.0, 4.0, 5.0]
    got = [1.0, 2.0, 3.3, 4.0, 50.0]
    assert train_cell.leaf_gap(got, want) == 9.0
    assert train_cell.median_leaf_gap(got, want) == 0.0
    assert np.isclose(train_cell.median_leaf_gap(
        [1.1, 2.2, 3.3, 4.4, 5.5], want), 0.1)
