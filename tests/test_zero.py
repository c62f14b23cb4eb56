"""ZeRO-1 + all-to-all compressed reduce-scatter (repro/dist/zero.py).

The multi-worker equivalence test runs in a subprocess because it needs
XLA_FLAGS=--xla_force_host_platform_device_count set before jax init.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import zero as zero_lib
from repro.dist.gradcomp import GradCompConfig


def test_leaf_layout():
    assert zero_lib.leaf_layout((100,), 64, 4) == (4, 1)      # 2 chunks → pad 4
    assert zero_lib.leaf_layout((64, 64), 64, 4) == (64, 16)
    assert zero_lib.leaf_layout((1,), 64, 8) == (8, 1)


def test_owned_reconstruction_roundtrip():
    """pad→chunk→slice-per-owner→gather reproduces the leaf exactly."""
    cfg = GradCompConfig(bits=4, chunk=64)
    x = jnp.arange(1000, dtype=jnp.float32).reshape(25, 40)
    m = 4
    padded, rows_per = zero_lib.leaf_layout(x.shape, cfg.chunk, m)
    flat = jnp.pad(x.reshape(-1), (0, padded * cfg.chunk - x.size))
    owned = flat.reshape(m, rows_per, cfg.chunk)
    recon = owned.reshape(-1)[: x.size].reshape(x.shape)
    np.testing.assert_array_equal(recon, x)


@pytest.mark.slow
def test_multiworker_equivalence_subprocess():
    """m=4 data shards: ZeRO-1 all-to-all schedule must produce EXACTLY the
    same updated parameters as the paper-faithful all-gather consensus —
    including the sub-linear keep_fraction < 1 regime, where the chunk
    keep-mask is drawn at the pre-pad chunk count in both paths."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.data import batch_for_shape
        from repro.dist import step as step_lib, zero as zero_lib
        from repro.dist.gradcomp import GradCompConfig
        from repro.optimizer import sgd

        mesh = jax.sharding.Mesh(
            np.array(jax.devices()).reshape(4, 2), ("data", "model"))
        cfg = configs.get_reduced("phi3-mini-3.8b")
        opt = sgd(1.0)
        batch = batch_for_shape(cfg, 8, 32)

        def run_pair(tag, **gc_kwargs):
            gc_z = GradCompConfig(strategy="alltoall_zero1", **gc_kwargs)
            zstep = step_lib.make_zero_train_step(cfg, opt, gc_z, mesh)
            state = step_lib.init_zero_state(cfg, opt, gc_z, mesh)
            o1, _, _, mz = zstep(*state, batch)
            gc_a = GradCompConfig(strategy="allgather_packed", **gc_kwargs)
            tstep = step_lib.make_train_step(cfg, opt, gc_a, mesh)
            st2 = step_lib.init_train_state(cfg, opt, gc_a, mesh)
            p1, _, _, mr = tstep(*st2, batch)
            assert abs(float(mz["loss"]) - float(mr["loss"])) < 1e-6
            pmeta = zero_lib.params_meta(jax.eval_shape(lambda: p1), gc_z, 4)
            treedef, infos = pmeta
            flat_owned = treedef.flatten_up_to(
                jax.tree.map(lambda x: np.asarray(x), o1))
            recon = [x.reshape(-1)[:i[0]].reshape(i[1])
                     for x, i in zip(flat_owned, infos)]
            flat_ref = [np.asarray(x) for x in jax.tree.leaves(p1)]
            err = max(float(np.max(np.abs(a - b)))
                      for a, b in zip(recon, flat_ref))
            assert err < 1e-5, (tag, err)
            print("EXACT", tag, err)

        run_pair("dense", bits=8, chunk=256)
        run_pair("sublinear", bits=8, chunk=256, keep_fraction=0.5)
        run_pair("sublinear_exact", bits=8, chunk=256, keep_fraction=0.5,
                 exact_keep=True)
    """) % os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.count("EXACT") == 3


def test_zero1_bitwise_with_error_feedback_over_two_steps():
    """With error feedback, the second step adds the EF state to fresh
    gradients: ZeRO-1 must still match allgather_packed bit for bit (the
    embedding's scatter-add gradient is where a fused g + e would round
    differently in the two layouts)."""
    import jax
    from repro import configs
    from repro.data import batch_for_shape
    from repro.dist import step as step_lib
    from repro.launch.mesh import make_host_mesh
    from repro.optimizer import sgd

    if jax.device_count() < 4:
        pytest.skip(f"needs 4 devices, have {jax.device_count()}")
    mesh = make_host_mesh(data=4, model=1)
    cfg = configs.get_reduced("xlstm-350m")
    opt = sgd(1.0)
    key = jax.random.key(0)
    batches = [batch_for_shape(cfg, 8, 32, s, 0) for s in range(2)]
    gc_a = GradCompConfig(bits=4, strategy="allgather_packed")
    gc_z = GradCompConfig(bits=4, strategy="alltoall_zero1")
    assert gc_a.uses_ef and gc_z.uses_ef
    astep = step_lib.make_train_step(cfg, opt, gc_a, mesh)
    zstep = step_lib.make_zero_train_step(cfg, opt, gc_z, mesh)
    sa = step_lib.init_train_state(cfg, opt, gc_a, mesh, key)
    sz = step_lib.init_zero_state(cfg, opt, gc_z, mesh, key)
    for b in batches:
        *sa, ma = astep(*sa, b)
        *sz, mz = zstep(*sz, b)
        assert float(ma["loss"]) == float(mz["loss"])
    treedef, infos = zero_lib.params_meta(jax.eval_shape(lambda: sa[0]),
                                          gc_z, 4)
    owned = treedef.flatten_up_to(sz[0])
    for o, (size, shape, _, _), want in zip(owned, infos,
                                            jax.tree.leaves(sa[0])):
        got = np.asarray(o).reshape(-1)[:size].reshape(shape)
        np.testing.assert_array_equal(got, np.asarray(want))
