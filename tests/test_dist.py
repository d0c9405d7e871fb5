"""Distribution-layer tests: sharding rules + small-mesh end-to-end parity.

The heavy 512-device sweep lives in launch/dryrun.py (results in
EXPERIMENTS.md); here we verify on 8 host devices that (a) a train step
LOWERS and RUNS under a mesh, and (b) the distributed result matches the
single-device result (the shard_map MoE path vs the fallback path).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.dist import api as dist_api
from repro.dist import sharding as shd
from repro.launch.mesh import make_mesh


def test_resolve_spec_divisibility_fallback():
    import jax.numpy as jnp
    mesh = make_mesh((1,), ("model",))
    with dist_api.use_mesh(mesh):
        spec = dist_api.resolve_spec(("model", None), (7, 3))
        # 7 % 1 == 0 -> keeps axis
        assert spec[0] == "model"


def test_param_shardings_cover_all_leaves():
    from repro.configs import get_config
    from repro.models.transformer import Model

    cfg = get_config("arctic-480b").reduced()
    shapes = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = shd.param_shardings(shapes, mesh)
    n = len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")))
    assert n == len(jax.tree.leaves(shapes))


@pytest.mark.slow
def test_distributed_train_step_matches_single_device():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.models.transformer import Model
        from repro.train import optim
        from repro.train.step import make_train_step
        from repro.data.tokens import batch_for_config
        from repro.dist import api as dist_api, sharding as shd

        # MoE arch exercises the shard_map dispatch path
        cfg = get_config("granite-moe-3b-a800m").reduced(
            n_layers=2, remat="none", param_dtype="float32",
            compute_dtype="float32")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = jax.tree.map(jnp.asarray, batch_for_config(cfg, 8, 32, 0))

        # single device reference
        loss_ref, _ = jax.jit(model.loss_fn)(params, batch)

        mesh = make_mesh((4, 2), ("data", "model"))
        with dist_api.use_mesh(mesh), mesh:
            psh = shd.param_shardings(
                jax.eval_shape(lambda: params), mesh, fsdp=True)
            bsh = shd.batch_shardings(
                jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                             batch), mesh)
            fn = jax.jit(model.loss_fn, in_shardings=(psh, bsh))
            loss_dist, _ = fn(jax.device_put(params, psh),
                              jax.device_put(batch, bsh))
        rel = abs(float(loss_ref) - float(loss_dist)) / abs(float(loss_ref))
        assert rel < 2e-2, (float(loss_ref), float(loss_dist))
        print("DIST_OK", float(loss_ref), float(loss_dist))
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "DIST_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_sharded_attention_matches_single_device():
    """The shard_map head-parallel attention (incl. GQA kv slicing) must
    match the single-device path bit-for-bit-ish on an 8-device mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.models.transformer import Model
        from repro.data.tokens import batch_for_config
        from repro.dist import api as dist_api, sharding as shd

        # h=16, kv=8: with mp=4 -> h_loc=4, group=2, kv_loc=2 (slicing path)
        cfg = get_config("gemma2-9b").reduced(
            n_layers=2, n_heads=16, n_kv_heads=8, head_dim=16, d_model=128,
            remat="none", param_dtype="float32", compute_dtype="float32")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = jax.tree.map(jnp.asarray, batch_for_config(cfg, 4, 64, 0))
        loss_ref, _ = jax.jit(model.loss_fn)(params, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        with dist_api.use_mesh(mesh), mesh:
            psh = shd.param_shardings(jax.eval_shape(lambda: params), mesh)
            fn = jax.jit(model.loss_fn)
            loss_dist, _ = fn(jax.device_put(params, psh), batch)
        rel = abs(float(loss_ref) - float(loss_dist)) / abs(float(loss_ref))
        assert rel < 1e-4, (float(loss_ref), float(loss_dist))
        print("ATTN_SHARD_OK", float(loss_ref), float(loss_dist))
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "ATTN_SHARD_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_distributed_svm_solve_matches_local():
    """HSS factorization solve under an 8-device mesh == local solve."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import compression, factorization, tree as tree_mod
        from repro.core.kernelfn import KernelSpec
        from repro.core.distributed import fac_shardings, vec_sharding

        rng = np.random.default_rng(0)
        n = 1024
        x = rng.normal(size=(n, 3)).astype(np.float32)
        t = tree_mod.build_tree(x, leaf_size=64)
        xp = jnp.asarray(x[t.perm])
        hss = compression.compress(
            xp, t, KernelSpec(h=1.0),
            compression.CompressionParams(rank=24, n_near=32, n_far=48))
        fac = factorization.factorize(hss, 10.0)
        b = jnp.asarray(rng.normal(size=n), jnp.float32)
        ref = np.asarray(fac.solve(b))

        mesh = make_mesh((8,), ("data",))
        fac_sh = fac_shardings(jax.eval_shape(lambda: fac), mesh)
        fac_d = jax.device_put(fac, fac_sh)
        b_d = jax.device_put(b, vec_sharding(mesh))
        with mesh:
            out = np.asarray(jax.jit(lambda f, v: f.solve(v))(fac_d, b_d))
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
        print("SVM_DIST_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "SVM_DIST_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_distributed_admm_c_grid_matches_single_device():
    """The engine's warm-started C-grid on an 8-device mesh == the same
    engine on a 1-device mesh, per C, including the warm-start chaining
    across the grid."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import compression
        from repro.core.engine import HSSSVMEngine
        from repro.core.kernelfn import KernelSpec
        from repro.data import synthetic

        n = 1024
        x, y = synthetic.blobs(n, n_features=4, sep=1.6, seed=0)
        c_grid = [0.5, 1.0, 2.0]

        def c_grid_duals(mesh):
            eng = HSSSVMEngine(
                spec=KernelSpec(h=1.0),
                comp=compression.CompressionParams(rank=24, n_near=32,
                                                   n_far=48),
                leaf_size=64, beta=100.0, max_it=10, mesh=mesh)
            eng.prepare(x, y)
            out, warm = [], None
            for c in c_grid:
                _, warm = eng.train(c, warm=warm)
                out.append(np.asarray(warm[0]))
            return out

        res1 = c_grid_duals(make_mesh((1,), ("data",)))
        res8 = c_grid_duals(make_mesh((8,), ("data",)))
        for i in range(len(c_grid)):
            np.testing.assert_allclose(
                res8[i], res1[i], rtol=1e-4, atol=1e-5)
        print("ADMM_GRID_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "ADMM_GRID_OK" in r.stdout, r.stdout + r.stderr
