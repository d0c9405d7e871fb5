"""HSSSVMEngine: one code path for local and mesh-parallel training.

Fast tier: the local engine must reproduce the per-subsystem trainers
(binary + multiclass) and auto-detect the problem type.

Slow tier (8 emulated devices, subprocess like tests/test_dist.py): the
mesh-parallel build — compress_sharded / factorize_sharded — must match the
single-device build to <=1e-5 relative on solves, every O(N·m) artifact must
actually be sharded (never resident unsharded on one device), and the
1-device-mesh vs 8-device-mesh engines must train to matching results
end-to-end.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admm as admm_mod
from repro.core import engine as engine_mod
from repro.core import tasks as tasks_mod
from repro.core.compression import CompressionParams
from repro.core.engine import HSSSVMEngine
from repro.core.kernelfn import KernelSpec
from repro.core.multiclass import MulticlassHSSSVMTrainer
from repro.core.svm import HSSSVMTrainer, compute_bias_batched
from repro.data import synthetic
from repro.launch.mesh import make_mesh

COMP = CompressionParams(rank=24, n_near=32, n_far=48)


def _run_sub(code: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# --------------------------------------------------------------------- #
# fast tier: local engine vs the per-subsystem trainers                 #
# --------------------------------------------------------------------- #
def test_engine_local_binary_matches_trainer():
    xtr, ytr, xte, yte = synthetic.train_test("blobs", 1024, 256, seed=0,
                                              sep=1.6)
    kw = dict(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=64, max_it=10)
    trainer = HSSSVMTrainer(**kw)
    ref_model = trainer.fit(xtr, ytr, c_value=1.0)
    engine = HSSSVMEngine(**kw)
    model = engine.fit(xtr, ytr, c_value=1.0)
    assert model.binary
    assert engine.n_problems == 1
    pred_ref = np.asarray(ref_model.predict(jnp.asarray(xte)))
    pred = np.asarray(model.predict(jnp.asarray(xte)))
    # identical pipeline (same compression, factorization, ADMM): identical
    # predictions, not merely similar accuracy
    assert (pred == pred_ref).mean() > 0.99, (pred != pred_ref).sum()
    np.testing.assert_allclose(float(model.biases[0]), float(ref_model.bias),
                               rtol=1e-4, atol=1e-5)


def test_engine_local_multiclass_matches_trainer():
    xtr, ytr, xte, yte = synthetic.train_test(
        "multiclass_blobs", 1024, 256, seed=0, n_classes=4, sep=3.0)
    kw = dict(spec=KernelSpec(h=1.5), comp=COMP, leaf_size=64, max_it=10)
    ref = MulticlassHSSSVMTrainer(**kw).fit(xtr, ytr, c_value=1.0)
    engine = HSSSVMEngine(**kw)
    model = engine.fit(xtr, ytr, c_value=1.0)
    assert not model.binary
    assert engine.n_problems == 4
    pred_ref = np.asarray(ref.predict(jnp.asarray(xte)))
    pred = np.asarray(model.predict(jnp.asarray(xte)))
    assert (pred == pred_ref).mean() > 0.99


def test_engine_train_grid_warm_start():
    xtr, ytr, xte, yte = synthetic.train_test("blobs", 512, 128, seed=1,
                                              sep=1.6)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=64,
                          max_it=10)
    engine.prepare(xtr, ytr)
    models = engine.train_grid([0.1, 1.0, 10.0])
    assert len(models) == 3
    accs = [float(jnp.mean(m.predict(jnp.asarray(xte)) == yte))
            for m in models]
    assert max(accs) > 0.85, accs
    # one compression, one factorization for the whole sweep
    assert engine.report.compression_s > 0
    assert engine.report.admm_s > 0


def test_engine_multilevel_warm_start_reduces_iters():
    """AML-SVM-style coarse->fine warm start: train on a stratified
    subsample, prolong the duals by nearest-skeleton interpolation (scaled
    by n_c/n_f — copied coarse duals are ~n_f/n_c too large, see
    tasks.prolong_scale), and finish with early-stopping ADMM.  The warm
    run must CONVERGE IN FEWER ITERATIONS than the cold run at matched
    holdout accuracy — the measured quantity the subsystem exists for."""
    from repro.core.compression import CompressionParams as CP

    xtr, ytr, xte, yte = synthetic.train_test("blobs", 2048, 256, seed=0,
                                              n_features=5, sep=3.0)
    engine = HSSSVMEngine(spec=KernelSpec(h=2.0), comp=CP.crude(),
                          leaf_size=128, beta=100.0, tol=3e-2, max_it=400)
    engine.prepare(xtr, ytr)
    m_cold, _ = engine.train(1.0)
    iters_cold = int(np.max(np.asarray(engine.report.iters_run)))
    acc_cold = float(jnp.mean(m_cold.predict(jnp.asarray(xte)) == yte))

    m_warm, info = engine.train_multilevel(1.0, coarse_frac=0.25,
                                           coarse_leaf_size=64, seed=0)
    iters_warm = int(np.max(np.asarray(info["iters_run"])))
    acc_warm = float(jnp.mean(m_warm.predict(jnp.asarray(xte)) == yte))

    assert iters_warm < iters_cold, (iters_warm, iters_cold)
    assert iters_cold < 400, "cold run hit the cap - tolerance unreachable"
    assert info["coarse_n"] < len(xtr) // 2
    assert abs(acc_warm - acc_cold) <= 0.01, (acc_warm, acc_cold)


def test_engine_ovo_strategy():
    xtr, ytr, xte, yte = synthetic.train_test(
        "multiclass_blobs", 512, 128, seed=0, n_classes=3, sep=3.0)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.5), comp=COMP, leaf_size=64,
                          max_it=10, strategy="ovo")
    model = engine.fit(xtr, ytr, c_value=1.0)
    assert engine.n_problems == 3          # 3 choose 2
    assert model.pairs is not None
    acc = float(jnp.mean(model.predict(jnp.asarray(xte)) == jnp.asarray(yte)))
    assert acc > 0.9, acc


# --------------------------------------------------------------------- #
# the shared ADMM program against the per-engine closure it replaced    #
# --------------------------------------------------------------------- #
def _closure_build_task(task_name, svr_c, ys_, pmask_, knob):
    if task_name == "svr":
        return tasks_mod.svr_task(ys_, svr_c * pmask_, knob)
    if task_name == "oneclass":
        return tasks_mod.one_class_task(pmask_, knob)
    return admm_mod.svm_task(ys_, knob * pmask_)


def _closure_body(engine):
    """The fixed-length ADMM run as every engine used to build it: a fresh
    closure over the engine's task values (verbatim)."""
    max_it, tol = engine.max_it, engine.tol
    task_name, svr_c = engine.task, engine.svr_c

    def _run(fac_, ys_, pmask_, knob, z0, mu0):
        task = _closure_build_task(task_name, svr_c, ys_, pmask_, knob)
        state, trace = admm_mod.admm_boxqp(
            fac_.solve_mat, task, fac_.beta, max_it, tol=tol,
            z0=z0, mu0=mu0)
        hi = task.hi if task_name == "oneclass" else ()
        return (state.z, state.mu, task.sign * state.z, hi,
                trace.iters_run)

    return _run


def _closure_chunk(engine, n_it, tol):
    """One chunk of the adaptive-ρ run as every engine used to build it
    (verbatim)."""
    task_name, svr_c = engine.task, engine.svr_c

    def _chunk(fac_, ys_, pmask_, knob_, z0_, mu0_, done0_):
        task = _closure_build_task(task_name, svr_c, ys_, pmask_, knob_)
        state, trace = admm_mod.admm_boxqp(
            fac_.solve_mat, task, fac_.beta, n_it, tol=tol,
            z0=z0_, mu0=mu0_, done0=done0_)
        hi = task.hi if task_name == "oneclass" else ()
        return state, trace, task.sign * state.z, hi
    return jax.jit(_chunk)


def _problem(case):
    if case == "covtype7":
        from bench.data import covtype

        x, y = covtype.generate(3072, (5, 1))
        return x, y, KernelSpec(h=0.5), 128, None
    x, y, _, _ = synthetic.train_test("blobs", 1024, 8, seed=3, sep=1.6)
    return x, y, KernelSpec(h=1.0), 64, (1e-3 if case == "binary_tol"
                                         else None)


@pytest.mark.parametrize("case", ["binary", "binary_tol", "covtype7"])
def test_shared_admm_program_matches_the_per_engine_closure(case):
    """Same jaxpr as the closure, and bit-identical z, mu, z_y, biases and
    iteration counts, on a cold knob and a warm-started second one."""
    x, y, spec, leaf, tol = _problem(case)
    engine = HSSSVMEngine(spec=spec, comp=COMP, leaf_size=leaf, max_it=10,
                          tol=tol)
    engine.prepare(x, y)
    if case == "covtype7":
        assert engine.n_problems == 7
    fac, ys, pmask = engine.fac, engine.problem_labels, engine.problem_masks
    body = _closure_body(engine)
    zeros = jnp.zeros((ys.shape[1], ys.shape[0]), jnp.float32)
    args = (fac, ys, pmask, jnp.asarray(1.0, jnp.float32), zeros, zeros)
    old = jax.make_jaxpr(jax.jit(body))(*args).eqns[0].params["jaxpr"]
    new = jax.make_jaxpr(lambda *a: engine_mod._admm_run(
        *a, boxqp=admm_mod.admm_boxqp, task_name=engine.task,
        svr_c=engine.svr_c, max_it=engine.max_it,
        tol=engine.tol))(*args).eqns[0].params["jaxpr"]
    assert str(new) == str(old)

    run, bias = jax.jit(body), jax.jit(compute_bias_batched)
    warm, ref_warm = None, (zeros, zeros)
    for c in (1.0, 2.0):
        model, warm = engine.train(c, warm=warm)
        z, mu, z_y, _hi, iters = run(fac, ys, pmask,
                                     jnp.asarray(c, jnp.float32), *ref_warm)
        ref_warm = (z, mu)
        b = bias(engine.hss, ys.T, z, c * pmask.T, pmask.T)
        np.testing.assert_array_equal(np.asarray(warm[0]), np.asarray(z))
        np.testing.assert_array_equal(np.asarray(warm[1]), np.asarray(mu))
        np.testing.assert_array_equal(np.asarray(model.z_y), np.asarray(z_y))
        np.testing.assert_array_equal(np.asarray(model.biases),
                                      np.asarray(b))
        assert engine.report.iters_run == tuple(int(i) for i in iters)


def test_adaptive_rho_engine_matches_the_per_engine_chunks():
    """The adaptive-ρ path on the shared program: the same β rescales and
    bit-identical iterates as the per-engine chunk closures."""
    x, y, _, _ = synthetic.train_test("blobs", 512, 8, seed=4, sep=1.6)
    ap = admm_mod.ADMMParams(max_it=60, tol=1e-3, adapt_rho=True,
                             rho_every=5, rho_max_updates=4)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=64,
                          beta=1e4, admm=ap)
    engine.prepare(x, y)
    model, (z, mu) = engine.train(1.0)
    assert engine.report.rho_rescales > 0

    ys, pmask = engine.problem_labels, engine.problem_masks
    knob = jnp.asarray(1.0, jnp.float32)
    chunks = {}

    def run_chunk(beta, n_it, z_, mu_, done):
        fn = chunks.setdefault(n_it, _closure_chunk(engine, n_it, ap.tol))
        done = jnp.zeros((ys.shape[0],), bool) if done is None else done
        state, trace, z_y, _hi = fn(engine._fac_for(beta), ys, pmask, knob,
                                    z_, mu_, done)
        chunks["z_y"] = z_y
        return state, trace

    zeros = jnp.zeros((ys.shape[1], ys.shape[0]), jnp.float32)
    state, trace, info = admm_mod.adaptive_rho_outer(
        run_chunk, 1e4, ap, z0=zeros, mu0=zeros)
    assert engine.report.rho_rescales == info["rescales"]
    assert engine.report.rho_final == info["beta"]
    assert engine.report.iters_run == tuple(
        int(i) for i in np.asarray(trace.iters_run))
    np.testing.assert_array_equal(np.asarray(z), np.asarray(state.z))
    np.testing.assert_array_equal(np.asarray(mu), np.asarray(state.mu))
    np.testing.assert_array_equal(np.asarray(model.z_y),
                                  np.asarray(chunks["z_y"]))


# --------------------------------------------------------------------- #
# slow tier: multi-device parity + sharding guarantees                  #
# --------------------------------------------------------------------- #
@pytest.mark.slow
def test_sharded_build_matches_local_build():
    """compress_sharded + factorize_sharded on 8 devices == local build:
    solve results to <=1e-5 relative, and every O(N·m) artifact sharded."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import compression, factorization, tree as tree_mod
        from repro.core.distributed import fac_shardings
        from repro.core.kernelfn import KernelSpec
        from repro.dist import api as dist_api

        rng = np.random.default_rng(0)
        n, leaf = 4096, 64
        x = rng.normal(size=(n, 4)).astype(np.float32)
        t = tree_mod.build_tree(x, leaf_size=leaf)
        xp = x[t.perm]
        spec = KernelSpec(h=1.0)
        params = compression.CompressionParams(rank=24, n_near=32, n_far=48)
        mesh = make_mesh((8,), ("data",))

        hss_ref = compression.compress(jnp.asarray(xp), t, spec, params)
        fac_ref = factorization.factorize(hss_ref, 10.0)
        hss = compression.compress_sharded(xp, t, spec, params, mesh)
        fac = factorization.factorize_sharded(hss, 10.0, mesh)

        ndev = 8
        n_leaf = n // leaf
        # -- sharding guarantees: no unsharded O(N*m) / O(N*r) array --
        for name in ("d_leaf", "u_leaf", "x"):
            a = getattr(hss, name)
            assert not a.sharding.is_fully_replicated, name
            shard = a.addressable_shards[0].data.shape
            assert shard[0] == a.shape[0] // ndev, (name, shard, a.shape)
        for name in ("e_leaf", "g_leaf"):
            a = getattr(fac, name)
            assert not a.sharding.is_fully_replicated, name
            assert a.addressable_shards[0].data.shape[0] == n_leaf // ndev
        # factorization emitted already placed per fac_shardings (no
        # build-then-device_put round trip)
        want = fac_shardings(jax.eval_shape(lambda: fac), mesh)
        for a, s in zip(jax.tree.leaves(fac), jax.tree.leaves(want)):
            assert a.sharding.is_equivalent_to(s, a.ndim), (a.shape, s)

        # -- value parity: representation-level matvec and solve --
        v = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
        mv_ref = np.asarray(hss_ref.matmat(v))
        with dist_api.use_mesh(mesh), mesh:
            mv = np.asarray(jax.jit(lambda h, b: h.matmat(b))(hss, v))
            out = np.asarray(jax.jit(lambda f, b: f.solve_mat(b))(fac, v))
        ref = np.asarray(fac_ref.solve_mat(v))
        rel_mv = np.linalg.norm(mv - mv_ref) / np.linalg.norm(mv_ref)
        rel = np.linalg.norm(out - ref) / np.linalg.norm(ref)
        assert rel_mv <= 1e-5, rel_mv
        assert rel <= 1e-5, rel

        # -- non-f32 dtype: the sharded build must PRESERVE the caller's
        # dtype (it used to silently downcast everything to f32) and still
        # match the local bf16 build --
        xp_bf = jnp.asarray(xp, jnp.bfloat16)
        hss_bf_ref = compression.compress(xp_bf, t, spec, params)
        hss_bf = compression.compress_sharded(xp_bf, t, spec, params, mesh)
        for name in ("d_leaf", "u_leaf", "x"):
            got = getattr(hss_bf, name).dtype
            ref_dt = getattr(hss_bf_ref, name).dtype
            assert got == ref_dt == jnp.bfloat16, (name, got, ref_dt)
        # bf16 pivot ties may resolve differently between the eager local
        # and jitted sharded builds, so compare both against the EXACT
        # kernel matvec instead of against each other.
        from repro.core.kernelfn import gaussian_block_xla, kernel_matvec_streamed
        xf = xp_bf.astype(jnp.float32)
        vb = v.astype(jnp.bfloat16)
        ref_bf = np.asarray(kernel_matvec_streamed(spec, xf, xf, v))
        mv_lo = np.asarray(hss_bf_ref.matmat(vb), np.float32)
        with dist_api.use_mesh(mesh), mesh:
            mv_sh = np.asarray(
                jax.jit(lambda h, b: h.matmat(b))(hss_bf, vb), np.float32)
        rel_lo = np.linalg.norm(mv_lo - ref_bf) / np.linalg.norm(ref_bf)
        rel_sh = np.linalg.norm(mv_sh - ref_bf) / np.linalg.norm(ref_bf)
        assert rel_lo <= 0.35 and rel_sh <= 0.35, (rel_lo, rel_sh)
        assert abs(rel_lo - rel_sh) <= 0.05, (rel_lo, rel_sh)
        print("BUILD_PARITY_OK", rel_mv, rel, rel_lo, rel_sh)
    """)
    r = _run_sub(code)
    assert "BUILD_PARITY_OK" in r.stdout, r.stdout + r.stderr


def test_engine_refuses_a_mesh_it_cannot_shard_over():
    """A 3-device mesh cannot divide the perfect tree's 2^L leaves: prepare
    raises instead of quietly running everything on one device."""
    import types

    fake3 = types.SimpleNamespace(axis_names=("data",), shape={"data": 3})
    xtr, ytr, _, _ = synthetic.train_test("blobs", 256, 16, seed=0)
    eng = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=32,
                       mesh=fake3)
    with pytest.raises(ValueError, match="power-of-two"):
        eng.prepare(xtr, ytr)


def test_sharded_build_preserves_dtype_bf16():
    """Fast leg of the dtype-preservation fix: under a 1-device mesh the
    sharded build keeps bf16 end-to-end (no silent f32 downcast) and agrees
    with the local bf16 build."""
    import jax

    from repro.core import compression, tree as tree_mod
    from repro.core.kernelfn import KernelSpec

    rng = np.random.default_rng(5)
    n, leaf = 256, 32
    x = rng.normal(size=(n, 4)).astype(np.float32)
    t = tree_mod.build_tree(x, leaf_size=leaf)
    xp_bf = jnp.asarray(x[t.perm], jnp.bfloat16)
    spec = KernelSpec(h=1.0)
    params = compression.CompressionParams(rank=16, n_near=16, n_far=16)
    mesh = make_mesh((1,), ("data",))
    hss_lo = compression.compress(xp_bf, t, spec, params)
    hss_sh = compression.compress_sharded(xp_bf, t, spec, params, mesh)
    for name in ("d_leaf", "u_leaf", "x"):
        got = getattr(hss_sh, name).dtype
        assert got == getattr(hss_lo, name).dtype == jnp.bfloat16, (name, got)
    # bf16 pivot selection is tie-prone (the sampled blocks only carry ~3
    # significant digits), so eager-local and jitted-sharded builds may pick
    # different — equally valid — skeletons.  Parity at bf16 therefore means
    # BOTH builds approximate the exact kernel equally well, not that they
    # are bitwise equal.
    from repro.core.kernelfn import gaussian_block_xla

    xf = xp_bf.astype(jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    ref = np.asarray(gaussian_block_xla(xf, xf, 1.0) @ v)
    rels = {}
    for name, h in (("local", hss_lo), ("sharded", hss_sh)):
        mv = np.asarray(h.matmat(v.astype(jnp.bfloat16)), np.float32)
        rels[name] = np.linalg.norm(mv - ref) / np.linalg.norm(ref)
    assert rels["local"] <= 0.35 and rels["sharded"] <= 0.35, rels
    assert abs(rels["local"] - rels["sharded"]) <= 0.05, rels


@pytest.mark.slow
def test_engine_end_to_end_1_vs_8_devices():
    """The engine trains identically under a 1-device and an 8-device mesh
    (and matches the meshless local path), with sharded iterates/model."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core.compression import CompressionParams
        from repro.core.engine import HSSSVMEngine
        from repro.core.kernelfn import KernelSpec
        from repro.data import synthetic

        xtr, ytr, xte, yte = synthetic.train_test(
            "blobs", 4096, 512, seed=0, n_features=6, sep=1.6)
        kw = dict(spec=KernelSpec(h=1.0),
                  comp=CompressionParams(rank=24, n_near=32, n_far=48),
                  leaf_size=64, max_it=10, beta=100.0)

        def fit(mesh):
            eng = HSSSVMEngine(mesh=mesh, **kw)
            model = eng.fit(xtr, ytr, c_value=1.0)
            scores = np.asarray(model.decision_function(jnp.asarray(xte)))
            acc = float(np.mean(np.where(scores >= 0, 1, -1) == yte))
            return eng, model, scores, acc

        eng1, m1, s1, acc1 = fit(make_mesh((1,), ("data",)))
        eng8, m8, s8, acc8 = fit(make_mesh((8,), ("data",)))
        eng0, m0, s0, acc0 = fit(None)

        # 8-device model is genuinely sharded
        assert not m8.z_y.sharding.is_fully_replicated
        assert m8.z_y.addressable_shards[0].data.shape[0] == m8.z_y.shape[0] // 8
        assert not eng8.hss.d_leaf.sharding.is_fully_replicated

        rel18 = (np.linalg.norm(s1 - s8) /
                 max(np.linalg.norm(s1), 1e-30))
        rel08 = (np.linalg.norm(s0 - s8) /
                 max(np.linalg.norm(s0), 1e-30))
        assert rel18 <= 1e-5, rel18
        assert rel08 <= 1e-4, rel08            # meshless path: same math,
        assert acc1 == acc8, (acc1, acc8)      # different partitioning
        assert abs(acc0 - acc8) <= 0.004, (acc0, acc8)
        print("ENGINE_PARITY_OK", rel18, rel08, acc8)
    """)
    r = _run_sub(code)
    assert "ENGINE_PARITY_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_engine_multiclass_8_devices():
    """k-class engine under the mesh: sharded (d, P) iterates, accuracy
    matching the local multiclass trainer."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core.compression import CompressionParams
        from repro.core.engine import HSSSVMEngine
        from repro.core.kernelfn import KernelSpec
        from repro.core.multiclass import MulticlassHSSSVMTrainer
        from repro.data import synthetic

        xtr, ytr, xte, yte = synthetic.train_test(
            "multiclass_blobs", 2048, 512, seed=0, n_classes=4, sep=3.0)
        kw = dict(spec=KernelSpec(h=1.5),
                  comp=CompressionParams(rank=24, n_near=32, n_far=48),
                  leaf_size=64, max_it=10)
        ref = MulticlassHSSSVMTrainer(**kw).fit(xtr, ytr, c_value=1.0)
        acc_ref = float(jnp.mean(ref.predict(jnp.asarray(xte))
                                 == jnp.asarray(yte)))
        mesh = make_mesh((8,), ("data",))
        eng = HSSSVMEngine(mesh=mesh, **kw)
        model = eng.fit(xtr, ytr, c_value=1.0)
        assert model.z_y.shape[1] == 4
        assert not model.z_y.sharding.is_fully_replicated
        acc = float(jnp.mean(model.predict(jnp.asarray(xte))
                             == jnp.asarray(yte)))
        assert abs(acc - acc_ref) <= 0.01, (acc, acc_ref)
        print("MC_ENGINE_OK", acc, acc_ref)
    """)
    r = _run_sub(code)
    assert "MC_ENGINE_OK" in r.stdout, r.stdout + r.stderr
