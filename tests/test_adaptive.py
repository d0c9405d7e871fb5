"""Adaptive-rank HSS: tolerance-driven compression, masks, shrink-to-fit.

Fast tier: rank detection flows through compress -> HSSMatrix rank vectors,
masked arrays are structurally consistent (dead slots exactly zero), the
shrink-to-fit pass is EXACT (masked/shrunk-vs-full matmat and solve parity),
the mask-aware factorization solves the same system, and the engine /
trainers plumb rtol end-to-end with rank reporting.

Slow tier (8 emulated devices, subprocess like tests/test_engine.py): the
sharded adaptive build detects the same ranks as the local build, stays
sharded through shrink_to_fit, and keeps shrunk-vs-full parity <=1e-5 under
the mesh.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, factorization, tree as tree_mod
from repro.core.engine import HSSSVMEngine
from repro.core.hss import shrink_to_fit
from repro.core.kernelfn import KernelSpec, gaussian_block_xla
from repro.core.svm import HSSSVMTrainer, grid_search
from repro.data import synthetic


def _run_sub(code: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build(n=1024, leaf=64, rank=48, h=2.0, rtol=1e-2, n_features=2, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, n_features)).astype(np.float32)
    t = tree_mod.build_tree(x, leaf_size=leaf)
    xp = jnp.asarray(x[t.perm])
    spec = KernelSpec(h=h)
    params = compression.CompressionParams(
        rank=rank, n_near=32, n_far=48, seed=seed, rtol=rtol)
    return compression.compress(xp, t, spec, params), xp, spec, t, params


# --------------------------------------------------------------------- #
# representation: rank vectors, masks, structural zeros                 #
# --------------------------------------------------------------------- #
def test_adaptive_build_detects_subcap_ranks():
    hss, xp, spec, _, _ = _build()
    assert hss.adaptive
    obs = hss.observed_ranks()
    assert all(o < c for o, c in zip(obs, hss.ranks)), (obs, hss.ranks)
    # error still tracks the tolerance
    k_dense = gaussian_block_xla(xp, xp, spec.h)
    err = float(jnp.linalg.norm(hss.todense() - k_dense)
                / jnp.linalg.norm(k_dense))
    assert err < 10 * 1e-2, err


def test_fixed_build_has_no_rank_vectors():
    hss, _, _, _, _ = _build(rtol=None)
    assert not hss.adaptive
    assert hss.leaf_ranks is None and hss.level_ranks == ()
    assert hss.rank_masks() is None
    assert hss.observed_ranks() == hss.ranks
    assert shrink_to_fit(hss) is hss         # passthrough


def test_masked_slots_are_structural_zeros():
    """Everything beyond a node's detected rank must be EXACTLY zero — the
    invariant that makes shrink_to_fit exact rather than approximate."""
    hss, _, _, _, _ = _build()
    leaf_ranks = np.asarray(hss.leaf_ranks)
    u = np.asarray(hss.u_leaf)
    for i, r in enumerate(leaf_ranks):
        assert np.abs(u[i, :, r:]).max() == 0.0, i
    lvl_ranks = [np.asarray(r) for r in hss.level_ranks]
    for k, t in enumerate(hss.transfers):
        t = np.asarray(t)
        rp = t.shape[1] // 2
        child = lvl_ranks[k - 1] if k > 0 else leaf_ranks
        child = child.reshape(-1, 2)
        for i in range(t.shape[0]):
            assert np.abs(t[i, :, lvl_ranks[k][i]:]).max() == 0.0   # parent
            assert np.abs(t[i, child[i, 0]:rp, :]).max() == 0.0     # child 1
            assert np.abs(t[i, rp + child[i, 1]:, :]).max() == 0.0  # child 2
    for k, b in enumerate(hss.b_mats):
        b = np.asarray(b)
        child = (leaf_ranks if k == 0 else lvl_ranks[k - 1]).reshape(-1, 2)
        for i in range(b.shape[0]):
            assert np.abs(b[i, child[i, 0]:, :]).max() == 0.0
            assert np.abs(b[i, :, child[i, 1]:]).max() == 0.0


# --------------------------------------------------------------------- #
# shrink-to-fit: exact parity                                           #
# --------------------------------------------------------------------- #
def test_shrunk_vs_full_matmat_and_solve_parity():
    """Acceptance bar: masked/shrunk-vs-full matmat and hss_solve_mat
    parity <= 1e-5."""
    hss, _, _, _, _ = _build()
    shr = shrink_to_fit(hss)
    assert shr.ranks == hss.observed_ranks()
    assert shr.memory_bytes() < hss.memory_bytes()
    assert shr.stored_rank_sum() < hss.stored_rank_sum()
    v = jnp.asarray(np.random.default_rng(1).normal(size=(hss.n, 4)),
                    jnp.float32)
    mv_full = np.asarray(hss.matmat(v))
    mv_shr = np.asarray(shr.matmat(v))
    rel = np.linalg.norm(mv_shr - mv_full) / np.linalg.norm(mv_full)
    assert rel <= 1e-5, rel

    fac_full = factorization.factorize(hss, 20.0)
    fac_shr = factorization.factorize(shr, 20.0)
    s_full = np.asarray(fac_full.solve_mat(v))
    s_shr = np.asarray(fac_shr.solve_mat(v))
    rel_s = np.linalg.norm(s_shr - s_full) / np.linalg.norm(s_full)
    assert rel_s <= 1e-5, rel_s
    # and the solve actually inverts the shifted operator
    resid = np.asarray(shr.matmat(jnp.asarray(s_shr))) + 20.0 * s_shr \
        - np.asarray(v)
    assert np.linalg.norm(resid) / np.linalg.norm(np.asarray(v)) < 1e-4


def test_shrink_multiple_rounding():
    hss, _, _, _, _ = _build()
    shr8 = shrink_to_fit(hss, multiple=8)
    assert all(r % 8 == 0 or r == c
               for r, c in zip(shr8.ranks, hss.ranks)), shr8.ranks
    assert all(r >= o for r, o in zip(shr8.ranks, hss.observed_ranks()))
    v = jnp.asarray(np.random.default_rng(2).normal(size=(hss.n, 2)),
                    jnp.float32)
    np.testing.assert_allclose(np.asarray(shr8.matmat(v)),
                               np.asarray(hss.matmat(v)),
                               rtol=1e-5, atol=1e-5)


def test_adaptive_accuracy_tracks_tolerance():
    """Tighter rtol => better reconstruction and larger detected ranks."""
    errs, sums = [], []
    for rtol in (1e-1, 1e-2, 1e-4):
        hss, xp, spec, _, _ = _build(rtol=rtol)
        k_dense = gaussian_block_xla(xp, xp, spec.h)
        errs.append(float(jnp.linalg.norm(hss.todense() - k_dense)
                          / jnp.linalg.norm(k_dense)))
        sums.append(shrink_to_fit(hss).stored_rank_sum())
    assert errs[0] > errs[2], errs
    assert sums[0] < sums[2], sums
    assert errs[2] < 5e-3, errs


# --------------------------------------------------------------------- #
# engine / trainers / grid search plumbing                              #
# --------------------------------------------------------------------- #
def test_engine_adaptive_matches_fixed_accuracy_with_smaller_ranks():
    xtr, ytr, xte, yte = synthetic.train_test(
        "circles", 2048, 512, seed=0, n_features=2, gap=0.8)
    kw = dict(spec=KernelSpec(h=1.5), leaf_size=128, max_it=10)
    eng_f = HSSSVMEngine(
        comp=compression.CompressionParams(rank=48, n_near=48, n_far=64),
        **kw)
    acc_f = float(jnp.mean(
        eng_f.fit(xtr, ytr, c_value=1.0).predict(jnp.asarray(xte)) == yte))
    eng_a = HSSSVMEngine(
        comp=compression.CompressionParams(rank=48, n_near=48, n_far=64,
                                           rtol=1e-4), **kw)
    acc_a = float(jnp.mean(
        eng_a.fit(xtr, ytr, c_value=1.0).predict(jnp.asarray(xte)) == yte))
    rep = eng_a.report
    assert rep.rank_sum_post < rep.rank_sum_pre, rep
    assert rep.ranks_post != rep.ranks_pre
    assert rep.kernel_evals and rep.kernel_evals > 0
    assert abs(acc_a - acc_f) <= 0.01, (acc_a, acc_f)
    # the factorization was built on the shrunk representation
    assert eng_a.fac.e_leaf.shape[-1] == rep.ranks_post[0]
    # fixed-rank engine reports pre == post
    rep_f = eng_f.report
    assert rep_f.rank_sum_pre == rep_f.rank_sum_post


def test_trainer_adaptive_prepare_shrinks():
    xtr, ytr, _, _ = synthetic.train_test(
        "blobs", 1024, 256, seed=0, n_features=2, sep=2.5)
    tr = HSSSVMTrainer(
        spec=KernelSpec(h=2.0),
        comp=compression.CompressionParams.accurate(), leaf_size=128,
        max_it=5)
    rep = tr.prepare(xtr, ytr)
    assert rep.rank_sum_post < rep.rank_sum_pre
    model, _ = tr.train(1.0)
    acc = float(jnp.mean(model.predict(jnp.asarray(xtr)) == ytr))
    assert acc > 0.9, acc


def test_grid_search_rtol_plumbing():
    """rtol reaches CompressionParams through the grid search kwargs."""
    xtr, ytr, xte, yte = synthetic.train_test(
        "blobs", 512, 128, seed=1, n_features=2, sep=2.5)
    model, info = grid_search(
        xtr, ytr, xte, yte, hs=[2.0], cs=[1.0],
        trainer_kwargs=dict(leaf_size=64, max_it=5,
                            comp=compression.CompressionParams(rank=32)),
        rtol=1e-2)
    assert model.spec.h == 2.0
    assert info["best_accuracy"] > 0.85


def test_multiclass_adaptive_shared_factorization():
    from repro.core.multiclass import MulticlassHSSSVMTrainer

    xtr, ytr, xte, yte = synthetic.train_test(
        "multiclass_blobs", 1024, 256, seed=0, n_classes=3, n_features=2,
        sep=4.0)
    tr = MulticlassHSSSVMTrainer(
        spec=KernelSpec(h=2.0),
        comp=compression.CompressionParams(rank=48, n_near=48, n_far=64,
                                           rtol=1e-4),
        leaf_size=128, max_it=10)
    model = tr.fit(xtr, ytr, c_value=1.0)
    assert tr.report.rank_sum_post < tr.report.rank_sum_pre
    acc = float(jnp.mean(model.predict(jnp.asarray(xte))
                         == jnp.asarray(yte)))
    assert acc > 0.9, acc


# --------------------------------------------------------------------- #
# slow tier: 8-device mesh                                              #
# --------------------------------------------------------------------- #
@pytest.mark.slow
def test_adaptive_sharded_build_8_devices():
    """Sharded adaptive build: same detected ranks as the local build,
    sharded rank vectors and shrunk arrays, shrunk-vs-full parity <= 1e-5
    under the mesh, sharded-vs-local agreement at O(rtol)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import compression, factorization, tree as tree_mod
        from repro.core.hss import shrink_to_fit
        from repro.core.kernelfn import KernelSpec
        from repro.dist import api as dist_api

        rng = np.random.default_rng(0)
        n, leaf = 4096, 64
        x = rng.normal(size=(n, 2)).astype(np.float32)
        t = tree_mod.build_tree(x, leaf_size=leaf)
        xp = x[t.perm]
        spec = KernelSpec(h=1.5)
        rtol = 1e-4
        params = compression.CompressionParams(
            rank=24, n_near=32, n_far=48, rtol=rtol)
        mesh = make_mesh((8,), ("data",))

        hss_ref = compression.compress(jnp.asarray(xp), t, spec, params)
        hss = compression.compress_sharded(xp, t, spec, params, mesh)
        assert hss.adaptive
        # identical per-node rank detection, rank vectors sharded
        assert (np.asarray(hss.leaf_ranks)
                == np.asarray(hss_ref.leaf_ranks)).all()
        assert hss.observed_ranks() == hss_ref.observed_ranks()
        assert not hss.leaf_ranks.sharding.is_fully_replicated

        shr = shrink_to_fit(hss, mesh=mesh)
        assert shr.ranks == hss.observed_ranks()
        ndev = 8
        for name in ("d_leaf", "u_leaf", "x"):
            a = getattr(shr, name)
            assert not a.sharding.is_fully_replicated, name
            assert a.addressable_shards[0].data.shape[0] == a.shape[0] // ndev

        fac = factorization.factorize_sharded(hss, 10.0, mesh)
        fac_s = factorization.factorize_sharded(shr, 10.0, mesh)
        assert fac_s.e_leaf.shape[-1] == shr.ranks[0]
        v = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
        with dist_api.use_mesh(mesh), mesh:
            mv = np.asarray(jax.jit(lambda h_, b: h_.matmat(b))(hss, v))
            mv_s = np.asarray(jax.jit(lambda h_, b: h_.matmat(b))(shr, v))
            out = np.asarray(jax.jit(lambda f, b: f.solve_mat(b))(fac, v))
            out_s = np.asarray(jax.jit(lambda f, b: f.solve_mat(b))(fac_s, v))
        rel_mv = np.linalg.norm(mv_s - mv) / np.linalg.norm(mv)
        rel_sv = np.linalg.norm(out_s - out) / np.linalg.norm(out)
        assert rel_mv <= 1e-5, rel_mv
        assert rel_sv <= 1e-5, rel_sv
        # sharded-vs-local: both builds truncate at rtol, so near-tie pivot
        # flips bound the difference by O(rtol), not float noise
        mv_ref = np.asarray(hss_ref.matmat(v))
        rel_ml = np.linalg.norm(mv - mv_ref) / np.linalg.norm(mv_ref)
        assert rel_ml <= rtol, rel_ml
        print("ADAPTIVE_SHARDED_OK", rel_mv, rel_sv, rel_ml)
    """)
    r = _run_sub(code)
    assert "ADAPTIVE_SHARDED_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_engine_adaptive_8_devices_matches_local():
    """Adaptive engine under an 8-device mesh: shrunk sharded build, same
    accuracy as the local adaptive engine, rank report populated."""
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
            "circles", 4096, 512, seed=0, n_features=2, gap=0.8)
        kw = dict(spec=KernelSpec(h=1.5),
                  comp=CompressionParams(rank=48, n_near=48, n_far=64,
                                         rtol=1e-4),
                  leaf_size=64, max_it=10, beta=100.0)

        eng0 = HSSSVMEngine(**kw)
        m0 = eng0.fit(xtr, ytr, c_value=1.0)
        acc0 = float(jnp.mean(m0.predict(jnp.asarray(xte)) == yte))
        mesh = make_mesh((8,), ("data",))
        eng8 = HSSSVMEngine(mesh=mesh, **kw)
        m8 = eng8.fit(xtr, ytr, c_value=1.0)
        acc8 = float(jnp.mean(m8.predict(jnp.asarray(xte)) == yte))

        rep = eng8.report
        assert rep.rank_sum_post < rep.rank_sum_pre, rep
        assert not eng8.hss.d_leaf.sharding.is_fully_replicated
        assert not m8.z_y.sharding.is_fully_replicated
        assert eng8.fac.e_leaf.shape[-1] == rep.ranks_post[0]
        assert abs(acc0 - acc8) <= 0.01, (acc0, acc8)
        print("ADAPTIVE_ENGINE_OK", acc0, acc8, rep.ranks_post)
    """)
    r = _run_sub(code)
    assert "ADAPTIVE_ENGINE_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_shrink_to_fit_sharding_matches_partition_spec_8_devices():
    """EVERY node-stacked field of the shrunk matrix must carry exactly the
    sharding node_partition_spec prescribes for its shape — including the
    2-D skeleton index arrays, whose post-slice device_put pins them
    replicated instead of leaking the gather's inferred output sharding."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding
        from repro.core import compression, tree as tree_mod
        from repro.core.hss import shrink_to_fit
        from repro.core.kernelfn import KernelSpec
        from repro.dist.api import node_partition_spec

        rng = np.random.default_rng(0)
        n, leaf = 4096, 64
        x = rng.normal(size=(n, 2)).astype(np.float32)
        t = tree_mod.build_tree(x, leaf_size=leaf)
        params = compression.CompressionParams(
            rank=24, n_near=32, n_far=48, rtol=1e-4)
        mesh = make_mesh((8,), ("data",))
        hss = compression.compress_sharded(
            x[t.perm], t, KernelSpec(h=1.5), params, mesh)
        shr = shrink_to_fit(hss, mesh=mesh)

        def want(a):
            return NamedSharding(
                mesh, node_partition_spec(mesh, a.ndim, a.shape[0]))

        checked = 0
        fields = dict(d_leaf=shr.d_leaf, u_leaf=shr.u_leaf,
                      skel_leaf=shr.skel_leaf)
        for k, a in enumerate(shr.transfers):
            fields[f"transfers[{k}]"] = a
        for k, a in enumerate(shr.skels):
            fields[f"skels[{k}]"] = a
        for k, a in enumerate(shr.b_mats):
            fields[f"b_mats[{k}]"] = a
        for name, a in fields.items():
            assert a.sharding.is_equivalent_to(want(a), a.ndim), (
                name, a.shape, a.sharding)
            checked += 1
        # the 2-D index arrays must have come out REPLICATED
        assert shr.skel_leaf.sharding.is_fully_replicated
        assert all(s.sharding.is_fully_replicated for s in shr.skels)
        print("SHRINK_SHARDING_OK", checked)
    """)
    r = _run_sub(code)
    assert "SHRINK_SHARDING_OK" in r.stdout, r.stdout + r.stderr
