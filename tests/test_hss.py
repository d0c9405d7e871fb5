import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, tree as tree_mod
from repro.core.kernelfn import KernelSpec, gaussian_block_xla
from tests.conftest import make_blobs


def _build(n=512, leaf=64, rank=32, h=1.0, seed=0, n_features=4,
           n_near=64, n_far=128):
    x, y = make_blobs(n, n_features=n_features, seed=seed)
    t = tree_mod.build_tree(x, leaf_size=leaf)
    xp = jnp.asarray(x[t.perm])
    spec = KernelSpec(h=h)
    params = compression.CompressionParams(
        rank=rank, n_near=n_near, n_far=n_far, seed=seed)
    hss = compression.compress(xp, t, spec, params)
    k_dense = gaussian_block_xla(xp, xp, h)
    return hss, k_dense, xp, spec


def test_dense_reconstruction_error_small():
    hss, k_dense, _, _ = _build()
    rec = hss.todense()
    err = float(jnp.linalg.norm(rec - k_dense) / jnp.linalg.norm(k_dense))
    assert err < 6e-2, err


def test_rank_increases_accuracy():
    errs = []
    for rank in (8, 24, 48):
        hss, k_dense, _, _ = _build(rank=rank)
        rec = hss.todense()
        errs.append(float(jnp.linalg.norm(rec - k_dense) / jnp.linalg.norm(k_dense)))
    assert errs[0] > errs[1] > errs[2] or errs[2] < 1e-3


def test_matvec_matches_todense():
    hss, _, _, _ = _build(n=256, leaf=32, rank=16)
    v = jnp.asarray(np.random.default_rng(0).normal(size=256), jnp.float32)
    dense = hss.todense()
    np.testing.assert_allclose(
        np.asarray(hss.matvec(v)), np.asarray(dense @ v), rtol=2e-4, atol=2e-4
    )


def test_matvec_against_exact_kernel():
    hss, k_dense, _, _ = _build()
    v = jnp.asarray(np.random.default_rng(1).normal(size=hss.n), jnp.float32)
    approx = hss.matvec(v)
    exact = k_dense @ v
    rel = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
    assert rel < 8e-2, rel


def test_matmat():
    hss, _, _, _ = _build(n=256, leaf=32, rank=16)
    v = jnp.asarray(np.random.default_rng(2).normal(size=(256, 3)), jnp.float32)
    out = hss.matmat(v)
    for j in range(3):
        np.testing.assert_allclose(
            np.asarray(out[:, j]), np.asarray(hss.matvec(v[:, j])),
            rtol=1e-4, atol=1e-4,
        )


def test_native_matmat_equals_columnwise_matvec():
    """Regression for the native multi-RHS telescoping sweep: the (N, k)
    matmat must match k column-wise matvecs to 1e-6."""
    hss, _, _, _ = _build(n=512, leaf=64, rank=24)
    v = jnp.asarray(np.random.default_rng(9).normal(size=(512, 5)), jnp.float32)
    out = hss.matmat(v)
    cols = jnp.stack([hss.matvec(v[:, j]) for j in range(5)], axis=1)
    # 2e-6 absolute: f32 reduction-order noise between the c=1 and c=k sweeps
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(cols), rtol=1e-6, atol=2e-6)


def test_shifted_adds_identity():
    hss, _, _, _ = _build(n=256, leaf=32, rank=16)
    v = jnp.ones(256, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(hss.shifted(3.0).matvec(v)),
        np.asarray(hss.matvec(v) + 3.0 * v),
        rtol=1e-5,
    )


def test_symmetry_of_reconstruction():
    hss, _, _, _ = _build(n=256, leaf=32, rank=16)
    d = np.asarray(hss.todense())
    np.testing.assert_allclose(d, d.T, atol=1e-5)


def test_memory_linear_in_n():
    hss_small, _, _, _ = _build(n=256, leaf=32, rank=16)
    hss_big, _, _, _ = _build(n=1024, leaf=32, rank=16)
    ratio = hss_big.memory_bytes() / hss_small.memory_bytes()
    assert ratio < 5.0  # O(N r): 4x data -> ~4x memory, NOT 16x (dense)


def test_compression_error_probe():
    hss, k_dense, xp, spec = _build()
    err = float(compression.compression_error(hss, spec, n_probe=4))
    assert err < 8e-2


@pytest.mark.parametrize("on_device", [False, True], ids=["host", "device"])
def test_leaf_near_deficit_topup_has_no_duplicates(on_device):
    """Regression: on tiny problems the neighbour candidate pool runs short
    and the deficit top-up used to sample the sibling leaf WITH possible
    repeats of already-placed candidates — duplicate NEAR proxies waste ID
    sample budget.  Each row must now be duplicate-free whenever the leaf's
    complement has at least n_near points, and never contain in-leaf points,
    whichever search (host KD-tree or device k-NN) fed the pool."""
    for seed in range(5):
        rng = np.random.default_rng(seed)
        m, levels = 8, 2                       # n = 32, n_near = 8
        n = m * 2 ** levels
        x = rng.normal(size=(n, 2)).astype(np.float32)
        t = tree_mod.build_tree(x, leaf_size=m)
        params = compression.CompressionParams(rank=4, n_near=8, n_far=4,
                                               seed=seed)
        xp = x[t.perm]
        near = compression._host_leaf_near(
            t, params, xp, x_device=jnp.asarray(xp) if on_device else None)
        assert near.shape == (2 ** levels, params.n_near)
        leaf_of = np.arange(n) // m
        for i in range(near.shape[0]):
            row = near[i]
            assert len(np.unique(row)) == len(row), (seed, i, row)
            assert not np.any(leaf_of[row] == i), (seed, i, row)


def test_leaf_near_data_free_fallback_shapes():
    """The data-free (x=None) fallback keeps its sibling-sampling contract."""
    rng = np.random.default_rng(0)
    m, levels = 16, 2
    x = rng.normal(size=(m * 2 ** levels, 3)).astype(np.float32)
    t = tree_mod.build_tree(x, leaf_size=m)
    params = compression.CompressionParams(rank=8, n_near=8, n_far=8)
    near = compression._host_leaf_near(t, params, None)
    for i in range(near.shape[0]):
        sib = i ^ 1
        assert np.all((near[i] >= sib * m) & (near[i] < (sib + 1) * m))


def _knn_rows(case):
    """(x, leaf size, n_near) of the neighbour-search exactness cases."""
    if case == "susy-2^14":
        from bench.data import susy
        x, _ = susy.generate(2 ** 14, (3141592653, 0))
        return x, 256, 48
    if case == "blobs-512":
        return make_blobs(512, n_features=4, seed=0)[0], 64, 64
    if case == "tiny-32":
        return np.random.default_rng(3).normal(
            size=(32, 2)).astype(np.float32), 8, 8
    x = make_blobs(1024, n_features=18, seed=1)[0]    # bf16-1024
    return np.asarray(x.astype(jnp.bfloat16)), 128, 32


KNN_CASES = ["susy-2^14", "blobs-512", "tiny-32", "bf16-1024"]


@pytest.mark.parametrize("case", KNN_CASES)
def test_device_knn_matches_the_kdtree_row_for_row(case):
    """The device k-NN finds the KD-tree's exact neighbour set on every
    row (the KD-tree ranks in f64; the device in f32-exact products)."""
    from scipy.spatial import cKDTree

    x, m, n_near = _knn_rows(case)
    x32 = np.asarray(x, np.float32)
    k = min(max(2 * n_near // m + 4, 4), len(x))
    _, want = cKDTree(x32).query(x32, k=k)
    got = np.asarray(compression._device_knn(
        jnp.asarray(x), k=k, block=compression._knn_block(len(x))))
    assert got.shape == (len(x), k) and got.dtype == np.int32
    np.testing.assert_array_equal(np.sort(got, axis=1),
                                  np.sort(want, axis=1))


@pytest.mark.parametrize("case", KNN_CASES)
def test_device_leaf_near_equals_the_kdtree_leaf_near(case):
    x, m, n_near = _knn_rows(case)
    t = tree_mod.build_tree(np.asarray(x, np.float32), leaf_size=m)
    xp = x[t.perm]
    params = compression.CompressionParams(rank=8, n_near=n_near, n_far=8)
    host = compression._host_leaf_near(t, params, xp)
    device = compression._host_leaf_near(t, params, xp,
                                         x_device=jnp.asarray(xp))
    np.testing.assert_array_equal(device, host)


def test_compress_skeletons_equal_a_kdtree_fed_build(monkeypatch):
    """``compress`` searches on the device; fed the KD-tree's neighbours
    instead it evaluates the same kernel entries and picks the same
    skeletons."""
    x, _ = make_blobs(512, n_features=6, seed=2)
    t = tree_mod.build_tree(x, leaf_size=32)
    xp = x[t.perm]
    spec = KernelSpec(h=1.0)
    params = compression.CompressionParams(rank=16, n_near=32, n_far=32)

    def build():
        with compression.counting_kernel_evals() as evals:
            hss = compression.compress(xp, t, spec, params)
        return hss, evals["count"]

    dev, dev_evals = build()
    monkeypatch.setattr(
        compression, "_device_knn",
        lambda xd, *, k, block: compression._kdtree_query(
            np.asarray(xd, np.float32), k))
    kdt, kdt_evals = build()
    assert dev_evals == kdt_evals > 0
    np.testing.assert_array_equal(dev.skel_leaf, kdt.skel_leaf)
    for a, b in zip(dev.skels, kdt.skels, strict=True):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("rank,n_near,n_far", [(113, 48, 64), (128, 48, 64),
                                               (33, 16, 16)])
def test_a_rank_above_the_proxy_columns_is_refused(rank, n_near, n_far):
    # the leaf ID chooses its rank rows from n_near + n_far sampled columns
    with pytest.raises(ValueError, match="proxy columns"):
        compression.CompressionParams(rank=rank, n_near=n_near, n_far=n_far)


def test_the_launch_driver_refuses_a_rank_above_its_proxies():
    from repro.launch.train import build_svm_engine

    assert build_svm_engine("svm", 0.5, 112, 256).comp.rank == 112
    with pytest.raises(ValueError, match="rank 128 exceeds the 112 proxy"):
        build_svm_engine("svm", 0.5, 128, 256)


@pytest.mark.parametrize("ranks", [(12, 12, 12), (12, 5, 9)],
                         ids=["full", "adaptive"])
def test_kernel_interp_leaves_a_psd_residual_and_zero_dead_slots(ranks):
    """T = K(C, S) (K(S, S) + shift)^-1 from each node's live skeletons S:
    K(C, C) - T K(S, S) T^T is positive semidefinite (what keeps the
    levels it is used at PSD), skeleton rows interpolate themselves, and
    dead slots get exact zeros."""
    from repro.core import compression as comp
    from repro.core.kernelfn import KernelSpec, kernel_block

    spec = KernelSpec(h=1.0)
    r = np.random.default_rng(4)
    xc = jnp.asarray(r.normal(size=(3, 24, 5)), jnp.float32)
    xp = jnp.asarray(r.normal(size=(3, 40, 5)), jnp.float32)
    piv, _, _ = comp._batched_row_id(spec, xc, xp, 12, None, False)
    rk = jnp.asarray(ranks, jnp.int32)
    t = np.asarray(comp._kernel_interp(spec, xc, piv, rk, None))
    for i, live in enumerate(ranks):
        kcc = np.asarray(kernel_block(spec, xc[i], xc[i]), np.float64)
        s = np.asarray(piv[i][:live])
        res = kcc - t[i][:, :live] @ kcc[np.ix_(s, s)] @ t[i][:, :live].T
        assert np.linalg.eigvalsh(res).min() > -1e-4
        np.testing.assert_allclose(t[i][s, :live], np.eye(live), atol=1e-3)
        assert not t[i][:, live:].any()


def test_large_nodes_take_the_kernel_interp_transfers(monkeypatch):
    """From ``KERNEL_INTERP_ROWS`` rows up an internal node's transfer is
    ``_kernel_interp``'s; below it, the ID's."""
    from repro.core import compression as comp
    from repro.core.kernelfn import KernelSpec

    r = np.random.default_rng(5)
    x = r.normal(size=(512, 3)).astype(np.float32)
    t = tree_mod.build_tree(x, leaf_size=32)              # 4 internal levels
    xp = jnp.asarray(x[t.perm])
    spec, params = KernelSpec(h=1.0), comp.CompressionParams(
        rank=8, n_near=8, n_far=8)
    ids = comp.compress(xp, t, spec, params)
    monkeypatch.setattr(comp, "KERNEL_INTERP_ROWS", 128)  # levels 2 and 3
    mixed = comp.compress(xp, t, spec, params)
    np.testing.assert_array_equal(np.asarray(ids.transfers[0]),
                                  np.asarray(mixed.transfers[0]))
    for k in (1, 2):
        # the same skeletons, other transfers
        np.testing.assert_array_equal(np.asarray(ids.skels[k]),
                                      np.asarray(mixed.skels[k]))
        assert not np.allclose(np.asarray(ids.transfers[k]),
                               np.asarray(mixed.transfers[k]), atol=1e-3)
    v = jnp.asarray(r.normal(size=(512, 2)), jnp.float32)
    exact = np.asarray(gaussian_block_xla(xp, xp, 1.0) @ v)
    err = [np.linalg.norm(np.asarray(h.matmat(v)) - exact)
           for h in (ids, mixed)]
    assert err[1] < 1.5 * err[0]
