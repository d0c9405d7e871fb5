import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, factorization, tree as tree_mod
from repro.core.kernelfn import KernelSpec
from tests.conftest import make_blobs


def _hss(n=512, leaf=64, rank=24, h=1.0, seed=0):
    x, _ = make_blobs(n, seed=seed)
    t = tree_mod.build_tree(x, leaf_size=leaf)
    xp = jnp.asarray(x[t.perm])
    spec = KernelSpec(h=h)
    hss = compression.compress(
        xp, t, spec, compression.CompressionParams(rank=rank, n_near=32, n_far=32)
    )
    return hss


@pytest.mark.parametrize("beta", [1.0, 10.0, 100.0])
def test_solve_matches_dense(beta):
    hss = _hss()
    fac = factorization.factorize(hss, beta)
    dense = hss.todense() + beta * jnp.eye(hss.n)
    b = jnp.asarray(np.random.default_rng(0).normal(size=hss.n), jnp.float32)
    x_hss = fac.solve(b)
    x_dense = jnp.linalg.solve(dense, b)
    rel = float(jnp.linalg.norm(x_hss - x_dense) / jnp.linalg.norm(x_dense))
    assert rel < 1e-3, rel


def test_solve_is_inverse_of_matvec():
    hss = _hss(n=256, leaf=32, rank=16)
    beta = 10.0
    fac = factorization.factorize(hss, beta)
    b = jnp.asarray(np.random.default_rng(1).normal(size=hss.n), jnp.float32)
    x = fac.solve(b)
    b_back = hss.matvec(x) + beta * x
    rel = float(jnp.linalg.norm(b_back - b) / jnp.linalg.norm(b))
    assert rel < 1e-3, rel


def test_solve_mat_multiple_rhs():
    hss = _hss(n=256, leaf=32, rank=16)
    fac = factorization.factorize(hss, 5.0)
    b = jnp.asarray(np.random.default_rng(2).normal(size=(256, 3)), jnp.float32)
    xs = fac.solve_mat(b)
    for j in range(3):
        np.testing.assert_allclose(
            np.asarray(xs[:, j]), np.asarray(fac.solve(b[:, j])),
            rtol=1e-4, atol=1e-5,
        )


def test_native_multi_rhs_equals_columnwise_single_rhs():
    """Regression for the native (N, k) block sweep: the multi-RHS solve must
    reproduce k column-wise single-RHS solves to 1e-6 (the vmap path it
    replaced was exact column-wise by construction)."""
    hss = _hss(n=512, leaf=64, rank=24)
    for beta in (1.0, 100.0):
        fac = factorization.factorize(hss, beta)
        b = jnp.asarray(
            np.random.default_rng(7).normal(size=(512, 6)), jnp.float32)
        block = factorization.hss_solve_mat(fac, b)
        cols = jnp.stack(
            [factorization.hss_solve(fac, b[:, j]) for j in range(6)], axis=1)
        np.testing.assert_allclose(
            np.asarray(block), np.asarray(cols), rtol=1e-6, atol=1e-6)


def test_two_level_tree():
    # K = 1: only leaves + root coupling — exercises the boundary case.
    hss = _hss(n=128, leaf=64, rank=24)
    assert hss.levels == 1
    fac = factorization.factorize(hss, 2.0)
    dense = hss.todense() + 2.0 * jnp.eye(128)
    b = jnp.ones(128, jnp.float32)
    rel = float(
        jnp.linalg.norm(fac.solve(b) - jnp.linalg.solve(dense, b))
        / jnp.linalg.norm(jnp.linalg.solve(dense, b))
    )
    assert rel < 1e-3


def test_factorize_jits_and_caches():
    """factorize + solve must be jittable (the paper's ADMM loop requirement)."""
    hss = _hss(n=256, leaf=32, rank=16)
    fac = factorization.factorize(hss, 7.0)

    @jax.jit
    def solve(b):
        return fac.solve(b)

    b = jnp.ones(256, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(solve(b)), np.asarray(fac.solve(b)), rtol=1e-5
    )


def test_woodbury_identity_lemma():
    """The Gillman–Martinsson inversion lemma on random SPD data."""
    rng = np.random.default_rng(3)
    m, r = 24, 6
    d = rng.normal(size=(m, m))
    d = jnp.asarray(d @ d.T + m * np.eye(m), jnp.float32)
    u = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
    a_tilde = rng.normal(size=(r, r))
    a_tilde = jnp.asarray(a_tilde + a_tilde.T, jnp.float32)
    a_full = d + u @ a_tilde @ u.T

    dinv = jnp.linalg.inv(d)
    d_hat = jnp.linalg.inv(u.T @ dinv @ u)
    e = dinv @ u @ d_hat
    g = dinv - e @ (dinv @ u).T
    a_inv_lemma = g + e @ jnp.linalg.inv(a_tilde + d_hat) @ e.T
    np.testing.assert_allclose(
        np.asarray(a_inv_lemma), np.asarray(jnp.linalg.inv(a_full)),
        rtol=5e-3, atol=5e-4,
    )


@pytest.mark.parametrize("beta", [10.0, 100.0])
def test_bf16_storage_solve_accuracy(beta):
    """store_dtype='bfloat16' must stay within bf16 rounding of the f32
    solve: every per-level einsum pins preferred_element_type=float32, so
    the only error source is factor STORAGE rounding (~1e-2), never bf16
    accumulation (which would be ~1e-1 at these depths).  Regression for
    the mixed-precision accumulation contract."""
    hss = _hss(n=1024, leaf=64, rank=24)
    fac32 = factorization.factorize(hss, beta)
    fac16 = factorization.factorize(hss, beta, store_dtype="bfloat16")
    assert fac16.e_leaf.dtype == jnp.bfloat16
    assert fac16.root_lu.dtype == jnp.float32    # root stays f32
    b = jnp.asarray(
        np.random.default_rng(0).normal(size=(hss.n, 3)), jnp.float32)
    x32 = fac32.solve_mat(b)
    x16 = fac16.solve_mat(b)
    assert x16.dtype == jnp.float32              # f32 accumulation contract
    rel = float(jnp.linalg.norm(x16 - x32) / jnp.linalg.norm(x32))
    assert rel < 1e-2, rel                       # measured ~3.3e-3


def test_bf16_inputs_bias_extraction_accuracy():
    """compute_bias_batched keeps f32 accumulation when its inputs arrive
    bf16: the bias einsums pin preferred_element_type=float32, so the only
    error vs the f32 path is INPUT rounding (~1e-2), never the ~1e-1 drift
    of a bf16 accumulator over d≈1000 terms.  Pins the core/tasks.py rule;
    the jaxpr assertion proves no contraction anywhere in the bias graph
    accumulates below f32."""
    from repro.analysis import jaxpr_check
    from repro.core.tasks import compute_bias_batched

    hss = _hss(n=1024, leaf=64, rank=24)
    d = hss.n
    rng = np.random.default_rng(5)
    ys = jnp.asarray(np.sign(rng.normal(size=(d, 1))), jnp.float32)
    z = jnp.asarray(rng.uniform(0.05, 0.95, size=(d, 1)), jnp.float32)
    ones = jnp.ones((d, 1), jnp.float32)
    b32 = compute_bias_batched(hss, ys, z, ones, ones)

    ys16, z16 = ys.astype(jnp.bfloat16), z.astype(jnp.bfloat16)
    ones16 = ones.astype(jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda y_, z_, m_: compute_bias_batched(
        hss, y_, z_, m_, m_))(ys16, z16, ones16)
    assert jaxpr_check.dtype_downcasts(jaxpr) == []
    b16 = compute_bias_batched(hss, ys16, z16, ones16, ones16)
    rel = float(jnp.abs(b16 - b32)[0] / jnp.maximum(jnp.abs(b32)[0], 1e-6))
    assert rel < 5e-2, rel


def test_bf16_storage_admm_no_downcast_and_accuracy():
    """The full ADMM graph (solve + equality projection + box clamp) over a
    bf16-STORED factorization: (a) its jaxpr contains no low-precision
    dot_general accumulator — pins the core/admm.py eq-projection fix and
    the solve chain together; (b) the iterates stay within bf16 storage
    rounding of the f32 run."""
    from repro.analysis import jaxpr_check
    from repro.core import admm as admm_mod

    hss = _hss(n=512, leaf=64, rank=24)
    fac32 = factorization.factorize(hss, 10.0)
    fac16 = factorization.factorize(hss, 10.0, store_dtype="bfloat16")
    rng = np.random.default_rng(4)
    ys = jnp.asarray(np.sign(rng.normal(size=(1, 512))), jnp.float32)
    cbox = jnp.ones((1, 512), jnp.float32)

    def run(fac):
        task = admm_mod.svm_task(ys, cbox)
        state, _ = admm_mod.admm_boxqp(fac.solve_mat, task, fac.beta, 8)
        return state.z

    jaxpr = jax.make_jaxpr(lambda f_: run(f_))(fac16)
    assert jaxpr_check.dtype_downcasts(jaxpr) == [], \
        jaxpr_check.dtype_downcasts(jaxpr)
    z32, z16 = run(fac32), run(fac16)
    rel = float(jnp.linalg.norm(z16 - z32) / jnp.linalg.norm(z32))
    assert rel < 2e-2, rel
