"""Kernel linear-algebra task family: KRR / GP mean / Lanczos eigenpairs.

Golden tier: KRR on noisy-sine recovers the noise floor with ZERO ADMM
iterations and matches the dense (K̃+λI)⁻¹y solve to 1e-5 at the accurate
tolerance; the Hutchinson GP log marginal tracks the dense logdet and ranks
the true noise level first; Lanczos top-k eigenpairs match a dense eigh of
the SAME compressed operator.

Property tier: Lanczos Ritz residuals ‖K̃v−θv‖ stay small over randomized
trees/bandwidths, and the KRR solve residual tracks the factorization
tolerance across λ.

Precision/transfer pins for the satellites ride along: the streamed
scoring matvec keeps f32 accumulation under bf16 inputs (numeric pin + raw
jaxpr probe), and ``observed_ranks()`` costs exactly ONE host transfer.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, factorization, tree as tree_mod
from repro.core.compression import CompressionParams
from repro.core.engine import HSSSVMEngine, grid_search
from repro.core.kernelfn import KernelSpec, kernel_matvec_streamed
from repro.core.krr import gp_log_marginal, krr_solve
from repro.core.lanczos import lanczos, top_eigenpairs, tridiag_eigh
from repro.data import synthetic
from tests import proptest as pt

COMP = CompressionParams(rank=32, n_near=48, n_far=64)
COMP_ACC = CompressionParams(rank=48, n_near=48, n_far=64, rtol=1e-4)


def _dense_operator(hss):
    """K̃ (+pads) as a dense array — the operator the solves/Lanczos see."""
    return np.asarray(hss.matmat(jnp.eye(hss.n, dtype=jnp.float32)))


# --------------------------------------------------------------------- #
# golden: KRR                                                           #
# --------------------------------------------------------------------- #
def test_golden_krr_noise_floor_zero_admm_iterations():
    """KRR must hit the 0.1 noise floor with iters_run pinned at 0."""
    xtr, ytr, xte, yte = synthetic.train_test("noisy_sine", 1024, 256,
                                              seed=0, noise=0.1)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=128,
                          task="krr")
    engine.prepare(xtr, ytr)
    model, _ = engine.train(0.5)
    assert engine.report.iters_run == (0,)        # no ADMM ever ran
    pred = np.asarray(model.predict(jnp.asarray(xte)))
    rmse = float(np.sqrt(np.mean((pred - yte) ** 2)))
    assert rmse < 0.12, rmse                      # measured 0.0977


def test_krr_matches_dense_solve_at_accurate_tolerance():
    """α from the HSS path vs dense (K̃+λI)⁻¹y on the same operator."""
    xtr, ytr, _, _ = synthetic.train_test("noisy_sine", 1024, 128,
                                          seed=0, noise=0.1)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP_ACC,
                          leaf_size=128, task="krr")
    engine.prepare(xtr, ytr)
    lam = 8.0
    model, _ = engine.train(lam)
    alpha = np.asarray(jax.device_get(model.z_y))[:, 0]
    kt = _dense_operator(engine._hss)
    y = np.asarray(jax.device_get(engine._ys))[0]
    ref = np.linalg.solve(kt + lam * np.eye(kt.shape[0]), y)
    rel = np.linalg.norm(alpha - ref) / np.linalg.norm(ref)
    assert rel <= 1e-5, rel                       # measured 6.1e-6


def test_krr_rejects_nonpositive_lambda():
    xtr, ytr, _, _ = synthetic.train_test("noisy_sine", 256, 64, seed=0)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=64,
                          task="krr")
    engine.prepare(xtr, ytr)
    with pytest.raises(ValueError):
        engine.train(0.0)


# --------------------------------------------------------------------- #
# golden: GP log marginal                                               #
# --------------------------------------------------------------------- #
def test_gp_log_marginal_tracks_dense():
    """Hutchinson+Lanczos log p(y) vs the dense slogdet reference on the
    real (pad-masked) block."""
    xtr, ytr, _, _ = synthetic.train_test("noisy_sine", 512, 64,
                                          seed=0, noise=0.1)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP_ACC,
                          leaf_size=64, task="gp")
    engine.prepare(xtr, ytr)
    lam = 0.5
    engine.train(lam)
    lml = engine.log_marginal(lam, n_probes=8, num_iters=30)

    kt = _dense_operator(engine._hss)
    mask = np.asarray(jax.device_get(engine._pmask))[0] > 0
    kr = kt[np.ix_(mask, mask)]
    y = np.asarray(jax.device_get(engine._ys))[0][mask]
    n = kr.shape[0]
    a = kr + lam * np.eye(n)
    _, logdet = np.linalg.slogdet(a)
    ref = (-0.5 * y @ np.linalg.solve(a, y) - 0.5 * logdet
           - 0.5 * n * math.log(2 * math.pi))
    rel = abs(lml - ref) / abs(ref)
    assert rel < 0.1, (lml, ref)                  # measured 0.025


def test_gp_evidence_ranks_true_noise_first():
    """log p(y) must prefer λ near the generating noise variance (0.1² =
    0.01) over a 100x-too-large λ — the model-selection property the GP
    grid driver relies on."""
    xtr, ytr, _, _ = synthetic.train_test("noisy_sine", 512, 64,
                                          seed=0, noise=0.1)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP_ACC,
                          leaf_size=64, task="gp")
    engine.prepare(xtr, ytr)
    lmls = {}
    for lam in (0.01, 1.0):
        engine.train(lam)
        lmls[lam] = engine.log_marginal(lam, n_probes=4, num_iters=25)
    assert lmls[0.01] > lmls[1.0], lmls


@pytest.mark.parametrize("task", ["krr", "gp"])
def test_grid_search_scores_each_regression_task(monkeypatch, task):
    """The one grid driver: one compression per h for the whole λ sweep;
    KRR scored by negated holdout RMSE, GP by the training log marginal
    with no validation split (λ near the noise variance 0.01 wins)."""
    calls = {"compress": 0}
    real_compress = compression.compress

    def counting_compress(*a, **k):
        calls["compress"] += 1
        return real_compress(*a, **k)

    monkeypatch.setattr(compression, "compress", counting_compress)
    xtr, ytr, xte, yte = synthetic.train_test("noisy_sine", 512, 128,
                                              seed=0, noise=0.1)
    gp = task == "gp"
    model, info = grid_search(
        xtr, ytr, None if gp else xte, None if gp else yte, hs=[1.0],
        knobs=[0.01, 1.0], task=task,
        trainer_kwargs=dict(comp=COMP_ACC, leaf_size=64))
    assert calls["compress"] == 1, calls
    assert set(info["results"]) == {(1.0, 0.01), (1.0, 1.0)}
    assert info["best_h"] == 1.0
    if gp:
        assert info["best_lam"] == 0.01, info["results"]
        assert info["best_log_marginal"] == max(
            r["log_marginal"] for r in info["results"].values())
    else:
        pred = np.asarray(model.predict(jnp.asarray(xte)))
        rmse = float(np.sqrt(np.mean((pred - yte) ** 2)))
        assert -info["best_accuracy"] == pytest.approx(rmse, rel=1e-6)
        assert rmse < 0.15, rmse


# --------------------------------------------------------------------- #
# golden: Lanczos eigenpairs                                            #
# --------------------------------------------------------------------- #
def test_lanczos_top_eigenpairs_match_dense_eigh():
    xtr, ytr, _, _ = synthetic.train_test("noisy_sine", 512, 64, seed=0)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP_ACC,
                          leaf_size=64, task="krr")
    engine.prepare(xtr, ytr)
    k = 4
    evals, vecs = engine.top_eigenpairs(k)
    evals = np.asarray(jax.device_get(evals))
    vecs = np.asarray(jax.device_get(vecs))
    kt = _dense_operator(engine._hss)
    ref = np.linalg.eigvalsh(kt)[::-1][:k]
    np.testing.assert_allclose(evals, ref, rtol=1e-3)
    # Ritz residuals: K̃v = θv to a scale-relative tolerance
    for i in range(k):
        res = np.linalg.norm(kt @ vecs[:, i] - evals[i] * vecs[:, i])
        assert res <= 1e-3 * evals[0], (i, res)
    # descending order and normalized vectors
    assert np.all(np.diff(evals) <= 0)
    np.testing.assert_allclose(np.linalg.norm(vecs, axis=0), 1.0, atol=1e-4)


def test_spectral_embed_unmaps_to_input_order():
    """Embedding rows must line up with the INPUT point order (the engine
    stores permuted+padded points internally)."""
    xtr, ytr, _, _ = synthetic.train_test("noisy_sine", 300, 64, seed=0)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=64,
                          task="krr")
    engine.prepare(xtr, ytr)                      # 300 pads to 512
    k = 3
    emb = engine.spectral_embed(k)
    assert emb.shape == (300, k)
    evals, vecs = engine.top_eigenpairs(k)
    vecs = np.asarray(jax.device_get(vecs))
    scaled = vecs * np.sqrt(np.maximum(np.asarray(jax.device_get(evals)), 0))
    perm = engine._perm_host
    real = perm < 300
    np.testing.assert_allclose(emb[perm[real]], scaled[real], atol=1e-6)


def test_top_eigenpairs_validates_k():
    xtr, ytr, _, _ = synthetic.train_test("noisy_sine", 256, 64, seed=0)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=64,
                          task="krr")
    engine.prepare(xtr, ytr)
    with pytest.raises(ValueError):
        engine.top_eigenpairs(0)


# --------------------------------------------------------------------- #
# property: Lanczos residuals + solve residual over random trees        #
# --------------------------------------------------------------------- #
def _random_hss(case, rank=24, rtol=None):
    n = case["leaf"] * 2 ** case["depth"]
    rng = np.random.default_rng(case["data_seed"])
    x = rng.normal(size=(n, 3)).astype(np.float32)
    t = tree_mod.build_tree(x, leaf_size=case["leaf"], levels=case["depth"])
    xp = jnp.asarray(x[t.perm])
    return compression.compress(
        xp, t, KernelSpec(h=case["h"]),
        CompressionParams(rank=rank, n_near=32, n_far=48, rtol=rtol))


def test_property_lanczos_ritz_residuals_random_trees():
    """‖K̃v − θv‖ ≤ tol·θ_max for every returned Ritz pair, across random
    tree depths, leaf sizes and bandwidths."""
    for case in pt.Cases(n_cases=5, seed=11).draw(dict(
            leaf=pt.choice(32, 64),
            depth=pt.ints(1, 3),
            h=pt.floats(0.5, 4.0, log=True),
            data_seed=pt.ints(0, 1000))):
        hss = _random_hss(case)
        k = 3
        evals, vecs = top_eigenpairs(hss, k, seed=0)
        kt = np.asarray(hss.matmat(jnp.eye(hss.n, dtype=jnp.float32)))
        evals = np.asarray(evals)
        vecs = np.asarray(vecs)
        for i in range(k):
            res = np.linalg.norm(kt @ vecs[:, i] - evals[i] * vecs[:, i])
            assert res <= 5e-3 * max(evals[0], 1.0), (case, i, res)


def test_property_krr_solve_residual_tracks_factorization():
    """‖(K̃+λI)α − y‖/‖y‖ stays at factorization accuracy across sampled
    (λ, tree) — the multi-RHS path inherits the solver's tolerance."""
    for case in pt.Cases(n_cases=5, seed=12).draw(dict(
            leaf=pt.choice(32, 64),
            depth=pt.ints(1, 3),
            h=pt.floats(0.5, 4.0, log=True),
            lam=pt.floats(0.5, 50.0, log=True),
            data_seed=pt.ints(0, 1000))):
        hss = _random_hss(case)
        rng = np.random.default_rng(case["data_seed"] + 1)
        y = jnp.asarray(rng.normal(size=(hss.n, 2)), jnp.float32)
        fac = factorization.factorize(hss, float(case["lam"]))
        alpha = krr_solve(fac, y)
        resid = hss.matmat(alpha) + case["lam"] * alpha - y
        rel = float(jnp.linalg.norm(resid) / jnp.linalg.norm(y))
        assert rel < 1e-3, (case, rel)


def test_property_gp_log_marginal_finite_random_trees():
    for case in pt.Cases(n_cases=3, seed=13).draw(dict(
            h=pt.floats(0.5, 4.0, log=True),
            lam=pt.floats(0.1, 10.0, log=True),
            data_seed=pt.ints(0, 1000))):
        case = dict(case, leaf=32, depth=2)
        hss = _random_hss(case)
        rng = np.random.default_rng(case["data_seed"] + 1)
        y = jnp.asarray(rng.normal(size=hss.n), jnp.float32)
        fac = factorization.factorize(hss, float(case["lam"]))
        lml = gp_log_marginal(hss, fac, y, n_probes=2, num_iters=15)
        assert np.isfinite(lml), case


def test_lanczos_tridiagonal_matches_operator_projection():
    """T = Vᵀ K̃ V on the built Krylov basis (the Rayleigh-Ritz identity
    full reorthogonalization is supposed to preserve)."""
    case = dict(leaf=32, depth=2, h=1.5, data_seed=7)
    hss = _random_hss(case)
    m = 12
    v0 = jax.random.normal(jax.random.PRNGKey(0), (hss.n,), jnp.float32)
    alphas, betas, basis = lanczos(hss.matvec, v0, m)
    alphas, betas = np.asarray(alphas), np.asarray(betas)
    v = np.asarray(basis)[:m].T                       # (n, m)
    kt = np.asarray(hss.matmat(jnp.eye(hss.n, dtype=jnp.float32)))
    t_full = v.T @ kt @ v
    t_ref = np.diag(alphas) + np.diag(betas[:-1], 1) + np.diag(betas[:-1], -1)
    np.testing.assert_allclose(t_full, t_ref, atol=5e-3)
    theta, _ = tridiag_eigh(jnp.asarray(alphas), jnp.asarray(betas[:-1]))
    np.testing.assert_allclose(np.asarray(theta), np.linalg.eigvalsh(t_ref),
                               atol=5e-3)


# --------------------------------------------------------------------- #
# satellite pins: bf16 scoring accumulation + single-transfer ranks     #
# --------------------------------------------------------------------- #
def test_streamed_matvec_bf16_inputs_accumulate_f32():
    """bf16 queries/support/coefficients must produce an f32 result that
    stays within bf16 INPUT rounding of the all-f32 path — the pin that
    fails if the contraction itself accumulates in bf16."""
    rng = np.random.default_rng(0)
    spec = KernelSpec(h=1.0)
    xq = jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(256, 4)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(256, 2)), jnp.float32)
    ref = kernel_matvec_streamed(spec, xq, xs, v, block=64)
    out = kernel_matvec_streamed(spec, xq.astype(jnp.bfloat16),
                                 xs.astype(jnp.bfloat16),
                                 v.astype(jnp.bfloat16), block=64)
    assert out.dtype == jnp.float32
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    assert rel < 2e-2, rel


def test_streamed_matvec_bf16_jaxpr_has_no_bf16_contractions():
    """Raw jaxpr probe: every dot_general in the streamed scoring matvec
    must land in f32 even when every INPUT is bf16."""
    from repro.analysis.jaxpr_check import dtype_downcasts

    spec = KernelSpec(h=1.0)
    xq = jnp.zeros((32, 4), jnp.bfloat16)
    xs = jnp.zeros((64, 4), jnp.bfloat16)
    v = jnp.zeros((64, 3), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda a, c, w: kernel_matvec_streamed(spec, a, c, w, block=32))(
            xq, xs, v)
    assert dtype_downcasts(jaxpr) == []


def test_observed_ranks_single_host_transfer(monkeypatch):
    """Adaptive observed_ranks() must batch ALL rank vectors into ONE
    jax.device_get — the per-level version serialized K+1 round-trips on
    every shrink_report."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(512, 2)).astype(np.float32)
    t = tree_mod.build_tree(x, leaf_size=64)
    hss = compression.compress(
        jnp.asarray(x[t.perm]), t, KernelSpec(h=1.5),
        CompressionParams(rank=24, n_near=32, n_far=48, rtol=1e-2))
    assert hss.adaptive
    calls = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda tree: calls.append(1) or real_get(tree))
    obs = hss.observed_ranks()
    assert len(calls) == 1, len(calls)
    assert len(obs) == len(hss.ranks)
    assert all(isinstance(r, int) for r in obs)
