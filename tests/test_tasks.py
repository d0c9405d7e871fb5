"""Box-QP task layer tests: the generic ADMM refactor + ε-SVR + one-class.

Load-bearing assertions (ISSUE acceptance):
  * EXACT equivalence (≤ 1e-12, in practice bit-identical) of the
    refactored generic path against a verbatim copy of the pre-refactor
    ``admm_svm`` loop — the tentpole refactor cannot silently change
    binary-SVM numerics;
  * SVR and one-class train end-to-end through HSSSVMEngine on ONE shared
    HSS compression + factorization per (h, β), proven by call counting
    across the warm-started knob sweeps;
  * the residual stopping rule freezes iterates EXACTLY at the stopping
    iteration and reports iters_run;
  * slow tier: 8-device mesh parity per new task at ≤ 1e-5.
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
from repro.core import compression, factorization, tree as tree_mod
from repro.core import tasks as tasks_mod
from repro.core.compression import CompressionParams
from repro.core.engine import HSSSVMEngine, grid_search
from repro.core.kernelfn import KernelSpec, gaussian_block_xla
from repro.data import synthetic
from tests import proptest as pt

COMP = CompressionParams(rank=24, n_near=32, n_far=48)


# --------------------------------------------------------------------- #
# exact-equivalence pin: generic path == pre-refactor admm_svm loop     #
# --------------------------------------------------------------------- #
def _prerefactor_admm_svm_batched(solver_mat, ys, c_upper, beta, max_it=10,
                                  z0=None, mu0=None):
    """Verbatim copy of the pre-refactor (PR 4) admm_svm_batched loop —
    the reference the BoxQPTask generalization is pinned against."""
    k, d = ys.shape
    dtype = ys.dtype
    y_cols = ys.T
    e = jnp.ones((d,), dtype)
    w = solver_mat(e[:, None])[:, 0]
    w1 = e @ w
    w_y = y_cols * w[:, None]
    c_arr = jnp.asarray(c_upper, dtype)
    if c_arr.ndim == 1:
        c_arr = c_arr[:, None]
    elif c_arr.ndim == 2:
        c_arr = c_arr.T
    c_mat = jnp.broadcast_to(c_arr, (d, k))
    z_init = jnp.zeros((d, k), dtype) if z0 is None else z0
    mu_init = jnp.zeros((d, k), dtype) if mu0 is None else mu0

    def step(state, _):
        x, z, mu = state
        q = 1.0 + mu + beta * z
        yq = y_cols * q
        u = solver_mat(yq)
        w2 = w @ yq
        x_new = y_cols * u - (w2 / w1)[None, :] * w_y
        z_new = jnp.clip(x_new - mu / beta, 0.0, c_mat)
        mu_new = mu - beta * (x_new - z_new)
        trace = (jnp.linalg.norm(x_new - z_new, axis=0),
                 beta * jnp.linalg.norm(z_new - z, axis=0))
        return admm_mod.ADMMState(x_new, z_new, mu_new), trace

    init = admm_mod.ADMMState(jnp.zeros((d, k), dtype), z_init, mu_init)
    return jax.lax.scan(step, init, None, length=max_it)


def _equivalence_case(solver_mat, ys, c_upper, beta, max_it, z0=None,
                      mu0=None):
    ref_state, (ref_p, ref_d) = _prerefactor_admm_svm_batched(
        solver_mat, ys, c_upper, beta, max_it, z0=z0, mu0=mu0)
    state, trace = admm_mod.admm_svm_batched(
        solver_mat, ys, c_upper, beta, max_it, z0=z0, mu0=mu0)
    for ref, new, name in [
            (ref_state.x, state.x, "x"), (ref_state.z, state.z, "z"),
            (ref_state.mu, state.mu, "mu"),
            (ref_p, trace.primal_res, "primal_res"),
            (ref_d, trace.dual_res, "dual_res")]:
        diff = float(jnp.max(jnp.abs(ref - new)))
        assert diff <= 1e-12, (name, diff)
    assert np.all(np.asarray(trace.iters_run) == max_it)


def test_generic_path_equals_prerefactor_svm_dense():
    """Dense-solver pin: scalar C, vector C, per-problem C, warm starts."""
    rng = np.random.default_rng(0)
    n, k = 96, 3
    x = rng.normal(size=(n, 3)).astype(np.float32)
    xj = jnp.asarray(x)
    k_mat = gaussian_block_xla(xj, xj, 1.0)
    beta = 10.0
    solver = pt.dense_solver_mat(k_mat, beta)
    ys = jnp.asarray(np.sign(rng.normal(size=(k, n))).astype(np.float32))
    _equivalence_case(solver, ys, 1.0, beta, 12)
    c_vec = jnp.asarray(rng.uniform(0.2, 2.0, size=n).astype(np.float32))
    _equivalence_case(solver, ys, c_vec, beta, 12)
    c_kd = jnp.asarray(rng.uniform(0.2, 2.0, size=(k, n)).astype(np.float32))
    _equivalence_case(solver, ys, c_kd, beta, 12)
    warm, _ = _prerefactor_admm_svm_batched(solver, ys, 1.0, beta, 10)
    _equivalence_case(solver, ys, 1.5, beta, 12, z0=warm.z, mu0=warm.mu)


def test_generic_path_equals_prerefactor_svm_hss():
    """HSS-factorization pin: the real solver path, traces to ≤ 1e-12."""
    x, y = synthetic.blobs(512, n_features=4, sep=1.6, seed=3)
    t = tree_mod.build_tree(x, leaf_size=64)
    xp = jnp.asarray(x[t.perm])
    yp = jnp.asarray(y[t.perm])
    hss = compression.compress(xp, t, KernelSpec(h=1.0), COMP)
    fac = factorization.factorize(hss, 100.0)
    ys = jnp.stack([yp, -yp])
    _equivalence_case(fac.solve_mat, ys, 1.0, 100.0, 10)


# --------------------------------------------------------------------- #
# SVR / one-class duals vs a dense QP reference                         #
# --------------------------------------------------------------------- #
def test_svr_task_matches_scipy_reference():
    from scipy.optimize import minimize

    rng = np.random.default_rng(1)
    n = 96
    x = rng.normal(size=(n, 2)).astype(np.float32)
    yt = np.sin(2.0 * x[:, 0]).astype(np.float32)
    xj = jnp.asarray(x)
    k_mat = gaussian_block_xla(xj, xj, 1.0)
    beta, c_val, eps = 10.0, 1.0, 0.1
    solver = pt.dense_solver_mat(k_mat, beta)
    task = tasks_mod.svr_task(jnp.asarray(yt)[None, :], c_val, eps)
    state, _ = admm_mod.admm_boxqp(solver, task, beta, max_it=800)
    alpha = np.asarray(state.z[:, 0], np.float64)
    kn = np.asarray(k_mat, np.float64)

    def obj(a):
        return 0.5 * a @ kn @ a - yt @ a + eps * np.abs(a).sum()

    res = minimize(obj, np.zeros(n), bounds=[(-c_val, c_val)] * n,
                   constraints=[dict(type="eq", fun=lambda a: a.sum())],
                   method="SLSQP", options=dict(maxiter=800))
    f_admm, f_ref = obj(alpha), float(res.fun)
    assert f_admm <= f_ref + 1e-3 * abs(f_ref) + 1e-4, (f_admm, f_ref)
    assert abs(alpha.sum()) < 1e-4                  # equality feasibility
    assert np.all(np.abs(alpha) <= c_val + 1e-5)    # box feasibility


def test_one_class_task_matches_scipy_reference():
    from scipy.optimize import minimize

    rng = np.random.default_rng(2)
    n, nu = 96, 0.2
    x = rng.normal(size=(n, 2)).astype(np.float32)
    xj = jnp.asarray(x)
    k_mat = gaussian_block_xla(xj, xj, 1.0)
    beta = 10.0
    solver = pt.dense_solver_mat(k_mat, beta)
    task = tasks_mod.one_class_task(jnp.ones((1, n), jnp.float32), nu)
    state, _ = admm_mod.admm_boxqp(solver, task, beta, max_it=800)
    alpha = np.asarray(state.z[:, 0], np.float64)
    kn = np.asarray(k_mat, np.float64)
    hi = 1.0 / (nu * n)

    res = minimize(lambda a: 0.5 * a @ kn @ a, np.full(n, 1.0 / n),
                   bounds=[(0.0, hi)] * n,
                   constraints=[dict(type="eq", fun=lambda a: a.sum() - 1.0)],
                   method="SLSQP", options=dict(maxiter=800))
    f_admm = 0.5 * alpha @ kn @ alpha
    assert f_admm <= float(res.fun) + 1e-3 * abs(res.fun) + 1e-5
    assert abs(alpha.sum() - 1.0) < 1e-4
    assert np.all(alpha >= -1e-6) and np.all(alpha <= hi + 1e-6)


def test_oneclass_nu_bounds_train_outlier_fraction():
    """The Schölkopf ν-property on the real engine: the fraction of training
    points scored as outliers is ≤ ν (+ slack for the f32 margin band)."""
    x, _ = synthetic.blobs_with_outliers(1024, n_features=4,
                                         outlier_frac=0.08, seed=0)
    engine = HSSSVMEngine(spec=KernelSpec(h=2.0), comp=COMP, leaf_size=64,
                          max_it=40, task="oneclass")
    engine.prepare(x)
    for nu in (0.05, 0.15):
        model, _ = engine.train(nu)
        frac = float(jnp.mean(model.predict(jnp.asarray(x)) < 0))
        assert frac <= nu + 0.05, (nu, frac)


# --------------------------------------------------------------------- #
# shared-factorization economy: call-count proofs per new task          #
# --------------------------------------------------------------------- #
def _count_build_calls(monkeypatch):
    calls = {"compress": 0, "factorize": 0}
    orig_c, orig_f = compression.compress, factorization.factorize

    def cc(*a, **kw):
        calls["compress"] += 1
        return orig_c(*a, **kw)

    def cf(*a, **kw):
        calls["factorize"] += 1
        return orig_f(*a, **kw)

    monkeypatch.setattr(compression, "compress", cc)
    monkeypatch.setattr(factorization, "factorize", cf)
    return calls


def test_svr_one_compression_one_factorization_per_h(monkeypatch):
    calls = _count_build_calls(monkeypatch)
    xtr, ytr, xte, yte = synthetic.train_test("noisy_sine", 1000, 256,
                                              seed=0, noise=0.1)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=64,
                          max_it=10, task="svr", svr_c=2.0)
    engine.prepare(xtr, ytr)
    warm = None
    for eps in (0.05, 0.1, 0.2):            # warm-started ε sweep
        model, warm = engine.train(eps, warm=warm)
    assert calls == {"compress": 1, "factorize": 1}, calls
    pred = np.asarray(model.predict(jnp.asarray(xte)))
    rmse = float(np.sqrt(np.mean((pred - yte) ** 2)))
    assert rmse < 0.25, rmse


def test_oneclass_one_compression_one_factorization_per_h(monkeypatch):
    calls = _count_build_calls(monkeypatch)
    x, _ = synthetic.blobs_with_outliers(1000, n_features=4,
                                         outlier_frac=0.1, seed=0)
    xval, yval = synthetic.blobs_with_outliers(512, n_features=4,
                                               outlier_frac=0.1, seed=1)
    engine = HSSSVMEngine(spec=KernelSpec(h=2.0), comp=COMP, leaf_size=64,
                          max_it=30, task="oneclass")
    engine.prepare(x)                        # unsupervised: no y
    warm = None
    scores = {}
    for nu in (0.05, 0.1, 0.2):             # warm-started ν sweep
        model, warm = engine.train(nu, warm=warm)
        scores[nu] = tasks_mod.oneclass_score(model, jnp.asarray(xval), yval)
    assert calls == {"compress": 1, "factorize": 1}, calls
    assert max(scores.values()) > 0.8, scores


# --------------------------------------------------------------------- #
# grid drivers: ε / ν sweep in place of C                               #
# --------------------------------------------------------------------- #
def test_grid_search_svr_shares_compression():
    xtr, ytr, xte, yte = synthetic.train_test("noisy_sine", 1024, 256,
                                              seed=0, noise=0.1)
    model, info = grid_search(
        xtr, ytr, xte, yte, hs=[1.0], knobs=[0.05, 0.1, 0.3], task="svr",
        trainer_kwargs=dict(svr_c=2.0, comp=COMP, leaf_size=64, max_it=10))
    assert len(info["results"]) == 3
    assert -info["best_accuracy"] < 0.2     # scores are negated RMSE
    comp_times = {v["compression_s"] for v in info["results"].values()}
    assert len(comp_times) == 1             # one compression per h
    pred = model.predict(jnp.asarray(xte))
    assert pred.shape == (256,)


def test_grid_search_oneclass_shares_compression():
    xtr, _ = synthetic.blobs_with_outliers(1024, n_features=4,
                                           outlier_frac=0.1, seed=0)
    xval, yval = synthetic.blobs_with_outliers(512, n_features=4,
                                               outlier_frac=0.1, seed=2)
    model, info = grid_search(
        xtr, None, xval, yval, hs=[2.0], knobs=[0.05, 0.1, 0.2],
        task="oneclass",
        trainer_kwargs=dict(comp=COMP, leaf_size=64, max_it=30))
    assert len(info["results"]) == 3
    assert info["best_accuracy"] > 0.8
    comp_times = {v["compression_s"] for v in info["results"].values()}
    assert len(comp_times) == 1


# --------------------------------------------------------------------- #
# residual-based early stopping                                         #
# --------------------------------------------------------------------- #
def test_early_stop_freezes_exactly_at_stopping_iteration():
    rng = np.random.default_rng(0)
    n = 256
    x = rng.normal(size=(n, 3)).astype(np.float32)
    y = np.sign(rng.normal(size=n)).astype(np.float32)
    xj = jnp.asarray(x)
    k_mat = gaussian_block_xla(xj, xj, 1.0)
    beta = 10.0
    solver = pt.dense_solver_mat(k_mat, beta)
    ys = jnp.asarray(y)[None, :]
    state, trace = admm_mod.admm_svm_batched(solver, ys, 1.0, beta,
                                             max_it=300, tol=1e-2)
    it = int(trace.iters_run[0])
    assert 0 < it < 300, it
    # frozen state == the plain run truncated at the stopping iteration
    ref, _ = admm_mod.admm_svm_batched(solver, ys, 1.0, beta, max_it=it)
    for a, b in zip(state, ref):
        assert float(jnp.max(jnp.abs(a - b))) == 0.0
    # post-freeze trace: primal constant, dual exactly 0 (z stopped moving)
    primal = np.asarray(trace.primal_res[:, 0])
    dual = np.asarray(trace.dual_res[:, 0])
    np.testing.assert_array_equal(primal[it:], primal[it])
    np.testing.assert_array_equal(dual[it:], 0.0)
    # tol=None path is untouched: runs all iterations
    _, tr_full = admm_mod.admm_svm_batched(solver, ys, 1.0, beta, max_it=20)
    assert int(tr_full.iters_run[0]) == 20


def test_early_stop_is_per_problem_and_reported_in_fitreport():
    rng = np.random.default_rng(4)
    n = 256
    x = rng.normal(size=(n, 3)).astype(np.float32)
    y = np.sign(rng.normal(size=n)).astype(np.float32)
    xj = jnp.asarray(x)
    k_mat = gaussian_block_xla(xj, xj, 1.0)
    beta = 10.0
    solver = pt.dense_solver_mat(k_mat, beta)
    # two problems with very different conditioning: tiny C converges fast
    ys = jnp.asarray(np.stack([y, y]))
    c_kd = jnp.asarray(np.stack([np.full(n, 0.01), np.full(n, 5.0)])
                       .astype(np.float32))
    _, trace = admm_mod.admm_svm_batched(solver, ys, c_kd, beta,
                                         max_it=300, tol=1e-3)
    iters = np.asarray(trace.iters_run)
    assert iters[0] < iters[1], iters       # per-column freeze, not global

    # the engine surfaces iters_run through FitReport
    xtr, ytr = synthetic.blobs(512, n_features=4, sep=2.5, seed=0)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=64,
                          max_it=200, tol=1e-2, beta=10.0)
    engine.prepare(xtr, ytr)
    engine.train(1.0)
    assert engine.report.iters_run is not None
    assert 0 < engine.report.iters_run[0] < 200, engine.report.iters_run


# --------------------------------------------------------------------- #
# slow tier: 8-device mesh parity per task                              #
# --------------------------------------------------------------------- #
def _run_sub(code: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_MESH_PARITY_TMPL = """
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

    kw = dict(spec=KernelSpec(h={h}),
              comp=CompressionParams(rank=24, n_near=32, n_far=48),
              leaf_size=64, max_it={max_it}, beta=100.0, task="{task}",
              svr_c=2.0)
    {data}

    def fit(mesh):
        eng = HSSSVMEngine(mesh=mesh, **kw)
        eng.prepare(xtr, ytr)
        model, _ = eng.train({knob})
        return eng, model, np.asarray(
            model.decision_function(jnp.asarray(xte)))

    eng1, m1, s1 = fit(make_mesh((1,), ("data",)))
    eng8, m8, s8 = fit(make_mesh((8,), ("data",)))
    assert not m8.z_y.sharding.is_fully_replicated
    assert not eng8.hss.d_leaf.sharding.is_fully_replicated
    rel = np.linalg.norm(s1 - s8) / max(np.linalg.norm(s1), 1e-30)
    assert rel <= 1e-5, rel
    print("TASK_MESH_PARITY_OK", rel)
"""


@pytest.mark.slow
def test_svr_mesh_parity_8_devices():
    """SVR through the engine: 1-device vs 8-device mesh scores ≤ 1e-5."""
    code = textwrap.dedent(_MESH_PARITY_TMPL.format(
        task="svr", h=1.0, max_it=10, knob=0.1,
        data=('xtr, ytr, xte, yte = synthetic.train_test('
              '"noisy_sine", 4096, 512, seed=0, noise=0.1)')))
    r = _run_sub(code)
    assert "TASK_MESH_PARITY_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_oneclass_mesh_parity_8_devices():
    """One-class through the engine: 1- vs 8-device mesh scores ≤ 1e-5."""
    code = textwrap.dedent(_MESH_PARITY_TMPL.format(
        task="oneclass", h=2.0, max_it=30, knob=0.1,
        data=('xtr, _ = synthetic.blobs_with_outliers('
              '4096, n_features=4, outlier_frac=0.1, seed=0)\n'
              '    xte, _yte = synthetic.blobs_with_outliers('
              '512, n_features=4, outlier_frac=0.1, seed=1)\n'
              '    ytr = None')))
    r = _run_sub(code)
    assert "TASK_MESH_PARITY_OK" in r.stdout, r.stdout + r.stderr
