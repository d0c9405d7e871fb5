"""Property-based tests of the system's invariants (see tests/proptest.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admm as admm_mod
from repro.core import compression, factorization, tree as tree_mod
from repro.core.kernelfn import KernelSpec, gaussian_block_xla
from tests import proptest as pt


def test_property_shifted_kernel_spd():
    """K̃ + beta I stays SPD for all sampled (h, beta, data) — the property
    the Cholesky leaf factorization relies on."""
    for case in pt.Cases(n_cases=6, seed=1).draw(dict(
            h=pt.floats(0.3, 10.0, log=True),
            beta=pt.floats(1.0, 1e4, log=True),
            n_feat=pt.ints(2, 8),
            x=pt.arrays(lambda rng: (256, int(rng.integers(2, 9)))))):
        x = case["x"][:, :case["n_feat"]]
        t = tree_mod.build_tree(x, leaf_size=64)
        xp = jnp.asarray(x[t.perm])
        hss = compression.compress(
            xp, t, KernelSpec(h=case["h"]),
            compression.CompressionParams(rank=16, n_near=24, n_far=24))
        dense = np.asarray(hss.todense()) + case["beta"] * np.eye(256)
        evals = np.linalg.eigvalsh(dense)
        assert evals.min() > 0, case


def test_property_tree_permutation_equivariance():
    """Shuffling input rows must not change the (sorted) leaf contents."""
    for case in pt.Cases(n_cases=5, seed=2).draw(dict(
            x=pt.arrays((128, 3)), perm_seed=pt.ints(0, 1000))):
        x = case["x"]
        rng = np.random.default_rng(case["perm_seed"])
        p = rng.permutation(len(x))
        t1 = tree_mod.build_tree(x, leaf_size=32)
        t2 = tree_mod.build_tree(x[p], leaf_size=32)
        a = np.sort(x[t1.perm].reshape(4, 32, 3).sum(axis=1), axis=0)
        b = np.sort(x[p][t2.perm].reshape(4, 32, 3).sum(axis=1), axis=0)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_property_skeletons_subset_of_node():
    """Every node's skeleton indices must lie inside the node's span."""
    for case in pt.Cases(n_cases=4, seed=3).draw(dict(
            x=pt.arrays((256, 4)))):
        t = tree_mod.build_tree(case["x"], leaf_size=64)
        xp = jnp.asarray(case["x"][t.perm])
        hss = compression.compress(
            xp, t, KernelSpec(h=1.0),
            compression.CompressionParams(rank=16, n_near=24, n_far=24))
        skel = np.asarray(hss.skel_leaf)
        for leaf in range(hss.n_leaves):
            lo, hi = leaf * 64, (leaf + 1) * 64
            assert ((skel[leaf] >= lo) & (skel[leaf] < hi)).all()
        for k, sk in enumerate(hss.skels, start=1):
            width = 64 * 2 ** k
            sk = np.asarray(sk)
            for node in range(sk.shape[0]):
                lo, hi = node * width, (node + 1) * width
                assert ((sk[node] >= lo) & (sk[node] < hi)).all()


def test_property_solve_residual_small_across_betas():
    for case in pt.Cases(n_cases=5, seed=4).draw(dict(
            beta=pt.floats(1.0, 1e3, log=True),
            x=pt.arrays((256, 4)), b=pt.arrays((256,)))):
        t = tree_mod.build_tree(case["x"], leaf_size=64)
        xp = jnp.asarray(case["x"][t.perm])
        hss = compression.compress(
            xp, t, KernelSpec(h=1.0),
            compression.CompressionParams(rank=24, n_near=32, n_far=48))
        fac = factorization.factorize(hss, case["beta"])
        b = jnp.asarray(case["b"])
        xsol = fac.solve(b)
        resid = hss.matvec(xsol) + case["beta"] * xsol - b
        rel = float(jnp.linalg.norm(resid) / jnp.linalg.norm(b))
        assert rel < 1e-3, (rel, case["beta"])


def test_property_admm_iterates_feasible():
    """For all sampled (beta, C): z in box, |yᵀx| ~ 0 after every run."""
    for case in pt.Cases(n_cases=5, seed=5).draw(dict(
            beta=pt.floats(1.0, 300.0, log=True),
            c=pt.floats(0.1, 10.0, log=True),
            x=pt.arrays((96, 3)), labels=pt.arrays((96,)))):
        import jax.scipy.linalg as jsl
        xj = jnp.asarray(case["x"])
        y = jnp.sign(jnp.asarray(case["labels"]) + 1e-9)
        k_mat = gaussian_block_xla(xj, xj, 1.0)
        chol = jsl.cholesky(k_mat + case["beta"] * jnp.eye(96), lower=True)
        state, _ = admm_mod.admm_svm(
            lambda b: jsl.cho_solve((chol, True), b), y, case["c"],
            case["beta"], max_it=15)
        assert float(state.z.min()) >= 0
        assert float(state.z.max()) <= case["c"] + 1e-5
        assert float(jnp.abs(y @ state.x)) < 1e-2 * 96, case


def test_property_hss_invariants_randomized_trees():
    """Structural HSS invariants over randomized tree depths, leaf sizes and
    ranks: matvec ≡ todense()@v, symmetry, shift identity, and O(N r) storage
    strictly below dense storage."""
    for case in pt.Cases(n_cases=6, seed=8).draw(dict(
            leaf=pt.choice(32, 64),
            depth=pt.ints(1, 3),
            rank=pt.choice(8, 16),
            h=pt.floats(0.5, 4.0, log=True),
            beta=pt.floats(1.0, 1e3, log=True),
            data_seed=pt.ints(0, 1000))):
        leaf, depth = case["leaf"], case["depth"]
        n = leaf * 2 ** depth
        rng = np.random.default_rng(case["data_seed"])
        x = rng.normal(size=(n, 4)).astype(np.float32)
        t = tree_mod.build_tree(x, leaf_size=leaf, levels=depth)
        xp = jnp.asarray(x[t.perm])
        hss = compression.compress(
            xp, t, KernelSpec(h=case["h"]),
            compression.CompressionParams(
                rank=case["rank"], n_near=24, n_far=32))
        dense = hss.todense()
        # matvec consistent with the dense reconstruction
        v = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(hss.matvec(v)), np.asarray(dense @ v),
            rtol=2e-4, atol=2e-4, err_msg=str(case))
        # symmetry of the reconstruction
        np.testing.assert_allclose(
            np.asarray(dense), np.asarray(dense).T, atol=1e-5,
            err_msg=str(case))
        # shifted(beta) adds exactly beta*I
        np.testing.assert_allclose(
            np.asarray(hss.shifted(case["beta"]).todense()),
            np.asarray(dense) + case["beta"] * np.eye(n, dtype=np.float32),
            rtol=1e-5, atol=1e-4, err_msg=str(case))
        # storage strictly below the dense kernel matrix
        assert hss.memory_bytes() < n * n * 4, case


def _random_tree_kernel(case):
    """Dense kernel reconstructed from an HSS build over a RANDOM tree —
    the KKT checks then measure ADMM optimality against the exact kernel
    the solver used, while still exercising randomized tree geometry."""
    leaf, depth = case["leaf"], case["depth"]
    n = leaf * 2 ** depth
    rng = np.random.default_rng(case["data_seed"])
    x = rng.normal(size=(n, 3)).astype(np.float32)
    t = tree_mod.build_tree(x, leaf_size=leaf, levels=depth)
    hss = compression.compress(
        jnp.asarray(x[t.perm]), t, KernelSpec(h=case["h"]),
        compression.CompressionParams(rank=16, n_near=24, n_far=32))
    k_mat = np.asarray(hss.todense(), np.float64)
    k_mat = 0.5 * (k_mat + k_mat.T)           # exact symmetry for the checks
    return jnp.asarray(k_mat, jnp.float32), rng


_TREE_SPEC = dict(
    leaf=pt.choice(32, 64),
    depth=pt.ints(1, 2),
    h=pt.floats(0.8, 3.0, log=True),
    beta=pt.floats(3.0, 30.0, log=True),
    data_seed=pt.ints(0, 1000),
    knob_seed=pt.ints(0, 1000),
)

# Residual bounds for the KKT tier: ADMM at 800 iterations on float32
# iterates (measured worst case across the drawn cases: stationarity
# 9.3e-3 — the slowest-converging residual at the large-β draws — eq
# 4.4e-5, split 1.7e-5, comp_slack 1.5e-6; box is exact by construction
# of the clip).  comp_slack is near-zero by construction of the z-step
# (z IS a prox output) up to float32 rounding of the μ update.
_KKT_TOL = dict(stationarity=2e-2, eq=1e-3, box=1e-6, split=2e-4,
                comp_slack=1e-5)


def _assert_kkt(k_mat, task, state, case, label):
    res = pt.kkt_residuals(k_mat, task, state)
    for name, bound in _KKT_TOL.items():
        assert np.all(res[name] <= bound), (
            label, name, res[name], case)


def test_property_kkt_all_tasks_random_trees():
    """The generic ADMM drives EVERY box-QP task to a KKT point: SVM, ε-SVR
    and one-class verified by the same stationarity / feasibility /
    complementary-slackness residuals over random trees and knobs."""
    from repro.core import tasks as tasks_mod

    for case in pt.Cases(n_cases=4, seed=11).draw(_TREE_SPEC):
        k_mat, rng = _random_tree_kernel(case)
        n = k_mat.shape[0]
        beta = case["beta"]
        solver = pt.dense_solver_mat(k_mat, beta)
        krng = np.random.default_rng(case["knob_seed"])
        c_val = float(krng.uniform(0.3, 3.0))

        y = np.sign(krng.normal(size=n)).astype(np.float32)
        svm = admm_mod.svm_task(jnp.asarray(y)[None, :], c_val)
        state, _ = admm_mod.admm_boxqp(solver, svm, beta, max_it=800)
        _assert_kkt(k_mat, svm, state, case, "svm")

        targets = np.sin(2.0 * krng.normal(size=n)).astype(np.float32)
        svr = tasks_mod.svr_task(jnp.asarray(targets)[None, :], c_val,
                                 float(krng.uniform(0.02, 0.3)))
        state, _ = admm_mod.admm_boxqp(solver, svr, beta, max_it=800)
        _assert_kkt(k_mat, svr, state, case, "svr")

        ocl = tasks_mod.one_class_task(jnp.ones((1, n), jnp.float32),
                                       float(krng.uniform(0.05, 0.4)))
        state, _ = admm_mod.admm_boxqp(solver, ocl, beta, max_it=800)
        _assert_kkt(k_mat, ocl, state, case, "oneclass")


def test_property_kkt_warm_equals_cold_fixed_point():
    """Warm starts are an accelerator, not a different algorithm: for every
    task the warm-started run must land on a KKT point of the NEW knob's
    problem (the correctness contract of every knob-grid sweep)."""
    from repro.core import tasks as tasks_mod

    for case in pt.Cases(n_cases=3, seed=12).draw(_TREE_SPEC):
        k_mat, _ = _random_tree_kernel(case)
        n = k_mat.shape[0]
        beta = case["beta"]
        solver = pt.dense_solver_mat(k_mat, beta)
        krng = np.random.default_rng(case["knob_seed"])
        y = np.sign(krng.normal(size=n)).astype(np.float32)
        targets = np.sin(2.0 * krng.normal(size=n)).astype(np.float32)
        mask = jnp.ones((1, n), jnp.float32)

        def build(task_name, knob):
            if task_name == "svm":
                return admm_mod.svm_task(jnp.asarray(y)[None, :], knob)
            if task_name == "svr":
                return tasks_mod.svr_task(
                    jnp.asarray(targets)[None, :], 1.5, knob)
            return tasks_mod.one_class_task(mask, knob)

        for task_name, k0, k1 in (("svm", 0.5, 1.5), ("svr", 0.3, 0.08),
                                  ("oneclass", 0.3, 0.12)):
            t_first = build(task_name, k0)
            s_first, _ = admm_mod.admm_boxqp(solver, t_first, beta,
                                             max_it=800)
            t_next = build(task_name, k1)
            s_warm, _ = admm_mod.admm_boxqp(solver, t_next, beta, max_it=800,
                                            z0=s_first.z, mu0=s_first.mu)
            s_cold, _ = admm_mod.admm_boxqp(solver, t_next, beta, max_it=800)
            _assert_kkt(k_mat, t_next, s_warm, case, f"{task_name}-warm")
            _assert_kkt(k_mat, t_next, s_cold, case, f"{task_name}-cold")
            # The dual QP is convex but not strictly so (PSD kernel): z may
            # be non-unique, but the objective and the primal image K(Sz)
            # ARE unique — compare those, not raw coordinates.
            kn = np.asarray(k_mat, np.float64)

            def objective(st):
                z = np.asarray(st.z, np.float64)[:, 0]
                s = np.asarray(t_next.sign, np.float64)[:, 0]
                p = np.asarray(t_next.lin, np.float64)[:, 0]
                gam = (0.0 if t_next.l1 is None
                       else float(np.asarray(t_next.l1)[0]))
                sz = s * z
                return (0.5 * sz @ kn @ sz + p @ z
                        + gam * np.abs(z).sum()), kn @ sz

            f_w, ksz_w = objective(s_warm)
            f_c, ksz_c = objective(s_cold)
            assert abs(f_w - f_c) <= 1e-3 * (1.0 + abs(f_c)), (
                task_name, f_w, f_c, case)
            assert np.abs(ksz_w - ksz_c).max() <= 3e-2, (
                task_name, np.abs(ksz_w - ksz_c).max(), case)


def test_property_rope_norm_preserving():
    """RoPE is a rotation: per-head vector norms are invariant."""
    from repro.models.layers import apply_rope

    for case in pt.Cases(n_cases=5, seed=6).draw(dict(
            x=pt.arrays((2, 16, 4, 32)), theta=pt.floats(1e3, 1e6, log=True))):
        x = jnp.asarray(case["x"])
        pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
        out = apply_rope(x, pos, case["theta"])
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(out), axis=-1),
            np.linalg.norm(case["x"], axis=-1), rtol=2e-4, atol=1e-5)


def test_property_moe_capacity_drop_bounded():
    """MoE output differs from unlimited-capacity only on dropped tokens;
    total routed weight never exceeds 1 per token."""
    from repro.models.layers import MoEParams, moe_block

    for case in pt.Cases(n_cases=3, seed=7).draw(dict(
            seed=pt.ints(0, 99), e=pt.choice(4, 8), k=pt.choice(1, 2))):
        rng = np.random.default_rng(case["seed"])
        e, k, d, bsz, s = case["e"], case["k"], 16, 2, 32
        p = MoEParams(
            router=jnp.asarray(rng.normal(size=(d, e)) * 0.1, jnp.float32),
            w_gate=jnp.asarray(rng.normal(size=(e, d, 32)) * 0.1, jnp.float32),
            w_up=jnp.asarray(rng.normal(size=(e, d, 32)) * 0.1, jnp.float32),
            w_down=jnp.asarray(rng.normal(size=(e, 32, d)) * 0.1, jnp.float32),
        )
        x = jnp.asarray(rng.normal(size=(bsz, s, d)), jnp.float32)
        out_small, _ = moe_block(x, p, k, capacity_factor=0.5)
        out_big, _ = moe_block(x, p, k, capacity_factor=1e9)
        # capped-capacity output is a "partial" version: where it differs it
        # must be strictly smaller in magnitude (dropped contributions)
        n_small = float(jnp.linalg.norm(out_small))
        n_big = float(jnp.linalg.norm(out_big))
        assert n_small <= n_big * 1.05 + 1e-6
        assert jnp.all(jnp.isfinite(out_small))


def test_property_kernel_eval_count_matches_instrumentation():
    """``kernel_eval_count`` (the bench's perf-trajectory denominator) must
    EXACTLY equal a counting-kernel instrumentation of ``compress`` across
    random trees/params — and the fused Pallas path must leave the count
    unchanged (it dispatches at the same seam, after the count is taken)."""
    for case in pt.Cases(n_cases=5, seed=13).draw(dict(
            levels=pt.ints(1, 3), leaf=pt.choice(8, 16, 32),
            rank=pt.ints(4, 24), n_near=pt.ints(4, 24),
            n_far=pt.ints(4, 24), seed=pt.ints(0, 99),
            rtol=pt.choice(None, 1e-2),
            name=pt.choice("gaussian", "laplacian"))):
        rng = np.random.default_rng(case["seed"])
        n = case["leaf"] * 2 ** case["levels"]
        x = rng.normal(size=(n, 3)).astype(np.float32)
        t = tree_mod.build_tree(x, leaf_size=case["leaf"])
        xp = jnp.asarray(x[t.perm])
        params = compression.CompressionParams(
            rank=case["rank"], n_near=min(case["n_near"], n - case["leaf"]),
            n_far=case["n_far"], rtol=case["rtol"])
        spec = KernelSpec(name=case["name"], h=1.0)
        with compression.counting_kernel_evals() as ctr:
            compression.compress(xp, t, spec, params)
        pred = compression.kernel_eval_count(t, params)
        assert ctr["count"] == pred, (case, ctr["count"], pred)


def test_property_streamed_kernel_eval_count_batching_independent():
    """The streamed out-of-core build counts the SAME kernel evaluations as
    ``kernel_eval_count`` predicts (= the resident build) at EVERY batch
    size — tiling the batch axis must not change what reaches the counting
    seams, or the bench's perf-trajectory denominator silently forks."""
    for case in pt.Cases(n_cases=3, seed=15).draw(dict(
            levels=pt.ints(2, 3), leaf=pt.choice(16, 32),
            rank=pt.ints(4, 12), seed=pt.ints(0, 99),
            rtol=pt.choice(None, 1e-2))):
        rng = np.random.default_rng(case["seed"])
        n = case["leaf"] * 2 ** case["levels"]
        x = rng.normal(size=(n, 3)).astype(np.float32)
        t = tree_mod.build_tree(x, leaf_size=case["leaf"])
        params = compression.CompressionParams(
            rank=case["rank"], n_near=8, n_far=8, rtol=case["rtol"])
        spec = KernelSpec(h=1.0)
        pred = compression.kernel_eval_count(t, params)
        for bl in (1, 3, 64):
            with compression.counting_kernel_evals() as ctr:
                compression.compress_streamed(
                    x[t.perm], t, spec, params,
                    stream=compression.StreamParams(batch_leaves=bl))
            assert ctr["count"] == pred, (case, bl, ctr["count"], pred)


def test_property_pallas_path_kernel_eval_count_unchanged():
    """impl='pallas_interpret' counts the SAME logical kernel evaluations as
    impl='xla' (tiny sizes — interpret mode is slow)."""
    for case in pt.Cases(n_cases=2, seed=14).draw(dict(
            seed=pt.ints(0, 99), name=pt.choice("gaussian", "laplacian"))):
        rng = np.random.default_rng(case["seed"])
        n, leaf = 64, 16
        x = rng.normal(size=(n, 3)).astype(np.float32)
        t = tree_mod.build_tree(x, leaf_size=leaf)
        xp = jnp.asarray(x[t.perm])
        params = compression.CompressionParams(rank=8, n_near=8, n_far=8)
        counts = {}
        for impl in ("xla", "pallas_interpret"):
            spec = KernelSpec(name=case["name"], h=1.0, impl=impl)
            with compression.counting_kernel_evals() as ctr:
                compression.compress(xp, t, spec, params)
            counts[impl] = ctr["count"]
        pred = compression.kernel_eval_count(t, params)
        assert counts["xla"] == counts["pallas_interpret"] == pred, (
            case, counts, pred)


def test_property_kernel_eval_count_counts_the_kernel_interp_blocks(
        monkeypatch):
    """With the transfers of every internal level from ``_kernel_interp``
    (its threshold lowered to the leaf size), ``kernel_eval_count`` still
    equals the instrumented count, resident and streamed."""
    monkeypatch.setattr(compression, "KERNEL_INTERP_ROWS", 16)
    for levels, rtol in ((3, None), (2, 1e-2)):
        rng = np.random.default_rng(levels)
        x = rng.normal(size=(16 * 2 ** levels, 3)).astype(np.float32)
        t = tree_mod.build_tree(x, leaf_size=16)
        xp = jnp.asarray(x[t.perm])
        params = compression.CompressionParams(rank=6, n_near=6, n_far=8,
                                               rtol=rtol)
        spec = KernelSpec(h=1.0)
        pred = compression.kernel_eval_count(t, params)
        with compression.counting_kernel_evals() as ctr:
            compression.compress(xp, t, spec, params)
        assert ctr["count"] == pred
        with compression.counting_kernel_evals() as ctr:
            compression.compress_streamed(
                np.asarray(xp), t, spec, params,
                stream=compression.StreamParams(batch_leaves=2))
        assert ctr["count"] == pred
