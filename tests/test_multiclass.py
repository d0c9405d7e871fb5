"""Multiclass subsystem tests: the shared-factorization economy + correctness.

The load-bearing assertion (ISSUE acceptance): ONE HSS compression and ONE
factorization per (h, beta) serve ALL k class subproblems AND the whole C
grid — verified by call counting, plus batched-vs-sequential equivalence.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admm as admm_mod
from repro.core import compression, factorization
from repro.core import multiclass as mc
from repro.core.compression import CompressionParams
from repro.core.kernelfn import KernelSpec
from repro.data import synthetic
from repro.launch.mesh import make_mesh

COMP = CompressionParams(rank=32, n_near=48, n_far=64)


@pytest.fixture(scope="module")
def blobs4():
    # 1000 is NOT leaf_size * 2**levels — exercises multiclass padding too.
    return synthetic.train_test("multiclass_blobs", 1000, 256, seed=0,
                                n_classes=4, sep=3.0)


@pytest.fixture(scope="module")
def trained4(blobs4):
    xtr, ytr, _, _ = blobs4
    trainer = mc.MulticlassHSSSVMTrainer(
        spec=KernelSpec(h=1.5), comp=COMP, leaf_size=64, max_it=10)
    trainer.prepare(xtr, ytr)
    model, warm = trainer.train(1.0)
    return trainer, model, warm


def test_one_compression_one_factorization_serve_all_classes_and_c_grid(
        blobs4, monkeypatch):
    xtr, ytr, xte, yte = blobs4
    calls = {"compress": 0, "factorize": 0}
    orig_compress, orig_factorize = compression.compress, factorization.factorize

    def counting_compress(*a, **kw):
        calls["compress"] += 1
        return orig_compress(*a, **kw)

    def counting_factorize(*a, **kw):
        calls["factorize"] += 1
        return orig_factorize(*a, **kw)

    monkeypatch.setattr(compression, "compress", counting_compress)
    monkeypatch.setattr(factorization, "factorize", counting_factorize)

    trainer = mc.MulticlassHSSSVMTrainer(
        spec=KernelSpec(h=1.5), comp=COMP, leaf_size=64, max_it=10)
    trainer.prepare(xtr, ytr)
    warm = None
    for c in (0.5, 1.0, 2.0):                    # C grid x 4 classes = 12 runs
        model, warm = trainer.train(c, warm=warm)
    assert calls["compress"] == 1, calls
    assert calls["factorize"] == 1, calls
    acc = float(jnp.mean(model.predict(jnp.asarray(xte)) == jnp.asarray(yte)))
    assert acc > 0.9, acc


def test_multiclass_accuracy_and_shapes(blobs4, trained4):
    xtr, ytr, xte, yte = blobs4
    trainer, model, warm = trained4
    assert trainer.n_problems == 4
    assert model.z_y.shape[1] == 4 and model.biases.shape == (4,)
    scores = model.decision_function(jnp.asarray(xte))
    assert scores.shape == (xte.shape[0], 4)
    acc = float(jnp.mean(model.predict(jnp.asarray(xte)) == jnp.asarray(yte)))
    assert acc > 0.9, acc
    # warm-start state has one column per class
    assert warm[0].shape == warm[1].shape == (trainer._ys.shape[1], 4)


def test_batched_admm_matches_sequential_per_class(trained4):
    """The (d, k)-block iteration must equal k independent binary runs."""
    trainer, _, _ = trained4
    fac, ys, pmask = trainer._fac, trainer._ys, trainer._pmask
    state_b, trace_b = admm_mod.admm_svm_batched(
        fac.solve_mat, ys, 1.0 * pmask, fac.beta, max_it=10)
    for i in range(ys.shape[0]):
        state_i, trace_i = admm_mod.admm_svm(
            fac.solve, ys[i], 1.0 * pmask[i], fac.beta, max_it=10)
        np.testing.assert_allclose(
            np.asarray(state_b.z[:, i]), np.asarray(state_i.z),
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(trace_b.primal_res[:, i]), np.asarray(trace_i.primal_res),
            rtol=1e-4, atol=1e-5)


def test_pads_carry_zero_weight(trained4):
    trainer, model, _ = trained4
    n_pad = model.z_y.shape[0] - 1000
    assert n_pad > 0
    # padded coordinates sit at the end in pre-permutation order; in permuted
    # order find them via the participation mask instead
    dead = np.asarray(trainer._pmask[0]) == 0
    assert dead.sum() == n_pad
    np.testing.assert_array_equal(np.asarray(model.z_y)[dead], 0.0)


def test_one_vs_one_pairs_and_accuracy():
    xtr, ytr, xte, yte = synthetic.train_test(
        "multiclass_blobs", 512, 128, seed=1, n_classes=3, sep=3.0)
    trainer = mc.MulticlassHSSSVMTrainer(
        spec=KernelSpec(h=1.5), comp=COMP, leaf_size=64, max_it=10,
        strategy="ovo")
    trainer.prepare(xtr, ytr)
    assert trainer.n_problems == 3          # 3*(3-1)/2 pairs
    model, _ = trainer.train(1.0)
    assert model.pairs.shape == (3, 2)
    # points outside a pair are pinned to the [0, 0] box -> zero coefficient
    z_y = np.asarray(model.z_y)
    for p in range(3):
        outsiders = np.asarray(trainer._pmask[p]) == 0
        np.testing.assert_array_equal(z_y[outsiders, p], 0.0)
    acc = float(jnp.mean(model.predict(jnp.asarray(xte)) == jnp.asarray(yte)))
    assert acc > 0.9, acc


def test_predict_returns_original_label_values():
    xtr, ytr, xte, yte = synthetic.train_test(
        "multiclass_blobs", 512, 128, seed=2, n_classes=3, sep=3.5)
    ytr2, yte2 = ytr * 3 + 5, yte * 3 + 5       # labels {5, 8, 11}
    trainer = mc.MulticlassHSSSVMTrainer(
        spec=KernelSpec(h=1.5), comp=COMP, leaf_size=64, max_it=10)
    model = trainer.fit(xtr, ytr2, c_value=1.0)
    pred = np.asarray(model.predict(jnp.asarray(xte)))
    assert set(np.unique(pred)) <= {5, 8, 11}
    assert float(np.mean(pred == yte2)) > 0.85


def test_grid_search_multiclass_shares_compression():
    xtr, ytr, xte, yte = synthetic.train_test(
        "spirals", 1024, 256, seed=0, n_classes=3)
    model, info = mc.grid_search_multiclass(
        xtr, ytr, xte, yte, hs=[0.2], cs=[0.5, 2.0, 8.0],
        trainer_kwargs=dict(comp=COMP, leaf_size=64, max_it=10))
    assert len(info["results"]) == 3
    assert info["best_accuracy"] > 0.85
    comp_times = {v["compression_s"] for v in info["results"].values()}
    assert len(comp_times) == 1             # one compression per h
    assert model.n_classes == 3


def test_multiclass_distributed_matches_local(trained4):
    """Data-parallel batched C-grid == local batched run (1-device mesh)."""
    from repro.core.distributed import admm_train_multiclass_distributed

    trainer, _, _ = trained4
    fac, ys, pmask = trainer._fac, trainer._ys, trainer._pmask
    mesh = make_mesh((jax.device_count(),), ("data",))
    out = admm_train_multiclass_distributed(
        fac, ys, [0.5, 1.0], mesh, max_it=8, pmask=pmask)
    st1, _ = admm_mod.admm_svm_batched(
        fac.solve_mat, ys, 0.5 * pmask, fac.beta, max_it=8)
    st2, _ = admm_mod.admm_svm_batched(
        fac.solve_mat, ys, 1.0 * pmask, fac.beta, max_it=8,
        z0=st1.z, mu0=st1.mu)
    np.testing.assert_allclose(
        np.asarray(out[-1][0]), np.asarray(st2.z), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("rows,seed", [(2048, (15, 1)), (4096, (15, 2))])
def test_engine_ovr_on_covtype_rows_agrees_with_the_exact_reference(rows,
                                                                    seed):
    """The covtype deployment's 7-column one-vs-rest fit through the launch
    driver's engine, against the exact-kernel reference of the benchmark:
    as many support vectors within 12% and holdout accuracy within 0.01.
    The benchmark's ``covtype.train`` cell holds the same path to the same
    reference at 65,536 rows."""
    import json
    from pathlib import Path

    from bench import program
    from bench.data import covtype
    from bench.reference import svm as ref
    from repro.launch.train import fit_svm_grid

    root = Path(__file__).resolve().parents[1] / "bench"
    cfg = json.loads((root / "configs" / "covtype.json").read_text())
    x, y = covtype.generate(rows + 2048, seed)
    x, y, xh, yh = x[:rows], y[:rows], x[rows:], y[rows:]

    eng = program.build_engine(cfg)
    (_, model, acc), = fit_svm_grid(eng, x, y, xh, yh, [1.0],
                                    log=lambda _m: None)
    assert model.z_y.shape[1] == 7 and eng.n_problems == 7

    chol = ref.TiledCholesky.build(x, cfg["h"], ref.paper_beta(rows), 1024)
    classes, ys = ref.one_vs_rest(y)
    fit, = ref.admm_grid(chol, ys, [1.0], cfg["max_it"])
    ref_acc = float(np.mean(
        ref.labels(ref.decision(x, fit, xh, cfg["h"]), classes) == yh))
    gap = program.count_gap(program.support_counts(model.z_y),
                            program.support_counts(fit.zy))
    assert gap <= 0.12, gap
    assert ref_acc - acc <= 0.01, (ref_acc, acc)
    assert program.support_mismatch(model.x_perm, x) == 0.0
