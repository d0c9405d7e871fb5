"""Golden regression tests: pinned training-quality numbers on fixed seeds.

Solver refactors (solve sweeps, ADMM updates, compression sampling) must not
silently regress convergence.  These pins were measured on the CPU backend
(binary/multiclass when the multiclass subsystem landed, SVR/one-class when
the box-QP task layer landed), with deliberate margin:

  binary blobs  (n=1024, seed 0): acc 0.953, dual_res 30.3 -> 21.3 over 10 it
  4-class blobs (n=1024, seed 0): acc 0.949, primal_res[-1] < 0.012/class
  SVR noisy sine (n=1024, seed 0, noise 0.1): rmse 0.0981 (the noise floor)
  one-class blobs+outliers (n=1024, seed 0, ν=0.1): precision 0.758,
    recall 0.980 on the seed-1 holdout

A failure here means convergence behaviour changed — inspect the solver diff
before touching the pins.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admm as admm_mod
from repro.core.compression import CompressionParams
from repro.core.engine import HSSSVMEngine
from repro.core.kernelfn import KernelSpec
from repro.data import synthetic

COMP = CompressionParams(rank=32, n_near=48, n_far=64)


def test_golden_binary_accuracy_and_residual_decay():
    xtr, ytr, xte, yte = synthetic.train_test("blobs", 1024, 256, seed=0,
                                              sep=1.6)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP,
                          leaf_size=128, max_it=10)
    engine.prepare(xtr, ytr)
    model, _ = engine.train(1.0)
    acc = float(jnp.mean(model.predict(jnp.asarray(xte)) == yte))
    assert acc >= 0.93, acc                       # measured 0.9531

    fac = engine.fac
    y, mask = engine.problem_labels[0], engine.problem_masks[0]
    _, trace = admm_mod.admm_svm(fac.solve, y, 1.0 * mask, fac.beta, max_it=10)
    primal = np.asarray(trace.primal_res)
    dual = np.asarray(trace.dual_res)
    assert primal[-1] < 0.05, primal              # measured 0.0
    # dual residual must decay (small slack for reduction-order noise
    # across backends) and by a pinned factor
    assert np.all(np.diff(dual) < 1e-3), dual     # measured 30.27 -> 21.27
    assert dual[-1] < 23.0, dual
    assert dual[-1] / dual[0] < 0.78, dual        # measured ratio 0.703


def test_golden_multiclass_accuracy_and_residual_decay():
    xtr, ytr, xte, yte = synthetic.train_test(
        "multiclass_blobs", 1024, 256, seed=0, n_classes=4, sep=3.0)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.5), comp=COMP,
                          leaf_size=128, max_it=10)
    engine.prepare(xtr, ytr)
    model, _ = engine.train(1.0)
    acc = float(jnp.mean(model.predict(jnp.asarray(xte)) == jnp.asarray(yte)))
    assert acc >= 0.92, acc                       # measured 0.9492

    fac, ys, pmask = (engine.fac, engine.problem_labels,
                      engine.problem_masks)
    _, trace = admm_mod.admm_svm_batched(
        fac.solve_mat, ys, 1.0 * pmask, fac.beta, max_it=10)
    primal = np.asarray(trace.primal_res)         # (10, 4)
    dual = np.asarray(trace.dual_res)
    assert np.all(primal[-1] < 0.05), primal[-1]  # measured <= 0.0113
    assert np.all(dual[-1] < 18.0), dual[-1]      # measured <= 14.58
    assert np.all(dual[-1] < dual[0]), (dual[0], dual[-1])


def test_golden_svr_rmse_noisy_sine():
    """ε-SVR on the engine must recover the sine to the noise floor."""
    xtr, ytr, xte, yte = synthetic.train_test("noisy_sine", 1024, 256,
                                              seed=0, noise=0.1)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=128,
                          max_it=30, task="svr", svr_c=2.0, beta=10.0)
    engine.prepare(xtr, ytr)
    model, _ = engine.train(0.1)
    pred = np.asarray(model.predict(jnp.asarray(xte)))
    rmse = float(np.sqrt(np.mean((pred - yte) ** 2)))
    assert rmse < 0.12, rmse                      # measured 0.0981
    # the ε tube keeps a sparse dual: most coefficients soft-thresholded out
    sv_frac = float(np.mean(np.abs(np.asarray(model.z_y)) > 1e-5))
    assert sv_frac < 0.8, sv_frac


def test_golden_oneclass_precision_recall_blobs_with_outliers():
    """ν one-class SVM must separate the planted outlier shell."""
    xtr, _ = synthetic.blobs_with_outliers(1024, n_features=4,
                                           outlier_frac=0.1, seed=0)
    xte, yte = synthetic.blobs_with_outliers(512, n_features=4,
                                             outlier_frac=0.1, seed=1)
    engine = HSSSVMEngine(spec=KernelSpec(h=2.0), comp=COMP, leaf_size=128,
                          max_it=30, task="oneclass")
    engine.prepare(xtr)
    model, _ = engine.train(0.1)
    pred = np.asarray(model.predict(jnp.asarray(xte)))
    flagged = pred < 0
    precision = (flagged & (yte < 0)).sum() / max(flagged.sum(), 1)
    recall = (flagged & (yte < 0)).sum() / max((yte < 0).sum(), 1)
    assert precision >= 0.65, precision           # measured 0.758
    assert recall >= 0.90, recall                 # measured 0.980
