"""Kernel ridge regression and GP posterior mean: ADMM-free solves.

KRR and the GP posterior mean are ONE multi-RHS triangular solve on the
same K̃ + λI factorization the SVM tasks use — the ridge λ rides the β
shift slot, so a λ sweep is a cached refactorization + solve per value and
zero ADMM iterations ever run.  This demo sweeps λ on one compression,
then scores a (h, λ) grid two ways: holdout RMSE (KRR) and the Hutchinson
log marginal likelihood (GP — no validation split needed).

  PYTHONPATH=src python examples/krr.py
"""
import sys
import time

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.core.compression import CompressionParams
from repro.core.engine import HSSSVMEngine, grid_search
from repro.core.kernelfn import KernelSpec
from repro.data import synthetic

COMP = CompressionParams(rank=32, n_near=48, n_far=64)


def lambda_sweep():
    xtr, ytr, xte, yte = synthetic.train_test(
        "noisy_sine", n_train=8192, n_test=2048, seed=0, noise=0.1)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=256,
                          task="krr")
    t0 = time.time()
    rep = engine.prepare(xtr, ytr)
    print(f"noisy sine, n=8192 (noise std 0.1): compressed "
          f"{rep.compression_s:.1f}s ONCE for the whole λ sweep")
    print(f"{'lam':>6} {'rmse':>8} {'admm iters':>11}")
    for lam in (0.1, 0.5, 2.0, 8.0, 32.0):
        model, _ = engine.train(lam)
        pred = np.asarray(model.predict(jnp.asarray(xte)))
        rmse = float(np.sqrt(np.mean((pred - yte) ** 2)))
        iters = int(max(engine.report.iters_run))
        print(f"{lam:>6} {rmse:>8.4f} {iters:>11}")
    print(f"[{time.time() - t0:.1f}s total; the noise floor is 0.1 — small "
          f"λ already sits on it, large λ over-smooths]\n")


def h_lambda_grid():
    xtr, ytr, xte, yte = synthetic.train_test(
        "noisy_sine", n_train=4096, n_test=1024, seed=0, noise=0.1)
    t0 = time.time()
    model, info = grid_search(
        xtr, ytr, xte, yte, hs=[0.5, 1.0], knobs=[0.3, 1.0, 4.0], task="krr",
        trainer_kwargs=dict(comp=COMP, leaf_size=128))
    print("KRR (h, λ) grid (scores are negated validation RMSE):")
    print(f"{'h':>6} {'lam':>6} {'rmse':>8}")
    for (h, lam), rec in sorted(info["results"].items()):
        print(f"{h:>6} {lam:>6} {-rec['accuracy']:>8.4f}")
    print(f"best: h={info['best_h']} λ={info['best_c']} "
          f"rmse={-info['best_accuracy']:.4f}  "
          f"[{time.time() - t0:.1f}s, 2 compressions for "
          f"{len(info['results'])} cells]\n")


def gp_evidence_grid():
    xtr, ytr, _, _ = synthetic.train_test(
        "noisy_sine", n_train=2048, n_test=256, seed=0, noise=0.1)
    t0 = time.time()
    model, info = grid_search(
        xtr, ytr, None, None, hs=[0.5, 1.0], knobs=[0.01, 0.1, 1.0],
        task="gp", trainer_kwargs=dict(comp=COMP, leaf_size=128))
    print("GP (h, λ) grid scored by log marginal likelihood — no holdout:")
    print(f"{'h':>6} {'lam':>6} {'log p(y)':>12}")
    for (h, lam), rec in sorted(info["results"].items()):
        print(f"{h:>6} {lam:>6} {rec['log_marginal']:>12.1f}")
    print(f"best: h={info['best_h']} λ={info['best_lam']} "
          f"log p(y)={info['best_log_marginal']:.1f}  "
          f"[{time.time() - t0:.1f}s; the evidence picks λ near the true "
          f"noise variance 0.01 without ever seeing a validation split]")


if __name__ == "__main__":
    lambda_sweep()
    h_lambda_grid()
    gp_evidence_grid()
