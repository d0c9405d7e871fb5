"""Multiclass SVM on ONE shared HSS factorization (factor once, solve k-many).

K̃ + βI never sees the labels, so a k-class one-vs-rest reduction reuses a
single compression + factorization for every class subproblem, and every
ADMM iteration solves all k class systems as ONE multi-RHS telescoping
sweep.  This demo trains 5-class blobs and 3-class spirals, compares against
k sequential binary trainings, and sweeps the (C × class) product grid.

  PYTHONPATH=src python examples/multiclass_svm.py
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


def batched_vs_sequential():
    xtr, ytr, xte, yte = synthetic.train_test(
        "multiclass_blobs", n_train=8192, n_test=2048, seed=0,
        n_classes=5, sep=3.0)
    classes = np.unique(ytr)
    k = len(classes)

    def run_batched():
        t0 = time.time()
        engine = HSSSVMEngine(
            spec=KernelSpec(h=1.5), comp=COMP, leaf_size=256, max_it=10)
        model = engine.fit(xtr, ytr, c_value=1.0)
        acc = float(jnp.mean(model.predict(jnp.asarray(xte))
                             == jnp.asarray(yte)))
        return time.time() - t0, acc, engine.report

    def run_sequential():
        t0 = time.time()
        preds = []
        for c in classes:
            yb = np.where(ytr == c, 1.0, -1.0).astype(np.float32)
            be = HSSSVMEngine(spec=KernelSpec(h=1.5), comp=COMP,
                              leaf_size=256, max_it=10)
            bm = be.fit(xtr, yb, c_value=1.0)
            preds.append(np.asarray(bm.decision_function(jnp.asarray(xte))))
        acc = float(np.mean(classes[np.argmax(np.stack(preds, 1), 1)] == yte))
        return time.time() - t0, acc

    # First runs pay one-off XLA compilation (shared between the two paths);
    # the factor-once economy is about the steady-state second runs.
    run_batched()
    run_sequential()
    t_batched, acc, rep = run_batched()
    t_seq, acc_seq = run_sequential()

    print(f"{k}-class blobs, n=8192 (steady state, post-compile):")
    print(f"  batched   : {t_batched:6.1f}s  acc={acc:.4f}  "
          f"(1 compression {rep.compression_s:.1f}s + 1 factorization "
          f"{rep.factorization_s:.2f}s + batched ADMM {rep.admm_s:.2f}s)")
    print(f"  sequential: {t_seq:6.1f}s  acc={acc_seq:.4f}  "
          f"({k} compressions + {k} factorizations + {k} ADMM runs)")
    print(f"  speedup   : {t_seq / max(t_batched, 1e-9):.2f}x\n")


def spirals_grid():
    xtr, ytr, xte, yte = synthetic.train_test(
        "spirals", n_train=4096, n_test=1024, seed=0, n_classes=3)
    t0 = time.time()
    model, info = grid_search(
        xtr, ytr, xte, yte, hs=[0.1, 0.3], knobs=[0.5, 2.0, 8.0],
        trainer_kwargs=dict(comp=COMP, leaf_size=128, max_it=10))
    dt = time.time() - t0
    print("3-class spirals (C x class) grid:")
    print(f"{'h':>6} {'C':>6} {'accuracy':>9}")
    for (h, c), rec in sorted(info["results"].items()):
        print(f"{h:>6} {c:>6} {rec['accuracy']:>9.4f}")
    print(f"best: h={info['best_h']} C={info['best_c']} "
          f"acc={info['best_accuracy']:.4f}  "
          f"[{dt:.1f}s total, 2 compressions for "
          f"{len(info['results'])} grid cells x 3 classes]")


if __name__ == "__main__":
    batched_vs_sequential()
    spirals_grid()
