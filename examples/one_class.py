"""One-class (ν-)SVM novelty detection on the shared HSS factorization.

The one-class dual is the simplest member of the box-QP family — no labels,
no linear term, box [0, 1/(νn)] with eᵀα = 1 — and it reuses the exact
compression + factorization machinery of the classifier.  ν directly bounds
the fraction of training points flagged as outliers; this demo sweeps ν on
one factorization and reports holdout precision/recall against the
generator's ground truth.

  PYTHONPATH=src python examples/one_class.py
"""
import sys
import time

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.core.compression import CompressionParams
from repro.core.engine import HSSSVMEngine, grid_search
from repro.core.kernelfn import KernelSpec
from repro.core.tasks import oneclass_metrics
from repro.data import synthetic

COMP = CompressionParams(rank=32, n_near=48, n_far=64)


def nu_sweep():
    xtr, _ytr = synthetic.blobs_with_outliers(8192, n_features=4,
                                              outlier_frac=0.1, seed=0)
    xte, yte = synthetic.blobs_with_outliers(2048, n_features=4,
                                             outlier_frac=0.1, seed=1)
    engine = HSSSVMEngine(spec=KernelSpec(h=2.0), comp=COMP, leaf_size=256,
                          max_it=30, task="oneclass")
    t0 = time.time()
    rep = engine.prepare(xtr)            # unsupervised: no labels
    print(f"blobs+outliers, n=8192 (10% planted outliers): compressed "
          f"{rep.compression_s:.1f}s + factorized {rep.factorization_s:.2f}s "
          f"ONCE for the whole ν sweep")
    warm = None
    print(f"{'nu':>6} {'train outlier frac':>19} {'precision':>10} "
          f"{'recall':>7}")
    for nu in (0.02, 0.05, 0.1, 0.2):
        model, warm = engine.train(nu, warm=warm)
        pred_tr = np.asarray(model.predict(jnp.asarray(xtr)))
        m = oneclass_metrics(model.predict(jnp.asarray(xte)), yte)
        print(f"{nu:>6} {float(np.mean(pred_tr < 0)):>19.3f} "
              f"{m['precision']:>10.3f} {m['recall']:>7.3f}")
    print(f"[{time.time() - t0:.1f}s total; ν upper-bounds the training "
          f"outlier fraction — the Schölkopf ν-property]\n")


def h_nu_grid():
    xtr, _ = synthetic.blobs_with_outliers(4096, n_features=4,
                                           outlier_frac=0.1, seed=0)
    xval, yval = synthetic.blobs_with_outliers(1024, n_features=4,
                                               outlier_frac=0.1, seed=2)
    t0 = time.time()
    model, info = grid_search(
        xtr, None, xval, yval, hs=[1.0, 2.0], knobs=[0.05, 0.1, 0.2],
        task="oneclass",
        trainer_kwargs=dict(comp=COMP, leaf_size=128, max_it=30))
    print("(h, ν) grid (scores are balanced inlier/outlier accuracy):")
    print(f"{'h':>6} {'nu':>6} {'balanced acc':>13}")
    for (h, nu), rec in sorted(info["results"].items()):
        print(f"{h:>6} {nu:>6} {rec['accuracy']:>13.4f}")
    print(f"best: h={info['best_h']} nu={info['best_c']} "
          f"balanced_acc={info['best_accuracy']:.4f}  "
          f"[{time.time() - t0:.1f}s, 2 compressions for "
          f"{len(info['results'])} cells]")


if __name__ == "__main__":
    nu_sweep()
    h_nu_grid()
