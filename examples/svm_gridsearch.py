"""Hyper-parameter grid search with compression/factorization amortization.

The paper's headline operational win (§3.3): for fixed kernel width h the
HSS approximation + factorization are computed ONCE and reused for every C —
so the grid column costs one ADMM run (~ms-s) instead of a full retrain.

  PYTHONPATH=src python examples/svm_gridsearch.py
"""
import sys
import time

sys.path.insert(0, "src")

from repro.core.compression import CompressionParams
from repro.core.engine import grid_search
from repro.data import synthetic


def main():
    xtr, ytr, xte, yte = synthetic.train_test(
        "susy_like", n_train=16384, n_test=4096, seed=0)

    t0 = time.time()
    model, info = grid_search(
        xtr, ytr, xte, yte,
        hs=[1.0, 3.0], knobs=[0.1, 1.0, 10.0],
        trainer_kwargs=dict(
            comp=CompressionParams(rank=32, n_near=48, n_far=64),
            leaf_size=256, max_it=10),
    )
    dt = time.time() - t0

    print(f"{'h':>6} {'C':>6} {'accuracy':>9} {'admm_s':>8}")
    for (h, c), rec in sorted(info["results"].items()):
        print(f"{h:>6} {c:>6} {rec['accuracy']:>9.4f} {rec['admm_s']:>8.3f}")
    print(f"\nbest: h={info['best_h']} C={info['best_c']} "
          f"acc={info['best_accuracy']:.4f}")
    print(f"total grid time: {dt:.1f}s for {len(info['results'])} cells "
          f"({len(set(h for h, _ in info['results']))} compressions)")


if __name__ == "__main__":
    main()
