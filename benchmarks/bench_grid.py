"""Paper Figure 2 + §3.3 amortization: (h, C) grid search.

Produces the accuracy heat-map data over h x C and measures the paper's
headline speed-up: total grid time with compress-once/factor-once reuse vs
the naive retrain-from-scratch-per-C estimate.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core.compression import CompressionParams
from repro.core.kernelfn import KernelSpec
from repro.core.svm import HSSSVMTrainer, grid_search
from repro.data import synthetic

HS = (0.3, 1.0, 3.0)
CS = (0.1, 1.0, 10.0)


def run(csv_rows: list) -> None:
    xtr, ytr, xte, yte = synthetic.train_test(
        "circles", 8192, 2048, seed=2, n_features=4, gap=0.6, noise=0.25)
    t0 = time.perf_counter()
    model, info = grid_search(
        xtr, ytr, xte, yte, hs=HS, cs=CS,
        trainer_kwargs=dict(
            comp=CompressionParams(rank=32, n_near=48, n_far=64),
            leaf_size=256, max_it=10))
    t_grid = time.perf_counter() - t0

    total_admm = 0.0
    total_setup = 0.0
    for (h, c), rec in info["results"].items():
        csv_rows.append((
            f"svm_fig2/h{h}/C{c}", rec["admm_s"] * 1e6,
            f"acc={rec['accuracy']:.4f}"))
    # setup cost appears once per h; admm cost once per (h, C)
    per_h = {}
    for (h, c), rec in info["results"].items():
        per_h[h] = rec["compression_s"] + rec["factorization_s"]
        total_admm += rec["admm_s"]
    total_setup = sum(per_h.values())
    naive = total_setup * len(CS) + total_admm   # recompress for every C
    csv_rows.append((
        "svm_grid_amortization", t_grid * 1e6,
        f"grid_s={t_grid:.2f};setup_s={total_setup:.2f};"
        f"admm_total_s={total_admm:.2f};naive_estimate_s={naive:.2f};"
        f"speedup={naive / max(t_grid, 1e-9):.2f};"
        f"best_h={info['best_h']};best_C={info['best_c']};"
        f"best_acc={info['best_accuracy']:.4f}"))


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    rows = []
    run(rows)
    for r in rows:
        print(",".join(str(x) for x in r))
