"""ε-SVR on the shared HSS factorization (factor once, sweep ε-many).

The ε-SVR difference-form dual rides the SAME K̃ + βI factorization the
classifier uses — only the O(d) linear term and the z-step's soft-threshold
change with (y, ε).  This demo trains on the noisy-sine generator, sweeps
the ε tube on one compression + factorization, and runs the (h, ε) grid.

  PYTHONPATH=src python examples/svr.py
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


def epsilon_sweep():
    xtr, ytr, xte, yte = synthetic.train_test(
        "noisy_sine", n_train=8192, n_test=2048, seed=0, noise=0.1)
    engine = HSSSVMEngine(spec=KernelSpec(h=1.0), comp=COMP, leaf_size=256,
                          max_it=10, task="svr", svr_c=2.0)
    t0 = time.time()
    rep = engine.prepare(xtr, ytr)
    print(f"noisy sine, n=8192 (noise std 0.1): compressed "
          f"{rep.compression_s:.1f}s + factorized {rep.factorization_s:.2f}s "
          f"ONCE for the whole ε sweep")
    warm = None
    print(f"{'eps':>6} {'rmse':>8} {'SV frac':>8}")
    for eps in (0.02, 0.05, 0.1, 0.2, 0.4):
        model, warm = engine.train(eps, warm=warm)
        pred = np.asarray(model.predict(jnp.asarray(xte)))
        rmse = float(np.sqrt(np.mean((pred - yte) ** 2)))
        sv_frac = float(np.mean(np.abs(np.asarray(model.z_y)) > 1e-5))
        print(f"{eps:>6} {rmse:>8.4f} {sv_frac:>8.3f}")
    print(f"[{time.time() - t0:.1f}s total; a wider ε tube means fewer "
          f"support vectors until the fit degrades]\n")


def h_eps_grid():
    xtr, ytr, xte, yte = synthetic.train_test(
        "noisy_step", n_train=4096, n_test=1024, seed=0, noise=0.05)
    t0 = time.time()
    model, info = grid_search(
        xtr, ytr, xte, yte, hs=[0.2, 0.5], knobs=[0.02, 0.1, 0.3], task="svr",
        trainer_kwargs=dict(svr_c=2.0, comp=COMP, leaf_size=128, max_it=10))
    print("noisy step (h, ε) grid (scores are negated validation RMSE):")
    print(f"{'h':>6} {'eps':>6} {'rmse':>8}")
    for (h, e), rec in sorted(info["results"].items()):
        print(f"{h:>6} {e:>6} {-rec['accuracy']:>8.4f}")
    print(f"best: h={info['best_h']} eps={info['best_c']} "
          f"rmse={-info['best_accuracy']:.4f}  "
          f"[{time.time() - t0:.1f}s, 2 compressions for "
          f"{len(info['results'])} cells]")


if __name__ == "__main__":
    epsilon_sweep()
    h_eps_grid()
