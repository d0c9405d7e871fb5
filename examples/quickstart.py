"""Quickstart: train a nonlinear SVM with HSS-ADMM (the paper's pipeline).

  PYTHONPATH=src python examples/quickstart.py

Steps (= paper Algorithm 3): build cluster tree -> HSS-compress the Gaussian
kernel (partially matrix-free) -> ULV-equivalent factorization -> 10
closed-form ADMM iterations -> bias via one HSS matvec -> predict.
"""
import sys

sys.path.insert(0, "src")

import jax.numpy as jnp

from repro.core.compression import CompressionParams
from repro.core.engine import HSSSVMEngine
from repro.core.kernelfn import KernelSpec
from repro.data import synthetic


def main():
    xtr, ytr, xte, yte = synthetic.train_test(
        "circles", n_train=8192, n_test=2048, seed=0, n_features=4, gap=0.8)

    engine = HSSSVMEngine(
        spec=KernelSpec(name="gaussian", h=1.0),
        comp=CompressionParams(rank=32, n_near=48, n_far=64),
        leaf_size=256,
        max_it=10,                      # the paper fixes MaxIt = 10
    )
    report = engine.prepare(xtr, ytr)    # compress once + factorize once
    print(f"compression:   {report.compression_s:.2f}s")
    print(f"factorization: {report.factorization_s:.2f}s")
    print(f"HSS memory:    {report.memory_mb:.1f} MB "
          f"(dense would be {8192 * 8192 * 4 / 1e6:.0f} MB)")

    model, _ = engine.train(c_value=1.0)    # ADMM only — reusable per C
    print(f"ADMM (10 iters, one C): {engine.report.admm_s:.3f}s")

    acc = float(jnp.mean(model.predict(jnp.asarray(xte)) == yte))
    print(f"test accuracy: {acc:.4f}")


if __name__ == "__main__":
    main()
