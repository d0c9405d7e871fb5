"""Mesh-parallel HSS-ADMM: build + factor + train sharded end-to-end.

Runs on 8 emulated host devices (the same code lowers on the 256/512-chip
production meshes — see launch/dryrun.py --arch svm-hss-admm).  Unlike the
pre-engine flow (single-device compress/factorize, then device_put), EVERY
stage here is mesh-parallel from the start: leaf kernel blocks, ID-QR bases,
E/G factors, ADMM iterates, bias extraction and prediction scoring all live
sharded over the node/sample axis — no device ever holds an unsharded
O(N·m) array.

  PYTHONPATH=src python examples/distributed_svm.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import CompressionParams
from repro.core.engine import HSSSVMEngine
from repro.core.kernelfn import KernelSpec
from repro.data import synthetic
from repro.launch.mesh import make_data_mesh


def main():
    print(f"devices: {jax.device_count()}")
    n = 16384
    xtr, ytr, xte, yte = synthetic.train_test(
        "blobs", n, 2048, seed=0, n_features=8, sep=1.8)

    mesh = make_data_mesh()
    engine = HSSSVMEngine(
        spec=KernelSpec(h=1.0),
        comp=CompressionParams(rank=32, n_near=48, n_far=64),
        leaf_size=256, beta=100.0, max_it=10, mesh=mesh)

    rep = engine.prepare(xtr, ytr)     # sharded compress + factorize, ONCE
    print(f"compress {rep.compression_s:.1f}s / factorize "
          f"{rep.factorization_s:.2f}s / HSS memory {rep.memory_mb:.1f} MB "
          f"across {jax.device_count()} devices")
    shard = engine.fac.e_leaf.addressable_shards[0].data.shape
    print(f"e_leaf: global {tuple(engine.fac.e_leaf.shape)}, "
          f"per-device {tuple(shard)}")

    # compress once, factor once, sweep C warm-started — the paper's
    # amortization claim, with every stage mesh-parallel via the engine
    c_grid = [0.1, 1.0, 10.0]
    for c, model in zip(c_grid, engine.train_grid(c_grid)):
        acc = float(jnp.mean(model.predict(jnp.asarray(xte)) == yte))
        sv = int(jnp.sum(jnp.abs(model.z_y) > 1e-6))
        print(f"C={c:>5}: holdout acc {acc:.4f}, "
              f"support vectors {sv} / {n}")
    print(f"z_y sharding: {model.z_y.sharding}")


if __name__ == "__main__":
    main()
