"""Paper Tables 2/3 analogue: baselines with the TRUE kernel matrix.

  dense-ADMM   — exact kernel + dense Cholesky (the RACQP role, Table 3)
  SMO          — max-violating-pair working-set solver (the LIBSVM role,
                 Table 2)
  nystrom-ADMM — low-rank approximation rival (paper §1.1's alternative)
  hss-ADMM     — ours

The paper's claim to reproduce: comparable accuracy, with HSS-ADMM's
*training* time flat in n while exact-kernel baselines blow up — the
crossover is visible already at CPU-feasible sizes.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines
from repro.core.compression import CompressionParams
from repro.core.engine import HSSSVMEngine
from repro.core.kernelfn import KernelSpec
from repro.data import synthetic


def run(csv_rows: list) -> None:
    h, c_val = 1.0, 1.0
    for n_train in (1024, 4096):
        xtr, ytr, xte, yte = synthetic.train_test(
            "circles", n_train, 1024, seed=1, n_features=4, gap=0.8)
        xj, yj = jnp.asarray(xtr), jnp.asarray(ytr)
        xtj = jnp.asarray(xte)
        spec = KernelSpec(h=h)

        # ---- dense ADMM (RACQP analogue) ----
        t0 = time.perf_counter()
        z, b = baselines.dense_admm_fit(xj, yj, spec, c_val, beta=100.0)
        jax.block_until_ready(z)
        t_dense = time.perf_counter() - t0
        acc = float(jnp.mean(
            baselines.dense_predict(xj, yj, z, b, spec, xtj) == yte))
        csv_rows.append((f"svm_table23/dense_admm/n{n_train}", t_dense * 1e6,
                         f"acc={acc:.4f};runtime_s={t_dense:.3f}"))

        # ---- SMO (LIBSVM analogue) ----
        t0 = time.perf_counter()
        alpha, b_smo, iters = baselines.smo_fit(xtr, ytr, spec, c_val,
                                                max_iter=4000)
        t_smo = time.perf_counter() - t0
        scores = np.asarray(
            baselines.dense_predict(xj, yj, jnp.asarray(alpha, jnp.float32),
                                    b_smo, spec, xtj))
        acc = float((scores == yte).mean())
        csv_rows.append((f"svm_table23/smo/n{n_train}", t_smo * 1e6,
                         f"acc={acc:.4f};runtime_s={t_smo:.3f};iters={iters}"))

        # ---- Nystrom ADMM ----
        t0 = time.perf_counter()
        z, b = baselines.nystrom_admm_fit(xj, yj, spec, c_val, beta=100.0,
                                          n_landmarks=min(256, n_train))
        jax.block_until_ready(z)
        t_nys = time.perf_counter() - t0
        acc = float(jnp.mean(
            baselines.dense_predict(xj, yj, z, b, spec, xtj) == yte))
        csv_rows.append((f"svm_table23/nystrom_admm/n{n_train}", t_nys * 1e6,
                         f"acc={acc:.4f};runtime_s={t_nys:.3f}"))

        # ---- HSS ADMM (ours) ----
        engine = HSSSVMEngine(
            spec=spec, comp=CompressionParams(rank=32, n_near=48, n_far=64),
            leaf_size=128, max_it=10)
        t0 = time.perf_counter()
        model = engine.fit(xtr, ytr, c_value=c_val)
        t_hss = time.perf_counter() - t0
        acc = float(jnp.mean(model.predict(xtj) == yte))
        csv_rows.append((
            f"svm_table23/hss_admm/n{n_train}", t_hss * 1e6,
            f"acc={acc:.4f};runtime_s={t_hss:.3f};"
            f"admm_only_s={engine.report.admm_s:.3f}"))


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    rows = []
    run(rows)
    for r in rows:
        print(",".join(str(x) for x in r))
