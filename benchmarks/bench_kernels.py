"""Kernel microbenchmarks + HSS scaling evidence.

  * gaussian/admm/ssd/attention Pallas kernels (interpret mode — correctness
    path; TPU wall-times come from the roofline analysis, not CPU timing)
  * HSS matvec / factorize / solve scaling in N at fixed rank — the paper's
    O(N r) / O(N r^2) claims: time ratios across doublings should approach
    2x, not 4x.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression, factorization, tree as tree_mod
from repro.core.kernelfn import KernelSpec


def _timeit(fn, n_iter=3):
    fn()  # compile
    t0 = time.perf_counter()
    for _ in range(n_iter):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n_iter


def run(csv_rows: list) -> None:
    rng = np.random.default_rng(0)

    # --- gaussian block kernel (XLA path — production CPU path) ---
    xa = jnp.asarray(rng.normal(size=(1024, 16)), jnp.float32)
    from repro.core.kernelfn import gaussian_block_xla

    dt = _timeit(lambda: gaussian_block_xla(xa, xa, 1.0))
    csv_rows.append(("kernel_gaussian_xla_1024x1024", dt * 1e6,
                     f"gbps={(1024*1024*4)/dt/1e9:.2f}"))

    # --- HSS scaling in N ---
    prev = {}
    for n in (2048, 4096, 8192):
        x = rng.normal(size=(n, 4)).astype(np.float32)
        t = tree_mod.build_tree(x, leaf_size=256)
        xp = jnp.asarray(x[t.perm])
        spec = KernelSpec(h=1.0)
        t0 = time.perf_counter()
        hss = compression.compress(
            xp, t, spec,
            compression.CompressionParams(rank=32, n_near=32, n_far=48))
        jax.block_until_ready(hss.d_leaf)
        t_comp = time.perf_counter() - t0

        t0 = time.perf_counter()
        fac = factorization.factorize(hss, 100.0)
        jax.block_until_ready(fac.root_lu)
        t_fac = time.perf_counter() - t0

        b = jnp.asarray(rng.normal(size=n), jnp.float32)
        solve = jax.jit(fac.solve)
        t_solve = _timeit(lambda: solve(b), n_iter=5)
        mv = jax.jit(hss.matvec)
        t_mv = _timeit(lambda: mv(b), n_iter=5)

        ratios = ""
        if prev:
            ratios = (f";solve_ratio={t_solve/prev['solve']:.2f}"
                      f";matvec_ratio={t_mv/prev['mv']:.2f}")
        csv_rows.append((
            f"hss_scaling/n{n}", t_solve * 1e6,
            f"compress_s={t_comp:.2f};factor_s={t_fac:.2f};"
            f"solve_us={t_solve*1e6:.0f};matvec_us={t_mv*1e6:.0f}"
            f";mem_mb={hss.memory_bytes()/1e6:.1f}" + ratios))
        prev = dict(solve=t_solve, mv=t_mv)

    # --- pallas kernels, interpret mode (correctness-path cost) ---
    from repro.kernels.admm_update import ops as aops

    xv = jnp.asarray(rng.normal(size=65536), jnp.float32)
    mu = jnp.zeros(65536, jnp.float32)
    cv = jnp.ones(65536, jnp.float32)
    dt = _timeit(lambda: aops.fused_zmu_update(xv, mu, cv, 100.0,
                                               interpret=True))
    csv_rows.append(("kernel_admm_fused_interpret_64k", dt * 1e6, ""))

    from repro.kernels.ssd import ops as sops

    x = jnp.asarray(rng.normal(size=(1, 128, 4, 32)), jnp.float32)
    dts = jnp.asarray(np.abs(rng.normal(size=(1, 128, 4))) * 0.1 + 0.01,
                      jnp.float32)
    a = jnp.asarray(-np.ones(4), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(1, 128, 1, 16)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(1, 128, 1, 16)) * 0.3, jnp.float32)
    dv = jnp.zeros(4, jnp.float32)
    dt = _timeit(lambda: sops.ssd_forward(x, dts, a, bm, cm, dv, chunk=32,
                                          interpret=True))
    csv_rows.append(("kernel_ssd_interpret_s128", dt * 1e6, ""))


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    rows = []
    run(rows)
    for r in rows:
        print(",".join(str(x) for x in r))
