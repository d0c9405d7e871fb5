"""Public wrapper for the fused ADMM update."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.admm_update.kernel import fused_zmu_update_pallas
from repro.kernels.admm_update.ref import fused_zmu_update_ref


@functools.partial(jax.jit, static_argnames=("beta", "interpret", "use_pallas"))
def fused_zmu_update(
    x: jax.Array, mu: jax.Array, c_vec: jax.Array, beta: float,
    interpret: bool = False, use_pallas: bool = True,
) -> tuple[jax.Array, jax.Array]:
    if not use_pallas:
        return fused_zmu_update_ref(x, mu, c_vec, beta)
    n = x.shape[0]
    block = min(65536, max(((n + 127) // 128) * 128, 128))
    n_p = ((n + block - 1) // block) * block
    pad = lambda a: jnp.pad(a, (0, n_p - n))
    z, mu_new = fused_zmu_update_pallas(
        pad(x), pad(mu), pad(c_vec), beta, block=block, interpret=interpret
    )
    return z[:n], mu_new[:n]
