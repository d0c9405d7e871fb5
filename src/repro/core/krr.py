"""KRR and GP posterior mean as ONE multi-RHS solve on the HSS factorization.

The kernel linear-algebra members of the task family: where the box-QP tasks
(repro.core.tasks) iterate ADMM against K_β⁻¹, kernel ridge regression and
the GP posterior mean ARE the solve —

  KRR:   α = (K̃ + λI)⁻¹ y,     f(x) = Σ αᵢ K(xᵢ, x)
  GP:    identical mean (λ = observation noise σ²); model selection adds the
         log marginal likelihood
           log p(y) = −½ yᵀα − ½ log det(K̃ + λI) − (n/2) log 2π
         whose logdet is estimated by Hutchinson probes with Lanczos (Gauss)
         quadrature on the O(N r) matvec — cheap enough to run inside an
         (h, λ) grid scan.

λ rides the factorization's existing β shift slot, so a λ sweep on one
compression is a sequence of O(N r²) refactorizations cached per visited λ
(``HSSSVMEngine._fac_for``), and the trained model scores through the same
``kernel_matvec_streamed`` path as every other task: zero new serving
machinery.  Padded datasets decouple exactly — pad rows of y are zero and
the pad block of K̃ + λI is ≈ (1 + λ)I, so the real-point restriction of the
padded solve is the unpadded solution (the mask still zeroes the pad
coefficients defensively against factorization float noise).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lanczos import lanczos, tridiag_eigh

Array = jax.Array


def krr_solve(fac, targets: Array) -> Array:
    """α = (K̃ + λI)⁻¹ Y for target columns Y (d, P); λ is ``fac.beta``.

    The whole train step of ``task="krr"`` / ``task="gp"``: one telescoping
    multi-RHS solve, ZERO ADMM iterations.  jit-compatible with ``fac`` as a
    pytree argument (β is a static field, so each distinct λ traces once —
    the refactorization it rides along with dominates anyway).
    """
    return fac.solve_mat(targets)


def gp_log_marginal(hss, fac, y: Array, mask: Array | None = None,
                    n_probes: int = 4, num_iters: int = 20, seed: int = 0
                    ) -> float:
    """Hutchinson + Lanczos-quadrature estimate of the GP log marginal.

    The data-fit term −½ yᵀ(K̃ + λI)⁻¹y is exact (one solve on the
    factorization); log det(K̃ + λI) = tr log(K̃ + λI) is estimated with
    ``n_probes`` Rademacher probes, each integrated by an ``num_iters``-point
    Gauss quadrature from the Lanczos tridiagonal of the shifted matvec —
    O(n_probes · num_iters · N r) total, no dense matrix ever formed.

    ``mask`` (1 real / 0 pad) removes the pad block's exact contribution
    n_pad · log(1 + λ) and counts only real points in the 2π term, so the
    estimate ranks (h, λ) on the data, not on the padding.  Deterministic
    for a fixed seed — grid scans compare like against like.
    """
    f32 = jnp.float32
    y = jnp.asarray(y, f32).reshape(-1)
    n = y.shape[0]
    lam = float(fac.beta)
    alpha = fac.solve_mat(y[:, None])[:, 0]
    fit = -0.5 * float(jnp.einsum("n,n->", y, alpha,
                                  preferred_element_type=f32))

    def matvec(v):
        return hss.matvec(v) + lam * v

    keys = jax.random.split(jax.random.PRNGKey(seed), n_probes)
    logdet = 0.0
    for key in keys:
        z = jax.random.rademacher(key, (n,), f32)
        alphas, betas, _ = lanczos(matvec, z, num_iters)
        theta, u = tridiag_eigh(alphas, betas[:-1])
        w = u[0, :] ** 2                     # Gauss weights: (e₁ᵀuᵢ)²
        quad = jnp.einsum("m,m->", w, jnp.log(jnp.maximum(theta, 1e-12)),
                          preferred_element_type=f32)
        logdet += float(n) * float(quad)     # ‖z‖² = n for Rademacher probes
    logdet /= n_probes

    n_eff = n
    if mask is not None:
        n_real = int(np.asarray(jax.device_get(mask)).sum())
        logdet -= (n - n_real) * math.log1p(lam)
        n_eff = n_real
    return fit - 0.5 * logdet - 0.5 * n_eff * math.log(2.0 * math.pi)
