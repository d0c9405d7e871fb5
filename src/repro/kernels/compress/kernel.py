"""Fused assemble-then-ID, Pallas TPU: the HSS compression hot stage.

One grid step = one tree node.  The kernel evaluates the node's sampled
block Aᵀ = K(x_proxy, x_candidate) tile-resident in VMEM — gaussian via the
MXU matmul expansion, laplacian via a per-feature L1 loop — and then
runs the greedy column-pivoted-QR deflation loop of ``idqr.cpqr_select``
directly on that block while it is still on-chip.  Only the pivot indices
(k,) and the projected factor R = QᵀAᵀ (k, m) are written back to HBM: the
(n_proxy, m) sampled block, its residual, and the Q basis never leave VMEM.
Per node that is O(k·m) HBM traffic instead of O(n_proxy·m) plus the
O(k·n_proxy·m) of an unfused deflation loop's intermediate round-trips.

The CPQR loop mirrors ``idqr.cpqr_select`` step for step (same norm,
argmax tie rule, re-orthogonalization, deflation, and exact-zeroing steps)
so the selected pivots are identical to the XLA path on non-degenerate
blocks; all contractions and the deflation state are f32 regardless of
input dtype (bf16 inputs are upcast on load — the precision-accumulate
convention).  The loop's vector products are VPU multiply + reduce over the
(s, m) tile rather than N = 1 matmuls.

Pivot bookkeeping is fully vectorized (one-hot accumulation against a lane
iota, an f32 liveness row) — no dynamic scalar stores and no boolean loop
carry, so the same kernel body compiles with Mosaic for TPU and runs under
``interpret=True`` on CPU.

VMEM budget per grid step at the largest committed shapes (accurate preset
leaf stage: m = 256 candidates, s = 192 proxies, k = 64, f padded to 128):
  xc 256·128·4 = 128 KiB, xp 192·128·4 = 96 KiB, Aᵀ + residual
  2·192·256·4 = 384 KiB, Q 192·64·4 = 48 KiB, R out 64·256·4 = 64 KiB
  — well under 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compress.laplacian import l1_dist


def _assemble_gaussian(xp: jax.Array, xc: jax.Array, h: float) -> jax.Array:
    """exp(-||xp_i - xc_j||² / 2h²) as one MXU contraction + VPU epilogue."""
    np_ = jnp.sum(xp * xp, axis=-1)[:, None]
    nc = jnp.sum(xc * xc, axis=-1)[None, :]
    cross = jax.lax.dot_general(
        xp, xc, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    sq = jnp.maximum(np_ + nc - 2.0 * cross, 0.0)
    return jnp.exp(sq * (-0.5 / (h * h)))


def _assemble_laplacian(xp: jax.Array, xc: jax.Array, h: float,
                        f_real: int) -> jax.Array:
    """exp(-||xp_i - xc_j||₁ / h) by the block kernel's feature loop
    (``laplacian.l1_dist``); the zero-padded feature tail is skipped."""
    return jnp.exp(-l1_dist(xp, xc, f_real) / h)


def _fused_tile(xc_ref, xp_ref, cmask_ref, piv_ref, rfull_ref, *,
                kernel_name: str, h: float, k: int,
                m_real: int, s_real: int, f_real: int):
    """One node: assemble Aᵀ = K(xp, xc) in VMEM, run k CPQR steps on it."""
    xc = xc_ref[0].astype(jnp.float32)            # (m_pad, f_pad) candidates
    xp = xp_ref[0].astype(jnp.float32)            # (s_pad, f_pad) proxies
    m_pad, s_pad = xc.shape[0], xp.shape[0]

    if kernel_name == "laplacian":
        a_t = _assemble_laplacian(xp, xc, h, f_real)
    else:
        a_t = _assemble_gaussian(xp, xc, h)

    # Padding rows/columns hold zero points whose kernel values are garbage
    # (exp of a finite distance, not 0) — mask them to exact zeros, and fold
    # in the caller's candidate-liveness mask (dead child skeletons of the
    # adaptive build; all-ones otherwise).
    row_ok = (jax.lax.broadcasted_iota(jnp.int32, (s_pad, 1), 0)
              < s_real).astype(jnp.float32)
    iota_m = jax.lax.broadcasted_iota(jnp.int32, (1, m_pad), 1)
    col_ok = (iota_m < m_real).astype(jnp.float32)             # (1, m_pad)
    cmask = cmask_ref[0].astype(jnp.float32)                   # (1, m_pad)
    a_t = a_t * row_ok * col_ok * cmask

    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def body(i, carry):
        # ``avail`` is an f32 0/1 liveness row: Mosaic cannot carry a
        # boolean vector through ``scf.for``.
        resid, qs, piv, avail = carry
        norms = jnp.where(avail > 0.5,
                          jnp.sum(resid * resid, axis=0, keepdims=True), -1.0)
        # argmax as max + first matching lane (same tie rule as jnp.argmax),
        # kept (1, 1) so every reduction stays a vector op.
        top = jnp.max(norms, axis=1, keepdims=True)
        p = jnp.min(jnp.where(norms == top, iota_m, m_pad), axis=1,
                    keepdims=True)                             # (1, 1) int32
        onehot = (iota_m == p).astype(jnp.float32)             # (1, m_pad)
        col = jnp.sum(resid * onehot, axis=1, keepdims=True)   # (s_pad, 1)
        nrm = jnp.sqrt(jnp.maximum(top, 1e-30))                # (1, 1)
        q = col / nrm
        # "Twice is enough": re-orthogonalize against prior directions.
        proj = jnp.sum(qs * q, axis=0, keepdims=True)          # (1, k)
        q = q - jnp.sum(qs * proj, axis=1, keepdims=True)
        q = q / jnp.sqrt(jnp.maximum(
            jnp.sum(q * q, axis=0, keepdims=True), 1e-30))
        # Deflate every remaining column; zero the chosen one exactly.
        qr = jnp.sum(q * resid, axis=0, keepdims=True)         # (1, m_pad)
        resid = (resid - q * qr) * (1.0 - onehot)
        # One-hot accumulation instead of dynamic stores (TPU-friendly).
        sel = iota_k == i                                      # (1, k)
        piv = piv + jnp.where(sel, p, 0)
        qs = qs + q * sel.astype(jnp.float32)
        avail = avail * (1.0 - onehot)
        return resid, qs, piv, avail

    qs0 = jnp.zeros((s_pad, k), jnp.float32)
    piv0 = jnp.zeros((1, k), jnp.int32)
    _, qs, piv, _ = jax.lax.fori_loop(
        0, k, body, (a_t, qs0, piv0, col_ok))
    rfull = jax.lax.dot_general(
        qs, a_t, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                    # (k, m_pad)
    piv_ref[0] = piv
    rfull_ref[0] = rfull


@functools.partial(jax.jit, static_argnames=(
    "kernel_name", "h", "k", "m_real", "s_real", "f_real", "interpret"))
def fused_assemble_id_pallas(
    xc: jax.Array,
    xp: jax.Array,
    cmask: jax.Array,
    kernel_name: str,
    h: float,
    k: int,
    m_real: int,
    s_real: int,
    f_real: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Batched fused assemble+CPQR over nodes.

    xc (B, m_pad, f_pad) candidate points, xp (B, s_pad, f_pad) proxy
    points, cmask (B, m_pad) candidate liveness (f32 0/1).  Returns
    (piv (B, k) int32, r_full (B, k, m_pad) f32) — the inputs of
    ``idqr.finish_interp``.  Shapes must arrive pre-padded (ops pads).
    """
    b, m_pad, f_pad = xc.shape
    s_pad = xp.shape[1]
    return pl.pallas_call(
        functools.partial(
            _fused_tile, kernel_name=kernel_name, h=h, k=k,
            m_real=m_real, s_real=s_real, f_real=f_real),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, m_pad, f_pad), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, s_pad, f_pad), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, m_pad), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k, m_pad), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((b, k, m_pad), jnp.float32),
        ],
        interpret=interpret,
    )(xc, xp, cmask)
