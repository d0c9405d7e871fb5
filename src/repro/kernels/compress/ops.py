"""Public wrapper for the fused assemble+ID Pallas kernel.

Pads every node's candidate/proxy point blocks to TPU tile boundaries
(candidates to the 128-lane width — they are the columns of the on-chip
sampled block — proxies to the 8-sublane width, features to the lane
width), launches ALL nodes of a tree level as one batched Pallas dispatch,
and finishes the interpolative decomposition with the shared
``idqr.finish_interp`` truncation + triangular solve on the small (k, m)
projected factor the kernel wrote back.

Numerics: pivot selection and the projected factor R = QᵀAᵀ match
``idqr.cpqr_select`` on the XLA-assembled block (same operation order, f32
state), and the finish stage IS the XLA path's code — so the fused row ID
equals ``idqr.row_interp_decomp(_ranked)`` of the XLA-evaluated block up to
f32 rounding, with identical pivots on non-degenerate blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import idqr
from repro.kernels.compress.kernel import fused_assemble_id_pallas


def _pad3(x: jax.Array, rows: int, cols: int) -> jax.Array:
    return jnp.pad(
        x, ((0, 0), (0, rows - x.shape[1]), (0, cols - x.shape[2])))


@functools.partial(jax.jit, static_argnames=(
    "k", "kernel_name", "h", "rtol", "adaptive", "interpret"))
def _batched_assemble_id(
    xc: jax.Array,
    xp: jax.Array,
    cmask: jax.Array,
    k: int,
    kernel_name: str,
    h: float,
    rtol: float,
    adaptive: bool,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    b, m, f = xc.shape
    s = xp.shape[1]
    m_p = max(-(-m // 128) * 128, 128)
    s_p = max(-(-s // 8) * 8, 8)
    f_p = max(-(-f // 128) * 128, 128)
    piv, r_full = fused_assemble_id_pallas(
        _pad3(xc, m_p, f_p), _pad3(xp, s_p, f_p),
        jnp.pad(cmask.astype(jnp.float32), ((0, 0), (0, m_p - m)))[:, None],
        kernel_name=kernel_name, h=h, k=k,
        m_real=m, s_real=s, f_real=f, interpret=interpret)
    piv = piv[:, 0]
    r_full = r_full[:, :, :m]
    t_full, ranks = jax.vmap(
        lambda p, r: idqr.finish_interp(
            p, r, rtol, keep_identity=not adaptive))(piv, r_full)
    p_mat = jnp.transpose(t_full, (0, 2, 1)).astype(xc.dtype)   # (B, m, k)
    return piv, p_mat, ranks


def batched_assemble_id(
    xc: jax.Array,
    xp: jax.Array,
    k: int,
    *,
    kernel_name: str,
    h: float,
    rtol: float,
    adaptive: bool,
    cmask: jax.Array | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """All row IDs of one tree level in ONE fused Pallas launch.

    xc (B, m, f): each node's candidate points (leaf points / child
    skeletons); xp (B, s, f): each node's proxy points (near + far).
    Returns (piv (B, k) int32, p_mat (B, m, k) in xc.dtype, ranks (B,)
    int32) — exactly the per-node ``idqr.row_interp_decomp(_ranked)`` of
    the sampled blocks K(xc_i, xp_i), without ever materializing them in
    HBM.  ``adaptive=False`` reproduces fixed-rank semantics (all-k ranks,
    identity on every skeleton column); ``cmask`` (B, m) zeroes dead
    candidate rows before pivoting (adaptive upper levels).
    """
    if cmask is None:
        cmask = jnp.ones(xc.shape[:2], jnp.float32)
    return _batched_assemble_id(
        xc, xp, cmask, k, kernel_name, h, float(rtol), bool(adaptive),
        bool(interpret))
