"""Tiled laplacian-kernel block evaluation, Pallas TPU.

Computes K = exp(-||xa_i - xb_j||₁ / h) one (bm, bn) output tile at a time.
The L1 distance has no MXU matmul expansion, so each tile accumulates the
distance one feature at a time on the VPU — a (bm, 1) column of xa against a
(1, bn) row of the transposed xb tile, so every intermediate is a plain
(bm, bn) tile — and the exp epilogue fuses into the tile while it is
VMEM-resident.  This is the Pallas twin of ``kernelfn.laplacian_block_xla``.

Padding rows are zero vectors: their pairwise L1 distance to other zero rows
is 0 (kernel value 1), which lands only in cropped-away tiles; zero-padded
FEATURES contribute |0 - 0| = 0 to every distance, so the feature loop
simply stops at the real feature count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

def l1_dist(xa: jax.Array, xb: jax.Array, f_real: int) -> jax.Array:
    """(ma, mb) L1 distances between the rows of f32 tiles xa (ma, f_pad)
    and xb (mb, f_pad), over their first ``f_real`` features.

    The feature loop is unrolled in Python, so every lane slice has a static
    offset (Mosaic has no lowering for a lane ``dynamic_slice``), and each
    step is one (ma, mb) VPU update; a (ma, mb, chunk) broadcast would put
    the short chunk axis on the 128 lanes.
    """
    xbt = xb.T                                 # (f_pad, mb)
    d1 = jnp.zeros((xa.shape[0], xb.shape[0]), jnp.float32)
    for j in range(f_real):
        d1 = d1 + jnp.abs(xa[:, j:j + 1] - xbt[j:j + 1, :])
    return d1


def _laplacian_tile(xa_ref, xb_ref, out_ref, *, inv_h: float, f_real: int):
    xa = xa_ref[...].astype(jnp.float32)       # (bm, f_pad) in VMEM
    xb = xb_ref[...].astype(jnp.float32)       # (bn, f_pad)
    out_ref[...] = jnp.exp(-l1_dist(xa, xb, f_real) * inv_h).astype(
        out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "h", "bm", "bn", "f_real", "interpret"))
def laplacian_block_pallas(
    xa: jax.Array,
    xb: jax.Array,
    h: float,
    bm: int = 256,
    bn: int = 256,
    f_real: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """xa (Ma, F), xb (Mb, F) -> (Ma, Mb). Ma % bm == Mb % bn == 0 (the
    ``laplacian_block`` wrapper pads)."""
    ma, f = xa.shape
    mb = xb.shape[0]
    grid = (ma // bm, mb // bn)
    return pl.pallas_call(
        functools.partial(
            _laplacian_tile, inv_h=1.0 / h,
            f_real=f if f_real is None else f_real),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, f), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, f), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((ma, mb), xa.dtype),
        interpret=interpret,
    )(xa, xb)


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


@functools.partial(jax.jit, static_argnames=("h", "interpret", "bm", "bn"))
def laplacian_block(
    xa: jax.Array,
    xb: jax.Array,
    h: float,
    interpret: bool = False,
    bm: int = 256,
    bn: int = 256,
) -> jax.Array:
    ma, f = xa.shape
    mb = xb.shape[0]
    bm_eff = min(bm, max(((ma + 7) // 8) * 8, 8))
    bn_eff = min(bn, max(((mb + 127) // 128) * 128, 128))
    ma_p = ((ma + bm_eff - 1) // bm_eff) * bm_eff
    mb_p = ((mb + bn_eff - 1) // bn_eff) * bn_eff
    # Feature padding to the lane width; the in-kernel feature loop stops
    # at f, so the zero tail costs nothing.
    f_p = max(((f + 127) // 128) * 128, 128)
    out = laplacian_block_pallas(
        _pad_to(xa, ma_p, f_p), _pad_to(xb, mb_p, f_p),
        h, bm=bm_eff, bn=bn_eff, f_real=f, interpret=interpret,
    )
    return out[:ma, :mb]
