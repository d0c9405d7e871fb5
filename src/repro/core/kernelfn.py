"""Kernel functions evaluated block-wise.

The Gaussian kernel K(x, y) = exp(-||x-y||^2 / (2 h^2)) is the paper's choice
(Cipolla & Gondzio §3.3).  Block evaluation is the compute hot-spot of both
HSS compression (sampled blocks) and prediction (test × support blocks); the
Pallas kernel in ``repro.kernels.gaussian`` implements the tiled TPU version,
and this module provides the XLA path plus the dispatch switch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array

# The ONE streaming block-size default shared by every predict/score path
# (EngineModel / the serving tier): the row count of each test×support
# kernel block kept live during scoring.  Serving tunes it in one place
# (serve.BatchPolicy.block defaults to it).
DEFAULT_SCORE_BLOCK = 2048


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """A positive-definite kernel with a single bandwidth-like parameter h."""

    name: str = "gaussian"
    h: float = 1.0
    # "xla" | "pallas" | "pallas_interpret" — which block-eval backend to use.
    impl: str = "xla"

    def with_h(self, h: float) -> "KernelSpec":
        return dataclasses.replace(self, h=h)


def _sqdist(xa: Array, xb: Array) -> Array:
    """Pairwise squared distances via the matmul expansion (MXU-friendly).

    The cross term accumulates in f32 (``preferred_element_type``): bf16
    inputs — the serving tier's compute_dtype="bfloat16" path feeds them in
    here — would otherwise accumulate the feature contraction in bf16 and
    hand systematically-off distances to ``exp``.  The f32 cross term also
    promotes the norms, so distances come out f32 regardless of input dtype.
    """
    na = jnp.sum(xa * xa, axis=-1, dtype=jnp.float32)[:, None]
    nb = jnp.sum(xb * xb, axis=-1, dtype=jnp.float32)[None, :]
    cross = jnp.matmul(xa, xb.T, preferred_element_type=jnp.float32)
    return jnp.maximum(na + nb - 2.0 * cross, 0.0)


def gaussian_block_xla(xa: Array, xb: Array, h: float) -> Array:
    """K(xa, xb) for row blocks xa (ma, r), xb (mb, r) -> (ma, mb).

    Distances and exp run in f32 (see ``_sqdist``); the block is then cast
    back to the input dtype — a bf16 build (store_dtype="bfloat16") must get
    bf16 blocks, with only the internal ACCUMULATION widened.
    """
    block = jnp.exp(_sqdist(xa, xb) * (-0.5 / (h * h)))
    return block.astype(jnp.result_type(xa.dtype, xb.dtype))


def laplacian_block_xla(xa: Array, xb: Array, h: float,
                        f_chunk: int = 16) -> Array:
    """exp(-||x-y||_1 / h); an optional PD kernel variant.

    The L1 distance has no matmul expansion, so the naive broadcast builds a
    (ma, mb, f) intermediate — at prediction block sizes that is the largest
    live array of the whole scoring path.  Instead the feature axis is
    scanned in ``f_chunk``-wide slices: live memory is O(ma * mb * f_chunk)
    regardless of the feature count (zero-padded tail chunks contribute
    |0 - 0| = 0 to the distance).
    """
    f = xa.shape[-1]
    n_chunks = -(-f // f_chunk)
    pad = n_chunks * f_chunk - f
    xa_c = jnp.moveaxis(
        jnp.pad(xa, ((0, 0), (0, pad))).reshape(xa.shape[0], n_chunks, f_chunk),
        1, 0)
    xb_c = jnp.moveaxis(
        jnp.pad(xb, ((0, 0), (0, pad))).reshape(xb.shape[0], n_chunks, f_chunk),
        1, 0)

    def body(acc, ab):
        a, b = ab
        return acc + jnp.sum(jnp.abs(a[:, None, :] - b[None, :, :]), -1), None

    d1, _ = jax.lax.scan(
        body, jnp.zeros((xa.shape[0], xb.shape[0]), xa.dtype), (xa_c, xb_c))
    return jnp.exp(-d1 / h)


_VALID_IMPLS = ("xla", "pallas", "pallas_interpret")


def kernel_block(spec: KernelSpec, xa: Array, xb: Array) -> Array:
    """Evaluate a (len(xa), len(xb)) kernel block under ``spec``.

    Both kernels have a Pallas implementation: gaussian in
    ``repro.kernels.gaussian`` (MXU matmul expansion) and laplacian in
    ``repro.kernels.compress.laplacian`` (feature-chunked L1 scan, the tiled
    twin of ``laplacian_block_xla``).  Unknown ``impl`` values raise instead
    of silently running XLA.
    """
    if spec.impl not in _VALID_IMPLS:
        raise ValueError(
            f"unknown kernel impl {spec.impl!r}; expected one of {_VALID_IMPLS}")
    if spec.name == "gaussian":
        if spec.impl in ("pallas", "pallas_interpret"):
            # Deferred import: kernels package depends on core being importable.
            from repro.kernels.gaussian import ops as gops

            return gops.gaussian_block(
                xa, xb, spec.h, interpret=(spec.impl == "pallas_interpret")
            )
        return gaussian_block_xla(xa, xb, spec.h)
    if spec.name == "laplacian":
        if spec.impl in ("pallas", "pallas_interpret"):
            from repro.kernels.compress import laplacian as lops

            return lops.laplacian_block(
                xa, xb, spec.h, interpret=(spec.impl == "pallas_interpret")
            )
        return laplacian_block_xla(xa, xb, spec.h)
    raise ValueError(f"unknown kernel {spec.name!r}")


def kernel_matvec_streamed(
    spec: KernelSpec, x_rows: Array, x_cols: Array, v: Array,
    block: int = DEFAULT_SCORE_BLOCK,
) -> Array:
    """(K(x_rows, x_cols) @ v) without materializing the full block.

    Streams over row blocks with ``lax.map`` — O(block * n_cols) live memory.
    Used by prediction when the support set is large.  ``v`` may be a single
    (n_cols,) vector or a (n_cols, k) block — multiclass prediction scores
    all k per-class coefficient columns against each kernel block while it
    is live, so k classes cost one pass over the kernel, not k.
    """
    n = x_rows.shape[0]
    pad = (-n) % block
    xr = jnp.pad(x_rows, ((0, pad), (0, 0)))
    xr = xr.reshape(-1, block, x_rows.shape[1])

    def body(xblk):
        # f32 accumulation over the (potentially huge) support axis — a bf16
        # coefficient vector must not drag the reduction down to bf16.
        return jnp.matmul(kernel_block(spec, xblk, x_cols), v,
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(body, xr)
    out = out.reshape(-1) if v.ndim == 1 else out.reshape(-1, v.shape[1])
    return out[:n]
