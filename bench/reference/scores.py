"""Plain reference for served scores: f(x) = K(x, X_s) v + b with the exact
Gaussian kernel, every matmul at ``highest`` precision, in blocks of query
rows and chunks of support rows, so that no more than ``block`` x
``chunk`` kernel entries are live at once.  Nothing of ``src/repro`` is
imported."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@jax.jit
def _block_scores(xq, xs, v, scale):
    nq = jnp.sum(xq * xq, axis=1)[:, None]
    ns = jnp.sum(xs * xs, axis=1)[None, :]
    cross = jnp.matmul(xq, xs.T, precision=HIGHEST)
    k = jnp.exp(jnp.maximum(nq + ns - 2.0 * cross, 0.0) * scale)
    return jnp.matmul(k, v, precision=HIGHEST)


def scores(xq: np.ndarray, xs: np.ndarray, v: np.ndarray, bias: np.ndarray,
           h: float, block: int = 512, chunk: int = 1 << 19) -> np.ndarray:
    """(m, P) scores of the query rows ``xq`` against support ``xs`` with
    coefficient columns ``v`` (n, P) and biases (P,).  Support rows are
    padded to whole chunks with zero coefficients, which add nothing."""
    n = xs.shape[0]
    chunk = min(chunk, n)
    spad = (-n) % chunk
    xs = np.concatenate([xs, np.zeros((spad, xs.shape[1]), xs.dtype)])
    v = np.concatenate([v, np.zeros((spad, v.shape[1]), v.dtype)])
    parts = [(jnp.asarray(xs[s:s + chunk]), jnp.asarray(v[s:s + chunk]))
             for s in range(0, xs.shape[0], chunk)]
    scale = jnp.float32(-0.5 / (h * h))
    m = xq.shape[0]
    pad = (-m) % block
    xq = np.concatenate([xq, np.zeros((pad, xq.shape[1]), xq.dtype)])
    out = []
    for s in range(0, xq.shape[0], block):
        q = jnp.asarray(xq[s:s + block])
        acc = np.zeros((block, v.shape[1]), np.float64)
        for xs_d, v_d in parts:
            acc += np.asarray(_block_scores(q, xs_d, v_d, scale), np.float64)
        out.append(acc)
    return np.concatenate(out)[:m] + np.asarray(bias, np.float64)[None, :]
