"""Plain reference for the cells that train: the exact-kernel SVM dual
solved by the paper's closed-form ADMM (Algorithm 2), with nothing of
``src/repro`` imported.

Where the program compresses the kernel into an HSS matrix and factorizes
``K~ + beta I`` level by level, this reference builds the exact Gaussian
kernel ``K + beta I`` tile by tile and factorizes it with a right-looking
blocked Cholesky, so that sizes whose dense matrix would not fit in one
piece (2^16 rows is 17 GB in f32) still fit: only the lower triangle is
kept, in ``tile`` x ``tile`` blocks.  Every matmul runs at ``highest``
precision.  k problems (one-vs-rest columns) share the factorization.

Per C value and per iteration (the paper's x-, z- and mu-steps):

    q  = e + mu + beta z,      u = (K + beta I)^-1 (Y q),
    v  = (K + beta I)^-1 e,    lam = sum(u) / sum(v),
    x  = Y (u - lam v),        z = clip(x - mu / beta, 0, C),
    mu = mu - beta (x - z)

then the bias of eq. (7) over the margin support vectors, and the decision
function ``f(x) = K(x, X) (Y z) + b``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import solve_triangular

HIGHEST = jax.lax.Precision.HIGHEST
MARGIN_TOL = 1e-6


def paper_beta(n: int) -> float:
    """The paper's beta rule (section 3.3): 1e2 / 1e3 / 1e4 by rows."""
    if n >= 1_000_000:
        return 1e4
    if n >= 100_000:
        return 1e3
    return 1e2


@jax.jit
def _sqdist(xa, xb):
    na = jnp.sum(xa * xa, axis=1)[:, None]
    nb = jnp.sum(xb * xb, axis=1)[None, :]
    cross = jnp.matmul(xa, xb.T, precision=HIGHEST)
    return jnp.maximum(na + nb - 2.0 * cross, 0.0)


def gaussian(xa, xb, h: float):
    """exp(-||a - b||^2 / (2 h^2)) for row blocks xa, xb."""
    return jnp.exp(_sqdist(xa, xb) * (-0.5 / (h * h)))


@jax.jit
def _trsm_right(l_kk, a_ik):
    # A_ik L_kk^-T, as (L_kk^-1 A_ik^T)^T
    return solve_triangular(l_kk, a_ik.T, lower=True).T


@jax.jit
def _syrk_update(a_ij, l_ik, l_jk):
    return a_ij - jnp.matmul(l_ik, l_jk.T, precision=HIGHEST)


@jax.jit
def _tile_cholesky(a):
    return jnp.linalg.cholesky(a)


@dataclasses.dataclass
class TiledCholesky:
    """Lower Cholesky factor of ``K(x, x) + beta I`` in tiles."""

    tiles: list            # tiles[i][j], j <= i, each (tile, tile)
    tile: int
    beta: float

    @classmethod
    def build(cls, x, h: float, beta: float, tile: int) -> "TiledCholesky":
        n = x.shape[0]
        if n % tile:
            raise ValueError(f"{n} rows do not split into tiles of {tile}")
        nt = n // tile
        xs = [jnp.asarray(x[i * tile:(i + 1) * tile]) for i in range(nt)]
        eye = beta * jnp.eye(tile, dtype=jnp.float32)
        a = [[None] * (i + 1) for i in range(nt)]
        for i in range(nt):
            for j in range(i + 1):
                blk = gaussian(xs[i], xs[j], h)
                a[i][j] = blk + eye if i == j else blk
        for k in range(nt):
            a[k][k] = _tile_cholesky(a[k][k])
            for i in range(k + 1, nt):
                a[i][k] = _trsm_right(a[k][k], a[i][k])
            for i in range(k + 1, nt):
                for j in range(k + 1, i + 1):
                    a[i][j] = _syrk_update(a[i][j], a[i][k], a[j][k])
        return cls(a, tile, float(beta))

    def solve(self, b):
        """(K + beta I)^-1 b for b of shape (n, k)."""
        return _cho_solve(self.tiles, b)

    def kernel_matmul(self, v):
        """K v = L L^T v - beta v."""
        return _llt(self.tiles, v) - self.beta * v


@jax.jit
def _cho_solve(tiles, b):
    nt = len(tiles)
    t = tiles[0][0].shape[0]
    y = []
    for i in range(nt):
        r = b[i * t:(i + 1) * t]
        for j in range(i):
            r = r - jnp.matmul(tiles[i][j], y[j], precision=HIGHEST)
        y.append(solve_triangular(tiles[i][i], r, lower=True))
    out = [None] * nt
    for i in reversed(range(nt)):
        r = y[i]
        for j in range(i + 1, nt):
            r = r - jnp.matmul(tiles[j][i].T, out[j], precision=HIGHEST)
        out[i] = solve_triangular(tiles[i][i], r, lower=True, trans=1)
    return jnp.concatenate(out, axis=0)


@jax.jit
def _llt(tiles, v):
    nt = len(tiles)
    t = tiles[0][0].shape[0]
    w = []                                  # L^T v
    for j in range(nt):
        acc = jnp.zeros((t, v.shape[1]), jnp.float32)
        for i in range(j, nt):
            acc = acc + jnp.matmul(tiles[i][j].T, v[i * t:(i + 1) * t],
                                   precision=HIGHEST)
        w.append(acc)
    out = []                                # L (L^T v)
    for i in range(nt):
        acc = jnp.zeros((t, v.shape[1]), jnp.float32)
        for j in range(i + 1):
            acc = acc + jnp.matmul(tiles[i][j], w[j], precision=HIGHEST)
        out.append(acc)
    return jnp.concatenate(out, axis=0)


def one_vs_rest(y: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(classes, (n, k) +-1 label columns); binary +-1 labels give one
    column, +1 against the rest."""
    classes = np.unique(y)
    if classes.shape[0] == 2 and set(classes.tolist()) == {-1.0, 1.0}:
        return classes, np.where(y > 0, 1.0, -1.0)[:, None].astype(np.float32)
    cols = np.where(y[:, None] == classes[None, :], 1.0, -1.0)
    return classes, cols.astype(np.float32)


@dataclasses.dataclass
class Fit:
    c_value: float
    zy: jax.Array          # (n, k) y_i z_i per problem
    bias: jax.Array        # (k,)


def admm_grid(chol: TiledCholesky, ys, c_values, max_it: int,
              warm_start: bool = True) -> list[Fit]:
    """The paper's ADMM at each C of ``c_values``, warm-started from the
    previous C's (z, mu) as the program's knob sweep is."""
    ys = jnp.asarray(ys)
    n, k = ys.shape
    beta = chol.beta
    e = jnp.ones((n, 1), jnp.float32)
    v = chol.solve(e)                                   # (n, 1)
    z = mu = jnp.zeros((n, k), jnp.float32)
    fits = []
    for c in c_values:
        if not warm_start:
            z = mu = jnp.zeros((n, k), jnp.float32)
        z, mu = _admm(chol, ys, v, z, mu, jnp.float32(c), beta, max_it)
        fits.append(Fit(float(c), ys * z, _bias(chol, ys, z, float(c))))
    return fits


def _admm(chol, ys, v, z, mu, c, beta, max_it):
    for _ in range(max_it):
        q = 1.0 + mu + beta * z
        u = chol.solve(ys * q)
        lam = jnp.sum(u, axis=0) / jnp.sum(v)
        x = ys * (u - lam[None, :] * v)
        z = jnp.clip(x - mu / beta, 0.0, c)
        mu = mu - beta * (x - z)
    return z, mu


def _bias(chol, ys, z, c: float):
    """Eq. (7): b = -(sum_M (K Y z) - sum_M y) / |M| over the margin SVs
    M = {0 < z < C}; the average over all SVs when M is empty."""
    kz = chol.kernel_matmul(ys * z)
    margin = (z > MARGIN_TOL) & (z < c - MARGIN_TOL)
    sv = z > MARGIN_TOL
    out = []
    for sel in (margin, sv):
        s = sel.astype(jnp.float32)
        cnt = jnp.maximum(jnp.sum(s, axis=0), 1.0)
        out.append(-(jnp.sum(s * kz, axis=0) - jnp.sum(s * ys, axis=0)) / cnt)
    return jnp.where(jnp.sum(margin, axis=0) > 0, out[0], out[1])


def decision(x_train, fit: Fit, x_query, h: float, block: int = 4096):
    """f(x) = K(x, X) (Y z) + b, in blocks of query rows: (m, k)."""
    xt = jnp.asarray(x_train)
    out = []
    for s in range(0, x_query.shape[0], block):
        kq = gaussian(jnp.asarray(x_query[s:s + block]), xt, h)
        out.append(jnp.matmul(kq, fit.zy, precision=HIGHEST))
    return np.asarray(jnp.concatenate(out, axis=0) + fit.bias[None, :])


def labels(scores: np.ndarray, classes: np.ndarray) -> np.ndarray:
    """Binary: sign; k columns: the class of the largest score."""
    if scores.shape[1] == 1:
        return np.where(scores[:, 0] >= 0, 1, -1)
    return classes[np.argmax(scores, axis=1)]
