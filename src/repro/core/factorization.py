"""ULV-equivalent direct factorization of the shifted HSS matrix.

The paper factorizes K̃_β = K̃ + βI once per (h, β) with STRUMPACK's ULV
(Chandrasekaran–Gu–Pals) and then solves one system per ADMM iteration and
reuses the factorization across the whole C grid.  ULV's node-sequential
orthogonal eliminations are hostile to the MXU/jit, so we compute the
mathematically equivalent telescoping inversion (Gillman–Martinsson HBS
solver), which has the identical compute pattern — O(N r^2) factor once,
O(N r) per solve — but runs as *batched dense ops per tree level*:

  A(ℓ) = D(ℓ) + U(ℓ) A(ℓ−1) U(ℓ)ᵀ          (telescoping form)
  A(ℓ)⁻¹ = G(ℓ) + E(ℓ) (A(ℓ−1) + D̂(ℓ))⁻¹ E(ℓ)ᵀ      with
  D̂ = (Uᵀ D⁻¹ U)⁻¹,   E = D⁻¹ U D̂,   G = D⁻¹ − D⁻¹ U D̂ Uᵀ D⁻¹

(the identity is verified in tests/test_factorization.py against dense
inversion).  At each level the reduced diagonal blocks are assembled from
the children D̂ and the sibling couplings B; the root system is solved dense.

Leaf diagonal blocks of K̃+βI are SPD (Gaussian kernel + positive shift), so
leaves use Cholesky; reduced levels use LU for robustness (the compression
error can perturb definiteness of the small reduced blocks).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from repro.core.hss import HSSMatrix

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HSSFactorization:
    """Factor-once / solve-many artifact for K̃ + beta I."""

    e_leaf: Array               # (n_leaf, m, r0)
    g_leaf: Array               # (n_leaf, m, m)
    e_lvls: tuple[Array, ...]   # per k=1..K-1: (n_k, 2 r_{k-1}, r_k)
    g_lvls: tuple[Array, ...]   # per k=1..K-1: (n_k, 2 r_{k-1}, 2 r_{k-1})
    root_lu: Array              # (2 r_{K-1}, 2 r_{K-1})
    root_piv: Array
    levels: int = dataclasses.field(metadata=dict(static=True))
    leaf_size: int = dataclasses.field(metadata=dict(static=True))
    beta: float = dataclasses.field(metadata=dict(static=True))

    def solve(self, b: Array) -> Array:
        return hss_solve(self, b)

    def solve_mat(self, b: Array) -> Array:
        """Solve for multiple RHS, b of shape (N, c) — one native block sweep."""
        return hss_solve_mat(self, b)


def _leaf_factors(d_shift: Array, u: Array, mask: Array | None = None
                  ) -> tuple[Array, Array, Array]:
    """Batched leaf EGD̂ from Cholesky of the shifted diagonal blocks.

    ``mask`` (n_leaf, r) is the adaptive build's per-node skeleton liveness
    (``HSSMatrix.rank_masks``): dead columns of U are exact zeros, so
    Ŝ = Uᵀ D⁻¹ U is structurally singular — adding 1 on each dead diagonal
    slot makes it [[Ŝ_live, 0], [0, I]] (the zero cross blocks are exact),
    whose inverse keeps the live block's exact D̂ and decouples dead slots
    as inert unit equations: E's dead columns stay exactly 0 and every live
    value matches the factorization of the sliced-down representation.
    """

    def one(d_i: Array, u_i: Array, mask_i: Array | None = None):
        m = d_i.shape[0]
        chol = jsl.cholesky(d_i, lower=True)
        dinv_u = jsl.cho_solve((chol, True), u_i)             # (m, r)
        s_hat = u_i.T @ dinv_u                                # (r, r)
        if mask_i is not None:
            s_hat = s_hat + jnp.diag(1.0 - mask_i)
        d_hat = jnp.linalg.inv(s_hat)
        e_i = dinv_u @ d_hat                                  # (m, r)
        dinv = jsl.cho_solve((chol, True), jnp.eye(m, dtype=d_i.dtype))
        g_i = dinv - e_i @ dinv_u.T
        return e_i, g_i, d_hat

    if mask is None:
        return jax.vmap(one)(d_shift, u)
    return jax.vmap(one)(d_shift, u, mask)


def _level_factors(d_blk: Array, u: Array, mask: Array | None = None
                   ) -> tuple[Array, Array, Array]:
    """Batched reduced-level EGD̂ via LU of the (2r x 2r) assembled blocks.

    ``mask`` (n_k, r_k) regularizes dead PARENT skeleton slots exactly as in
    ``_leaf_factors`` (the transfer's dead columns are exact zeros).
    """

    def one(d_i: Array, u_i: Array, mask_i: Array | None = None):
        c = d_i.shape[0]
        lu, piv = jsl.lu_factor(d_i)
        dinv_u = jsl.lu_solve((lu, piv), u_i)
        s_hat = u_i.T @ dinv_u
        if mask_i is not None:
            s_hat = s_hat + jnp.diag(1.0 - mask_i)
        d_hat = jnp.linalg.inv(s_hat)
        e_i = dinv_u @ d_hat
        dinv = jsl.lu_solve((lu, piv), jnp.eye(c, dtype=d_i.dtype))
        g_i = dinv - e_i @ dinv_u.T
        return e_i, g_i, d_hat

    if mask is None:
        return jax.vmap(one)(d_blk, u)
    return jax.vmap(one)(d_blk, u, mask)


def _assemble_next(d_hat: Array, b: Array) -> Array:
    """Pair children D̂ with their sibling coupling into parent blocks.

    d_hat (n_{k-1}, r, r), b (n_k, r, r)  ->  (n_k, 2r, 2r) blocks
    [[D̂_c1, B], [Bᵀ, D̂_c2]].
    """
    n_k, r = b.shape[0], b.shape[1]
    pair = d_hat.reshape(n_k, 2, r, r)
    top = jnp.concatenate([pair[:, 0], b], axis=-1)
    bot = jnp.concatenate([jnp.swapaxes(b, -1, -2), pair[:, 1]], axis=-1)
    return jnp.concatenate([top, bot], axis=-2)


def factorize(hss: HSSMatrix, beta: float,
              store_dtype: str | None = None) -> HSSFactorization:
    """Factor K̃ + beta*I.  Reused for every ADMM iteration and C value.

    ``store_dtype="bfloat16"`` stores the E/G factors in bf16 (the solve
    accumulates in f32) — halves the solve's HBM traffic, the dominant
    roofline term of the distributed ADMM step (§Perf change D1).  The
    root LU stays f32.
    """
    K, m = hss.levels, hss.leaf_size
    dtype = hss.d_leaf.dtype
    eye = jnp.eye(m, dtype=dtype)
    d_shift = hss.d_leaf + beta * eye

    if K == 0:
        # Degenerate single-block problem: dense Cholesky path.
        chol = jsl.cholesky(d_shift[0], lower=True)
        return HSSFactorization(
            e_leaf=jnp.zeros((1, m, 0), dtype),
            g_leaf=jnp.zeros((1, m, m), dtype),
            e_lvls=(), g_lvls=(),
            root_lu=chol, root_piv=jnp.arange(m, dtype=jnp.int32),
            levels=0, leaf_size=m, beta=beta,
        )

    masks = hss.rank_masks()
    e_leaf, g_leaf, d_hat = _leaf_factors(
        d_shift, hss.u_leaf, None if masks is None else masks[0])
    e_lvls: list[Array] = []
    g_lvls: list[Array] = []
    for k in range(1, K):
        d_blk = _assemble_next(d_hat, hss.b_mats[k - 1])
        e_k, g_k, d_hat = _level_factors(
            d_blk, hss.transfers[k - 1],
            None if masks is None else masks[1][k - 1])
        e_lvls.append(e_k)
        g_lvls.append(g_k)
    root = _assemble_next(d_hat, hss.b_mats[K - 1])[0]
    lu, piv = jsl.lu_factor(root)
    if store_dtype is not None:
        sd = jnp.dtype(store_dtype)
        e_leaf, g_leaf = e_leaf.astype(sd), g_leaf.astype(sd)
        e_lvls = [a.astype(sd) for a in e_lvls]
        g_lvls = [a.astype(sd) for a in g_lvls]
    return HSSFactorization(
        e_leaf=e_leaf, g_leaf=g_leaf,
        e_lvls=tuple(e_lvls), g_lvls=tuple(g_lvls),
        root_lu=lu, root_piv=piv,
        levels=K, leaf_size=m, beta=beta,
    )


def factorize_sharded(hss: HSSMatrix, beta: float, mesh,
                      store_dtype: str | None = None) -> HSSFactorization:
    """Mesh-parallel ``factorize``: E/G emitted already placed per level.

    The level loop is numerically identical to ``factorize`` but runs as ONE
    jitted program whose per-level arrays are pinned (via sharding
    constraints) to the ``distributed.fac_shardings`` layout: leaf and
    lower-level factors stay device-local along the node axis (zero
    communication — every EGD̂ block is an independent small dense solve),
    and ``_assemble_next``'s child-pairing reshape at the first level whose
    node count stops dividing the device count lowers to the one all-gather
    of the (tiny, O(r² n_k)) reduced blocks — the same collective schedule as
    ``hss_solve_mat``.  The result needs NO build-then-``device_put``
    round-trip before the engine's ADMM runs on it.

    Works on an ``hss`` whose arrays are themselves sharded
    (``compression.compress_sharded``) or local; parity with ``factorize``
    is tested to <=1e-5 in tests/test_engine.py.
    """
    from jax.sharding import NamedSharding

    from repro.dist.api import node_partition_spec

    K, m = hss.levels, hss.leaf_size
    if K == 0:
        return factorize(hss, beta, store_dtype=store_dtype)

    def pin(a):
        # The one shared placement rule (dist.api.node_partition_spec):
        # node-stacked arrays shard along the node axis when it divides the
        # device count; everything else (root LU, pivots) replicates.
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, node_partition_spec(mesh, a.ndim,
                                                       a.shape[0])))

    sd = None if store_dtype is None else jnp.dtype(store_dtype)

    @jax.jit
    def _build(d_leaf, u_leaf, transfers, b_mats, leaf_ranks, level_ranks):
        dtype = d_leaf.dtype

        def mask(ranks, cap):
            # Adaptive skeleton-liveness masks (the shared hss.rank_mask
            # rule), built in-graph from the rank vectors so the whole
            # factorization stays ONE jitted program.
            if ranks is None:
                return None
            from repro.core.hss import rank_mask

            return rank_mask(ranks, cap, dtype)

        d_shift = pin(d_leaf) + beta * jnp.eye(m, dtype=dtype)
        e_leaf, g_leaf, d_hat = _leaf_factors(
            d_shift, pin(u_leaf), mask(leaf_ranks, u_leaf.shape[-1]))
        e_leaf, g_leaf, d_hat = pin(e_leaf), pin(g_leaf), pin(d_hat)
        e_lvls, g_lvls = [], []
        for k in range(1, K):
            d_blk = pin(_assemble_next(d_hat, pin(b_mats[k - 1])))
            e_k, g_k, d_hat = _level_factors(
                d_blk, pin(transfers[k - 1]),
                mask(None if leaf_ranks is None else level_ranks[k - 1],
                     transfers[k - 1].shape[-1]))
            e_k, g_k, d_hat = pin(e_k), pin(g_k), pin(d_hat)
            e_lvls.append(e_k)
            g_lvls.append(g_k)
        root = _assemble_next(d_hat, b_mats[K - 1])[0]
        lu, piv = jsl.lu_factor(root)
        lu, piv = pin(lu), pin(piv)
        if sd is not None:
            e_leaf, g_leaf = e_leaf.astype(sd), g_leaf.astype(sd)
            e_lvls = [pin(a.astype(sd)) for a in e_lvls]
            g_lvls = [pin(a.astype(sd)) for a in g_lvls]
        return (pin(e_leaf), pin(g_leaf), tuple(e_lvls), tuple(g_lvls),
                lu, piv)

    e_leaf, g_leaf, e_lvls, g_lvls, lu, piv = _build(
        hss.d_leaf, hss.u_leaf, hss.transfers, hss.b_mats,
        hss.leaf_ranks, hss.level_ranks)
    return HSSFactorization(
        e_leaf=e_leaf, g_leaf=g_leaf,
        e_lvls=e_lvls, g_lvls=g_lvls,
        root_lu=lu, root_piv=piv,
        levels=K, leaf_size=m, beta=beta,
    )


def hss_solve(fac: HSSFactorization, b: Array) -> Array:
    """x = (K̃ + beta I)^{-1} b in O(N r): single-RHS view of the block sweep."""
    return hss_solve_mat(fac, b[:, None])[:, 0]


def hss_solve_mat(fac: HSSFactorization, b: Array) -> Array:
    """X = (K̃ + beta I)^{-1} B for B (N, c): one upward + one downward sweep.

    The RHS block is carried as a trailing axis through every level einsum,
    so all c columns (ADMM iterates of c classes, or a warm-started C grid)
    share a single pass over the E/G factors — the multiclass analogue of
    the paper's factor-once/solve-many economy.

    Every per-level contraction pins ``preferred_element_type=float32``:
    with ``store_dtype="bfloat16"`` the E/G factors are bf16 and implicit
    promotion alone would leave the accumulator dtype to the backend's
    discretion — the f32 accumulation is what makes the bf16 storage mode
    a pure bandwidth win instead of an accuracy cliff (regression-tested in
    tests/test_factorization.py).
    """
    from repro.dist.api import constrain_nodes

    K, m = fac.levels, fac.leaf_size
    c = b.shape[1]
    if K == 0:
        return jsl.cho_solve((fac.root_lu, True), b)

    f32 = jnp.float32
    n_leaf = fac.e_leaf.shape[0]
    b0 = b.reshape(n_leaf, m, c)
    # Upward sweep: project the RHS through Eᵀ level by level.  Under an
    # active mesh every per-level block is pinned to the fac_shardings
    # layout (constrain_nodes) so the pair/unpair reshapes lower to the
    # designed per-level collective schedule.
    bs = [b0]
    bt = constrain_nodes(
        jnp.einsum("nmr,nmc->nrc", fac.e_leaf, b0, preferred_element_type=f32))
    for k in range(1, K):
        n_k = fac.e_lvls[k - 1].shape[0]
        b_k = bt.reshape(n_k, -1, c)                        # (n_k, 2 r_{k-1}, c)
        bs.append(b_k)
        bt = constrain_nodes(
            jnp.einsum("nsr,nsc->nrc", fac.e_lvls[k - 1], b_k,
                       preferred_element_type=f32))
    b_root = bt.reshape(-1, c)
    # root stays f32 regardless of the factor storage dtype
    x_root = jsl.lu_solve(
        (fac.root_lu, fac.root_piv), b_root.astype(fac.root_lu.dtype)
    ).astype(bt.dtype)

    # Downward sweep: x_k = G_k b_k + E_k xi_k.
    xi = x_root.reshape(2, -1, c)                           # level K-1 nodes
    for k in range(K - 1, 0, -1):
        b_k = bs[k]
        x_k = (
            jnp.einsum("nsd,ndc->nsc", fac.g_lvls[k - 1], b_k,
                       preferred_element_type=f32)
            + jnp.einsum("nsr,nrc->nsc", fac.e_lvls[k - 1], xi,
                         preferred_element_type=f32)
        )
        xi = constrain_nodes(
            x_k.reshape(-1, x_k.shape[1] // 2, c))          # children skeleton
    x0 = (
        jnp.einsum("nab,nbc->nac", fac.g_leaf, b0, preferred_element_type=f32)
        + jnp.einsum("nmr,nrc->nmc", fac.e_leaf, xi, preferred_element_type=f32)
    )
    return x0.reshape(-1, c)
