"""HSS-ANN-style compression of a kernel matrix, partially matrix-free.

Paper §3.1 / Chávez et al. IPDPS'20: instead of random sketching, use the
data geometry to pick the kernel entries that matter.  TPU adaptation
(DESIGN.md §3.2):

  * proxy columns per node = NEAR points (the sibling cluster — the ANN
    surrogate: boundary neighbours dominate the off-diagonal block's range)
    + FAR points (uniform sample of the complement) — index sets built once
    on the host;
  * skeleton selection per node = interpolative decomposition via pivoted QR
    on the sampled block (repro.core.idqr), vmapped over all nodes of a
    level; a large internal node's transfer matrix is the kernel's own
    interpolation from its skeleton instead (``_kernel_interp``);
  * total kernel evaluations O(N * n_proxy) — never the full matrix.

Construction cost O(r^2 N) and storage O(r N), matching the paper's claims
for HSS-ANN (§1.2).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import idqr
from repro.core.hss import HSSMatrix, rank_mask
from repro.core.kernelfn import KernelSpec, kernel_block
from repro.core.tree import ClusterTree

Array = jax.Array

KERNEL_EVALS = "hss.kernel_evals"


@contextlib.contextmanager
def counting_kernel_evals():
    """Count the kernel entries a ``compress`` call actually evaluates.

    Every kernel evaluation inside the build flows through the two seams
    below (``_batched_kernel_block`` / ``_batched_row_id``), which add the
    logical block sizes to the recorder counter ``hss.kernel_evals``
    whenever their operands are concrete — i.e. for the eager
    host-orchestrated ``compress``.  Inside traced contexts
    (``compress_sharded``'s shard_map bodies) the operands are tracers and
    nothing is counted: per-device shapes would double-count.

    Yields a dict whose ``"count"`` entry holds the evaluations counted in
    the block once it exits; the property test pins it against the
    hand-derived ``kernel_eval_count`` formula.
    """
    out = {"count": 0}
    start = obs.total(KERNEL_EVALS)
    try:
        yield out
    finally:
        out["count"] = obs.total(KERNEL_EVALS) - start


def _note_evals(xa: Array, xb: Array, count: int) -> None:
    if not (isinstance(xa, jax.core.Tracer)
            or isinstance(xb, jax.core.Tracer)):
        obs.count(KERNEL_EVALS, count)


def _batched_kernel_block(spec: KernelSpec, xa: Array, xb: Array) -> Array:
    """vmapped ``kernel_block`` over (B, ·, f) stacks — the eval-count seam."""
    _note_evals(xa, xb, xa.shape[0] * xa.shape[1] * xb.shape[1])
    return jax.vmap(lambda a, b: kernel_block(spec, a, b))(xa, xb)


def _batched_row_id(
    spec: KernelSpec,
    xc: Array,
    xp: Array,
    k: int,
    rtol: float | None,
    adaptive: bool,
    cmask: Array | None = None,
) -> tuple[Array, Array, Array]:
    """All row IDs of one tree level behind ``KernelSpec.impl``.

    xc (B, m, f) candidate points, xp (B, s, f) proxy points.  Returns
    (piv (B, k) int32, p_mat (B, m, k), ranks (B,) int32).  The Pallas impls
    dispatch to the fused assemble+ID kernel (``repro.kernels.compress``):
    the sampled blocks K(xc_i, xp_i) are evaluated in VMEM and consumed by
    the pivoted-QR deflation loop in place, one launch for the whole level.
    ``impl="xla"`` keeps the reference per-node assemble-then-ID closures.
    Both paths count the SAME logical kernel evaluations at this seam, so
    ``kernel_eval_count`` is impl-independent.
    """
    _note_evals(xc, xp, xc.shape[0] * xc.shape[1] * xp.shape[1])
    eff_rtol = 1e-5 if rtol is None else rtol
    if spec.impl in ("pallas", "pallas_interpret"):
        from repro.kernels.compress import ops as cops

        return cops.batched_assemble_id(
            xc, xp, k, kernel_name=spec.name, h=spec.h, rtol=eff_rtol,
            adaptive=adaptive, cmask=cmask,
            interpret=(spec.impl == "pallas_interpret"))

    def one(xc_i: Array, xp_i: Array, cm_i: Array | None):
        a = kernel_block(spec, xc_i, xp_i)
        if cm_i is not None:
            # Zero dead candidate rows: skeleton propagation only ever
            # forwards LIVE child skeleton points (dead rows get zero
            # interpolation weights and sort behind every live pivot).
            a = a * cm_i[:, None]
        if adaptive:
            piv, p_mat, rk = idqr.row_interp_decomp_ranked(a, k, eff_rtol)
        else:
            piv, p_mat = idqr.row_interp_decomp(a, k)
            rk = jnp.int32(k)
        return piv.astype(jnp.int32), p_mat, rk

    if cmask is None:
        return jax.vmap(lambda c, p: one(c, p, None))(xc, xp)
    return jax.vmap(one)(xc, xp, cmask)


def _batched_level_id(
    spec: KernelSpec,
    xc: Array,
    xp: Array,
    k: int,
    rtol: float | None,
    adaptive: bool,
    cmask: Array | None = None,
    *,
    node_rows: int,
) -> tuple[Array, Array, Array]:
    """``_batched_row_id`` for an internal level whose nodes hold
    ``node_rows`` rows each: the same skeletons and ranks, and, from
    ``KERNEL_INTERP_ROWS`` rows up, ``_kernel_interp``'s transfer matrices
    in place of the ID's."""
    piv, t, ranks = _batched_row_id(spec, xc, xp, k, rtol, adaptive, cmask)
    if node_rows < KERNEL_INTERP_ROWS:
        return piv, t, ranks
    return piv, _kernel_interp(spec, xc, piv, ranks, cmask), ranks


# Internal nodes of at least this many rows take ``_kernel_interp``'s
# transfer matrices.  On covtype rows (10 continuous + 44 one-hot columns)
# at 2^16 rows the ID's transfers of the 8,192-row level and above left
# K~ with eigenvalues down to -66 against beta = 100 and one seed in three
# off the exact SVM; the kernel's interpolation at every internal level
# holds K~ PSD there but is less accurate on SUSY rows, whose accuracy
# stays within its limit with it from 8,192 rows up (PERF.md, PR 15).
KERNEL_INTERP_ROWS = 8192


# Diagonal shift of each skeleton block K(S, S) before its Cholesky: keeps
# the f32 factor finite on near-coincident skeletons, and damps (never
# amplifies) their directions, so the residuals below stay PSD.
_INTERP_SHIFT = 1e-5


def _kernel_interp(spec: KernelSpec, xc: Array, piv: Array, ranks: Array,
                   cmask: Array | None) -> Array:
    """(B, m, k) kernel interpolation of every candidate row from its node's
    live skeleton rows: T = K(xc, xs) (K(xs, xs) + shift I)^-1.

    A large node's candidates are its children's skeletons, and its
    couplings to the rest of the data are weak and spread over more
    directions than the rank where the data falls into many groups (one-hot
    categories): an ID fit to the node's few proxy columns then overshoots
    off them, and K~ + beta I comes near singular.  With this basis from a
    level up, the levels above it add, in that level's basis,
    block-diagonal residuals K(C, C) - T K(S, S) T^T, each at least the
    Schur complement of K(S, S) in K(C, C), so positive semidefinite,
    over the root's exact K(C, C).  Dead slots (adaptive ranks) and dead
    candidate rows get exact zeros.
    """
    dtype = xc.dtype
    xs = jnp.take_along_axis(xc, piv[:, :, None], axis=1)        # (B, k, f)
    kcs = _batched_kernel_block(spec, xc, xs).astype(jnp.float32)
    live = rank_mask(ranks, piv.shape[1], jnp.float32)          # (B, k)
    kss = jnp.take_along_axis(kcs, piv[:, :, None], axis=1)      # (B, k, k)
    eye = jnp.eye(piv.shape[1], dtype=jnp.float32)
    kss = (kss * live[:, :, None] * live[:, None, :]
           + eye * (1.0 - live)[:, :, None] + _INTERP_SHIFT * eye)
    chol = jnp.linalg.cholesky(kss)
    rhs = jnp.swapaxes(kcs * live[:, None, :], 1, 2)              # (B, k, m)
    t = jnp.swapaxes(jax.vmap(lambda c, b: jax.scipy.linalg.cho_solve(
        (c, True), b))(chol, rhs), 1, 2) * live[:, None, :]
    if cmask is not None:
        t = t * cmask[:, :, None]
    return t.astype(dtype)


@dataclasses.dataclass(frozen=True)
class CompressionParams:
    """Accuracy knobs, analogous to the paper's STRUMPACK parameters.

    rtol      ~ rel_tol        (Table 4 "crude": 1e-2, Table 5 "accurate":
                1e-4) — the paper-facing accuracy knob.  None = legacy
                fixed-rank mode: every node stores the full ``rank`` columns.
                A float switches on the ADAPTIVE build: each node's numerical
                rank is detected from the pivoted-QR diagonal decay against
                rtol, truncated columns are exact zeros, and
                ``hss.shrink_to_fit`` can slice each level to its observed
                max rank.
    rank      ~ hss_max_rank   (Table 4: 200, Table 5: 2000 — here per
                level).  With rtol set this is only the CAP on the detected
                rank (STRUMPACK semantics); without it, the rank itself.
    n_near    ~ hss_approximate_neighbors (Table 4: 64, Table 5: 512)
    n_far     — far-field proxy sample size
    """

    rank: int = 32
    n_near: int = 32
    n_far: int = 32
    seed: int = 0
    rtol: float | None = None

    def __post_init__(self):
        # a leaf's row ID picks ``rank`` rows of its (leaf × n_proxy)
        # sampled block, whose rank is at most n_proxy: the rows past it
        # interpolate nothing, and the model trained on that basis is
        # wrong while nothing raises
        if self.rank > self.n_proxy:
            raise ValueError(
                f"rank {self.rank} exceeds the {self.n_proxy} proxy columns "
                f"(n_near {self.n_near} + n_far {self.n_far}) it is chosen "
                f"from")

    @property
    def n_proxy(self) -> int:
        return self.n_near + self.n_far

    @classmethod
    def crude(cls, **kw) -> "CompressionParams":
        """Paper Table 4 regime: loose tolerance, small cap/neighbourhoods."""
        return cls(**{**dict(rank=32, n_near=32, n_far=32, rtol=1e-2), **kw})

    @classmethod
    def accurate(cls, **kw) -> "CompressionParams":
        """Paper Table 5 regime: tight tolerance, larger cap/neighbourhoods."""
        return cls(**{**dict(rank=64, n_near=64, n_far=128, rtol=1e-4), **kw})


def kernel_eval_count(tree: ClusterTree, params: CompressionParams) -> int:
    """Exact number of kernel entries ``compress`` evaluates for this tree.

    The partially matrix-free build touches O(N · n_proxy) entries instead of
    N² — this counts them exactly (leaf diagonal blocks + leaf sampled
    blocks + per-level candidate×proxy and candidate×skeleton blocks + B
    couplings), for the bench's
    perf trajectory.  Static per (tree, params): the adaptive build masks
    entries but the sampled block SHAPES are the rank cap, so adaptivity
    shows up in stored ranks and factor/solve cost, not here.
    """
    m, K = tree.leaf_size, tree.levels
    n_leaf = 2 ** K
    r0 = min(params.rank, m)
    total = n_leaf * (m * m + m * params.n_proxy)
    r_prev = r0
    for k in range(1, K + 1):
        n_k = 2 ** (K - k)
        total += n_k * r_prev * r_prev                  # sibling couplings B
        if k == K:
            break
        r_k = min(params.rank, 2 * r_prev)
        total += n_k * (2 * r_prev) * (2 * r_prev + params.n_far)
        if m * 2 ** k >= KERNEL_INTERP_ROWS:         # candidate×skeleton
            total += n_k * (2 * r_prev) * r_k
        r_prev = r_k
    return total


def _cand_mask(ranks: Array, rp: int, dtype) -> Array:
    """(2·n,) child rank vector -> (n, 2·rp) candidate-slot liveness.

    One row per parent: the two children's ``hss.rank_mask`` rows side by
    side — shared by the local and sharded builds so the masking rule cannot
    drift between them.
    """
    return rank_mask(ranks, rp, dtype).reshape(-1, 2 * rp)


def _mask_b(b: Array, cm: Array, rp: int) -> Array:
    """Zero B rows/columns of dead child skeletons (exact structural zeros)."""
    return b * cm[:, :rp, None] * cm[:, rp:][:, None, :]


def _complement_sample(
    rng: np.random.Generator, n: int, span_start: int, span_width: int, count: int
) -> np.ndarray:
    """Uniform sample of indices in [0, n) \\ [span_start, span_start+width)."""
    u = rng.integers(0, n - span_width, size=count)
    return np.where(u < span_start, u, u + span_width).astype(np.int32)


def _host_proxy_indices(
    tree: ClusterTree, params: CompressionParams
) -> list[np.ndarray]:
    """Per-level FAR proxy index arrays: far[k] has shape (n_k, n_far)."""
    rng = np.random.default_rng(params.seed)
    n, m, K = tree.n, tree.leaf_size, tree.levels
    out = []
    with obs.span("hss.far_proxies"):
        for k in range(K):  # levels 0..K-1 need bases/skeletons
            n_k = 2 ** (K - k)
            width = m * 2 ** k
            rows = [
                _complement_sample(rng, n, node * width, width, params.n_far)
                for node in range(n_k)
            ]
            out.append(np.stack(rows, axis=0))
    return out


def _host_leaf_near(
    tree: ClusterTree, params: CompressionParams,
    x_perm: np.ndarray | None = None, x_device: Array | None = None,
) -> np.ndarray:
    """(n_leaf, n_near) NEAR-proxy indices per leaf.

    The paper's HSS-ANN strategy: the dominant entries of a leaf's
    off-diagonal block row correspond to its points' nearest neighbours in
    *other* clusters.  With data available we find every point's exact
    nearest neighbours — with ``_device_knn`` on ``x_device`` when the
    build holds the data on the device, else with a host KD-tree (scipy),
    the exact analogue of STRUMPACK's ANN preprocessing — and pool them per
    leaf (``_select_near``).  Without data we fall back to sampling the
    sibling leaf (tree-adjacent ≈ near).
    """
    with obs.span("hss.near_search"):
        rng = np.random.default_rng(params.seed + 1)
        m, n_leaf = tree.leaf_size, 2 ** tree.levels
        if x_perm is not None and n_leaf > 1:
            # f32 is plenty for neighbour RANKING and keeps scipy happy with
            # dtypes it cannot handle (bf16); the kernel evaluations
            # themselves stay in the caller's dtype.
            x_f32 = np.asarray(x_perm, np.float32)
            k_query = min(max(2 * params.n_near // m + 4, 4), tree.n)
            if x_device is None:
                nbr = _kdtree_query(x_f32, k_query)
            else:
                obs.count("hss.near_search.device")
                with obs.span("hss.near_search.knn"):
                    nbr = np.asarray(jax.device_get(_device_knn(
                        x_device, k=k_query, block=_knn_block(tree.n))))
            with obs.span("hss.near_search.select"):
                return _select_near(tree, params, x_f32, nbr, rng)
        out = np.empty((n_leaf, params.n_near), dtype=np.int32)
        for i in range(n_leaf):
            sib = i ^ 1
            out[i] = rng.choice(m, size=params.n_near,
                                replace=params.n_near > m) + sib * m
        return out


def _kdtree_query(x_f32: np.ndarray, k: int) -> np.ndarray:
    """(n, k) ids of every row's k nearest rows (self included), on the
    host."""
    from scipy.spatial import cKDTree

    obs.count("hss.near_search.host")
    with obs.span("hss.near_search.kdtree"):
        kdt = cKDTree(x_f32)
    with obs.span("hss.near_search.query"):
        # workers=-1: one query thread per host core.  In 18 dimensions the
        # KD-tree prunes little and the query grows ~N^1.8.
        _, nbr = kdt.query(x_f32, k=k, workers=-1)
    return nbr


# ``_device_knn``'s query block: 128 rows keep the score tile of 2^16
# rows (32 MiB) in a TPU v5e's on-chip memory, where the whole search
# over 2^16 rows ran 0.105 s against 0.164 s with 2,048-row blocks.
# Larger n halves the block until the block × n f32 tile fits
# ``_KNN_TILE_BYTES``.
_KNN_TILE_BYTES = 2 ** 29
_KNN_MAX_BLOCK = 128
# Candidate columns per group of ``_device_knn``'s two-step top-k.
_KNN_GROUP = 128


def _knn_block(n: int) -> int:
    """Query rows per ``_device_knn`` step for n rows."""
    block = _KNN_MAX_BLOCK
    while block > 8 and block * n * 4 > _KNN_TILE_BYTES:
        block //= 2
    return block


@functools.partial(jax.jit, static_argnames=("k", "block"))
def _device_knn(x: Array, *, k: int, block: int) -> Array:
    """(n, k) int32 ids of every row's k exact nearest rows (self
    included), nearest first, on the device.

    Query blocks of ``block`` rows scan all n rows, scored by
    2 q·xᵀ − ‖x‖² (‖q‖² less the squared distance) with f32-exact
    products.  The 2k best per row are found exactly in two small steps:
    every 2k best lies in one of the 2k column groups of ``_KNN_GROUP``
    with the best group maxima, so ``lax.top_k`` runs on the group maxima
    and then on those groups' scores, never on a whole row.  The 2k are
    ranked again by their directly computed squared differences, which do
    not cancel as the expansion does, and the k nearest kept.  Inputs of
    any dtype are ranked in f32.
    """
    x = x.astype(jnp.float32)
    n, f = x.shape
    wide = min(2 * k, n)
    groups = -(-n // _KNN_GROUP)
    take = min(wide, groups)
    # Padded rows score -inf and are never chosen.
    sq = jnp.pad(jnp.sum(x * x, axis=1), (0, groups * _KNN_GROUP - n),
                 constant_values=jnp.inf)
    xs = jnp.pad(x, ((0, groups * _KNN_GROUP - n), (0, 0)))
    n_blocks = -(-n // block)
    q = jnp.pad(x, ((0, n_blocks * block - n), (0, 0)))

    def one(qb):
        g = jax.lax.dot_general(
            qb, xs, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        s = (2.0 * g - sq[None, :]).reshape(block, groups, _KNN_GROUP)
        _, grp = jax.lax.top_k(jnp.max(s, axis=2), take)
        sub = jnp.take_along_axis(s, grp[:, :, None], axis=1)
        _, j = jax.lax.top_k(sub.reshape(block, take * _KNN_GROUP), wide)
        cand = (jnp.take_along_axis(grp, j // _KNN_GROUP, axis=1)
                * _KNN_GROUP + j % _KNN_GROUP)
        diff = jnp.take(x, cand, axis=0) - qb[:, None, :]
        d = jnp.sum(diff * diff, axis=2)
        order = jnp.argsort(d, axis=1, stable=True)[:, :k]
        return jnp.take_along_axis(cand, order, axis=1)

    out = jax.lax.map(one, q.reshape(n_blocks, block, f))
    return out.reshape(n_blocks * block, k)[:n].astype(jnp.int32)


def _select_near(tree: ClusterTree, params: CompressionParams,
                 x_f32: np.ndarray, nbr: np.ndarray,
                 rng: np.random.Generator) -> np.ndarray:
    """Pool each leaf's points' neighbour ids ``nbr`` (n, k), drop in-leaf
    ids and repeats, and keep the n_near nearest the leaf centroid."""
    m, n_leaf = tree.leaf_size, 2 ** tree.levels
    k_query = nbr.shape[1]
    out = np.empty((n_leaf, params.n_near), dtype=np.int32)
    leaf_of = np.arange(tree.n) // m
    # Vectorized over ALL leaves at once (the per-leaf Python loop was
    # the host-preprocessing serial bottleneck at large n_leaf): each
    # leaf's candidate pool is its points' neighbour lists, flattened.
    cand = nbr.reshape(n_leaf, m * k_query).astype(np.int64)
    own = leaf_of[cand] == np.arange(n_leaf)[:, None]   # in-leaf -> drop
    # Duplicate suppression without per-row np.unique: sort ids per row,
    # mark repeats, scatter the mask back to original positions.
    order = np.argsort(cand, axis=1, kind="stable")
    sorted_ids = np.take_along_axis(cand, order, axis=1)
    dup_sorted = np.zeros_like(own)
    dup_sorted[:, 1:] = sorted_ids[:, 1:] == sorted_ids[:, :-1]
    dup = np.zeros_like(own)
    np.put_along_axis(dup, order, dup_sorted, axis=1)
    invalid = own | dup
    # Rank candidates by distance to the leaf centroid; invalid -> +inf.
    centroid = x_f32.reshape(n_leaf, m, -1).mean(axis=1)
    dist = np.linalg.norm(
        x_f32[cand] - centroid[:, None, :], axis=2)
    dist[invalid] = np.inf
    pick = np.argsort(dist, axis=1, kind="stable")[:, : params.n_near]
    out[:] = np.take_along_axis(cand, pick, axis=1)
    # Deficit rows (candidate pool smaller than n_near — tiny problems
    # only): top up from the sibling leaf, EXCLUDING candidates already
    # placed (a duplicate NEAR proxy is a duplicate sampled-block column:
    # it wastes ID sample budget and skews the pivot order).  Repeats are
    # only permitted once the whole sibling leaf is exhausted.
    counts = (~invalid).sum(axis=1)
    for i in np.nonzero(counts < params.n_near)[0]:
        c = int(counts[i])
        short = params.n_near - c
        sib = int(i) ^ 1
        pool = np.setdiff1d(
            np.arange(m, dtype=np.int64) + sib * m, out[i, :c])
        if len(pool) >= short:
            fill = rng.choice(pool, size=short, replace=False)
        else:
            extra = rng.choice(m, size=short - len(pool)) + sib * m
            fill = np.concatenate([pool, extra])
        out[i, c:] = fill
    return out


def compress(
    x_perm: Array,
    tree: ClusterTree,
    spec: KernelSpec,
    params: CompressionParams = CompressionParams(),
) -> HSSMatrix:
    """Build the HSS approximation of K(x_perm, x_perm).

    ``x_perm`` must already be in tree (leaf-major) order:
    ``x_perm = x[tree.perm]``.  A host numpy array is accepted directly —
    the host copy the proxy preprocessing needs anyway — so callers that
    already hold the data on the host (``compress_sharded``'s fallback, the
    engine) never pay a device round-trip for it.
    """
    n, m, K = tree.n, tree.leaf_size, tree.levels
    n_leaf = 2 ** K
    if x_perm.shape[0] != n:
        raise ValueError(f"x has {x_perm.shape[0]} rows, tree expects {n}")
    r0 = min(params.rank, m)
    adaptive, rtol = params.rtol is not None, params.rtol

    far_idx = [jnp.asarray(a) for a in _host_proxy_indices(tree, params)]
    if isinstance(x_perm, np.ndarray):
        # Already on the host: use it as-is for the NEAR selection.
        # (Wrapping it in jnp.asarray first and gathering it back — the old
        # fallback behaviour — kept TWO full copies of the dataset alive.)
        x_host = x_perm
        x_perm = jnp.asarray(x_host)
    else:
        x_host = np.asarray(jax.device_get(x_perm))
    leaf_near = jnp.asarray(
        _host_leaf_near(tree, params, x_host, x_device=x_perm))

    # ---------------- leaves ---------------- #
    with obs.span("hss.compress.leaves"):
        x_leaves = x_perm.reshape(n_leaf, m, -1)
        d_leaf = _batched_kernel_block(spec, x_leaves, x_leaves)

        prox0 = jnp.concatenate([leaf_near, far_idx[0]], axis=1)
        x_prox0 = jnp.take(x_perm, prox0, axis=0)      # (n_leaf, n_proxy, f)
        piv0, u_leaf, leaf_ranks = _batched_row_id(
            spec, x_leaves, x_prox0, r0, rtol, adaptive)
        leaf_starts = jnp.arange(n_leaf, dtype=jnp.int32) * m
        skel_leaf = leaf_starts[:, None] + piv0

    # ---------------- internal levels ---------------- #
    with obs.span("hss.compress.levels"):
        transfers: list[Array] = []
        skels: list[Array] = []
        b_mats: list[Array] = []
        level_ranks: list[Array] = []
        skel_prev = skel_leaf                 # (n_{k-1}, r_{k-1})
        rank_prev = leaf_ranks                # (n_{k-1},) numerical ranks
        r_prev = r0
        for k in range(1, K + 1):
            n_k = 2 ** (K - k)
            cand = skel_prev.reshape(n_k, 2 * r_prev)  # children skeleton ids
            # Liveness of each candidate slot under the children's detected
            # ranks (all-ones in fixed-rank mode).
            cmask = _cand_mask(rank_prev, r_prev, x_perm.dtype)
            # B couplings: K(skel_c1, skel_c2) — pure kernel evals.  Dead
            # skeleton rows/columns are masked to exact zeros so the
            # truncation is structural (factorization decouples them; shrink
            # slices them).
            xa = jnp.take(x_perm, cand[:, :r_prev], axis=0)
            xb = jnp.take(x_perm, cand[:, r_prev:], axis=0)
            b_k = _batched_kernel_block(spec, xa, xb)
            if adaptive:
                b_k = _mask_b(b_k, cmask, r_prev)
            b_mats.append(b_k)
            if k == K:
                break
            r_k = min(params.rank, 2 * r_prev)
            # NEAR proxies: the sibling node's candidate skeletons (dynamic).
            sib = cand.reshape(n_k // 2, 2, 2 * r_prev)[:, ::-1, :].reshape(
                n_k, 2 * r_prev)
            prox = jnp.concatenate([sib, far_idx[k]], axis=1)
            xc = jnp.take(x_perm, cand, axis=0)            # (n_k, 2 r_prev, f)
            xp = jnp.take(x_perm, prox, axis=0)
            piv_k, t_k, rank_k = _batched_level_id(
                spec, xc, xp, r_k, rtol, adaptive,
                cmask=cmask if adaptive else None, node_rows=m * 2 ** k)
            skel_k = jnp.take_along_axis(cand, piv_k, axis=1)
            transfers.append(t_k)
            skels.append(skel_k)
            level_ranks.append(rank_k)
            skel_prev, rank_prev, r_prev = skel_k, rank_k, r_k

    return HSSMatrix(
        x=x_perm,
        d_leaf=d_leaf,
        u_leaf=u_leaf,
        skel_leaf=skel_leaf,
        transfers=tuple(transfers),
        skels=tuple(skels),
        b_mats=tuple(b_mats),
        levels=K,
        leaf_size=m,
        leaf_ranks=leaf_ranks if adaptive else None,
        level_ranks=tuple(level_ranks) if adaptive else (),
    )


def _mesh_nodes(mesh) -> tuple[tuple[str, ...], int]:
    """All mesh axes combined into one logical node axis, + device count."""
    nodes = tuple(mesh.axis_names)
    ndev = 1
    for a in nodes:
        ndev *= mesh.shape[a]
    return nodes, ndev


def compress_sharded(
    x_perm,
    tree: ClusterTree,
    spec: KernelSpec,
    params: CompressionParams = CompressionParams(),
    mesh=None,
) -> HSSMatrix:
    """Mesh-parallel HSS build: every stage node-sharded from the start.

    The single-device ``compress`` materializes every per-level array on one
    device — the O(N m) leaf blocks alone exceed a single device's HBM at the
    paper's Table-1 scales.  Here the leaf axis is sharded over ALL mesh
    devices end-to-end:

      * host preprocessing gathers each leaf's proxy *points* (near + far,
        O(n_leaf * n_proxy * f)) so no device-side global gather over the
        full dataset is ever needed;
      * the leaf stage (diagonal blocks, ID-QR bases, skeleton selection)
        runs under ``shard_map`` with n_leaf/ndev leaves per device;
      * each level transition carries only the skeleton POINTS
        (n_k, r_k, f) and their global ids upward — O(r n_k) per level, the
        distributed-memory HSS-ANN communication pattern (STRUMPACK §3.1);
      * a level degrades to replicated (one all-gather of the skeleton
        points, after which every device redundantly computes the tiny
        upper-tree arrays) exactly when its node count stops being evenly
        pair-shardable — the same fallback rule as
        ``distributed.fac_shardings``.

    ``x_perm`` may be a host numpy array (preferred — the proxy-point
    gathers need it on the host anyway) or a jax array.  Requires
    ``tree.n_leaves % n_devices == 0``; otherwise falls back to the local
    build (the result is then unsharded).  Numerically this computes the
    same interpolative decompositions on the same sampled blocks as
    ``compress`` (parity-tested to <=1e-5 in tests/test_engine.py).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.dist.api import shard_map

    n, m, K = tree.n, tree.leaf_size, tree.levels
    n_leaf = 2 ** K
    # Preserve the caller's dtype: the local build does, and downcasting here
    # (the old behaviour) made the two builds disagree for f64/bf16 inputs.
    # The neighbour search ranks in f32 internally.
    x_host = np.asarray(jax.device_get(x_perm))
    if x_host.shape[0] != n:
        raise ValueError(f"x has {x_host.shape[0]} rows, tree expects {n}")
    nodes, ndev = _mesh_nodes(mesh)
    if K == 0 or n_leaf % ndev != 0:
        # compress() takes host arrays directly — re-wrapping x_host in a
        # device array here would pay the host->device copy a second time.
        return compress(x_host, tree, spec, params)

    r0 = min(params.rank, m)
    adaptive, rtol = params.rtol is not None, params.rtol
    p_nodes = PartitionSpec(nodes)
    sh_nodes = NamedSharding(mesh, p_nodes)
    sh_repl = NamedSharding(mesh, PartitionSpec())

    far_idx = _host_proxy_indices(tree, params)
    # The neighbour query runs on one unsharded device copy of the data.
    leaf_near = _host_leaf_near(tree, params, x_host,
                                x_device=jnp.asarray(x_host))
    prox0 = np.concatenate([leaf_near, far_idx[0]], axis=1)

    # ---------------- leaves (shard_map over the node axis) ------------- #
    with obs.span("hss.compress.leaves"):
        x_leaves = jax.device_put(x_host.reshape(n_leaf, m, -1),
                                  sh_nodes)
        # (n_leaf, n_proxy, f)
        x_prox0 = jax.device_put(x_host[prox0], sh_nodes)
        leaf_starts = jax.device_put(
            np.arange(n_leaf, dtype=np.int32) * m, sh_nodes)

        def _leaf_stage(xl, xp, starts):
            d = _batched_kernel_block(spec, xl, xl)
            piv, u, rks = _batched_row_id(spec, xl, xp, r0, rtol, adaptive)
            skel = starts[:, None] + piv
            spts = jax.vmap(lambda xa, p: jnp.take(xa, p, axis=0))(xl, piv)
            return d, u, skel, spts, rks

        leaf_fn = jax.jit(shard_map(
            _leaf_stage, mesh,
            in_specs=(p_nodes, p_nodes, p_nodes),
            out_specs=(p_nodes,) * 5))
        d_leaf, u_leaf, skel_leaf, spts, leaf_ranks = leaf_fn(
            x_leaves, x_prox0, leaf_starts)
        sids, sranks = skel_leaf, leaf_ranks

    # ---------------- internal levels ---------------- #
    with obs.span("hss.compress.levels"):
        transfers: list[Array] = []
        skels: list[Array] = []
        b_mats: list[Array] = []
        level_ranks: list[Array] = []
        r_prev = r0
        sharded = True
        for k in range(1, K + 1):
            n_k = 2 ** (K - k)
            # Pair-shardable: parents divide the devices AND each device holds
            # an even number of parents so the sibling-NEAR exchange is local.
            want = (sharded and n_k % ndev == 0
                    and (k == K or (n_k // ndev) % 2 == 0))
            if sharded and not want:
                # Degradation point: one all-gather of the skeleton points/ids/
                # ranks (O(r * n_k) — the only cross-device traffic of the
                # upper tree).
                spts = jax.device_put(spts, sh_repl)
                sids = jax.device_put(sids, sh_repl)
                sranks = jax.device_put(sranks, sh_repl)
                sharded = False
            r_k = min(params.rank, 2 * r_prev)

            if sharded:
                loc = n_k // ndev
                rp, rk = r_prev, r_k
                if k == K:
                    def _b_only(sp, sr):
                        cp = sp.reshape(loc, 2 * rp, sp.shape[-1])
                        b = _batched_kernel_block(spec, cp[:, :rp], cp[:, rp:])
                        if adaptive:
                            b = _mask_b(b, _cand_mask(sr, rp, b.dtype), rp)
                        return b

                    b_fn = jax.jit(shard_map(
                        _b_only, mesh, in_specs=(p_nodes, p_nodes),
                        out_specs=p_nodes))
                    b_mats.append(b_fn(spts, sranks))
                    break

                far_pts = jax.device_put(x_host[far_idx[k]], sh_nodes)

                def _level(sp, si, sr, fp):
                    f = sp.shape[-1]
                    cp = sp.reshape(loc, 2 * rp, f)
                    ci = si.reshape(loc, 2 * rp)
                    cm = _cand_mask(sr, rp, sp.dtype)
                    b = _batched_kernel_block(spec, cp[:, :rp], cp[:, rp:])
                    if adaptive:
                        b = _mask_b(b, cm, rp)
                    sib = cp.reshape(loc // 2, 2, 2 * rp, f)[:, ::-1]
                    sib = sib.reshape(loc, 2 * rp, f)
                    xp_ = jnp.concatenate([sib, fp], axis=1)
                    piv, t, rks = _batched_level_id(
                        spec, cp, xp_, rk, rtol, adaptive,
                        cmask=cm if adaptive else None, node_rows=m * 2 ** k)
                    ids = jnp.take_along_axis(ci, piv, axis=1)
                    pts = jax.vmap(
                        lambda c, p: jnp.take(c, p, axis=0))(cp, piv)
                    return b, t, ids, pts, rks

                lvl_fn = jax.jit(shard_map(
                    _level, mesh,
                    in_specs=(p_nodes,) * 4,
                    out_specs=(p_nodes,) * 5))
                b_k, t_k, sids, spts, sranks = lvl_fn(
                    spts, sids, sranks, far_pts)
                b_mats.append(b_k)
                transfers.append(t_k)
                skels.append(sids)
                level_ranks.append(sranks)
            else:
                # Replicated upper tree: same math, every device computes it.
                f = spts.shape[-1]
                cand_pts = spts.reshape(n_k, 2 * r_prev, f)
                cand_ids = sids.reshape(n_k, 2 * r_prev)
                cmask = _cand_mask(sranks, r_prev, spts.dtype)
                b_k = _batched_kernel_block(
                    spec, cand_pts[:, :r_prev], cand_pts[:, r_prev:])
                if adaptive:
                    b_k = _mask_b(b_k, cmask, r_prev)
                b_mats.append(b_k)
                if k == K:
                    break
                sib = cand_pts.reshape(n_k // 2, 2, 2 * r_prev, f)[:, ::-1]
                sib = sib.reshape(n_k, 2 * r_prev, f)
                far_pts = jax.device_put(x_host[far_idx[k]], sh_repl)
                xp_ = jnp.concatenate([sib, far_pts], axis=1)
                piv_k, t_k, sranks = _batched_level_id(
                    spec, cand_pts, xp_, r_k, rtol, adaptive,
                    cmask=cmask if adaptive else None, node_rows=m * 2 ** k)
                sids = jnp.take_along_axis(cand_ids, piv_k, axis=1)
                spts = jax.vmap(lambda c, p: jnp.take(c, p, axis=0))(
                    cand_pts, piv_k)
                transfers.append(t_k)
                skels.append(sids)
                level_ranks.append(sranks)
            r_prev = r_k

    return HSSMatrix(
        x=jax.device_put(x_host, sh_nodes),
        d_leaf=d_leaf,
        u_leaf=u_leaf,
        skel_leaf=skel_leaf,
        transfers=tuple(transfers),
        skels=tuple(skels),
        b_mats=tuple(b_mats),
        levels=K,
        leaf_size=m,
        leaf_ranks=leaf_ranks if adaptive else None,
        level_ranks=tuple(level_ranks) if adaptive else (),
    )


# --------------------------------------------------------------------- #
# streamed (out-of-core) build                                          #
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class StreamParams:
    """Knobs of the out-of-core streamed build (``compress_streamed``).

    batch_leaves      — nodes processed per device round-trip.  The build's
                        peak device working set is O(batch·m·(m + n_proxy))
                        plus the current batch's outputs — independent of N.
                        Internal levels reuse the same node-batch size
                        (rounded down to even so the sibling-NEAR exchange
                        stays batch-local).
    ckpt_dir          — directory for per-level checkpoints through
                        ``repro.ckpt``; None disables checkpointing (the
                        build is then streamed but not restartable).
    ckpt_every_levels — checkpoint cadence in completed levels (the leaf
                        stage counts as one level).
    max_restarts      — in-process restart budget handed to
                        ``dist.fault.run_resilient``.
    assemble          — "device" materializes the finished HSS as jax
                        arrays (mesh-placed when ``mesh`` is given);
                        "host" leaves the leaves as numpy for callers that
                        checkpoint or inspect without a device footprint.
    """

    batch_leaves: int = 64
    ckpt_dir: str | None = None
    ckpt_every_levels: int = 1
    max_restarts: int = 3
    assemble: str = "device"


@dataclasses.dataclass
class StreamStats:
    """Observability record of one streamed build (bench/CI artifact)."""

    peak_stream_bytes: int = 0      # max over batches of in+out device bytes
    n_batches: int = 0
    resumed_level: int | None = None    # completed levels found on disk
    restarts: int = 0                   # in-process run_resilient restarts
    checkpointed_levels: int = 0


def _stream_leaf_batch(spec, xl, xp, r0, rtol, adaptive):
    """One node batch of the streamed leaf stage (pure and traceable —
    repro.analysis traces it to prove the hot loop is callback-free).

    Identical math to the leaf stage of ``compress``: diagonal blocks +
    proxy-sampled row ID, through the same two eval-counting seams."""
    d = _batched_kernel_block(spec, xl, xl)
    piv, u, rks = _batched_row_id(spec, xl, xp, r0, rtol, adaptive)
    return d, u, piv, rks


def _stream_level_batch(spec, cp, xp, cm, rk, rtol, adaptive, node_rows):
    """One node batch of a streamed internal level: sibling couplings B +
    the candidate->proxy row ID.  ``cp`` (b, 2·r_prev, f) candidate points,
    ``xp`` (b, 2·r_prev + n_far, f) proxy points, ``cm`` candidate liveness
    (None in fixed-rank mode)."""
    rp = cp.shape[1] // 2
    b = _batched_kernel_block(spec, cp[:, :rp], cp[:, rp:])
    if adaptive:
        b = _mask_b(b, cm, rp)
    piv, t, rks = _batched_level_id(
        spec, cp, xp, rk, rtol, adaptive, cmask=cm if adaptive else None,
        node_rows=node_rows)
    return b, piv, t, rks


def _stream_root_batch(spec, cp, cm, adaptive):
    """The root level stores only the sibling coupling B."""
    rp = cp.shape[1] // 2
    b = _batched_kernel_block(spec, cp[:, :rp], cp[:, rp:])
    if adaptive:
        b = _mask_b(b, cm, rp)
    return b


def _device_bytes(*arrays) -> int:
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays)


def _stream_fingerprint(n, m, K, spec, params, dtype) -> dict:
    """Identity of a streamed build — a checkpoint from ANY other problem
    (different data size, tree, kernel, accuracy knobs, dtype) must never be
    resumed into this one.  Stored in the checkpoint manifest's ``extra``
    and compared after a JSON round-trip, so values are plain scalars."""
    return dict(
        kind="hss_streamed_build", n=int(n), leaf_size=int(m), levels=int(K),
        rank=int(params.rank), n_near=int(params.n_near),
        n_far=int(params.n_far), seed=int(params.seed),
        rtol=None if params.rtol is None else float(params.rtol),
        kernel=spec.name, h=float(spec.h), impl=spec.impl,
        dtype=str(np.dtype(dtype)))


def compress_streamed(
    x_perm,
    tree: ClusterTree,
    spec: KernelSpec,
    params: CompressionParams = CompressionParams(),
    stream: StreamParams = StreamParams(),
    mesh=None,
    on_level=None,
) -> tuple[HSSMatrix, StreamStats]:
    """Out-of-core HSS build: the dataset stays on the HOST, the device only
    ever sees one node batch at a time.

    ``compress`` materializes the full (N, f) dataset plus every per-level
    array on the device — O(N·f + N·m) resident bytes, the wall at the
    paper's 10⁵–10⁷ scales.  Here the leaf level is walked in
    ``stream.batch_leaves``-node batches: per batch, gather the batch's
    points and proxy points from host numpy, run the SAME fused per-node
    kernels (``_batched_kernel_block`` / ``_batched_row_id`` — Pallas or
    XLA per ``spec.impl``), and copy the results back into preallocated
    host accumulators.  Level transitions carry skeleton POINTS only
    (gathered per batch from the host by skeleton id), so peak device bytes
    during the build are O(batch·m·(m + n_proxy)) — independent of N
    (``StreamStats.peak_stream_bytes`` records the measured max).

    Restartability: with ``stream.ckpt_dir`` set, each completed level's
    host state is checkpointed through ``repro.ckpt`` and the level loop
    runs under ``dist.fault.run_resilient`` — an interrupted build (same
    process via the restart budget, or a fresh call pointed at the same
    directory) resumes at the last completed level and produces
    BIT-IDENTICAL output: the state is saved as raw bytes and every level
    is a deterministic function of it.  A checkpoint whose fingerprint
    (data size, tree shape, kernel, accuracy knobs, dtype) does not match
    is ignored, not trusted.

    Numerics: identical sampled blocks and IDs to ``compress`` — the same
    points reach the same seams in the same order, only the batch axis is
    tiled — so skeletons match exactly and ``counting_kernel_evals`` counts
    the same total (batching-independence is property-tested).

    ``x_perm`` should be host numpy in tree order (a jax array is gathered
    once).  Returns ``(HSSMatrix, StreamStats)``; with ``mesh`` the
    finished arrays are placed node-sharded so ``factorize_sharded``
    consumes them directly.
    """
    from repro import ckpt
    from repro.dist.fault import run_resilient

    n, m, K = tree.n, tree.leaf_size, tree.levels
    n_leaf = 2 ** K
    if K == 0:
        raise ValueError("streamed build needs at least one tree level")
    x_host = (x_perm if isinstance(x_perm, np.ndarray)
              else np.asarray(jax.device_get(x_perm)))
    if x_host.shape[0] != n:
        raise ValueError(f"x has {x_host.shape[0]} rows, tree expects {n}")
    r0 = min(params.rank, m)
    adaptive, rtol = params.rtol is not None, params.rtol
    if stream.assemble not in ("device", "host"):
        raise ValueError(f"unknown assemble mode {stream.assemble!r}")

    far_idx = _host_proxy_indices(tree, params)          # host, per level
    leaf_near = _host_leaf_near(tree, params, x_host)
    prox0 = np.concatenate([leaf_near, far_idx[0]], axis=1)
    x_leaves = x_host.reshape(n_leaf, m, -1)
    stats = StreamStats()
    fp = _stream_fingerprint(n, m, K, spec, params, x_host.dtype)

    def _run_leaves(state: dict) -> dict:
        bsz = max(1, stream.batch_leaves)
        d_out = np.empty((n_leaf, m, m), x_host.dtype)
        u_out = np.empty((n_leaf, m, r0), x_host.dtype)
        skel_out = np.empty((n_leaf, r0), np.int32)
        rank_out = np.empty((n_leaf,), np.int32)
        for s in range(0, n_leaf, bsz):
            e = min(s + bsz, n_leaf)
            xl = jnp.asarray(x_leaves[s:e])
            xp = jnp.asarray(x_host[prox0[s:e]])
            d, u, piv, rks = _stream_leaf_batch(spec, xl, xp, r0, rtol,
                                                adaptive)
            stats.peak_stream_bytes = max(
                stats.peak_stream_bytes,
                _device_bytes(xl, xp, d, u, piv, rks))
            stats.n_batches += 1
            d_out[s:e] = jax.device_get(d)
            u_out[s:e] = jax.device_get(u)
            skel_out[s:e] = (np.asarray(jax.device_get(piv))
                             + np.arange(s, e, dtype=np.int32)[:, None] * m)
            rank_out[s:e] = jax.device_get(rks)
        state = dict(state)
        state.update(d_leaf=d_out, u_leaf=u_out, skel_leaf=skel_out,
                     ranks_leaf=rank_out)
        return state

    def _run_level(state: dict, k: int) -> dict:
        skel_prev = state["skel_leaf"] if k == 1 else state[f"skel_{k - 1}"]
        rank_prev = state["ranks_leaf"] if k == 1 else state[f"ranks_{k - 1}"]
        r_prev = skel_prev.shape[1]
        n_k = 2 ** (K - k)
        cand = skel_prev.reshape(n_k, 2 * r_prev)
        # Host-side candidate liveness, same rule as hss.rank_mask.
        cm_all = ((np.arange(r_prev)[None, :] < rank_prev[:, None])
                  .reshape(n_k, 2 * r_prev).astype(x_host.dtype))
        bsz = max(2, stream.batch_leaves - stream.batch_leaves % 2)
        state = dict(state)
        if k == K:                                       # root: B only
            cp = jnp.asarray(x_host[cand])
            cm = jnp.asarray(cm_all) if adaptive else None
            b = _stream_root_batch(spec, cp, cm, adaptive)
            stats.peak_stream_bytes = max(stats.peak_stream_bytes,
                                          _device_bytes(cp, b))
            stats.n_batches += 1
            state[f"b_{k}"] = np.asarray(jax.device_get(b))
            return state
        r_k = min(params.rank, 2 * r_prev)
        b_out = np.empty((n_k, r_prev, r_prev), x_host.dtype)
        t_out = np.empty((n_k, 2 * r_prev, r_k), x_host.dtype)
        skel_out = np.empty((n_k, r_k), np.int32)
        rank_out = np.empty((n_k,), np.int32)
        for s in range(0, n_k, bsz):
            e = min(s + bsz, n_k)                # n_k, bsz even -> e-s even
            cand_b = cand[s:e]
            # NEAR proxies: the sibling's candidates, exchanged batch-locally
            # (batches are even-aligned so both siblings are present).
            sib = cand_b.reshape(-1, 2, 2 * r_prev)[:, ::-1].reshape(
                e - s, 2 * r_prev)
            cp = jnp.asarray(x_host[cand_b])
            xp = jnp.asarray(np.concatenate(
                [x_host[sib], x_host[far_idx[k][s:e]]], axis=1))
            cm = jnp.asarray(cm_all[s:e]) if adaptive else None
            b, piv, t, rks = _stream_level_batch(spec, cp, xp, cm, r_k,
                                                 rtol, adaptive, m * 2 ** k)
            stats.peak_stream_bytes = max(
                stats.peak_stream_bytes,
                _device_bytes(cp, xp, b, piv, t, rks))
            stats.n_batches += 1
            b_out[s:e] = jax.device_get(b)
            t_out[s:e] = jax.device_get(t)
            skel_out[s:e] = np.take_along_axis(
                cand_b, np.asarray(jax.device_get(piv)), axis=1)
            rank_out[s:e] = jax.device_get(rks)
        state.update({f"b_{k}": b_out, f"t_{k}": t_out,
                      f"skel_{k}": skel_out, f"ranks_{k}": rank_out})
        return state

    def _step(state: dict, i: int) -> dict:
        if on_level is not None:
            on_level(i)
        if i == 0:
            with obs.span("hss.compress.leaves"):
                return _run_leaves(state)
        with obs.span("hss.compress.levels"):
            return _run_level(state, i)

    def _save(state: dict, completed: int) -> None:
        if stream.ckpt_dir is None:
            return
        ckpt.save_checkpoint(stream.ckpt_dir, state, completed, extra=fp)
        stats.checkpointed_levels = completed

    def _restore():
        if stream.ckpt_dir is None:
            return None
        step = ckpt.latest_step(stream.ckpt_dir)
        if step is None:
            return None
        arrays, got, extra = ckpt.load_checkpoint_arrays(
            stream.ckpt_dir, step)
        if {key: extra.get(key) for key in fp} != fp:
            return None                      # someone else's checkpoint
        stats.resumed_level = got
        return arrays, got

    state, report = run_resilient(
        K + 1, dict, _step, _save, _restore,
        ckpt_every=stream.ckpt_every_levels if stream.ckpt_dir else 0,
        max_restarts=stream.max_restarts)
    stats.restarts = report["restarts"]

    # ---------------- assembly ---------------- #
    if stream.assemble == "host" and mesh is None:
        def put(a):
            return a
        x_out = x_host
    elif mesh is None:
        put = jnp.asarray
        x_out = jnp.asarray(x_host)
    else:
        from jax.sharding import NamedSharding, PartitionSpec

        nodes, ndev = _mesh_nodes(mesh)

        def put(a):
            # compress_sharded-compatible placement: node-stacked arrays are
            # sharded along the node axis when it divides the device count,
            # tiny upper-tree arrays replicate; factorize_sharded re-pins
            # everything itself, so this only has to be a sane start.
            if a.ndim >= 1 and a.shape[0] > 1 and a.shape[0] % ndev == 0:
                p = PartitionSpec(nodes, *([None] * (a.ndim - 1)))
            else:
                p = PartitionSpec()
            return jax.device_put(a, NamedSharding(mesh, p))

        x_out = put(x_host)

    hss = HSSMatrix(
        x=x_out,
        d_leaf=put(state["d_leaf"]),
        u_leaf=put(state["u_leaf"]),
        skel_leaf=put(state["skel_leaf"]),
        transfers=tuple(put(state[f"t_{k}"]) for k in range(1, K)),
        skels=tuple(put(state[f"skel_{k}"]) for k in range(1, K)),
        b_mats=tuple(put(state[f"b_{k}"]) for k in range(1, K + 1)),
        levels=K,
        leaf_size=m,
        leaf_ranks=put(state["ranks_leaf"]) if adaptive else None,
        level_ranks=tuple(put(state[f"ranks_{k}"])
                          for k in range(1, K)) if adaptive else (),
    )
    return hss, stats


def compression_error(hss: HSSMatrix, spec: KernelSpec, n_probe: int = 8,
                      seed: int = 0) -> Array:
    """Stochastic relative Frobenius error ||K̃ - K||_F / ||K||_F via probes.

    Uses Hutchinson-style probing with the *streamed* exact kernel matvec, so
    it never materializes K — usable at large N as a compression diagnostic
    (paper eq. (9) ties this to the objective gap).
    """
    from repro.core.kernelfn import kernel_matvec_streamed

    key = jax.random.PRNGKey(seed)
    v = jax.random.normal(key, (hss.n, n_probe), hss.x.dtype)
    kv = kernel_matvec_streamed(spec, hss.x, hss.x, v)
    kv_hss = hss.matmat(v)
    return jnp.linalg.norm(kv_hss - kv) / jnp.maximum(jnp.linalg.norm(kv), 1e-30)
