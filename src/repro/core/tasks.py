"""The task layer on the shared K_β⁻¹: problem columns, bias rules, scores.

Classification, ε-SVR and one-class SVM all train on ONE HSS compression and
factorization; what differs per task lives here: how labels become (d, P)
problem columns, how each column's bias or offset is recovered, and how
``engine.grid_search`` scores a trained model.

  k-class SVM: one-vs-rest (k columns) or one-vs-one (k(k−1)/2 pair
    columns) on the same K̃ + βI, which never sees the labels.  A pair
    problem keeps the full padded coordinate set and pins every point
    outside its two classes to the box [0, 0] (the mechanism that makes
    tree padding inert).  The bias is paper eq. (7), batched over columns.

The shifted kernel K̃ + βI depends only on the data, the bandwidth h, and β —
never on the task.  Related work (semismooth-Newton and augmented-Lagrangian
kernel-machine solvers) treats kernel SVC, ε-SVR, and one-class/novelty
detection as instances of one box-QP family; this module supplies the two
non-classification members on the exact same HSS compression + factorization
the SVM path uses (see repro.core.admm for the generic solver and
repro.core.engine for the orchestration):

  ε-SVR (difference-form dual, variables α = α⁺ − α⁻ ∈ R^d):
      min ½ αᵀKα − yᵀα + ε‖α‖₁   s.t. eᵀα = 0,  α ∈ [−C, C]^d
    The ℓ1 term — which makes the 2d-variable form a QP — is handled
    exactly by the ADMM z-step's soft-threshold prox, so the d-dimensional
    difference form rides K_β⁻¹ directly: ONE multi-RHS solve per
    iteration, same as classification.  Prediction is f(x) = Σ αᵢ K(xᵢ, x)
    + b with b recovered from the margin support vectors (|αᵢ| strictly
    inside (0, C): y_i − f(x_i) = ε·sign(αᵢ)).

  one-class SVM (Schölkopf ν-parameterization):
      min ½ αᵀKα   s.t. eᵀα = 1,  α ∈ [0, 1/(νn)]^d
    ν bounds the outlier fraction; the offset ρ = (Kα)ᵢ on the margin
    support vectors (0 < αᵢ < 1/(νn)), and f(x) = Σ αᵢ K(xᵢ, x) − ρ is
    ≥ 0 on the estimated support of the data.

Both bias extractions cost ONE HSS matmat (paper eq. (7)'s trick applied to
the new tasks) and are batched over problem columns like compute_bias_batched.
Padded points (tree.pad_dataset) are pinned to the [0, 0] box through the
participation mask exactly as in classification, so the restriction of the
ADMM fixed point to real points solves the original problem.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.admm import BoxQPTask, box_matrix
from repro.core.hss import HSSMatrix

Array = jax.Array


# --------------------------------------------------------------------- #
# task builders                                                         #
# --------------------------------------------------------------------- #
def svr_task(targets: Array, c_box: Array | float, epsilon: Array | float
             ) -> BoxQPTask:
    """ε-SVR difference-form dual for k regression problems.

    ``targets`` is (k, d) (or (d,)) response vectors; ``c_box`` a scalar or
    (k, d) per-coordinate bound — pass C·mask so padded points get the
    inert [0, 0] box; ``epsilon`` the tube half-width (scalar or (k,)).
    """
    t = jnp.atleast_2d(jnp.asarray(targets))            # (k, d)
    k, d = t.shape
    dtype = t.dtype
    c_mat = box_matrix(c_box, d, k, dtype)
    return BoxQPTask(
        sign=jnp.ones((d, k), dtype),
        lin=-t.T,
        lo=-c_mat,
        hi=c_mat,
        eq_sa=jnp.ones((d,), dtype),
        eq_b=None,
        l1=jnp.broadcast_to(jnp.asarray(epsilon, dtype), (k,)),
    )


def one_class_task(mask: Array, nu: Array | float) -> BoxQPTask:
    """Schölkopf ν one-class SVM for k problems.

    ``mask`` is (k, d) (or (d,)) participation masks (1 real, 0 pad): the
    box upper bound is mask/(ν·n_real) so pads are pinned to [0, 0] and the
    feasible mass eᵀα = 1 lives on real points (1/ν ≥ 1 of box headroom).
    """
    m = jnp.atleast_2d(jnp.asarray(mask))               # (k, d)
    k, d = m.shape
    dtype = m.dtype
    n_real = jnp.sum(m, axis=1)                         # (k,)
    nu_arr = jnp.broadcast_to(jnp.asarray(nu, dtype), (k,))
    hi = m.T / (nu_arr * n_real)[None, :]               # (d, k); pads -> 0
    return BoxQPTask(
        sign=jnp.ones((d, k), dtype),
        lin=jnp.zeros((d, k), dtype),
        lo=jnp.zeros((d, k), dtype),
        hi=hi,
        eq_sa=jnp.ones((d,), dtype),
        eq_b=jnp.ones((k,), dtype),
        l1=None,
    )


# --------------------------------------------------------------------- #
# multiclass reductions: k classes -> P binary problems on one K̃        #
# --------------------------------------------------------------------- #
def ovr_problems(y: np.ndarray, classes: np.ndarray, real_mask: np.ndarray
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
    """One-vs-rest label matrix (k, d) and participation masks (k, d)."""
    ys = np.where(y[None, :] == classes[:, None], 1.0, -1.0)
    masks = np.broadcast_to(real_mask[None, :], ys.shape)
    return ys.astype(np.float32), masks.astype(np.float32), None


def ovo_problems(y: np.ndarray, classes: np.ndarray, real_mask: np.ndarray
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One-vs-one problems: (P, d) labels/masks + (P, 2) class-index pairs.

    Non-participating points keep label -1 but get box [0, 0] via the mask,
    so they are inert in the pair's ADMM fixed point.
    """
    k = classes.shape[0]
    pairs = np.array([(a, b) for a in range(k) for b in range(a + 1, k)],
                     dtype=np.int32).reshape(-1, 2)
    ys, masks = [], []
    for a, b in pairs:
        in_pair = (y == classes[a]) | (y == classes[b])
        ys.append(np.where(y == classes[a], 1.0, -1.0))
        masks.append((real_mask & in_pair).astype(np.float32))
    return (np.stack(ys).astype(np.float32), np.stack(masks).astype(np.float32),
            pairs)


def ovo_vote(scores: Array, pairs: np.ndarray, n_classes: int) -> Array:
    """One-vs-one decision: (n_test, P) pair scores -> (n_test,) class indices.

    Each pair votes for its winner; vote ties break toward the larger summed
    functional margin.  Shared by ``core.engine.EngineModel`` and mirrored
    on the host by the serving tier's decode, so the tie-break can never
    drift.
    """
    pairs = jnp.asarray(pairs)
    winner = jnp.where(scores >= 0, pairs[:, 0][None, :],
                       pairs[:, 1][None, :])
    votes = jax.nn.one_hot(winner, n_classes).sum(axis=1)
    margin = jnp.zeros_like(votes)
    margin = margin.at[:, pairs[:, 0]].add(scores)
    margin = margin.at[:, pairs[:, 1]].add(-scores)
    return jnp.argmax(votes + 1e-3 * jnp.tanh(margin), axis=1)


# --------------------------------------------------------------------- #
# bias / offset extraction (one HSS matmat each, batched over columns)  #
# --------------------------------------------------------------------- #
def compute_bias_batched(hss: HSSMatrix, ys: Array, z: Array, c_mat: Array,
                         masks: Array, margin_tol: float = 1e-6) -> Array:
    """Paper eq. (7) for P problems sharing one kernel, with ONE HSS matmat.

    b_p = (z_yᵀ K̃ ē − Σ_{j∈M_p} y_j) / |M_p| where M_p = margin support
    vectors {j : 0 < z_jp < C_jp} of problem p.  Falls back to the average
    functional margin over all bounded SVs when M_p is empty.  ``ys``/``z``/
    ``c_mat``/``masks`` are (d, P) column blocks; returns (P,).
    """
    f32 = jnp.float32
    on_margin = (
        (z > margin_tol) & (z < c_mat - margin_tol) & (masks > 0)
    ).astype(z.dtype)
    n_m = jnp.sum(on_margin, axis=0)                       # (P,)
    kz = hss.matmat(ys * z)                 # K̃ (Y z) — one O(N r) sweep
    num = (jnp.einsum("dp,dp->p", on_margin, kz, preferred_element_type=f32)
           - jnp.einsum("dp,dp->p", on_margin, ys,
                        preferred_element_type=f32))
    b_margin = -num / jnp.maximum(n_m, 1.0)
    # Fallback per problem: average functional margin over all (bounded) SVs.
    sv = ((z > margin_tol) & (masks > 0)).astype(z.dtype)
    n_sv = jnp.maximum(jnp.sum(sv, axis=0), 1.0)
    b_all = -(jnp.einsum("dp,dp->p", sv, kz, preferred_element_type=f32)
              - jnp.einsum("dp,dp->p", sv, ys,
                           preferred_element_type=f32)) / n_sv
    return jnp.where(n_m > 0, b_margin, b_all)


def compute_bias_svr_batched(hss: HSSMatrix, targets: Array, alpha: Array,
                             c_mat: Array, masks: Array,
                             epsilon: Array | float,
                             margin_rel: float = 1e-4) -> Array:
    """SVR bias from the margin SVs, with ONE HSS matmat for all P problems.

    For margin support vectors (0 < |αᵢ| < C strictly) the KKT conditions
    give yᵢ − (Kα)ᵢ − b = ε·sign(αᵢ), so b averages yᵢ − (Kα)ᵢ − ε·sign(αᵢ)
    over them.  Falls back to all support vectors, then to all real points
    (ε term dropped — the unbiased residual mean).  All column blocks are
    (d, P); returns (P,).
    """
    k_alpha = hss.matmat(alpha)                         # K̃ α, one O(N r) sweep
    absa = jnp.abs(alpha)
    tol = margin_rel * c_mat
    resid = targets - k_alpha - epsilon * jnp.sign(alpha)
    on_margin = ((absa > tol) & (absa < c_mat - tol)
                 & (masks > 0)).astype(alpha.dtype)
    n_m = jnp.sum(on_margin, axis=0)
    f32 = jnp.float32
    b_margin = (jnp.einsum("dp,dp->p", on_margin, resid,
                           preferred_element_type=f32)
                / jnp.maximum(n_m, 1.0))
    sv = ((absa > tol) & (masks > 0)).astype(alpha.dtype)
    n_sv = jnp.sum(sv, axis=0)
    b_sv = (jnp.einsum("dp,dp->p", sv, resid, preferred_element_type=f32)
            / jnp.maximum(n_sv, 1.0))
    b_all = (jnp.einsum("dp,dp->p", masks, targets - k_alpha,
                        preferred_element_type=f32)
             / jnp.maximum(jnp.sum(masks, axis=0), 1.0))
    return jnp.where(n_m > 0, b_margin, jnp.where(n_sv > 0, b_sv, b_all))


def compute_rho_oneclass_batched(hss: HSSMatrix, alpha: Array, hi_mat: Array,
                                 masks: Array, margin_rel: float = 1e-3
                                 ) -> Array:
    """One-class offset ρ = (K̃α)ᵢ averaged over margin SVs (0 < αᵢ < 1/(νn)).

    Falls back to all support vectors when every SV sits at the bound.  The
    decision function is f(x) = Σ αᵢ K(xᵢ, x) − ρ (≥ 0 inside the estimated
    support), i.e. the model bias is −ρ.  Blocks are (d, P); returns (P,).
    """
    k_alpha = hss.matmat(alpha)
    tol = margin_rel * hi_mat
    on_margin = ((alpha > tol) & (alpha < hi_mat - tol)
                 & (masks > 0)).astype(alpha.dtype)
    n_m = jnp.sum(on_margin, axis=0)
    f32 = jnp.float32
    rho_margin = (jnp.einsum("dp,dp->p", on_margin, k_alpha,
                             preferred_element_type=f32)
                  / jnp.maximum(n_m, 1.0))
    sv = ((alpha > tol) & (masks > 0)).astype(alpha.dtype)
    n_sv = jnp.maximum(jnp.sum(sv, axis=0), 1.0)
    rho_sv = (jnp.einsum("dp,dp->p", sv, k_alpha, preferred_element_type=f32)
              / n_sv)
    return jnp.where(n_m > 0, rho_margin, rho_sv)


def prolong_scale(task: str, n_coarse_real: int, n_fine_real: int) -> float:
    """Dual rescale factor n_c/n_f for coarse→fine prolongation.

    The decision function f(x) = Σᵢ αᵢ K(xᵢ, x) sums one kernel term per
    training point, so at a comparable margin the individual duals shrink
    like 1/n as the training set grows: nearest-neighbour prolongation
    copies each coarse dual ≈ n_f/n_c times, and without the n_c/n_f
    rescale the warm start overshoots the fine-level magnitudes by that
    factor (measurably worse than a cold start for SVC).  For one-class
    the same factor additionally restores unit mass eᵀα = 1 and maps the
    coarse box bound 1/(ν·n_c) onto the fine one 1/(ν·n_f).
    """
    del task  # the 1/n magnitude argument applies to every box-QP family
    return float(n_coarse_real) / float(max(n_fine_real, 1))


def prolong_duals(x_coarse: np.ndarray, z_coarse: np.ndarray,
                  x_fine: np.ndarray) -> np.ndarray:
    """Nearest-neighbour prolongation of per-point dual columns.

    The AML-SVM multilevel scheme (arXiv 2011.02592): a dual vector trained
    on a coarse subsample is lifted to the fine set by giving every fine
    point its nearest coarse point's dual value — support-vector regions
    stay support-vector regions, so the fine ADMM starts near its fixed
    point instead of at zero.  ``x_coarse`` (n_c, f) / ``x_fine`` (n_f, f)
    are point sets (padded, permuted — any consistent order), ``z_coarse``
    is (n_c,) or (n_c, P); returns the matching (n_f, ...) array.  Distances
    are ranked in f32 (bf16 inputs are fine); the dual VALUES are copied
    untouched.  Task-dependent mass rescaling is ``tasks.prolong_scale``.
    """
    from scipy.spatial import cKDTree

    xc = np.asarray(x_coarse, np.float32)
    xf = np.asarray(x_fine, np.float32)
    _, nn = cKDTree(xc).query(xf, k=1)
    return np.asarray(z_coarse)[nn]


# --------------------------------------------------------------------- #
# holdout scores: grid_search's rule per task (higher is better)        #
# --------------------------------------------------------------------- #
def accuracy_score(model, x_val: Array, y_val: Array) -> float:
    """Holdout classification accuracy."""
    return float(jnp.mean(model.predict(x_val) == jnp.asarray(y_val)))


def neg_rmse_score(model, x_val: Array, y_val: Array) -> float:
    """Negated holdout RMSE of a regression model."""
    pred = model.predict(x_val)
    return -float(jnp.sqrt(jnp.mean((pred - jnp.asarray(y_val)) ** 2)))


def oneclass_metrics(pred, y_true) -> dict:
    """Outlier-detection metrics from ±1 predictions vs ±1 ground truth:
    precision/recall of the outlier (−1) class and balanced accuracy.
    The ONE home of the flagged/precision/recall arithmetic — bench, serve
    and the examples all report from here so the numbers cannot diverge."""
    pred = np.asarray(pred)
    y_true = np.asarray(y_true)
    flagged = pred < 0
    out = y_true < 0
    precision = float((flagged & out).sum() / max(flagged.sum(), 1))
    recall = float((flagged & out).sum() / max(out.sum(), 1))
    r_in = float((~flagged & ~out).sum() / max((~out).sum(), 1))
    return dict(precision=precision, recall=recall,
                balanced_accuracy=0.5 * (recall + r_in))


def oneclass_score(model, x_val: Array, y_val: Array) -> float:
    """Balanced accuracy of inlier(+1)/outlier(−1) detection."""
    return oneclass_metrics(model.predict(x_val), y_val)["balanced_accuracy"]


# The holdout rule ``engine.grid_search`` scores each task by.  "gp" has no
# entry: it is scored by the training log marginal, with no validation split.
HOLDOUT_SCORES = {
    "svm": accuracy_score,
    "svr": neg_rmse_score,
    "krr": neg_rmse_score,
    "oneclass": oneclass_score,
}
