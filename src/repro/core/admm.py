"""Closed-form ADMM for box-constrained kernel QPs (paper Algorithm 2).

The paper's ADMM solves one specific instance — the binary SVM dual

  min_x ½ xᵀ Y K Y x − eᵀx   s.t. yᵀx = 0,  x ∈ [0, C]^d

— but the expensive machinery (one shifted-kernel solve K_β⁻¹ per
iteration on the shared HSS factorization) is task-agnostic.  This module
therefore solves the general *box QP family*

  min_x ½ xᵀ S K S x + pᵀx + γ‖x‖₁   s.t. aᵀx = b,  x ∈ [lo, hi]^d

specified by a :class:`BoxQPTask` (S a diagonal ±1 "sign"/label matrix, so
S(K+βI)S = SKS + βI and ONE factorization of K+βI serves every task), split
as x − z = 0.  Per iteration (paper §2.1, generalized):

  x-step: the KKT system of the equality-constrained QP has the closed form
     x⁺ = S K_β⁻¹ S q − λ · S K_β⁻¹ (S a),
     q = −p + μ + β z,      λ = (vᵀ(S q) − b) / ((Sa)ᵀ v),   v = K_β⁻¹ (S a)
     — exactly ONE shifted-kernel solve per iteration (the HSS
     factorization's raison d'être) plus O(d) vector work; v is precomputed
     once per task (paper Alg. 3 lines 4–6; for the SVM instance S a = e and
     v is the paper's w).  Without an equality constraint the λ term drops.
  z-step: z⁺ = Π_[lo,hi](soft(x⁺ − μ/β, γ/β))   (prox of γ‖·‖₁ + box; with
     γ = 0 this is the paper's component-wise box projection)
  μ-step: μ⁺ = μ − β (x⁺ − z⁺)

Instances (see also repro.core.tasks for the ε-SVR / one-class builders):
  binary/multiclass SVM  S=Y, p=−e, a=y, b=0, [0, C], γ=0   (svm_task)
  ε-SVR difference dual  S=I, p=−y, a=e, b=0, [−C, C], γ=ε  (tasks.svr_task)
  one-class (ν-) SVM     S=I, p=0,  a=e, b=1, [0, 1/(νn)]   (tasks.one_class_task)

Note: paper Alg. 3 line 10 writes w2 = wᵀ x^k; from the derivation of eq. (5)
the projected vector is q^k = e + μ^k + β z^k (Alg. 2 line 2) — we follow the
math (Alg. 2).  The box bounds may be per-coordinate vectors, which is how
padded (inert) points are pinned to [0, 0] (tree.pad_dataset).

The loop is a ``lax.scan`` → a single fused trace regardless of MaxIt; the
paper's stopping rule is honored by ``tol``: once a problem's
max(primal, dual) residual drops below it, its updates are masked (iterates
frozen) and ``ADMMTrace.iters_run`` reports the live iteration count.  The
fused z/μ elementwise update is also available as a Pallas kernel
(repro.kernels.admm_update) for the TPU target (γ=0, lo=0 tasks only).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array
Solver = Callable[[Array], Array]      # b (d,)   -> K_beta^{-1} b
SolverMat = Callable[[Array], Array]   # B (d, k) -> K_beta^{-1} B


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BoxQPTask:
    """One batch of k box-QP problems sharing a single K_β factorization.

    min ½ xᵀ S K S x + pᵀx + γ‖x‖₁  s.t. aᵀx = b,  lo ≤ x ≤ hi — the sign
    diagonal S (±1) is the only way the kernel enters per-problem, so every
    task instance rides the SAME (K + βI) factorization.  All per-coordinate
    fields are (d, k) column blocks (problem axis last, matching the batched
    multi-RHS solve layout).
    """

    sign: Array             # (d, k) diagonal of S per problem (±1)
    lin: Array              # (d, k) linear term p
    lo: Array               # (d, k) box lower bounds
    hi: Array               # (d, k) box upper bounds
    # Equality constraint aᵀx = b, stored pre-multiplied by the sign
    # diagonal: eq_sa = S a — the only form the closed-form x-step needs
    # (v = K_β⁻¹(Sa) is precomputed once).  (d,) when all k problems share
    # it (every built-in task: SVM has Sa = y·y = e, SVR/one-class have
    # S = I, a = e), (d, k) for per-problem vectors, None for no constraint.
    eq_sa: Array | None = None
    eq_b: Array | None = None          # (k,) right-hand sides (None -> 0)
    l1: Array | None = None            # (k,) ℓ1 weights γ (None -> no prox)


class ADMMState(NamedTuple):
    x: Array
    z: Array
    mu: Array


class ADMMTrace(NamedTuple):
    primal_res: Array   # (max_it, k)  ||x - z|| per iteration
    dual_res: Array     # (max_it, k)  beta * ||z - z_prev|| per iteration
    iters_run: Array    # (k,) int32   iterations before the tol freeze
                        # (= max_it when tol is None / never reached)
    done: Array | None = None   # (k,) bool final freeze mask (tol runs only)
                                # — lets chunked outer loops (adaptive ρ)
                                # carry the freeze state across calls


@dataclasses.dataclass(frozen=True)
class ADMMParams:
    """Iteration-control bundle for engine-level ADMM runs.

    ``max_it``/``tol`` are the knobs the engine already exposes.  The rest
    switch on residual-balancing adaptive ρ (Boyd §3.4.1, default OFF to
    keep the committed golden pins bit-stable): the run is chunked into
    ``rho_every``-iteration pieces, and between chunks the penalty β is
    multiplied by ``rho_tau`` when the primal residual exceeds ``rho_mu``
    times the dual residual (divided when the imbalance is the other way).
    β is ALSO the factorization shift here — S(K+βI)S — so every rescale
    implies a refactorization of K̃ + βI; the caller owns that (it is cheap
    next to compression and the engine caches one factorization per visited
    β), and ``rho_max_updates`` caps how many times it can happen.
    """

    max_it: int = 10
    tol: float | None = None
    adapt_rho: bool = False
    rho_every: int = 5
    rho_mu: float = 10.0
    rho_tau: float = 2.0
    rho_max_updates: int = 4


def box_matrix(bound: Array | float, d: int, k: int, dtype) -> Array:
    """Normalize a box bound to (d, k) columns: accepts a scalar, a shared
    (d,) vector, or a per-problem (k, d) matrix (task row layout)."""
    arr = jnp.asarray(bound, dtype)
    if arr.ndim == 1:                              # shared (d,) box vector
        arr = arr[:, None]
    elif arr.ndim == 2:                            # per-problem (k, d)
        arr = arr.T
    return jnp.broadcast_to(arr, (d, k))


def svm_task(ys: Array, c_upper: Array | float) -> BoxQPTask:
    """The paper's binary SVM dual as a BoxQPTask: the (k, d) label matrix
    ``ys`` gives S = Y and a = y per problem (so S a = e, shared), p = −e,
    box [0, C].  ``c_upper`` may be a scalar, a shared (d,) vector, or a
    per-problem (k, d) matrix (one-vs-one pins non-participants to [0, 0])."""
    k, d = ys.shape
    dtype = ys.dtype
    return BoxQPTask(
        sign=ys.T,
        lin=jnp.full((d, k), -1.0, dtype),
        lo=jnp.zeros((d, k), dtype),
        hi=box_matrix(c_upper, d, k, dtype),
        eq_sa=jnp.ones((d,), dtype),
        eq_b=None,
        l1=None,
    )


def admm_boxqp(
    solver_mat: SolverMat,
    task: BoxQPTask,
    beta: float,
    max_it: int = 10,
    tol: float | None = None,
    z0: Array | None = None,
    mu0: Array | None = None,
    use_fused_update: bool = False,
    done0: Array | None = None,
) -> tuple[ADMMState, ADMMTrace]:
    """Run k box-QP ADMM problems that share one (K̃ + βI) factorization.

    ``solver_mat`` must apply (K̃ + βI)^{-1} to a (d, k) block; with the HSS
    factorization each call is ONE O(d r) multi-RHS sweep
    (factorization.hss_solve_mat) — the per-iteration solves of all k
    problems fused, the paper's factor-once economy extended across the
    problem axis.  The equality-side vector v = K_β⁻¹(Sa) is computed once
    per call and shared when ``task.eq_sa`` is a shared (d,) vector.

    State arrays are (d, k); traces are (max_it, k).  Supports (d, k) warm
    starts ``z0``/``mu0`` for knob-grid sweeps (C, ε, ν).  ``tol`` masks a
    problem's updates once both residuals pass the RELATIVE stopping test
    (Boyd §3.3.1: ‖x−z‖ < tol·(1+max(‖x‖,‖z‖)) and β‖Δz‖ < tol·(1+‖μ‖)) —
    its iterates freeze at the stopping iterate (the paper's stopping rule
    inside the fixed-length scan) and ``trace.iters_run`` reports how many
    live iterations it ran.  ``done0`` seeds the freeze mask, so a chunked
    outer loop (``adaptive_rho_outer``) can carry it across calls without
    re-running finished problems.
    ``use_fused_update`` routes the elementwise z/μ step through the Pallas
    kernel (repro.kernels.admm_update) on the flattened (d·k,) block — only
    valid for γ=0, lo=0 tasks (the SVM instance).
    """
    d, k = task.sign.shape
    dtype = task.sign.dtype
    s_cols = task.sign
    neg_lin = -task.lin
    lo_mat = jnp.broadcast_to(task.lo, (d, k))
    hi_mat = jnp.broadcast_to(task.hi, (d, k))

    has_eq = task.eq_sa is not None
    if has_eq:
        if task.eq_sa.ndim == 1:       # shared vector: ONE single-RHS solve
            v = solver_mat(task.eq_sa[:, None])[:, 0]      # K_β^{-1} (Sa)
            w1 = task.eq_sa @ v
            sv = s_cols * v[:, None]                       # (d, k)

            def eq_dot(sq):
                return v @ sq                              # (k,)
        else:                          # per-problem vectors: one k-RHS solve
            v = solver_mat(task.eq_sa)
            w1 = jnp.einsum("dk,dk->k", task.eq_sa, v,
                            preferred_element_type=jnp.float32)
            sv = s_cols * v

            def eq_dot(sq):
                return jnp.einsum("dk,dk->k", v, sq,
                                  preferred_element_type=jnp.float32)
        eq_b = jnp.zeros((k,), dtype) if task.eq_b is None else task.eq_b

    z_init = jnp.zeros((d, k), dtype) if z0 is None else z0
    mu_init = jnp.zeros((d, k), dtype) if mu0 is None else mu0

    if use_fused_update:
        if task.l1 is not None:
            raise ValueError("fused z/mu update supports only gamma=0 tasks")
        # The Pallas kernel clips to [0, c]: a nonzero lower bound would be
        # silently mis-projected.  lo is only checkable when concrete (the
        # engine builds tasks inside jit; its svm path always has lo = 0).
        if (not isinstance(task.lo, jax.core.Tracer)
                and bool(jnp.any(task.lo != 0))):
            raise ValueError("fused z/mu update supports only lo=0 tasks")
        from repro.kernels.admm_update import ops as admm_ops

        c_flat = hi_mat.reshape(-1)                # the Pallas kernel is 1-D
        # Compiled by Mosaic on a TPU; elsewhere only the interpreter can
        # run a TPU kernel.
        interpret = jax.default_backend() != "tpu"

        def zmu_update(x, mu):
            z_f, mu_f = admm_ops.fused_zmu_update(
                x.reshape(-1), mu.reshape(-1), c_flat, beta,
                interpret=interpret)
            return z_f.reshape(x.shape), mu_f.reshape(x.shape)
    else:
        if task.l1 is None:
            def prox(t):
                return jnp.clip(t, lo_mat, hi_mat)
        else:
            thr = (jnp.broadcast_to(task.l1, (k,)) / beta)[None, :]

            def prox(t):               # prox of (γ‖·‖₁ + box)/β: shrink, clip
                t = jnp.sign(t) * jnp.maximum(jnp.abs(t) - thr, 0.0)
                return jnp.clip(t, lo_mat, hi_mat)

        def zmu_update(x, mu):
            z_new = prox(x - mu / beta)
            mu_new = mu - beta * (x - z_new)
            return z_new, mu_new

    def step(carry, _):
        if tol is None:
            state = carry
        else:
            state, done, iters = carry
        x, z, mu = state
        q = neg_lin + mu + beta * z
        sq = s_cols * q                            # (d, k)
        u = solver_mat(sq)                         # ONE k-RHS solve
        if has_eq:
            lam = (eq_dot(sq) - eq_b) / w1         # (k,)
            x_new = s_cols * u - lam[None, :] * sv
        else:
            x_new = s_cols * u
        z_new, mu_new = zmu_update(x_new, mu)
        if tol is not None:
            keep = done[None, :]                   # frozen problems hold
            x_new = jnp.where(keep, x, x_new)
            z_new = jnp.where(keep, z, z_new)
            mu_new = jnp.where(keep, mu, mu_new)
            iters = iters + (~done).astype(jnp.int32)
        primal = jnp.linalg.norm(x_new - z_new, axis=0)
        dual = beta * jnp.linalg.norm(z_new - z, axis=0)
        new_state = ADMMState(x_new, z_new, mu_new)
        if tol is None:
            return new_state, (primal, dual)
        # Relative stopping criteria (Boyd §3.3.1): the raw residual norms
        # scale with √d, β, and the iterate magnitudes, so tol gates the
        # residuals normalized by the natural primal/dual scales.
        p_scale = 1.0 + jnp.maximum(jnp.linalg.norm(x_new, axis=0),
                                    jnp.linalg.norm(z_new, axis=0))
        d_scale = 1.0 + jnp.linalg.norm(mu_new, axis=0)
        done = done | ((primal < tol * p_scale) & (dual < tol * d_scale))
        return (new_state, done, iters), (primal, dual)

    init_state = ADMMState(jnp.zeros((d, k), dtype), z_init, mu_init)
    if tol is None:
        final, (primal, dual) = jax.lax.scan(step, init_state, None,
                                             length=max_it)
        iters_run = jnp.full((k,), max_it, jnp.int32)
        done_out = None
    else:
        d_init = jnp.zeros((k,), bool) if done0 is None else done0
        carry = (init_state, d_init, jnp.zeros((k,), jnp.int32))
        (final, done_out, iters_run), (primal, dual) = jax.lax.scan(
            step, carry, None, length=max_it)
    return final, ADMMTrace(primal, dual, iters_run, done_out)


def adaptive_rho_outer(
    run_chunk: Callable,
    beta0: float,
    params: ADMMParams,
    z0: Array | None = None,
    mu0: Array | None = None,
) -> tuple[ADMMState, ADMMTrace, dict]:
    """Residual-balancing ρ (Boyd §3.4.1) as a host loop of scan chunks.

    ``run_chunk(beta, n_it, z0, mu0, done0) -> (ADMMState, ADMMTrace)`` runs
    ``n_it`` iterations at penalty β — the caller owns the factorization of
    K̃ + βI a rescale implies (the engine passes its process-wide jitted
    ADMM program, which takes the factorization as a pytree argument).  Between chunks the last live residuals are balanced:
    primal > ρ_μ·dual ⟹ β ← τβ, dual > ρ_μ·primal ⟹ β ← β/τ, at most
    ``rho_max_updates`` times.  The UNSCALED multiplier μ is carried across
    a rescale — it is the β-invariant quantity (Boyd eq. 3.14 rescales the
    scaled u = μ/β; μ itself is unchanged) — and the freeze mask is reset
    because the relative stopping test moves with β.

    Returns (state, trace, info): ``trace.iters_run`` sums LIVE iterations
    across chunks, the residual traces are the chunks concatenated, and
    ``info`` records the final β and the rescale count.
    """
    z, mu, done = z0, mu0, None
    beta = float(beta0)
    it_left = int(params.max_it)
    rescales = 0
    iters_total = None
    state = None
    prs: list[Array] = []
    drs: list[Array] = []
    while it_left > 0:
        n_it = min(params.rho_every, it_left) if params.adapt_rho else it_left
        state, trace = run_chunk(beta, n_it, z, mu, done)
        z, mu, done = state.z, state.mu, trace.done
        iters_total = (trace.iters_run if iters_total is None
                       else iters_total + trace.iters_run)
        prs.append(trace.primal_res)
        drs.append(trace.dual_res)
        it_left -= n_it
        if done is not None and bool(jnp.all(done)):
            break
        if (params.adapt_rho and it_left > 0
                and rescales < params.rho_max_updates):
            pr, dr = trace.primal_res[-1], trace.dual_res[-1]
            if done is not None:      # balance on LIVE problems only
                pr = jnp.where(done, 0.0, pr)
                dr = jnp.where(done, 0.0, dr)
            p, d = float(jnp.max(pr)), float(jnp.max(dr))
            new_beta = beta
            if p > params.rho_mu * d:
                new_beta = beta * params.rho_tau
            elif d > params.rho_mu * p:
                new_beta = beta / params.rho_tau
            if new_beta != beta:
                beta = new_beta
                rescales += 1
                done = None
    trace = ADMMTrace(jnp.concatenate(prs), jnp.concatenate(drs),
                      iters_total, done)
    return state, trace, dict(beta=beta, rescales=rescales)


def admm_boxqp_adaptive(
    solver_for: Callable[[float], SolverMat],
    task: BoxQPTask,
    beta0: float,
    params: ADMMParams,
    z0: Array | None = None,
    mu0: Array | None = None,
) -> tuple[ADMMState, ADMMTrace, dict]:
    """:func:`admm_boxqp` under the residual-balancing outer loop.

    ``solver_for(beta)`` must return a (d, k)-block solver for (K̃ + βI) —
    with the HSS machinery that is ``factorization.factorize(hss, beta)
    .solve_mat``, and callers should cache it per visited β (the engine
    does).  With ``params.adapt_rho`` False this is a single plain
    ``admm_boxqp`` run (plus the info dict).
    """
    def run_chunk(beta, n_it, z, mu, done):
        return admm_boxqp(solver_for(beta), task, beta, max_it=n_it,
                          tol=params.tol, z0=z, mu0=mu, done0=done)

    return adaptive_rho_outer(run_chunk, beta0, params, z0=z0, mu0=mu0)


def admm_svm(
    solver: Solver,
    y: Array,
    c_upper: Array | float,
    beta: float,
    max_it: int = 10,
    z0: Array | None = None,
    mu0: Array | None = None,
    use_fused_update: bool = False,
    tol: float | None = None,
) -> tuple[ADMMState, ADMMTrace]:
    """Run MaxIt closed-form ADMM iterations (paper fixes MaxIt = 10).

    ``solver`` must apply (K̃ + βI)^{-1}; with the HSS factorization each call
    is O(d r).  Supports warm starts (z0, mu0) — used by the C-grid search.
    Single-problem (k = 1) view of ``admm_svm_batched``.
    """
    d = y.shape[0]
    c_vec = jnp.broadcast_to(jnp.asarray(c_upper, y.dtype), (d,))
    state, trace = admm_svm_batched(
        lambda b: solver(b[:, 0])[:, None],
        y[None, :], c_vec[None, :], beta, max_it,
        z0=None if z0 is None else z0[:, None],
        mu0=None if mu0 is None else mu0[:, None],
        use_fused_update=use_fused_update,
        tol=tol,
    )
    return (ADMMState(*(a[:, 0] for a in state)),
            ADMMTrace(trace.primal_res[:, 0], trace.dual_res[:, 0],
                      trace.iters_run[0],
                      None if trace.done is None else trace.done[0]))


def admm_svm_batched(
    solver_mat: SolverMat,
    ys: Array,
    c_upper: Array | float,
    beta: float,
    max_it: int = 10,
    z0: Array | None = None,
    mu0: Array | None = None,
    use_fused_update: bool = False,
    tol: float | None = None,
) -> tuple[ADMMState, ADMMTrace]:
    """Run k SVM dual ADMM problems that share one (K̃ + βI) factorization.

    ``ys`` is (k, d): one ±1 label vector per problem (the per-class label
    vectors of a one-vs-rest reduction, or per-pair vectors of one-vs-one).
    The binary-classification instance of :func:`admm_boxqp` — the kernel
    side of the x-step is label-independent, so w = K_β⁻¹ e is computed ONCE
    and shared by every problem, and the per-iteration solves of all k
    problems are ONE multi-RHS sweep over a (d, k) block.
    """
    return admm_boxqp(solver_mat, svm_task(ys, c_upper), beta, max_it=max_it,
                      tol=tol, z0=z0, mu0=mu0,
                      use_fused_update=use_fused_update)


def paper_beta(d: int) -> float:
    """The paper's β staging rule (§3.3): 1e2 / 1e3 / 1e4 by training size."""
    if d >= 1_000_000:
        return 1e4
    if d >= 100_000:
        return 1e3
    return 1e2
