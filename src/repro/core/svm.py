"""SVM training/prediction via HSS + ADMM (paper Algorithm 3).

Pipeline (= paper Alg. 3):
  1. K̃   = HSScompression(K(F_train, F_train), h)          [compress once]
  2. fac  = factorize(K̃ + βI)                               [factor once]
  3. for C in grid: run MaxIt ADMM iterations                [O(d r) each]
  4. bias via eq. (7) — ONE HSS matvec instead of d kernel evaluations
  5. predict: sign(Σ_i (z_y)_i K(f_i, f_test_j) + b), streamed block kernel
     evaluations (the Pallas gaussian kernel on TPU).

Padding: datasets are padded to leaf_size * 2**levels with mutually-far
points (tree.pad_dataset).  Pads get box constraint [0, 0] so the ADMM fixed
point has x_pad = z_pad = 0 and the restriction to real points solves the
original problem; kernel rows of pads are ~0 so K̃_pad ≈ blockdiag(K̃, I),
leaving the real block's solves untouched.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import admm as admm_mod
from repro.core import compression, factorization, tree as tree_mod
from repro.core.hss import HSSMatrix, shrink_report
from repro.core.kernelfn import DEFAULT_SCORE_BLOCK, KernelSpec, kernel_block

Array = jax.Array


@dataclasses.dataclass
class SVMModel:
    """A trained classifier: support coefficients in permuted order."""

    x_perm: Array          # (N, f) padded+permuted training points
    z_y: Array             # (N,)  y_i * z_i  (pads are exactly 0)
    bias: float
    spec: KernelSpec
    c_value: float

    def decision_function(self, x_test: Array,
                          block: int = DEFAULT_SCORE_BLOCK) -> Array:
        from repro.core.kernelfn import kernel_matvec_streamed

        scores = kernel_matvec_streamed(
            self.spec, x_test, self.x_perm, self.z_y, block=block
        )
        return scores + self.bias

    def predict(self, x_test: Array,
                block: int = DEFAULT_SCORE_BLOCK) -> Array:
        return jnp.where(self.decision_function(x_test, block=block) >= 0,
                         1, -1)


@dataclasses.dataclass
class FitReport:
    """Timings mirroring the paper's Tables 4/5 columns.

    The rank fields are populated by adaptive (``CompressionParams.rtol``)
    builds: per-level stored rank caps before/after the shrink-to-fit pass,
    the corresponding Σ n_k·r_k storage sums, and the exact number of kernel
    entries the compression evaluated — the observability hooks the bench
    records so rank adaptivity shows up in the perf trajectory.
    """

    compression_s: float
    factorization_s: float
    admm_s: float
    memory_mb: float
    hss_levels: int
    beta: float
    ranks_pre: tuple | None = None
    ranks_post: tuple | None = None
    rank_sum_pre: int | None = None
    rank_sum_post: int | None = None
    kernel_evals: int | None = None
    # per-problem ADMM iterations actually run by the last train() — below
    # max_it when the residual stopping rule (``tol``) froze the iterates
    iters_run: tuple | None = None
    # streamed-build observability (compression.StreamStats): peak device
    # bytes of any one batch round-trip — the build's working set, which a
    # streamed build bounds by batch size instead of O(N·d) — plus the
    # batch count and resume/restart record
    peak_stream_bytes: int | None = None
    stream_batches: int | None = None
    stream_resumed_level: int | None = None
    stream_restarts: int | None = None
    # adaptive-ρ record of the last train(): final β and rescale count
    rho_final: float | None = None
    rho_rescales: int | None = None


@dataclasses.dataclass
class HSSSVMTrainer:
    """compress-once / factor-once / train-many driver."""

    spec: KernelSpec
    comp: compression.CompressionParams = dataclasses.field(
        default_factory=compression.CompressionParams
    )
    leaf_size: int = 128
    beta: float | None = None     # default: the paper's rule by dataset size
    max_it: int = 10
    tol: float | None = None      # ADMM residual early-stop (paper's rule)

    # populated by prepare():
    _hss: HSSMatrix | None = None
    _fac: factorization.HSSFactorization | None = None
    _y: Array | None = None
    _cmask: Array | None = None    # 1.0 for real points, 0.0 for pads
    _report: FitReport | None = None
    _jit_admm: object = None       # jitted ADMM over (fac, y, c_vec, warm)

    # ------------------------------------------------------------------ #
    def prepare(self, x: np.ndarray, y: np.ndarray) -> FitReport:
        """Pad, build tree, compress, factorize.  (Paper Alg. 3 lines 1–6.)"""
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        d_real = x.shape[0]
        x_pad, y_pad, mask, levels = tree_mod.pad_dataset(x, y, self.leaf_size)
        t = tree_mod.build_tree(x_pad, self.leaf_size, levels)
        xp = jnp.asarray(x_pad[t.perm])
        yp = jnp.asarray(y_pad[t.perm])
        maskp = jnp.asarray(mask[t.perm].astype(np.float32))

        with obs.span("hss.compress") as compress_span:
            hss = compression.compress(xp, t, self.spec, self.comp)
            # Adaptive builds: slice every level to its observed max rank
            # before the factorization, so factor + every per-iteration solve
            # run at the detected ranks instead of the cap (shrink time bills
            # to compression).
            hss, rank_info = shrink_report(hss)
            jax.block_until_ready(hss.d_leaf)
        beta = self.beta if self.beta is not None else admm_mod.paper_beta(d_real)
        with obs.span("hss.factorize") as factorize_span:
            fac = factorization.factorize(hss, beta)
            jax.block_until_ready(fac.root_lu)

        self._hss, self._fac, self._y, self._cmask = hss, fac, yp, maskp
        self._report = FitReport(
            compression_s=compress_span.seconds,
            factorization_s=factorize_span.seconds,
            admm_s=0.0,
            memory_mb=hss.memory_bytes() / 1e6,
            hss_levels=t.levels,
            beta=beta,
            kernel_evals=compression.kernel_eval_count(t, self.comp),
            **rank_info,
        )
        return self._report

    # ------------------------------------------------------------------ #
    def train(self, c_value: float, warm: tuple[Array, Array] | None = None
              ) -> tuple[SVMModel, tuple[Array, Array]]:
        """One ADMM run for a fixed C, reusing the cached factorization."""
        assert self._fac is not None, "call prepare() first"
        fac, y, mask = self._fac, self._y, self._cmask
        c_vec = c_value * mask           # pads pinned to [0, 0]

        if self._jit_admm is None:
            max_it, tol = self.max_it, self.tol

            def _run(fac_, y_, c_vec_, z0, mu0):
                return admm_mod.admm_svm(fac_.solve, y_, c_vec_, fac_.beta,
                                         max_it, z0=z0, mu0=mu0, tol=tol)

            self._jit_admm = jax.jit(_run)

        zeros = jnp.zeros_like(y)
        with obs.span("hss.admm") as admm_span:
            state, trace = self._jit_admm(
                fac, y, c_vec,
                zeros if warm is None else warm[0],
                zeros if warm is None else warm[1],
            )
            z = jax.block_until_ready(state.z)
        if self._report is not None:
            self._report.admm_s += admm_span.seconds
            self._report.iters_run = (int(trace.iters_run),)

        bias = compute_bias(self._hss, y, z, c_value, mask)
        model = SVMModel(
            x_perm=self._hss.x, z_y=y * z, bias=float(bias),
            spec=self.spec, c_value=c_value,
        )
        return model, (state.z, state.mu)

    # ------------------------------------------------------------------ #
    def fit(self, x: np.ndarray, y: np.ndarray, c_value: float = 1.0) -> SVMModel:
        self.prepare(x, y)
        model, _ = self.train(c_value)
        return model

    @property
    def report(self) -> FitReport:
        assert self._report is not None
        return self._report


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


def compute_bias(hss: HSSMatrix, y: Array, z: Array, c_value: float,
                 mask: Array, margin_tol: float = 1e-6) -> Array:
    """Paper eq. (7) for a single binary problem (P=1 view of the batched
    computation)."""
    c_mat = jnp.full((z.shape[0], 1), c_value, z.dtype)
    return compute_bias_batched(
        hss, y[:, None], z[:, None], c_mat, mask[:, None], margin_tol)[0]


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


def run_grid_search(
    make_trainer,
    x: np.ndarray,
    y: np.ndarray,
    x_val: np.ndarray,
    y_val: np.ndarray,
    hs: Sequence[float],
    cs: Sequence[float],
    score_fn=None,
) -> tuple[object, dict]:
    """Generic (h, knob) grid driver shared by every box-QP task sweep.

    Per h: ONE trainer (= one compression + one factorization via prepare);
    the knob sweep — C for classification, ε for SVR, ν for one-class —
    reuses them (the paper's headline amortization) and warm-starts
    consecutive values.  ``make_trainer(h)`` builds the trainer; the best
    model is picked by ``score_fn(model, x_val, y_val)`` (higher is better;
    default: classification accuracy).  Returns it + a results table whose
    ``accuracy`` entries hold the score.
    """
    if score_fn is None:
        def score_fn(model, x_v, y_v):
            return float(jnp.mean(model.predict(x_v) == jnp.asarray(y_v)))
    results = {}
    best = (None, -np.inf, None, None)
    for h in hs:
        trainer = make_trainer(float(h))
        trainer.prepare(x, y)
        warm = None
        admm_seen = 0.0
        for c in cs:
            model, warm = trainer.train(float(c), warm=warm)
            acc = score_fn(model, jnp.asarray(x_val), y_val)
            # report.admm_s accumulates across the warm-started C sweep;
            # each cell records only its own run's time
            admm_total = trainer.report.admm_s
            results[(h, c)] = dict(
                accuracy=acc,
                admm_s=admm_total - admm_seen,
                compression_s=trainer.report.compression_s,
                factorization_s=trainer.report.factorization_s,
            )
            admm_seen = admm_total
            if acc > best[1]:
                best = (model, acc, h, c)
    return best[0], dict(results=results, best_h=best[2], best_c=best[3],
                         best_accuracy=best[1])


def resolve_rtol(trainer_kwargs: dict | None, rtol: float | None) -> dict:
    """Fold the paper-facing accuracy knob into a trainer kwargs dict.

    ``rtol`` mirrors STRUMPACK's rel_tol (crude ≈ 1e-2, accurate ≈ 1e-4,
    Tables 4–5); it overrides the ``comp`` entry's tolerance while keeping
    every other compression knob — ``rank`` stays the hss_max_rank cap.
    """
    kw = dict(trainer_kwargs or {})
    if rtol is not None:
        base = kw.get("comp", compression.CompressionParams())
        kw["comp"] = dataclasses.replace(base, rtol=rtol)
    return kw


def grid_search(
    x: np.ndarray,
    y: np.ndarray,
    x_val: np.ndarray,
    y_val: np.ndarray,
    hs: Sequence[float],
    cs: Sequence[float],
    trainer_kwargs: dict | None = None,
    rtol: float | None = None,
) -> tuple[SVMModel, dict]:
    """(h, C) grid search (paper §3.3) for the binary trainer.

    ``rtol`` switches the sweep to the adaptive tolerance-driven HSS build
    (see ``resolve_rtol``): each h's compression detects per-node ranks,
    shrinks to fit, and the whole C sweep reuses the smaller factorization.
    """
    kw = resolve_rtol(trainer_kwargs, rtol)
    return run_grid_search(
        lambda h: HSSSVMTrainer(spec=KernelSpec(h=h), **kw),
        x, y, x_val, y_val, hs, cs)
