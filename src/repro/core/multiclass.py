"""Multiclass SVM training on ONE shared HSS factorization (paper Alg. 3 × k).

The shifted kernel K̃ + βI depends only on the data, the bandwidth h, and β —
never on the labels.  A one-vs-rest (or one-vs-one) reduction of a k-class
problem therefore needs exactly ONE HSS compression and ONE ULV-equivalent
factorization, shared by every binary subproblem; only the O(d) label-side
vector work differs per class.  This module exploits that three ways:

  * ``admm_svm_batched`` runs all k per-class ADMM iterations as a single
    (d, k)-block computation — each iteration is ONE multi-RHS telescoping
    solve (``factorization.hss_solve_mat``) instead of k sequential solves,
    and the label-independent w = K_β⁻¹ e is computed once for all classes;
  * the per-class biases come from ONE ``HSSMatrix.matmat`` over the (d, k)
    coefficient block (paper eq. (7), batched);
  * prediction streams each test×support kernel block against all k
    coefficient columns while the block is live (``kernel_matvec_streamed``).

One-vs-one rides on the SAME factorization: pair problem (a, b) keeps the
full padded coordinate set and pins every point outside classes {a, b} to the
box [0, 0] (exactly the mechanism that makes tree padding inert), so its ADMM
fixed point restricted to participating points solves the pair subproblem.
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
from repro.core.kernelfn import (
    DEFAULT_SCORE_BLOCK, KernelSpec, kernel_matvec_streamed,
)
from repro.core.svm import (
    FitReport, compute_bias_batched, resolve_rtol, run_grid_search,
)

Array = jax.Array


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
    functional margin.  Shared by the multiclass trainer's model and the
    engine's (core.engine.EngineModel) so the tie-break can never drift.
    """
    pairs = jnp.asarray(pairs)
    winner = jnp.where(scores >= 0, pairs[:, 0][None, :],
                       pairs[:, 1][None, :])
    votes = jax.nn.one_hot(winner, n_classes).sum(axis=1)
    margin = jnp.zeros_like(votes)
    margin = margin.at[:, pairs[:, 0]].add(scores)
    margin = margin.at[:, pairs[:, 1]].add(-scores)
    return jnp.argmax(votes + 1e-3 * jnp.tanh(margin), axis=1)


@dataclasses.dataclass
class MulticlassSVMModel:
    """k-class classifier: per-problem support coefficients, permuted order."""

    x_perm: Array          # (d, f) padded+permuted training points
    z_y: Array             # (d, P) per-problem y_i * z_i columns (pads are 0)
    biases: Array          # (P,)
    classes: np.ndarray    # (k,) original class labels
    spec: KernelSpec
    c_value: float
    strategy: str = "ovr"          # "ovr" | "ovo"
    pairs: np.ndarray | None = None  # (P, 2) class indices, ovo only

    @property
    def n_classes(self) -> int:
        return int(self.classes.shape[0])

    def decision_function(self, x_test: Array,
                          block: int = DEFAULT_SCORE_BLOCK) -> Array:
        """(n_test, P) per-problem scores, one streamed pass over the kernel."""
        scores = kernel_matvec_streamed(
            self.spec, x_test, self.x_perm, self.z_y, block=block
        )
        return scores + self.biases[None, :]

    def predict(self, x_test: Array,
                block: int = DEFAULT_SCORE_BLOCK) -> Array:
        scores = self.decision_function(x_test, block=block)
        if self.strategy == "ovr":
            idx = jnp.argmax(scores, axis=1)
        else:
            idx = ovo_vote(scores, self.pairs, self.n_classes)
        return jnp.asarray(self.classes)[idx]


@dataclasses.dataclass
class MulticlassHSSSVMTrainer:
    """compress-once / factor-once / train-ALL-classes-at-once driver."""

    spec: KernelSpec
    comp: compression.CompressionParams = dataclasses.field(
        default_factory=compression.CompressionParams
    )
    leaf_size: int = 128
    beta: float | None = None     # default: the paper's rule by dataset size
    max_it: int = 10
    strategy: str = "ovr"         # "ovr" | "ovo"

    # populated by prepare():
    _hss: HSSMatrix | None = None
    _fac: factorization.HSSFactorization | None = None
    _ys: Array | None = None       # (P, d) per-problem labels
    _pmask: Array | None = None    # (P, d) per-problem participation masks
    _classes: np.ndarray | None = None
    _pairs: np.ndarray | None = None
    _report: FitReport | None = None
    _jit_admm: object = None

    # ------------------------------------------------------------------ #
    def prepare(self, x: np.ndarray, y: np.ndarray) -> FitReport:
        """Pad, build tree, compress ONCE, factorize ONCE for all classes."""
        if self.strategy not in ("ovr", "ovo"):
            raise ValueError(f"unknown strategy {self.strategy!r}")
        x = np.asarray(x, np.float32)
        y = np.asarray(y)
        classes = np.unique(y)
        if classes.shape[0] < 2:
            raise ValueError("need at least 2 classes")
        d_real = x.shape[0]
        x_pad, y_pad, mask, levels = tree_mod.pad_dataset(
            x, y.astype(np.float32), self.leaf_size)
        t = tree_mod.build_tree(x_pad, self.leaf_size, levels)
        xp = jnp.asarray(x_pad[t.perm])
        yp = y_pad[t.perm]
        maskp = mask[t.perm]
        # pad rows inherit pad_dataset's filler label (1.0), which MAY
        # collide with a real class — harmless: the participation mask pins
        # every pad to the [0, 0] box, so its dual weight is exactly 0
        build = ovr_problems if self.strategy == "ovr" else ovo_problems
        ys, pmasks, pairs = build(yp, classes.astype(np.float32), maskp)

        with obs.span("hss.compress") as compress_span:
            hss = compression.compress(xp, t, self.spec, self.comp)
            # Adaptive builds shrink to the observed ranks before
            # factorizing: ALL k class subproblems then share the smaller
            # factors.
            hss, rank_info = shrink_report(hss)
            jax.block_until_ready(hss.d_leaf)
        beta = self.beta if self.beta is not None else admm_mod.paper_beta(d_real)
        with obs.span("hss.factorize") as factorize_span:
            fac = factorization.factorize(hss, beta)
            jax.block_until_ready(fac.root_lu)

        self._hss, self._fac = hss, fac
        self._ys, self._pmask = jnp.asarray(ys), jnp.asarray(pmasks)
        self._classes, self._pairs = classes, pairs
        self._jit_admm = None
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

    @property
    def n_problems(self) -> int:
        assert self._ys is not None, "call prepare() first"
        return int(self._ys.shape[0])

    # ------------------------------------------------------------------ #
    def train(self, c_value: float, warm: tuple[Array, Array] | None = None
              ) -> tuple[MulticlassSVMModel, tuple[Array, Array]]:
        """ONE batched ADMM run training every class subproblem for fixed C."""
        assert self._fac is not None, "call prepare() first"
        fac, ys, pmask = self._fac, self._ys, self._pmask
        c_upper = c_value * pmask             # (P, d): outsiders pinned to [0,0]

        if self._jit_admm is None:
            max_it = self.max_it

            def _run(fac_, ys_, c_upper_, z0, mu0):
                return admm_mod.admm_svm_batched(
                    fac_.solve_mat, ys_, c_upper_, fac_.beta, max_it,
                    z0=z0, mu0=mu0)

            self._jit_admm = jax.jit(_run)

        zeros = jnp.zeros((ys.shape[1], ys.shape[0]), ys.dtype)
        with obs.span("hss.admm") as admm_span:
            state, _trace = self._jit_admm(
                fac, ys, c_upper,
                zeros if warm is None else warm[0],
                zeros if warm is None else warm[1],
            )
            z = jax.block_until_ready(state.z)        # (d, P)
        if self._report is not None:
            self._report.admm_s += admm_span.seconds

        y_cols = ys.T                                 # (d, P)
        biases = compute_bias_batched(
            self._hss, y_cols, z, c_value * pmask.T, pmask.T)
        model = MulticlassSVMModel(
            x_perm=self._hss.x, z_y=y_cols * z, biases=biases,
            classes=self._classes, spec=self.spec, c_value=c_value,
            strategy=self.strategy, pairs=self._pairs,
        )
        return model, (state.z, state.mu)

    # ------------------------------------------------------------------ #
    def fit(self, x: np.ndarray, y: np.ndarray, c_value: float = 1.0
            ) -> MulticlassSVMModel:
        self.prepare(x, y)
        model, _ = self.train(c_value)
        return model

    @property
    def report(self) -> FitReport:
        assert self._report is not None
        return self._report


def grid_search_multiclass(
    x: np.ndarray,
    y: np.ndarray,
    x_val: np.ndarray,
    y_val: np.ndarray,
    hs: Sequence[float],
    cs: Sequence[float],
    trainer_kwargs: dict | None = None,
    rtol: float | None = None,
) -> tuple[MulticlassSVMModel, dict]:
    """(h, C) grid over the full (C × class) product (paper §3.3, batched).

    Per h: ONE compression + ONE factorization serve the whole C sweep of
    ALL k class subproblems; consecutive C values warm-start every class
    column from the previous (d, P) iterates at once.  ``rtol`` switches
    each h's build to the adaptive tolerance-driven compression (crude ≈
    1e-2, accurate ≈ 1e-4 — see ``svm.resolve_rtol``).
    """
    kw = resolve_rtol(trainer_kwargs, rtol)
    return run_grid_search(
        lambda h: MulticlassHSSSVMTrainer(spec=KernelSpec(h=h), **kw),
        x, y, x_val, y_val, hs, cs)
