"""One orchestration layer for the whole HSS-ADMM SVM pipeline.

``HSSSVMEngine`` owns every stage of paper Algorithm 3 — partition (pad +
cluster tree) → HSS compression → ULV-equivalent factorization → batched
ADMM → bias → prediction — through ONE code path for both the local
single-device case and the mesh-parallel case:

  * ``mesh=None``: the stages are exactly ``compression.compress`` /
    ``factorization.factorize`` / ``admm_svm_batched`` on one device.
  * ``mesh=Mesh(...)``: the SAME stages run node/sample-sharded end-to-end
    (``compress_sharded`` / ``factorize_sharded``), so no stage ever
    materializes an unsharded O(N·m) array on a single device — the leaf
    diagonal blocks, leaf bases, E/G factors, label matrix, and ADMM
    iterates all live sharded over the full device set from the moment they
    are created.  Bias extraction and prediction scoring also run on the
    sharded representation (one ``psum`` of per-device partial scores)
    without ever gathering ``x_perm``.

Binary problems (labels ±1) and k-class problems (arbitrary labels, OVR or
OVO reduction, ``core.tasks``) share the path: the engine always trains the
(d, P)-block batched ADMM with P = 1 for binary.  ``grid_search`` is the one
(h, knob) sweep over it, for every task.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro import obs
from repro.core import admm as admm_mod
from repro.core import compression, factorization, krr as krr_mod
from repro.core import tasks as tasks_mod
from repro.core import tree as tree_mod
from repro.core.hss import HSSMatrix, shrink_report
from repro.core.kernelfn import (
    DEFAULT_SCORE_BLOCK, KernelSpec, kernel_matvec_streamed,
)
from repro.dist import api as dist_api
from repro.dist.api import mesh_ndev

Array = jax.Array


def _node_spec(mesh: Mesh) -> PartitionSpec:
    return PartitionSpec(tuple(mesh.axis_names))


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
class EngineModel:
    """A trained (binary or k-class) classifier, possibly mesh-resident.

    ``x_perm``/``z_y`` stay sharded over the mesh's sample axis when the
    model was trained under one; scoring then evaluates each device's
    test×local-support kernel blocks and psums the partial scores — the
    support set is never gathered to one device.
    """

    x_perm: Array          # (d, f) padded+permuted training points
    z_y: Array             # (d, P) per-problem s_i * z_i columns (pads are 0;
                           #  y_i z_i for SVM, the dual coefficients α for
                           #  SVR / one-class)
    biases: Array          # (P,)  (−ρ for one-class)
    classes: np.ndarray    # (k,) original class labels (an unused [-1, 1]
                           #  placeholder for svr / oneclass models)
    spec: KernelSpec
    c_value: float         # the task knob it was trained at (C / ε / ν)
    binary: bool
    strategy: str = "ovr"
    task: str = "svm"      # "svm" | "svr" | "oneclass" | "krr" | "gp"
    pairs: np.ndarray | None = None     # (P, 2) class indices, ovo only
    mesh: Mesh | None = None
    # β of the factorization the model was trained on — the serve-time
    # factorization-sharing cache key is (kernel, h, β, support set): two
    # models agreeing on it were trained on the SAME K̃ + βI.
    beta: float | None = None
    _score_fns: dict | None = None      # block -> cached jitted scorer

    @property
    def n_classes(self) -> int:
        return int(self.classes.shape[0])

    def _mesh_scorer(self, block: int):
        if self._score_fns is None:
            self._score_fns = {}
        fn = self._score_fns.get(block)
        if fn is None:
            spec, mesh = self.spec, self.mesh
            axes = tuple(mesh.axis_names)

            def body(xt, xp, zy):
                part = kernel_matvec_streamed(spec, xt, xp, zy, block=block)
                return jax.lax.psum(part, axes)

            fn = jax.jit(dist_api.shard_map(
                body, mesh,
                in_specs=(PartitionSpec(), _node_spec(mesh),
                          _node_spec(mesh)),
                out_specs=PartitionSpec()))
            self._score_fns[block] = fn
        return fn

    def decision_function(self, x_test: Array,
                          block: int = DEFAULT_SCORE_BLOCK) -> Array:
        """Scores (n_test, P); single-column tasks (binary SVM, SVR,
        one-class) return the flat (n_test,) column."""
        x_test = jnp.asarray(x_test)
        if self.mesh is None:
            scores = kernel_matvec_streamed(
                self.spec, x_test, self.x_perm, self.z_y, block=block)
        else:
            scores = self._mesh_scorer(block)(x_test, self.x_perm, self.z_y)
        scores = scores + self.biases[None, :]
        if self.binary or self.task in ("svr", "oneclass", "krr", "gp"):
            return scores[:, 0]
        return scores

    def predict(self, x_test: Array,
                block: int = DEFAULT_SCORE_BLOCK) -> Array:
        scores = self.decision_function(x_test, block=block)
        if self.task in ("svr", "krr", "gp"):
            return scores               # regression: scores ARE predictions
        if self.task == "oneclass":      # +1 inlier / −1 outlier
            return jnp.where(scores >= 0, 1, -1)
        if self.binary:
            return jnp.where(scores >= 0, 1, -1)
        if self.strategy == "ovr":
            idx = jnp.argmax(scores, axis=1)
        else:
            idx = tasks_mod.ovo_vote(scores, self.pairs, self.n_classes)
        return jnp.asarray(self.classes)[idx]


def _build_task(task_name: str, svr_c: float, ys, pmask, knob):
    """The engine's knob → BoxQPTask rule."""
    if task_name == "svr":
        return tasks_mod.svr_task(ys, svr_c * pmask, knob)
    if task_name == "oneclass":
        return tasks_mod.one_class_task(pmask, knob)
    return admm_mod.svm_task(ys, knob * pmask)


@functools.partial(
    jax.jit,
    static_argnames=("boxqp", "task_name", "svr_c", "max_it", "tol"))
def _admm_run(fac, ys, pmask, knob, z0, mu0, done0=None, *, boxqp,
              task_name: str, svr_c: float, max_it: int, tol: float | None):
    """The batched box-QP ADMM run of every engine, jitted once per process.

    The factorization is a pytree argument and the knob a traced scalar, so
    engines that agree on shapes, dtypes, shardings and the static task
    values share one trace and one loaded executable.  ``boxqp`` is
    ``admm.admm_boxqp`` as bound at the call: static, so the process-wide
    cache never serves a program traced from another binding of it (a
    test that replaces the solver gets a trace of its own).  Without
    ``done0`` (a fixed-length run) it returns ``(z, mu, sign * z, hi,
    iters_run)``; with it (one chunk of the adaptive-ρ loop) ``(state,
    trace, sign * z, hi)``.  ``hi`` is the (d, P) box bound for one-class,
    else ``()``.
    """
    obs.count("hss.admm.traces")      # runs only while jax traces
    task = _build_task(task_name, svr_c, ys, pmask, knob)
    state, trace = boxqp(
        fac.solve_mat, task, fac.beta, max_it, tol=tol, z0=z0, mu0=mu0,
        done0=done0)
    # only the oneclass rho extraction needs the box bounds — skip
    # materializing the (d, P) hi block everywhere else
    hi = task.hi if task_name == "oneclass" else ()
    if done0 is None:
        return state.z, state.mu, task.sign * state.z, hi, trace.iters_run
    return state, trace, task.sign * state.z, hi


# The tasks' bias rules and the KRR/GP solve, jitted once per process like
# ``_admm_run``: every engine shares their traces.
_bias_svm = jax.jit(tasks_mod.compute_bias_batched)
_bias_svr = jax.jit(tasks_mod.compute_bias_svr_batched)
_rho_oneclass = jax.jit(tasks_mod.compute_rho_oneclass_batched)
_krr_solve = jax.jit(krr_mod.krr_solve)


@dataclasses.dataclass
class HSSSVMEngine:
    """partition → compress → factorize → ADMM → bias/predict, local or mesh.

    The paper's compress-once / factor-once / train-many economy, owned by
    one object; pass ``mesh`` to run every stage sharded (see module
    docstring).  ``store_dtype="bfloat16"`` stores the E/G factors in bf16
    (solves still accumulate in f32).

    ``task`` selects the box-QP instance trained on the shared
    factorization (repro.core.admm / repro.core.tasks):
      * ``"svm"``      — classification; ``train``'s knob is C, ``y`` holds
        labels (binary ±1 or k-class, OVR/OVO per ``strategy``);
      * ``"svr"``      — ε-SVR; the knob is ε (the C box bound is the
        ``svr_c`` field), ``y`` holds float regression targets;
      * ``"oneclass"`` — ν one-class SVM; the knob is ν, ``y`` is ignored
        (unsupervised — pass None);
      * ``"krr"`` / ``"gp"`` — kernel ridge regression / GP posterior mean
        (repro.core.krr): the knob is the ridge / noise λ, which rides the
        factorization's β shift slot, and ``train`` is ONE multi-RHS solve
        with ZERO ADMM iterations (``FitReport.iters_run == (0,)``); ``y``
        holds float regression targets.  ``"gp"`` additionally exposes
        ``log_marginal`` for (h, λ) grid scoring.

    ``tol`` enables the paper's residual stopping rule: a problem's ADMM
    updates freeze once max(primal, dual) < tol and ``FitReport.iters_run``
    records the live iteration counts (None = always run ``max_it``).

    ``stream`` switches ``prepare`` to the out-of-core streamed build
    (``compression.compress_streamed``): the dataset never has to be
    device-resident during compression, peak device bytes are bounded by
    ``stream.batch_leaves``, and with ``stream.ckpt_dir`` set an interrupted
    build resumes at its last completed level.  ``admm`` (an
    ``ADMMParams``) overrides ``max_it``/``tol`` and can switch on
    residual-balancing adaptive ρ — each β rescale refactorizes K̃ + βI
    once, cached per visited β.
    """

    spec: KernelSpec
    comp: compression.CompressionParams = dataclasses.field(
        default_factory=compression.CompressionParams
    )
    leaf_size: int = 128
    beta: float | None = None     # default: the paper's rule by dataset size
    max_it: int = 10
    mesh: Mesh | None = None
    strategy: str = "ovr"         # multiclass reduction: "ovr" | "ovo"
    store_dtype: str | None = None
    task: str = "svm"             # "svm" | "svr" | "oneclass" | "krr" | "gp"
    svr_c: float = 1.0            # SVR box bound C (ε is the train knob)
    tol: float | None = None      # ADMM residual early-stop threshold
    stream: compression.StreamParams | None = None   # out-of-core build
    admm: admm_mod.ADMMParams | None = None          # iteration control

    # populated by prepare():
    _hss: HSSMatrix | None = None
    _fac: factorization.HSSFactorization | None = None
    _ys: Array | None = None       # (P, d) per-problem ±1 labels
    _pmask: Array | None = None    # (P, d) participation masks
    _classes: np.ndarray | None = None
    _pairs: np.ndarray | None = None
    _binary: bool = False
    _report: FitReport | None = None
    # The mesh the prepared stages were placed on (self.mesh at prepare).
    _mesh: Mesh | None = None
    # multilevel warm start inputs + adaptive-ρ machinery
    _x_raw: np.ndarray | None = None
    _y_raw: np.ndarray | None = None
    _perm_host: np.ndarray | None = None   # tree perm (host) — pad unmapping
    _xp_host: np.ndarray | None = None     # padded+permuted points (host)
    _maskp_host: np.ndarray | None = None  # (d,) real-point mask (host)
    _fac_cache: dict | None = None         # beta -> factorization

    # ------------------------------------------------------------------ #
    @contextlib.contextmanager
    def _active(self):
        """The mesh context all jitted stages trace/run under (no-op local)."""
        if self._mesh is None:
            yield
        else:
            with dist_api.use_mesh(self._mesh), self._mesh:
                yield

    def _min_levels(self) -> int:
        """Force enough splits that the leaf axis divides the device count.

        A mesh whose device count is not a power of two cannot divide the
        perfect tree's 2^L leaves, so it is refused rather than dropped: a
        run asked to use N devices must not quietly run on one.
        """
        if self.mesh is None:
            return 0
        ndev = mesh_ndev(self.mesh)
        if ndev & (ndev - 1):
            raise ValueError(
                f"the HSS tree shards over a power-of-two number of devices; "
                f"this mesh has {ndev}")
        levels = 0
        while 2 ** levels < ndev:
            levels += 1
        return levels

    # ------------------------------------------------------------------ #
    def prepare(self, x: np.ndarray, y: np.ndarray | None = None) -> FitReport:
        """Pad + tree + compress ONCE + factorize ONCE (Alg. 3 lines 1–6)."""
        with obs.span("hss.prepare"):
            return self._prepare(x, y)

    def _prepare(self, x: np.ndarray, y: np.ndarray | None) -> FitReport:
        if self.strategy not in ("ovr", "ovo"):
            raise ValueError(f"unknown strategy {self.strategy!r}")
        if self.task not in ("svm", "svr", "oneclass", "krr", "gp"):
            raise ValueError(f"unknown task {self.task!r}")
        x = np.asarray(x, np.float32)
        if self.task == "svm":
            if y is None:
                raise ValueError("task='svm' needs labels")
            y = np.asarray(y)
            classes = np.unique(y)
            if classes.shape[0] < 2:
                raise ValueError("need at least 2 classes")
            try:
                vals = set(np.asarray(classes, np.float64).tolist())
            except (TypeError, ValueError):
                vals = set()
            self._binary = classes.shape[0] == 2 and vals == {-1.0, 1.0}
        else:
            if self.task in ("svr", "krr", "gp") and y is None:
                raise ValueError(
                    f"task={self.task!r} needs regression targets")
            if y is None:                # one-class is unsupervised
                y = np.zeros(x.shape[0], np.float32)
            y = np.asarray(y)
            classes = np.array([-1.0, 1.0], np.float32)
            self._binary = False
        d_real = x.shape[0]
        with obs.span("hss.pad"):
            x_pad, y_pad, mask, levels = tree_mod.pad_dataset(
                x, y.astype(np.float32), self.leaf_size,
                min_levels=self._min_levels())
        mesh = self._mesh = self.mesh
        with obs.span("hss.tree") as sp:
            t = tree_mod.build_tree(x_pad, self.leaf_size, levels)
            sp.attrs.update(split_onehot=t.splits[0],
                            split_continuous=t.splits[1])
            xp_host = x_pad[t.perm]
            yp = y_pad[t.perm]
            maskp = mask[t.perm]

        with obs.span("hss.labels"):
            if self.task != "svm":
                # one problem column: SVR's ys row holds the (mask-zeroed)
                # regression targets, one-class ignores it — the
                # participation mask is what pins pads to the inert [0, 0]
                # box in both.
                ys = (yp * maskp)[None, :].astype(np.float32)
                pmasks = maskp[None, :].astype(np.float32)
                pairs = None
            elif self._binary:
                ys = np.where(yp > 0, 1.0, -1.0)[None, :].astype(np.float32)
                pmasks = maskp[None, :].astype(np.float32)
                pairs = None
            else:
                build = (tasks_mod.ovr_problems if self.strategy == "ovr"
                         else tasks_mod.ovo_problems)
                ys, pmasks, pairs = build(
                    yp, classes.astype(np.float32), maskp)

        with obs.span("hss.compress") as compress_span:
            sstats = None
            if self.stream is not None:
                hss, sstats = compression.compress_streamed(
                    xp_host, t, self.spec, self.comp, stream=self.stream,
                    mesh=mesh)
            elif mesh is not None:
                hss = compression.compress_sharded(
                    xp_host, t, self.spec, self.comp, mesh)
            else:
                hss = compression.compress(xp_host, t, self.spec, self.comp)
            # Adaptive builds (comp.rtol set): slice every level down to its
            # observed max rank before factorizing — the factorization and
            # every downstream solve/matmat then run at the detected ranks,
            # mesh placement preserved via the shared node_partition_spec
            # rule.
            with obs.span("hss.shrink"):
                hss, rank_info = shrink_report(hss, mesh=mesh)
            with obs.span("hss.compress.wait"):
                jax.block_until_ready(hss.d_leaf)
        beta = self.beta if self.beta is not None else admm_mod.paper_beta(
            d_real)
        with obs.span("hss.factorize") as factorize_span:
            if mesh is not None:
                fac = factorization.factorize_sharded(
                    hss, beta, mesh, store_dtype=self.store_dtype)
            else:
                fac = factorization.factorize(
                    hss, beta, store_dtype=self.store_dtype)
            jax.block_until_ready(fac.root_lu)

        with obs.span("hss.upload"):
            if mesh is not None:
                row_sh = NamedSharding(
                    mesh, PartitionSpec(None, tuple(mesh.axis_names)))
                ys_d = jax.device_put(jnp.asarray(ys), row_sh)
                pm_d = jax.device_put(jnp.asarray(pmasks), row_sh)
            else:
                ys_d, pm_d = jnp.asarray(ys), jnp.asarray(pmasks)

        self._hss, self._fac = hss, fac
        self._ys, self._pmask = ys_d, pm_d
        self._classes, self._pairs = classes, pairs
        self._x_raw, self._y_raw = x, (None if y is None else np.asarray(y))
        self._perm_host = t.perm
        self._xp_host = xp_host
        self._maskp_host = maskp.astype(np.float32)
        self._fac_cache = {float(beta): fac}
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
        if sstats is not None:
            self._report.peak_stream_bytes = sstats.peak_stream_bytes
            self._report.stream_batches = sstats.n_batches
            self._report.stream_resumed_level = sstats.resumed_level
            self._report.stream_restarts = sstats.restarts
        return self._report

    # ------------------------------------------------------------------ #
    @property
    def n_problems(self) -> int:
        assert self._ys is not None, "call prepare() first"
        return int(self._ys.shape[0])

    @property
    def problem_labels(self) -> Array:
        """(P, d) per-problem ±1 labels in tree order (mesh-placed)."""
        assert self._ys is not None, "call prepare() first"
        return self._ys

    @property
    def problem_masks(self) -> Array:
        """(P, d) participation masks (0 pins a coordinate to the [0,0] box)."""
        assert self._pmask is not None, "call prepare() first"
        return self._pmask

    @property
    def hss(self) -> HSSMatrix:
        assert self._hss is not None, "call prepare() first"
        return self._hss

    @property
    def fac(self) -> factorization.HSSFactorization:
        assert self._fac is not None, "call prepare() first"
        return self._fac

    @property
    def report(self) -> FitReport:
        assert self._report is not None
        return self._report

    # ------------------------------------------------------------------ #
    def train(self, c_value: float, warm: tuple[Array, Array] | None = None
              ) -> tuple[EngineModel, tuple[Array, Array]]:
        """ONE batched ADMM run over all P subproblems for a fixed knob.

        ``c_value`` is the task's sweep knob: C for classification, ε for
        SVR (box bound from ``self.svr_c``), ν for one-class.  It enters the
        jitted run as a traced scalar, so a warm-started knob sweep compiles
        exactly once.
        """
        assert self._fac is not None, "call prepare() first"
        with obs.span("hss.train", knob=float(c_value)):
            obs.count("hss.dual_columns", self.n_problems)
            if self.task in ("krr", "gp"):
                return self._train_krr(c_value)
            return self._train_box(c_value, warm)

    def _train_box(self, c_value: float, warm: tuple[Array, Array] | None
                   ) -> tuple[EngineModel, tuple[Array, Array]]:
        if self.task == "oneclass" and not 0.0 < c_value <= 1.0:
            # nu > 1 makes e'alpha = 1 infeasible (box mass < 1), nu <= 0
            # divides by zero — either silently yields a garbage model.
            raise ValueError(f"oneclass needs 0 < nu <= 1, got {c_value}")
        if self.task == "svr" and c_value < 0.0:
            raise ValueError(f"svr needs epsilon >= 0, got {c_value}")
        fac, ys, pmask = self._fac, self._ys, self._pmask
        n_prob, d = ys.shape
        ap = self.admm
        eff_max_it = self.max_it if ap is None else ap.max_it
        eff_tol = self.tol if ap is None else ap.tol
        adapt = ap is not None and ap.adapt_rho

        if self._mesh is None:
            zeros = jnp.zeros((d, n_prob), jnp.float32)
        else:
            zeros = jax.device_put(
                jnp.zeros((d, n_prob), jnp.float32),
                NamedSharding(self._mesh, PartitionSpec(
                    tuple(self._mesh.axis_names), None)))
        z0, mu0 = (zeros, zeros) if warm is None else warm
        knob = jnp.asarray(c_value, jnp.float32)

        rho_info = None
        with self._active():
            with obs.span("hss.admm") as admm_span:
                if adapt:
                    z, mu, z_y, hi_mat, iters_run, rho_info = \
                        self._train_adaptive(ap, knob, z0, mu0, n_prob)
                else:
                    z, mu, z_y, hi_mat, iters_run = _admm_run(
                        fac, ys, pmask, knob, z0, mu0,
                        boxqp=admm_mod.admm_boxqp, task_name=self.task,
                        svr_c=self.svr_c, max_it=eff_max_it, tol=eff_tol)
                jax.block_until_ready(z)
            with obs.span("hss.bias"):
                if self.task == "svr":
                    biases = _bias_svr(self._hss, ys.T, z,
                                       self.svr_c * pmask.T, pmask.T, knob)
                elif self.task == "oneclass":
                    biases = -_rho_oneclass(self._hss, z, hi_mat, pmask.T)
                else:
                    biases = _bias_svm(
                        self._hss, ys.T, z, c_value * pmask.T, pmask.T)
        if self._report is not None:
            self._report.admm_s += admm_span.seconds
            self._report.iters_run = tuple(
                int(i) for i in np.asarray(iters_run))
            if rho_info is not None:
                self._report.rho_final = rho_info["beta"]
                self._report.rho_rescales = rho_info["rescales"]

        model = EngineModel(
            x_perm=self._hss.x, z_y=z_y, biases=biases,
            classes=self._classes, spec=self.spec, c_value=c_value,
            binary=self._binary, strategy=self.strategy, task=self.task,
            pairs=self._pairs, mesh=self._mesh,
            beta=float(self._fac.beta),
        )
        return model, (z, mu)

    # ------------------------------------------------------------------ #
    def _train_krr(self, lam: float) -> tuple[EngineModel, tuple[Array, Array]]:
        """KRR / GP-mean train: ONE multi-RHS solve, ZERO ADMM iterations.

        The knob λ rides the factorization's β shift slot: each distinct λ
        refactorizes the shared compression once (``_fac_for`` caches per
        visited λ, exactly like the adaptive-ρ rescale path) and the train
        step is a single ``solve_mat`` on the (d, P) target block.  The
        solve is jitted with the factorization as a pytree argument; β is a
        static field, so each λ traces once — noise next to its O(N r²)
        refactorization.
        """
        if not lam > 0.0:
            raise ValueError(f"{self.task} needs lambda > 0, got {lam}")
        ys, pmask = self._ys, self._pmask
        n_prob = ys.shape[0]
        with self._active():
            with obs.span("hss.factorize") as factorize_span:
                fac = self._fac_for(float(lam))
                jax.block_until_ready(fac.root_lu)
            with obs.span("hss.admm") as solve_span:
                # pads decouple exactly ((1+λ)I block, zero targets); the
                # mask only clips factorization float noise off the pad
                # coefficients
                alpha = _krr_solve(fac, ys.T) * pmask.T
                jax.block_until_ready(alpha)
        if self._report is not None:
            self._report.factorization_s += factorize_span.seconds
            self._report.admm_s += solve_span.seconds
            self._report.iters_run = (0,) * n_prob
        model = EngineModel(
            x_perm=self._hss.x, z_y=alpha,
            biases=jnp.zeros((n_prob,), jnp.float32),
            classes=self._classes, spec=self.spec, c_value=lam,
            binary=False, strategy=self.strategy, task=self.task,
            pairs=None, mesh=self._mesh, beta=float(fac.beta),
        )
        return model, (alpha, alpha)

    def log_marginal(self, lam: float, n_probes: int = 4,
                     num_iters: int = 20, seed: int = 0) -> float:
        """GP log marginal likelihood estimate at noise λ (see
        ``krr.gp_log_marginal``) — the ``task="gp"`` (h, λ) grid score."""
        assert self._fac is not None, "call prepare() first"
        if self.task not in ("krr", "gp"):
            raise ValueError(f"log_marginal needs task='krr'/'gp', "
                             f"got {self.task!r}")
        fac = self._fac_for(float(lam))
        with self._active():
            return krr_mod.gp_log_marginal(
                self._hss, fac, self._ys[0], mask=self._pmask[0],
                n_probes=n_probes, num_iters=num_iters, seed=seed)

    def top_eigenpairs(self, k: int, num_iters: int | None = None,
                       seed: int = 0) -> tuple[Array, Array]:
        """Leading k eigenpairs of the compressed kernel (Lanczos on the
        O(N r) matvec), in permuted/padded row order — any prepared task."""
        from repro.core import lanczos as lanczos_mod

        assert self._hss is not None, "call prepare() first"
        with self._active():
            return lanczos_mod.top_eigenpairs(
                self._hss, k, num_iters=num_iters, seed=seed)

    def spectral_embed(self, k: int, num_iters: int | None = None,
                       seed: int = 0) -> np.ndarray:
        """Kernel-PCA coordinates (n, k) for the ORIGINAL input rows.

        Eigenvectors scaled by sqrt(eigenvalue), mapped back through the
        tree permutation with pad rows dropped.  Keep k below the count of
        kernel eigenvalues exceeding 1 — the pad block of a padded build
        contributes an eigenvalue cluster at ≈ 1 (see repro.core.lanczos).
        """
        evals, vecs = self.top_eigenpairs(k, num_iters=num_iters, seed=seed)
        emb = (np.asarray(jax.device_get(vecs))
               * np.sqrt(np.maximum(np.asarray(jax.device_get(evals)), 0.0)))
        n = self._x_raw.shape[0]
        out = np.zeros((n, k), np.float32)
        real = self._perm_host < n
        out[self._perm_host[real]] = emb[real]
        return out

    # ------------------------------------------------------------------ #
    def _fac_for(self, beta: float) -> factorization.HSSFactorization:
        """Factorization of K̃ + βI, cached per visited β.

        The adaptive-ρ rescale path: β is the factorization shift, so a
        rescale means ONE refactorization (O(N r²) — cheap next to the
        compression it reuses) the first time each β is visited.
        """
        fac = self._fac_cache.get(float(beta))
        if fac is None:
            if self._mesh is not None:
                fac = factorization.factorize_sharded(
                    self._hss, beta, self._mesh, store_dtype=self.store_dtype)
            else:
                fac = factorization.factorize(
                    self._hss, beta, store_dtype=self.store_dtype)
            self._fac_cache[float(beta)] = fac
        return fac

    def _train_adaptive(self, ap: admm_mod.ADMMParams, knob, z0, mu0,
                        n_prob: int):
        """Residual-balancing adaptive-ρ run (Boyd §3.4.1).

        Each chunk is :func:`_admm_run` with its length static and the
        factorization as a pytree argument: a chunk length traces once, and
        a β rescale only swaps which cached factorization is passed in (β is
        the factorization's static field, so each visited β traces once).
        """
        ys, pmask = self._ys, self._pmask
        last = {}

        def run_chunk(beta, n_it, z, mu, done):
            fac_b = self._fac_for(beta)
            done = jnp.zeros((n_prob,), bool) if done is None else done
            state, trace, z_y, hi = _admm_run(
                fac_b, ys, pmask, knob, z, mu, done,
                boxqp=admm_mod.admm_boxqp, task_name=self.task,
                svr_c=self.svr_c, max_it=n_it, tol=ap.tol)
            last["z_y"], last["hi"] = z_y, hi
            return state, trace

        state, trace, info = admm_mod.adaptive_rho_outer(
            run_chunk, float(self._fac.beta), ap, z0=z0, mu0=mu0)
        return (state.z, state.mu, last["z_y"], last["hi"],
                trace.iters_run, info)

    # ------------------------------------------------------------------ #
    def train_multilevel(
        self,
        c_value: float,
        coarse_frac: float = 0.125,
        coarse_comp: compression.CompressionParams | None = None,
        coarse_leaf_size: int | None = None,
        seed: int = 0,
    ) -> tuple[EngineModel, dict]:
        """AML-SVM-style multilevel warm start (arXiv 2011.02592).

        Train the same task on a ``coarse_frac`` subsample with a CRUDE
        compression (``CompressionParams.crude`` unless overridden), prolong
        the coarse duals to the full point set by nearest-neighbour
        interpolation (``tasks.prolong_duals`` over the padded/permuted host
        points), and let the warm-started early-stopping ADMM finish —
        ``FitReport.iters_run`` then measures the saved iterations against a
        cold ``train``.  The subsample is stratified per class for
        classification so the coarse problem set (OVR columns / OVO pairs)
        matches the fine one exactly.

        Returns (model, info) with the coarse size and both iteration
        records.  Requires ``prepare`` to have run (the fine factorization
        is reused untouched).
        """
        assert self._fac is not None, "call prepare() first"
        x, y = self._x_raw, self._y_raw
        n = x.shape[0]
        leaf_c = coarse_leaf_size or min(self.leaf_size, 64)
        n_c = int(max(min(n, 2 * leaf_c), round(n * coarse_frac)))
        rng = np.random.default_rng(seed)
        if self.task == "svm":
            parts = []
            for cls in self._classes:
                rows = np.nonzero(y == cls)[0]
                want = max(1, int(round(len(rows) * n_c / n)))
                parts.append(rng.choice(rows, size=min(want, len(rows)),
                                        replace=False))
            idx = np.sort(np.concatenate(parts))
        else:
            idx = np.sort(rng.choice(n, size=min(n_c, n), replace=False))

        coarse = HSSSVMEngine(
            spec=self.spec,
            comp=coarse_comp or compression.CompressionParams.crude(),
            leaf_size=leaf_c, beta=self.beta, max_it=self.max_it,
            strategy=self.strategy, store_dtype=self.store_dtype,
            task=self.task, svr_c=self.svr_c, tol=self.tol, admm=self.admm,
        )
        y_sub = None if self.task == "oneclass" else y[idx]
        coarse.prepare(x[idx], y_sub)
        _, (z_c, mu_c) = coarse.train(c_value)

        scale = tasks_mod.prolong_scale(
            self.task,
            int(coarse._maskp_host.sum()), int(self._maskp_host.sum()))
        z0 = tasks_mod.prolong_duals(
            coarse._xp_host, np.asarray(jax.device_get(z_c)),
            self._xp_host) * scale
        mu0 = tasks_mod.prolong_duals(
            coarse._xp_host, np.asarray(jax.device_get(mu_c)),
            self._xp_host) * scale
        # Fine pads carry no dual mass regardless of what they mapped to.
        z0 = (z0 * self._maskp_host[:, None]).astype(np.float32)
        mu0 = (mu0 * self._maskp_host[:, None]).astype(np.float32)
        if self._mesh is None:
            warm = (jnp.asarray(z0), jnp.asarray(mu0))
        else:
            row_sh = NamedSharding(self._mesh, PartitionSpec(
                tuple(self._mesh.axis_names), None))
            warm = (jax.device_put(z0, row_sh), jax.device_put(mu0, row_sh))

        model, _ = self.train(c_value, warm=warm)
        info = dict(
            coarse_n=int(idx.shape[0]),
            coarse_iters_run=coarse.report.iters_run,
            iters_run=self.report.iters_run,
        )
        return model, info

    # ------------------------------------------------------------------ #
    def train_grid(self, c_values: Sequence[float], warm_start: bool = True
                   ) -> list[EngineModel]:
        """Warm-started knob sweep (C / ε / ν) reusing the one
        compression+factorization."""
        warm = None
        models = []
        for c in c_values:
            model, w = self.train(float(c), warm=warm)
            if warm_start:
                warm = w
            models.append(model)
        return models

    def fit(self, x: np.ndarray, y: np.ndarray | None = None,
            c_value: float = 1.0) -> EngineModel:
        self.prepare(x, y)
        model, _ = self.train(c_value)
        return model


def grid_search(
    x: np.ndarray,
    y: np.ndarray | None,
    x_val: np.ndarray | None,
    y_val: np.ndarray | None,
    hs: Sequence[float],
    knobs: Sequence[float],
    *,
    task: str = "svm",
    trainer_kwargs: dict | None = None,
    rtol: float | None = None,
) -> tuple[EngineModel, dict]:
    """(h, knob) grid search (paper §3.3) for every task.

    Per h: ONE engine, so one compression and one factorization serve the
    whole knob sweep (C for classification, ε for SVR, ν for one-class, λ
    for KRR/GP, which refactorizes per λ), warm-started knob to knob.
    ``trainer_kwargs`` are the engine's fields (``svr_c`` is SVR's box
    bound); ``y`` is None for one-class.  ``rtol`` mirrors STRUMPACK's
    rel_tol (crude ≈ 1e-2, accurate ≈ 1e-4, Tables 4–5): it switches each
    h's build to the adaptive compression and keeps every other ``comp``
    knob.

    Each model is scored by ``tasks.HOLDOUT_SCORES[task]`` on (x_val,
    y_val), higher is better; ``task="gp"`` is scored by the training log
    marginal and needs no validation split.  Returns (best model, info):
    ``info["results"][(h, knob)]`` holds the score (``accuracy``, or
    ``log_marginal`` for gp), the cell's own ADMM seconds and the h's
    compression and factorization seconds; then ``best_h`` with
    ``best_c``/``best_accuracy``, or ``best_lam``/``best_log_marginal``.
    """
    kw = dict(trainer_kwargs or {})
    if rtol is not None:
        kw["comp"] = dataclasses.replace(
            kw.get("comp", compression.CompressionParams()), rtol=rtol)
    gp = task == "gp"
    score_key = "log_marginal" if gp else "accuracy"
    x_v = None if gp else jnp.asarray(x_val)
    results = {}
    best_model, best_h, best_knob, best_score = None, None, None, -np.inf
    for h in hs:
        engine = HSSSVMEngine(spec=KernelSpec(h=float(h)), task=task, **kw)
        engine.prepare(x, y)
        warm, admm_seen = None, 0.0
        for c in knobs:
            model, warm = engine.train(float(c), warm=warm)
            score = (engine.log_marginal(float(c)) if gp
                     else tasks_mod.HOLDOUT_SCORES[task](model, x_v, y_val))
            rep = engine.report
            # report.admm_s accumulates over the sweep; a cell records only
            # its own run
            results[(float(h), float(c))] = {
                score_key: score, "admm_s": rep.admm_s - admm_seen,
                "compression_s": rep.compression_s,
                "factorization_s": rep.factorization_s}
            admm_seen = rep.admm_s
            if score > best_score:
                best_model, best_h, best_knob, best_score = (
                    model, float(h), float(c), score)
    return best_model, {
        "results": results, "best_h": best_h,
        "best_lam" if gp else "best_c": best_knob,
        f"best_{score_key}": best_score}
