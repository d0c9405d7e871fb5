"""High-throughput serving engine: many models, one process, batched ticks.

The paper's serve-time asset is that scoring is a *streamed kernel matvec*
over the support set — and the PR 2/PR 5 economy (classification, SVR and
one-class SVM all train on one ``(K + βI)`` factorization) applies at serve
time too: every model trained on that factorization scores against the SAME
support points.  The engine exploits this three ways:

  * **Shared-factorization score cache.**  Loaded models are grouped by the
    key ``(kernel, h, β, support-set digest)``; one LRU entry per key holds
    the ONE device-resident copy of the support points plus the (d, ΣP)
    block of every member model's dual-coefficient columns.  k models from
    one training factorization cost one support upload, not k — and one
    kernel pass scores all of them.
  * **Request-level dynamic batching.**  ``submit`` enqueues; a *tick*
    (``flush`` — fired by the max-batch threshold, the max-wait timer of
    the threaded driver, or an explicit call) concatenates every queued
    query across the group's models into one ``(batch, f)`` block, pads it
    to a fixed BUCKET shape (one XLA compile per bucket, never one per
    occupancy), and runs ONE multi-column ``kernel_matvec_streamed`` launch
    covering all queued queries and all member models.  Scores come back to
    the host once per tick and are de-interleaved per request.
  * **bf16 block evaluation.**  ``BatchPolicy.compute_dtype="bfloat16"``
    evaluates the test×support kernel blocks from bf16 operands with
    f32-accumulation einsums (the PR 3 convention) — half the score-path
    bandwidth at a pinned tolerance (tests/test_serve.py).

``batched_scores`` is the one scoring entry point (the launch CLI's
demo loop and the bench's per-request baseline call the same function the
batched tick uses, so the two paths can be compared at identical numerics).
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import EngineModel
from repro.core.kernelfn import (
    DEFAULT_SCORE_BLOCK, KernelSpec, kernel_block, kernel_matvec_streamed,
)

Array = jax.Array


# --------------------------------------------------------------------- #
# scoring kernels                                                        #
# --------------------------------------------------------------------- #
def _bf16_matvec_streamed(spec: KernelSpec, x_rows: Array, x_cols: Array,
                          v: Array, block: int) -> Array:
    """``kernel_matvec_streamed`` with bf16 block evaluation, f32 accumulation.

    Gaussian blocks use the matmul expansion with every contraction pinned
    to an f32 accumulator (`preferred_element_type`) — the bf16×bf16→f32
    MXU shape; the exp and the score reduction then run in f32.  Non-matmul
    kernels (laplacian) evaluate the block on bf16 operands and accumulate
    the score einsum in f32.
    """
    bf16, f32 = jnp.bfloat16, jnp.float32
    n = x_rows.shape[0]
    pad = (-n) % block
    xr = jnp.pad(x_rows, ((0, pad), (0, 0))).astype(bf16)
    xr = xr.reshape(-1, block, x_rows.shape[1])
    xc = x_cols.astype(bf16)
    vc = v.astype(bf16)
    if spec.name == "gaussian":
        nb = jnp.einsum("df,df->d", xc, xc, preferred_element_type=f32)
        scale = -0.5 / (spec.h * spec.h)

        def body(xblk):
            na = jnp.einsum("qf,qf->q", xblk, xblk,
                            preferred_element_type=f32)
            cross = jnp.einsum("qf,df->qd", xblk, xc,
                               preferred_element_type=f32)
            sq = jnp.maximum(na[:, None] + nb[None, :] - 2.0 * cross, 0.0)
            k = jnp.exp(sq * scale)
            return jnp.einsum("qd,dp->qp", k, vc,
                              preferred_element_type=f32)
    else:
        def body(xblk):
            k = kernel_block(spec, xblk, xc).astype(f32)
            return jnp.einsum("qd,dp->qp", k, vc,
                              preferred_element_type=f32)

    out = jax.lax.map(body, xr)
    return out.reshape(-1, v.shape[1])[:n]


def batched_scores(xq: Array, xs: Array, zy: Array, biases: Array, *,
                   spec: KernelSpec, block: int = DEFAULT_SCORE_BLOCK,
                   compute_dtype: str = "float32") -> Array:
    """Scores ``(n_q, P) = K(xq, xs) @ zy + biases`` for a column block
    covering any number of same-factorization models.

    The f32 path is literally ``kernel_matvec_streamed`` — the same code
    ``EngineModel.decision_function`` runs, so batch-scored f32 results are
    bit-identical to per-model scoring at matched ``block``.
    """
    if compute_dtype == "float32":
        scores = kernel_matvec_streamed(spec, xq, xs, zy, block=block)
    elif compute_dtype == "bfloat16":
        scores = _bf16_matvec_streamed(spec, xq, xs, zy, block)
    else:
        raise ValueError(f"unknown compute_dtype {compute_dtype!r}")
    return scores + biases[None, :]


# --------------------------------------------------------------------- #
# per-task decode (host side, once per tick)                             #
# --------------------------------------------------------------------- #
def _ovo_vote_np(scores: np.ndarray, pairs: np.ndarray, n_classes: int
                 ) -> np.ndarray:
    """Numpy twin of ``tasks.ovo_vote`` (same tie-break, host-side).

    The per-class scatter-adds are expressed as matmuls against fixed
    (P, k) incidence matrices — ``np.add.at`` is an order of magnitude
    slower and sat squarely in the per-tick decode budget."""
    scores = scores.astype(np.float32)
    winner = np.where(scores >= 0, pairs[:, 0][None, :],
                      pairs[:, 1][None, :])
    votes = (winner[:, :, None]
             == np.arange(n_classes)[None, None, :]).sum(axis=1)
    inc = np.zeros((pairs.shape[0], n_classes), np.float32)
    rows = np.arange(pairs.shape[0])
    inc[rows, pairs[:, 0]] = 1.0
    inc[rows, pairs[:, 1]] = -1.0
    margin = scores @ inc
    return np.argmax(votes + 1e-3 * np.tanh(margin), axis=1)


def decode_predictions(scores: np.ndarray, *, task: str, binary: bool,
                       strategy: str, classes: np.ndarray,
                       pairs: np.ndarray | None
                       ) -> tuple[np.ndarray, np.ndarray]:
    """(decision values, predictions) from a model's (n, P) score columns,
    matching ``EngineModel.decision_function`` / ``predict`` conventions:
    single-column tasks return the flat score column."""
    if task in ("svr", "krr", "gp"):     # regression: raw-value decode
        flat = scores[:, 0]
        return flat, flat
    if task == "oneclass" or binary:
        flat = scores[:, 0]
        return flat, np.where(flat >= 0, 1, -1)
    if strategy == "ovr":
        idx = np.argmax(scores, axis=1)
    else:
        idx = _ovo_vote_np(scores, pairs, int(classes.shape[0]))
    return scores, np.asarray(classes)[idx]


# --------------------------------------------------------------------- #
# batching policy / tickets / groups                                     #
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class BatchPolicy:
    """Tick policy knobs.

    ``buckets`` are the padded batch shapes a tick may launch — occupancy
    is padded UP to the smallest fitting bucket, so XLA compiles once per
    bucket (and per loaded column count), never once per queue length.
    Oversize ticks are chunked at ``buckets[-1]``.  ``max_batch`` queued
    queries trigger an immediate tick; ``max_wait_ms`` is the threaded
    driver's tick period.  ``block`` is the streamed score block size
    (``DEFAULT_SCORE_BLOCK`` — one constant for every predict path).
    """

    max_batch: int = 4096
    max_wait_ms: float = 2.0
    buckets: tuple = (64, 256, 1024, 4096)
    block: int = DEFAULT_SCORE_BLOCK
    compute_dtype: str = "float32"      # "float32" | "bfloat16"

    def __post_init__(self):
        if not self.buckets or tuple(sorted(self.buckets)) != self.buckets:
            raise ValueError("buckets must be ascending and non-empty")
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown compute_dtype {self.compute_dtype!r}")

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]


class Ticket:
    """Handle for one submitted request; resolved at the covering tick."""

    __slots__ = ("_engine", "_event", "scores", "predictions", "t_submit",
                 "t_done")

    def __init__(self, engine: "ServingEngine"):
        self._engine = engine
        self._event = threading.Event()
        self.scores = None
        self.predictions = None
        self.t_submit = time.perf_counter()
        self.t_done = None

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def _resolve(self, scores, predictions) -> None:
        self.scores, self.predictions = scores, predictions
        self.t_done = time.perf_counter()
        self._event.set()

    def result(self, timeout: float | None = None
               ) -> tuple[np.ndarray, np.ndarray]:
        """(decision values, predictions).  Without the threaded driver a
        pending ticket is resolved by running a tick now."""
        if not self._event.is_set() and not self._engine.running:
            self._engine.flush()
        if not self._event.wait(timeout):
            raise TimeoutError("request not served within timeout")
        return self.scores, self.predictions

    @property
    def latency_s(self) -> float:
        assert self.t_done is not None, "not resolved yet"
        return self.t_done - self.t_submit


@dataclasses.dataclass
class _ModelEntry:
    key: tuple
    col0: int
    col1: int
    task: str
    binary: bool
    strategy: str
    classes: np.ndarray
    pairs: np.ndarray | None


class _Group:
    """One cache entry: host master copies + the device-resident mirrors."""

    def __init__(self, key: tuple, spec: KernelSpec, xs: np.ndarray):
        self.key = key
        self.spec = spec
        self.xs_host = xs                     # (d, f) — shared, immutable
        self.zy_host = np.zeros((xs.shape[0], 0), np.float32)
        self.biases_host = np.zeros((0,), np.float32)
        self.xs_dev: Array | None = None      # uploaded at most once per
        self.zy_dev: Array | None = None      # residency span
        self.biases_dev: Array | None = None
        self.queue: list[tuple[Ticket, _ModelEntry, np.ndarray]] = []
        self.queued_rows = 0

    @property
    def resident(self) -> bool:
        return self.xs_dev is not None

    def append_columns(self, zy: np.ndarray, biases: np.ndarray
                       ) -> tuple[int, int]:
        col0 = self.zy_host.shape[1]
        self.zy_host = np.concatenate(
            [self.zy_host, zy.astype(np.float32)], axis=1)
        self.biases_host = np.concatenate(
            [self.biases_host, biases.astype(np.float32).reshape(-1)])
        # the column block changed shape: the device mirror is stale (the
        # support points are NOT — xs_dev survives)
        self.zy_dev = self.biases_dev = None
        return col0, self.zy_host.shape[1]


def _support_digest(xs: np.ndarray) -> str:
    h = hashlib.sha1()
    h.update(str((xs.shape, str(xs.dtype))).encode())
    h.update(np.ascontiguousarray(xs).tobytes())
    return h.hexdigest()


def group_key(model: EngineModel, xs_host: np.ndarray) -> tuple:
    """The factorization-sharing cache key: models agreeing on it were
    trained on the same ``(K̃ + βI)`` build and score against the same
    device-resident support state."""
    spec = model.spec
    beta = None if model.beta is None else float(model.beta)
    return (spec.name, float(spec.h), spec.impl, beta,
            _support_digest(xs_host))


# --------------------------------------------------------------------- #
# the engine                                                             #
# --------------------------------------------------------------------- #
class ServingEngine:
    """Many trained models behind one process, scored in batched ticks.

    ``max_resident`` bounds how many cache entries hold device memory at
    once (LRU): evicting drops the entry's device arrays only — the host
    master copies stay, and the next request to a member model re-uploads
    (counted in ``stats()['support_uploads']``).
    """

    def __init__(self, policy: BatchPolicy = BatchPolicy(),
                 registry=None, max_resident: int = 8):
        self.policy = policy
        self.registry = registry
        self.max_resident = max_resident
        self._groups: "OrderedDict[tuple, _Group]" = OrderedDict()
        self._models: dict[str, _ModelEntry] = {}
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._thread: threading.Thread | None = None
        self._running = False
        self._counter = 0
        self._latencies: list[float] = []
        self._n_uploads = 0
        self._n_evictions = 0
        self._n_ticks = 0
        self._n_launches = 0
        self._n_queries = 0
        self._n_requests = 0
        # one jit PER ENGINE — wrapped in a fresh closure so the jit cache
        # (keyed on function identity) is private to this engine and the
        # compile-count guard (`_cache_size`) sees exactly its bucket shapes
        def _score_entry(xq, xs, zy, biases, *, spec, block, compute_dtype):
            return batched_scores(xq, xs, zy, biases, spec=spec,
                                  block=block, compute_dtype=compute_dtype)

        self._scorer = jax.jit(
            _score_entry,
            static_argnames=("spec", "block", "compute_dtype"))

    # ------------------------------------------------------------------ #
    # model management                                                    #
    # ------------------------------------------------------------------ #
    def add_model(self, model: EngineModel, model_id: str | None = None
                  ) -> str:
        """Register an in-memory model; returns its id.  Same-key models
        join the existing cache entry (no second support upload)."""
        if model.mesh is not None:
            # gather once: serving is single-process device-local
            model = dataclasses.replace(
                model, x_perm=jnp.asarray(jax.device_get(model.x_perm)),
                z_y=jnp.asarray(jax.device_get(model.z_y)), mesh=None)
        xs = np.asarray(jax.device_get(model.x_perm))
        zy = np.asarray(jax.device_get(model.z_y))
        if zy.ndim == 1:
            zy = zy[:, None]
        biases = np.asarray(jax.device_get(model.biases)).reshape(-1)
        key = group_key(model, xs)
        with self._lock:
            if model_id is None:
                self._counter += 1
                model_id = f"m{self._counter}"
            if model_id in self._models:
                raise ValueError(f"model id {model_id!r} already loaded")
            group = self._groups.get(key)
            if group is None:
                group = _Group(key, model.spec, xs)
                self._groups[key] = group
            col0, col1 = group.append_columns(zy, biases)
            self._models[model_id] = _ModelEntry(
                key=key, col0=col0, col1=col1, task=model.task,
                binary=model.binary, strategy=model.strategy,
                classes=np.asarray(model.classes),
                pairs=None if model.pairs is None
                else np.asarray(model.pairs))
        return model_id

    def load(self, name: str, version: int | None = None,
             prune_tol: float | None = None, model_id: str | None = None
             ) -> str:
        """Load a registry model into the engine; returns its id."""
        if self.registry is None:
            raise RuntimeError("engine was built without a registry")
        model, info = self.registry.load(name, version=version,
                                         prune_tol=prune_tol)
        return self.add_model(
            model, model_id=model_id or f"{name}@v{info.version}")

    def model_group(self, model_id: str):
        """The cache entry a model scores through (tests/introspection)."""
        return self._groups[self._models[model_id].key]

    # ------------------------------------------------------------------ #
    # cache residency                                                     #
    # ------------------------------------------------------------------ #
    def _ensure_resident(self, group: _Group) -> None:
        self._groups.move_to_end(group.key)          # LRU touch
        if group.xs_dev is None:
            group.xs_dev = jnp.asarray(group.xs_host)
            self._n_uploads += 1
        if group.zy_dev is None:
            group.zy_dev = jnp.asarray(group.zy_host)
            group.biases_dev = jnp.asarray(group.biases_host)
        # evict least-recently-used resident entries past the budget
        # (device arrays only — the host master copies stay)
        resident = [g for g in self._groups.values()
                    if g.resident and g.key != group.key]
        excess = len(resident) + 1 - self.max_resident
        for g in resident[:max(excess, 0)]:
            g.xs_dev = g.zy_dev = g.biases_dev = None
            self._n_evictions += 1

    # ------------------------------------------------------------------ #
    # request path                                                        #
    # ------------------------------------------------------------------ #
    def submit(self, model_id: str, x) -> Ticket:
        """Enqueue a request of one or more query points; returns a ticket
        resolved at the next covering tick."""
        entry = self._models[model_id]
        xq = np.asarray(x, np.float32)
        if xq.ndim == 1:
            xq = xq[None, :]
        ticket = Ticket(self)
        with self._lock:
            group = self._groups[entry.key]
            group.queue.append((ticket, entry, xq))
            group.queued_rows += xq.shape[0]
            if group.queued_rows >= self.policy.max_batch:
                if self._running:
                    self._cond.notify()       # wake the driver for the tick
                else:
                    self._flush_group(group)
        return ticket

    def score(self, model_id: str, x, timeout: float | None = 30.0
              ) -> tuple[np.ndarray, np.ndarray]:
        """Synchronous scoring entry point: submit + tick + result.

        This is THE scoring routine (the launch CLI's request loop uses it
        instead of hand-rolling per-task closures); under the threaded
        driver it waits for the covering tick instead of forcing one.
        """
        return self.submit(model_id, x).result(timeout=timeout)

    def flush(self) -> int:
        """Run one tick: score every queued request, group by group.
        Returns the number of requests resolved."""
        n = 0
        with self._lock:
            for group in list(self._groups.values()):
                n += self._flush_group(group)
        return n

    def _flush_group(self, group: _Group) -> int:
        queue, group.queue = group.queue, []
        group.queued_rows = 0
        if not queue:
            return 0
        self._ensure_resident(group)
        xq = np.concatenate([q for _, _, q in queue], axis=0)
        scores = self._score_rows(group, xq)
        self._n_ticks += 1
        # de-interleave: rows per request, columns per model
        row = 0
        for ticket, entry, q in queue:
            sl = scores[row:row + q.shape[0], entry.col0:entry.col1]
            row += q.shape[0]
            vals, preds = decode_predictions(
                sl, task=entry.task, binary=entry.binary,
                strategy=entry.strategy, classes=entry.classes,
                pairs=entry.pairs)
            ticket._resolve(vals, preds)
            self._latencies.append(ticket.latency_s)
        self._n_requests += len(queue)
        return len(queue)

    def _score_rows(self, group: _Group, xq: np.ndarray) -> np.ndarray:
        """One (or, past the largest bucket, a few) padded scorer launches
        covering every queued query row of the tick."""
        pol = self.policy
        out = []
        top = pol.buckets[-1]
        for start in range(0, xq.shape[0], top):
            chunk = xq[start:start + top]
            bucket = pol.bucket_for(chunk.shape[0])
            pad = bucket - chunk.shape[0]
            if pad:
                chunk = np.concatenate(
                    [chunk, np.zeros((pad, chunk.shape[1]), chunk.dtype)])
            # row-streaming the kernel exists to bound memory on LARGE
            # query sets — a small bucket must not pad up to a full
            # policy.block of kernel rows (block is a function of bucket,
            # so this stays one compile per bucket)
            block = min(pol.block, bucket)
            scores = self._scorer(
                jnp.asarray(chunk), group.xs_dev, group.zy_dev,
                group.biases_dev, spec=group.spec, block=block,
                compute_dtype=pol.compute_dtype)
            self._n_launches += 1
            self._n_queries += bucket - pad
            out.append(np.asarray(scores)[:bucket - pad])
        return np.concatenate(out, axis=0) if len(out) > 1 else out[0]

    # ------------------------------------------------------------------ #
    # threaded max-wait driver                                            #
    # ------------------------------------------------------------------ #
    @property
    def running(self) -> bool:
        return self._running

    def start(self) -> None:
        """Background tick loop: flush every ``max_wait_ms`` or as soon as
        a group hits ``max_batch`` queued queries."""
        with self._lock:
            if self._running:
                return
            self._running = True

        def loop():
            while True:
                with self._cond:
                    if not self._running:
                        return
                    self._cond.wait(self.policy.max_wait_ms / 1e3)
                    if not self._running:
                        return
                self.flush()

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self.flush()                         # drain anything still queued

    # ------------------------------------------------------------------ #
    # observability                                                       #
    # ------------------------------------------------------------------ #
    def drain_latencies(self) -> list[float]:
        with self._lock:
            out, self._latencies = self._latencies, []
        return out

    def scorer_compiles(self) -> int | None:
        """Jit cache entries of the batch scorer (None if unreadable) —
        must equal the number of distinct (bucket, column-count) shapes."""
        size = getattr(self._scorer, "_cache_size", lambda: None)()
        return size

    def stats(self) -> dict:
        with self._lock:
            resident = [g for g in self._groups.values() if g.resident]
            return dict(
                models=len(self._models),
                groups=len(self._groups),
                cache_entries=len(resident),
                resident_support_bytes=sum(
                    g.xs_host.nbytes for g in resident),
                support_uploads=self._n_uploads,
                evictions=self._n_evictions,
                ticks=self._n_ticks,
                launches=self._n_launches,
                queries=self._n_queries,
                requests=self._n_requests,
                scorer_compiles=self.scorer_compiles(),
            )
