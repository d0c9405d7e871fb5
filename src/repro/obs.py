"""Stage spans and jit counters of the program, kept in memory.

``span(name, **attrs)`` times one stage on the host clock
(``time.perf_counter_ns``) and opens ``jax.profiler.TraceAnnotation(name)``
around it, so that under the profiler the span also sits on the host plane
of the trace, on the device ops' clock.  Spans nest per thread (the open
span is a ``contextvars`` variable): a span opened on another thread, such
as the serving tick, starts a root of its own.  ``count(name, n)`` adds to
the innermost open span's counters and to a process total.

A span around asynchronous dispatch measures host time only: the device may
still be running what was enqueued inside it.  Nothing here waits for the
device; a stage whose span should cover device work ends in the
``block_until_ready`` the code already has.

The recorder stays on and writes nothing out: it keeps the span trees of the
last ``MAX_ROOTS`` roots.  Instrument stages, never per-op or per-iteration
work (a trained model opens about 20 spans).

Importing this module registers one ``jax.monitoring`` listener, which
counts on the span open on the emitting thread:

- ``jit.traces``: a function traced to a jaxpr;
- ``jit.compiles``: a backend compile not served by the persistent cache;
- ``jit.cache_reads``: an executable read from the persistent cache (jax
  also reports that read as a backend compile; it is counted here only).
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import itertools
import threading
import time

import jax

MAX_ROOTS = 64


@dataclasses.dataclass(eq=False)
class Span:
    name: str
    span_id: int
    parent_id: int | None
    trace_id: int                  # the root's span_id
    start_ns: int
    end_ns: int = 0                # set when the span closes
    attrs: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    children: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    @property
    def self_seconds(self) -> float:
        """Duration less the time its children cover (they run in turn)."""
        covered = sum(c.end_ns - c.start_ns for c in self.children)
        return (self.end_ns - self.start_ns - covered) / 1e9

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


@dataclasses.dataclass(frozen=True)
class RootTotals:
    """A finished root with per-name totals over its tree."""

    root: Span
    seconds: dict          # span name -> duration summed over its spans
    self_seconds: dict     # span name -> self time summed over its spans
    counters: dict         # counter name -> summed over the tree

    @classmethod
    def of(cls, root: Span) -> "RootTotals":
        sec, own, ctr = {}, {}, collections.Counter()
        for s in root.walk():
            sec[s.name] = sec.get(s.name, 0.0) + s.seconds
            own[s.name] = own.get(s.name, 0.0) + s.self_seconds
            ctr.update(s.counters)
        return cls(root, sec, own, dict(ctr))


class Recorder:
    """Span trees of the last ``MAX_ROOTS`` roots, and counter totals;
    ``clock`` gives nanoseconds."""

    def __init__(self, clock=time.perf_counter_ns):
        self._clock = clock
        self._open = contextvars.ContextVar("open_span", default=None)
        self._roots = collections.deque(maxlen=MAX_ROOTS)
        self._totals = collections.Counter()
        self._lock = threading.Lock()
        self._ids = itertools.count(1)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time the block as a child of this thread's open span.  Around
        asynchronous dispatch this is host time: nothing waits for the
        device."""
        parent = self._open.get()
        sid = next(self._ids)
        s = Span(name, sid, parent.span_id if parent else None,
                 parent.trace_id if parent else sid, 0, attrs=attrs)
        token = self._open.set(s)
        try:
            with jax.profiler.TraceAnnotation(name):
                s.start_ns = self._clock()
                try:
                    yield s
                finally:
                    s.end_ns = self._clock()
        finally:
            self._open.reset(token)
            if parent is None:
                with self._lock:
                    self._roots.append(s)
            else:
                parent.children.append(s)

    def count(self, name: str, n: int = 1) -> None:
        s = self._open.get()
        if s is not None:
            s.counters[name] = s.counters.get(name, 0) + n
        with self._lock:
            self._totals[name] += n

    def total(self, name: str) -> int:
        with self._lock:
            return self._totals[name]

    def recent_roots(self, n: int, name: str | None = None
                     ) -> list[RootTotals]:
        """The last ``n`` finished roots (named ``name``, if given), oldest
        first."""
        with self._lock:
            roots = [r for r in self._roots if name in (None, r.name)]
        return [RootTotals.of(r) for r in roots[-n:]] if n > 0 else []


RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
total = RECORDER.total
recent_roots = RECORDER.recent_roots

_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.traces",
    "/jax/core/compile/backend_compile_duration": "jit.compiles",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jit.cache_reads",
}
_jit_thread = threading.local()


def _on_jit_event(event: str, duration: float, **_kw) -> None:
    name = _JIT_EVENTS.get(event)
    if name is None:
        return
    if name == "jit.cache_reads":
        # emitted inside the backend-compile event that reports this read
        _jit_thread.cache_read = True
    elif name == "jit.compiles" and getattr(_jit_thread, "cache_read", False):
        _jit_thread.cache_read = False
        return
    count(name)


jax.monitoring.register_event_duration_secs_listener(_on_jit_event)
