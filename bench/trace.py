"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Only ``jax.profiler.ProfileData`` is used to read the file.  Device planes
are the ``/device:`` planes that hold an ``XLA Ops`` or ``XLA Modules``
line (``/device:TPU:<n>``; not, say, ``/device:CUSTOM:Megascale Trace``).
On a device plane:

- busy time is the union of the intervals of the line ``XLA Ops``, or of
  ``XLA Modules`` where a plane has no op line, clipped to the window;
- idle share is ``1 - busy / window``;
- device time per jitted module sums the ``XLA Modules`` events by name,
  with the ``(<id>)`` suffix the runtime appends taken off;
- the top device ops sum the ``XLA Ops`` events by module and op name
  (``<module>/<op>``, the op's HLO text cut at its `` = ``, with its
  custom-call target where it has one);
- an idle gap is a stretch of the window in which no op ran; it is named by
  the host event that overlaps it most, among the host threads' events
  (the Python tracer's own ``$sys`` events left out).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def module_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name.strip())


def op_name(event_name: str) -> str:
    """``%fusion.5 = f32[..] fusion(..)`` -> ``fusion.5``; a custom call
    keeps its target: ``custom-call.3:Cholesky``."""
    short = event_name.split(" = ", 1)[0].strip().lstrip("%")
    target = _TARGET.search(event_name)
    return f"{short}:{target.group(1)}" if target else short


def _owner(modules, t: int) -> str:
    """The module whose execution holds time ``t`` (modules sorted)."""
    import bisect

    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][1] >= t:
        return modules[i][2]
    return "?"


def union_length(intervals, lo: int, hi: int) -> int:
    """Length of the union of [start, end) intervals clipped to [lo, hi)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Events:
    """Intervals in ns, as (name, start, end), one list per kind."""

    ops: list                # device ops (or modules), every device plane
    modules: list            # device module executions
    host: list               # host-thread events
    n_devices: int


def read_events(path: str) -> Events:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops, modules, host = [], [], []
    n_dev = 0
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and (
                OPS_LINE in lines or MODULES_LINE in lines):
            n_dev += 1
            op_line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
            if op_line is not None:
                ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in op_line.events]
            if MODULES_LINE in lines:
                modules += [(module_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for e in lines[MODULES_LINE].events]
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in ln.events]
    return Events(ops, modules, host, n_dev)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                  # averaged over the device planes
    module_s: dict                 # module name -> device seconds
    module_calls: dict             # module name -> executions
    top_ops: list                  # [[name, seconds]], largest first
    idle_gaps: list                # [[host activity, seconds]], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce_events(ev: Events, lo: int, hi: int, top: int = 10,
                  unnamed: tuple = ()) -> Reduction:
    """Numbers of the window [lo, hi) (ns, the trace's clock); host events
    named in ``unnamed`` (the window's own marker) name no idle gap."""
    if ev.n_devices == 0 or not ev.ops:
        raise ValueError("the trace holds no device operation")
    busy = union_length([(s, e) for _, s, e in ev.ops], lo, hi) / ev.n_devices
    mod_s, mod_n = {}, {}
    for name, s, e in ev.modules:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            mod_s[name] = mod_s.get(name, 0) + d
            mod_n[name] = mod_n.get(name, 0) + 1
    op_s = {}
    mods = sorted((s, e, name) for name, s, e in ev.modules)
    for name, s, e in ev.ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = f"{_owner(mods, s)}/{op_name(name)}"
            op_s[key] = op_s.get(key, 0) + d
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps([(s, e) for _, s, e in ev.ops], lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    host = [h for h in ev.host
            if h[0] not in unnamed and not h[0].startswith("$sys ")]
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / 1e9,
        module_s={k: v / 1e9 for k, v in mod_s.items()},
        module_calls=mod_n,
        top_ops=[[n, v / 1e9] for n, v in top_ops],
        idle_gaps=[[_host_activity(host, s, e), (e - s) / 1e9]
                   for s, e in idle],
    )


def _host_activity(host, lo: int, hi: int) -> str:
    """The host event that overlaps [lo, hi) most; the shortest such event
    wins a tie, as the most specific."""
    best, best_key = "no host event", None
    for name, s, e in host:
        ov = min(e, hi) - max(s, lo)
        if ov <= 0:
            continue
        key = (ov, -(e - s))
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def window_bounds(ev: Events, marker: str) -> tuple[int, int]:
    """[start, end) of the host event named ``marker`` (the harness wraps
    its window in a ``TraceAnnotation`` of that name)."""
    spans = [(s, e) for name, s, e in ev.host if name == marker]
    if not spans:
        raise ValueError(f"no host event {marker!r} in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)
