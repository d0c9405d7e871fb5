#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process holds.

    python3 bench/run.py --workload susy.train --seed 7 --seconds 51 --trace 0

Everything is found by name.  The cell (``workloads`` in BENCHMARK.json)
names a configuration, ``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<traffic>.json``.  The mix names the driver that runs it,
``bench/drivers/<driver>.py``, with its parameters; the configuration names
its row generator, ``bench/data/<generator>.py``.  The limits of the
correctness check are ``bench/limits/<cell>.json``, and each per-layer
metric is read by ``bench/metrics/<metric>.py``.

A driver module has ``setup(run)`` (draw inputs, build, warm every shape),
``window(run, seconds)`` (the measured work; returns its end-to-end
metrics, counts and the per-layer readers' record), ``answers(run)``,
``free(run)``, ``reference(run, answers)`` and ``compare(answers,
reference, run)`` (the correctness check), and ``controls`` for
``bench/calibrate.py``.

A run refuses any platform but a TPU with as many chips as the cell asks,
turns on the program's persistent compile cache, lets the driver set up and
warm its shapes (``setup_s``, from process start), measures for
``--seconds``, reads the peak device memory, frees the program's state and
checks the window's answers against the plain reference.  With ``--trace
1`` the window runs under the profiler and the per-layer metrics are
printed in place of the end-to-end ones.  Each compared number is printed
beside its limit on the last lines of standard error; the last line of
standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
WINDOW_MARK = "bench_window"


class Refused(SystemExit):
    """The run cannot measure here; it exits non-zero and prints no result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------- #
# finding the pieces by name                                             #
# --------------------------------------------------------------------- #
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list
    bench_dir: Path

    def driver(self):
        return load_module(self.bench_dir / "drivers"
                           / f"{self.traffic['driver']}.py")

    def data(self):
        return load_module(self.bench_dir / "data"
                           / f"{self.config['generator']}.py")

    def metric_reader(self, name: str):
        return load_module(self.bench_dir / "metrics" / f"{name}.py")


def find_cell(name: str, root: Path = REPO) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    bdir = root / "bench"
    lim_path = bdir / "limits" / f"{name}.json"

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(bdir / "configs" / f"{w['config']}.json"),
        traffic=load_json(bdir / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(lim_path) if lim_path.is_file() else {},
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        bench_dir=bdir)


def load_peaks(device_kind: str, path: Path = BENCH / "peaks.json") -> dict:
    table = load_json(path)["devices"]
    if device_kind not in table:
        raise Refused(f"no peaks for device kind {device_kind!r} in "
                      f"{path.name}; add them with their source")
    return table[device_kind]


# --------------------------------------------------------------------- #
# the chip                                                               #
# --------------------------------------------------------------------- #
def require_chips(devices, chips: int) -> None:
    platform = devices[0].platform if devices else "none"
    if platform != "tpu":
        raise Refused(f"needs a TPU; jax found {platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips; jax found "
                      f"{len(devices)}")


def peak_bytes(devices) -> int | None:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


class CompileClock:
    """jax's compile events and persistent-cache reads, with their
    wall-clock end times and the function each was for."""

    _PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/")

    def __init__(self):
        self.events: list[tuple[str, float, float, str]] = []

    def __call__(self, event: str, duration: float, fun_name: str = "",
                 **_kw) -> None:
        if event.startswith(self._PREFIXES):
            self.events.append((event.rsplit("/", 1)[-1], time.perf_counter(),
                                duration, fun_name))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)

    def summary(self, t0: float, t1: float) -> str:
        kinds: dict = {}
        funs: dict = {}
        for name, t, d, fun in self.events:
            if t0 <= t <= t1:
                n, s = kinds.get(name, (0, 0.0))
                kinds[name] = (n + 1, s + d)
                if name == "backend_compile_duration":
                    funs[fun] = funs.get(fun, 0) + 1
        if not kinds:
            return "none"
        top = sorted(funs.items(), key=lambda kv: -kv[1])[:8]
        return (", ".join(f"{k} {n} ({s:.3f} s)"
                          for k, (n, s) in sorted(kinds.items()))
                + "; compiled or read from the cache: "
                + ", ".join(f"{f} x{n}" for f, n in top))


# --------------------------------------------------------------------- #
# one run                                                                #
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float | None

    @property
    def ok(self) -> bool:
        return (self.limit is not None and math.isfinite(self.value)
                and self.value <= self.limit)


@dataclasses.dataclass
class Run:
    """What a driver is given: the cell, the seed, and a place for state."""

    cell: Cell
    seed: int
    data: object
    state: dict = dataclasses.field(default_factory=dict)

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def limit(self, name: str) -> float | None:
        return self.cell.limits.get(name)


def check(driver, run: Run) -> list[Check]:
    """What the window produced against the plain reference: the driver
    collects the answers, frees the program's state, then runs the
    reference and compares."""
    prog = driver.answers(run)
    driver.free(run)
    gc.collect()
    return driver.compare(prog, driver.reference(run, prog), run)


def trace_dir(cell: str, seed: int) -> Path:
    return REPO / "bench_out" / "traces" / f"{cell}-{seed}"


def measure(cell: Cell, seed: int, seconds: float, trace: bool, devices,
            peaks: dict, t_start: float = T_START) -> dict:
    """Set up, measure, check; returns the result object (not printed)."""
    import jax

    driver = cell.driver()
    run = Run(cell, seed, cell.data())
    driver.setup(run)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    tdir = trace_dir(cell.name, seed)
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
    with CompileClock() as clock:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW_MARK):
            win = driver.window(run, seconds)
        t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    log("compile events in the window: " + clock.summary(t0, t1))
    memory = peak_bytes(devices[:cell.chips])

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    result = {"correct": False, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": {}, "device": device}
    if trace:
        from bench import trace as trace_mod

        ev = trace_mod.read_events(trace_mod.newest_xplane(str(tdir)))
        lo, hi = trace_mod.window_bounds(ev, WINDOW_MARK)
        red = trace_mod.reduce_events(ev, lo, hi, unnamed=(WINDOW_MARK,))
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        rec = dict(win["record"], trace=red, peaks=peaks, cell=cell)
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(rec)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red.top_ops,
                               "idle_gaps": red.idle_gaps}
    else:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else \
                win["metrics"].get(m["name"])
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}

    del win
    gc.collect()
    checks = check(driver, run)
    run.state.clear()
    gc.collect()
    result["correct"] = bool(checks) and all(c.ok for c in checks)
    result["checks"] = {
        c.name: {"value": c.value if math.isfinite(c.value) else None,
                 "limit": c.limit} for c in checks}
    for c in checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(REPO))
    sys.path.insert(0, str(REPO / "src"))
    cell = find_cell(args.workload)
    import jax

    devices = jax.devices()
    require_chips(devices, cell.chips)
    peaks = load_peaks(devices[0].device_kind)
    from repro.launch.cache import use_compile_cache

    cache = use_compile_cache()
    # every program in the cache, however quick its compile, so that only
    # a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"device {devices[0].device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {cache}")
    with jax.default_device(devices[0]):
        result = measure(cell, args.seed, args.seconds, bool(args.trace),
                         devices, peaks)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
