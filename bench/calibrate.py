#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/calibrate.py --workload susy.train --seeds 11,12,13 \
        --control-seeds 11,12,13 --seconds 0

For each seed, in one process: the driver's set-up, a window of
``--seconds`` (0: one model, one C cycle or the requests due at once),
then the numbers the check compares, program against the plain reference.
For each control seed also the driver's controls: the reference put in the
program's place with one guarantee of the configuration broken, and the
program's own lower-precision path.  One JSON line per reading goes to
standard output and to ``bench_out/calibrate/<cell>.jsonl``.  Needs the
chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import run as bench_run  # noqa: E402


def readings(cell, seed: int, seconds: float, control: bool):
    driver = cell.driver()
    run = bench_run.Run(copy.deepcopy(cell), seed, cell.data())
    t0 = time.perf_counter()
    driver.setup(run)
    driver.window(run, seconds)
    prog = driver.answers(run)
    driver.free(run)
    gc.collect()
    refs = driver.reference(run, prog)
    yield "program", driver.compare(prog, refs, run), time.perf_counter() - t0
    if control:
        t0 = time.perf_counter()
        for name, checks in driver.controls(run, prog, refs).items():
            yield name, checks, time.perf_counter() - t0
    run.state.clear()
    gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench_run.REPO / "src"))
    cell = bench_run.find_cell(args.workload)
    import jax

    devices = jax.devices()
    bench_run.require_chips(devices, cell.chips)
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out_dir = bench_run.REPO / "bench_out" / "calibrate"
    out_dir.mkdir(parents=True, exist_ok=True)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    with open(out_dir / f"{cell.name}.jsonl", "a") as f, \
            jax.default_device(devices[0]):
        for seed in (int(s) for s in args.seeds.split(",")):
            for name, checks, dt in readings(cell, seed, args.seconds,
                                             seed in controls):
                line = json.dumps({"cell": cell.name, "seed": seed,
                                   "reading": name, "seconds": dt,
                                   **{c.name: c.value for c in checks}})
                print(line, flush=True)
                f.write(line + "\n")
                f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
