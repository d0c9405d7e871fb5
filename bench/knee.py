#!/usr/bin/env python3
"""Find the serving knee: the highest offered rate at which the backlog
does not grow.

    python3 bench/knee.py --workload susy.serve --rates 1000,2000,4000 \
        --seconds 8 --seed 1

One process sets the cell up once, then offers each rate of the ladder (in
requests per second) for ``--seconds`` through the cell's own driver.  Per
rate it prints the rows per second offered and answered, the latency
median and 99th percentile, the median latency of the requests due in the
last quarter of the window against the first quarter (a backlog that grows
shows as a ratio well above 1), the generator's lateness and the rows per
launch.  Lines go to standard output and ``bench_out/knee/<cell>.jsonl``.
Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench_run.REPO / "src"))
    cell = bench_run.find_cell(args.workload)
    import jax

    devices = jax.devices()
    bench_run.require_chips(devices, cell.chips)
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out_dir = bench_run.REPO / "bench_out" / "knee"
    out_dir.mkdir(parents=True, exist_ok=True)
    driver = cell.driver()
    run = bench_run.Run(cell, args.seed, cell.data())
    with open(out_dir / f"{cell.name}.jsonl", "a") as f, \
            jax.default_device(devices[0]):
        driver.setup(run)
        engine, mid, pool = (run.state[k] for k in ("engine", "mid", "pool"))
        for b in engine.policy.buckets:
            times = []
            for _ in range(5):
                t = time.perf_counter()
                engine.score(mid, pool[:b])
                times.append(time.perf_counter() - t)
            line = json.dumps({"cell": cell.name, "bucket": b,
                               "launch_ms": float(np.median(times)) * 1e3})
            print(line, flush=True)
            f.write(line + "\n")
        for rate in (float(r) for r in args.rates.split(",")):
            cell.traffic["requests_per_s"] = rate
            win = driver.window(run, args.seconds)
            st = run.state
            lat, due, sizes = st["lat"], st["due"], st["sizes"]
            ok = np.isfinite(lat)
            q = args.seconds / 4
            first = np.median(lat[ok & (due < q)])
            last = np.median(lat[ok & (due >= args.seconds - q)])
            rec = win["record"]
            line = json.dumps({
                "cell": cell.name, "requests_per_s": rate,
                "rows_per_s_offered": float(sizes.sum() / args.seconds),
                "rows_per_s_answered": float(sizes[ok].sum() / (
                    args.seconds + max(float(np.max(lat[ok])), 0.0))),
                "p50_ms": float(np.median(lat[ok]) * 1e3),
                "p99_ms": win["metrics"]["serve_p99_ms"],
                "growth": float(last / first),
                "late_p99_ms": rec["late_p99_s"] * 1e3,
                "rows_per_launch": rec["queries"] / max(rec["launches"], 1),
                "missing": win["failed"]})
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
            for k in ("tickets",):
                st.pop(k, None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
