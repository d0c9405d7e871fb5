"""Benchmark driver — one module per paper table.

  bench_svm       — Tables 4/5 (HSS accuracy presets: compression /
                    factorization / memory / ADMM time / accuracy)
  bench_baselines — Tables 2/3 (dense-ADMM = RACQP role, SMO = LIBSVM role,
                    Nystrom rival, HSS-ADMM ours)
  bench_grid      — Figure 2 + the C-grid amortization headline
  bench_kernels   — kernel micro-benches + HSS O(N r) scaling evidence

Prints ``name,us_per_call,derived`` CSV.  Roofline numbers come from the
dry-run sweep (benchmarks/run_dryrun_sweep.sh -> EXPERIMENTS.md), not from
CPU wall-time.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import bench_baselines, bench_grid, bench_kernels, \
        bench_svm
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    rows: list = []
    print("name,us_per_call,derived")
    # A failing module stops the run with its traceback and a non-zero
    # exit: a suite that carries on would report a partial table as a pass.
    for mod in (bench_kernels, bench_svm, bench_baselines, bench_grid):
        t0 = time.time()
        start = len(rows)
        mod.run(rows)
        for r in rows[start:]:
            print(",".join(str(x) for x in r), flush=True)
        print(f"# {mod.__name__} done in {time.time()-t0:.1f}s",
              file=sys.stderr)


if __name__ == "__main__":
    main()
