"""The benchmark's own tests run on the CPU, at sizes a test run holds;
none of them loads the TPU's library."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402

# Sizes at which each cell's driver runs here: the cell's shapes (features,
# classes, kernel, preset, traffic mix) with fewer rows.
TINY = {
    "susy.train": dict(config=dict(rows=1024),
                       traffic=dict(holdout_rows=512,
                                    check_models=2, ref_tile=512)),
    # not a cell of BENCHMARK.json: at the cell's size the program departs
    # from the exact reference as far as the control does (PERF.md); its
    # driver is kept under test at a small size, with limits of the
    # test's own
    "covtype.sweep": dict(config=dict(rows=2048),
                          traffic=dict(validation_rows=512, ref_tile=1024,
                                       c_grid=[0.25, 1.0, 4.0]),
                          files=("covtype", "sweep"),
                          limits=dict(sv_count_gap=0.27, acc_drop=0.02,
                                      support_mismatch=0.0)),
    # not a cell of BENCHMARK.json yet (its knee at the full support set
    # is not measured); its driver is kept under test at a small size,
    # with the limits file it had
    "susy.serve": dict(config=dict(served_support_rows=2048),
                       traffic=dict(requests_per_s=150.0, pool_rows=2048,
                                    check_requests=10_000, wait_s=10),
                       files=("susy", "serve")),
}


@pytest.fixture
def tiny_cell():
    from bench import run as bench_run

    def make(name: str):
        tiny = TINY[name]
        if "files" in tiny:
            bdir = bench_run.BENCH
            config, traffic = tiny["files"]
            cell = bench_run.Cell(
                name=name, chips=1,
                config=bench_run.load_json(bdir / "configs" / f"{config}.json"),
                traffic=bench_run.load_json(
                    bdir / "traffic" / f"{traffic}.json"),
                limits=dict(tiny["limits"]) if "limits" in tiny else
                bench_run.load_json(bdir / "limits" / f"{name}.json"),
                end_to_end=[], per_layer=[],
                bench_dir=bdir)
        else:
            cell = bench_run.find_cell(name)
        cell.config.update(TINY[name]["config"])
        cell.traffic.update(TINY[name]["traffic"])
        return cell
    return make


@pytest.fixture
def measure_cpu():
    """``run.measure`` on the CPU, past the harness's look for a chip."""
    import time

    import jax
    from bench import run as bench_run

    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

    def go(cell, seed=123_456_789_012, seconds=1.0):
        return bench_run.measure(cell, seed, seconds, False, jax.devices(),
                                 peaks, t_start=time.perf_counter())
    return go
