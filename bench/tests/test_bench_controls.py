"""The controls of the correctness check, at sizes a test run holds: the
plain reference put in the program's place with one guarantee of the
configuration broken must come out not correct under the cell's limits.
On the chip the same controls run at the cells' own sizes
(``bench/calibrate.py``)."""
from bench import run as bench_run

SEED = 2**32 + 17


def test_train_control_half_the_admm_iterations_fails(tiny_cell):
    cell = tiny_cell("susy.train")
    cell.config["rows"] = 8192
    cell.traffic.update(holdout_rows=4096, ref_tile=4096)
    driver = cell.driver()
    run = bench_run.Run(cell, SEED, cell.data())
    prog = [dict(index=1)]
    refs = driver.reference(run, prog)
    ctrl = driver.reference(run, prog, max_it=cell.config["max_it"] // 2)
    checks = driver.compare(ctrl, refs, run)
    assert not all(c.ok for c in checks), checks
    assert all(c.ok for c in driver.compare(refs, refs, run))


def test_serve_control_half_the_support_set_fails(tiny_cell):
    import numpy as np

    cell = tiny_cell("susy.serve")
    driver = cell.driver()
    run = bench_run.Run(cell, SEED, cell.data())
    tr = cell.traffic
    xs, zy, bias = driver.make_model(run)
    pool, _ = run.data.generate(tr["pool_rows"], (SEED, 3))
    due, sizes = driver._schedule(tr, SEED, 2.0)
    starts = np.random.default_rng([SEED, 4]).integers(
        0, pool.shape[0] - tr["rows_max"], size=due.shape[0])
    run.state.update(xs=xs, zy=zy, bias=bias, pool=pool, starts=starts,
                     sizes=sizes, tickets=[None] * due.shape[0])
    prog = dict(idx=driver.sample(run), missing=0)
    r = driver.reference(run, prog)
    half = driver.reference(run, prog, support_frac=0.5)
    ctrl = dict(prog, scores=half, labels=np.where(half >= 0, 1, -1))
    assert not all(c.ok for c in driver.compare(ctrl, r, run))
    same = dict(prog, scores=r, labels=np.where(r >= 0, 1, -1))
    assert all(c.ok for c in driver.compare(same, r, run))


def test_calibrate_reads_program_and_controls(tiny_cell):
    from bench import calibrate

    got = {name: {c.name: c.value for c in checks}
           for name, checks, _ in calibrate.readings(
               tiny_cell("susy.serve"), SEED, 1.0, control=True)}
    assert set(got) == {"program", "ref_support_half", "program_bf16_scores"}
    assert got["program"]["score_gap"] <= 0.05
    assert got["ref_support_half"]["score_gap"] > 0.05
