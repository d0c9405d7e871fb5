"""covtype.train at a size a test run holds: the launch driver's 7-column
one-vs-rest path on 54 mixed features, checked against the plain
reference under the cell's own limits; the same run with the timed path
broken, which the check must catch; and the cell's per-layer readers, fed
a recorder on a clock of the test's own."""
import sys
import types

import pytest

from bench import run as bench_run
from bench.tests.test_bench_spans import Clock, recorder  # noqa: F401
from bench.tests.test_bench_train import (
    _answer_altered, _half_batch, _state_unchanged,
)

CELL = "covtype.train"


def _cell():
    cell = bench_run.find_cell(CELL)
    # the deployment's shapes (54 features, 7 classes, kernel, preset,
    # traffic mix) with fewer rows
    cell.config.update(rows=1024)
    cell.traffic.update(holdout_rows=512, check_models=2, ref_tile=512)
    return cell


def test_covtype_train_is_a_cell_with_its_limits_and_readers():
    cell = bench_run.find_cell(CELL)
    assert cell.config["name"] == "covtype" and cell.config["classes"] == 7
    assert cell.traffic["driver"] == "train_models" and cell.chips == 1
    assert set(cell.limits) == {"sv_count_gap", "acc_drop",
                                "support_mismatch"}
    assert cell.limits["support_mismatch"] == 0.0
    assert [m["name"] for m in cell.end_to_end] == ["train_s", "setup_s"]
    assert sorted(m["name"] for m in cell.per_layer) == sorted(
        [f"{m}.{CELL}" for m in READS] + [f"idle_share.{CELL}"])


def test_covtype_train_mix_runs_and_checks_correct(measure_cpu):
    res = measure_cpu(_cell(), seed=2_718_281_828_459)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["metrics"]["train_s"]["value"] > 0


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_covtype_train_check_catches_a_broken_timed_path(fault, monkeypatch,
                                                         measure_cpu):
    fault(monkeypatch)
    res = measure_cpu(_cell(), seed=2_718_281_828_459)
    assert res["correct"] is False, res["checks"]


def test_every_model_trains_seven_dual_columns():
    from repro import obs

    cell = _cell()
    driver = cell.driver()
    run = bench_run.Run(cell, 31_415_926_535, cell.data())
    driver.setup(run)
    win = driver.window(run, 0.0)
    n = len(win["record"]["models"])
    roots = obs.recent_roots(n, name="hss.fit")
    assert len(roots) == n >= 1
    assert all(r.counters["hss.dual_columns"] == 7 for r in roots)


# seconds of each span of one model, and its jit counters
STAGES = {"hss.tree": 0.15, "hss.far_proxies": 0.01,
          "hss.near_search": 0.2, "hss.compress.levels": 0.4,
          "hss.factorize": 0.12, "hss.admm": 0.4, "hss.predict": 0.04}
COUNTS = {"jit.compiles": 1, "jit.cache_reads": 2, "jit.traces": 600}
MODEL_S = sum(STAGES.values())
READS = {
    "tree_s": STAGES["hss.tree"],
    "compress_s": sum(STAGES[s] for s in ("hss.far_proxies",
                                          "hss.near_search",
                                          "hss.compress.levels")),
    "factorize_s": STAGES["hss.factorize"],
    "admm_s": STAGES["hss.admm"],
    "predict_s": STAGES["hss.predict"],
    "near_search_s": STAGES["hss.near_search"],
    "jit_compiles": 3.0,
    "jit_traces": 600.0,
}


def _train_model(rec, clock, scale=1.0):
    """One model's span tree, as ``fit_svm_grid`` records it."""
    def stage(name):
        with rec.span(name):
            clock.advance(STAGES[name] * scale)

    with rec.span("hss.fit"):
        with rec.span("hss.prepare"):
            stage("hss.tree")
            with rec.span("hss.compress"):
                stage("hss.far_proxies")
                stage("hss.near_search")
                stage("hss.compress.levels")
            stage("hss.factorize")
        with rec.span("hss.train"):
            rec.count("hss.dual_columns", 7)
            stage("hss.admm")
            for name, n in COUNTS.items():
                rec.count(name, n)
        stage("hss.predict")


def _reader(metric):
    return bench_run.find_cell(CELL).metric_reader(f"{metric}.{CELL}")


def _window(n, scale=1.0):
    return {"models": [{"model_s": MODEL_S * scale} for _ in range(n)]}


@pytest.mark.parametrize("metric", sorted(READS))
def test_covtype_reader_averages_the_window_models(metric, recorder):  # noqa: F811
    rec, clock = recorder
    _train_model(rec, clock, scale=3.0)       # set-up's model, not read
    for _ in range(3):
        _train_model(rec, clock)
    assert _reader(metric).read(_window(3)) == pytest.approx(READS[metric])
    # roots that are not the window's models are not read
    assert _reader(metric).read(_window(3, scale=1.2)) is None


@pytest.mark.parametrize("metric", sorted(READS))
def test_covtype_reader_reads_nothing_from_a_program_without_the_recorder(
        metric, monkeypatch):
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _reader(metric).read(_window(1)) is None
    assert _reader(metric).read({"models": []}) is None


def test_covtype_idle_share_reads_the_trace():
    red = types.SimpleNamespace(window_s=2.0, idle_share=0.9)
    assert _reader("idle_share").read({"trace": red}) == pytest.approx(90.0)
    assert _reader("idle_share").read({"models": []}) is None
