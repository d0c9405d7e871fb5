"""The C-sweep driver (covtype.sweep, not yet a cell of BENCHMARK.json) at
a size a test run holds: a prepared engine's C sweep checked against the
plain reference; then the timed path broken."""
import pytest

from bench.tests.test_bench_train import (
    _answer_altered, _half_batch, _state_unchanged,
)


def test_sweep_mix_runs_and_checks_correct(tiny_cell, measure_cpu):
    res = measure_cpu(tiny_cell("covtype.sweep"))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] % 3 == 0 and res["failed"] == 0
    assert res["metrics"] == {}   # no end-to-end metric names it yet


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_sweep_check_catches_a_broken_timed_path(fault, monkeypatch,
                                                 tiny_cell, measure_cpu):
    fault(monkeypatch)
    res = measure_cpu(tiny_cell("covtype.sweep"))
    assert res["correct"] is False, res["checks"]
