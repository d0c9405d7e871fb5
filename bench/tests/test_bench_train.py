"""susy.train at a size a test run holds: the mix through the launch
driver's path, checked against the plain reference; then the same run
with the timed path broken underneath, which the check must catch."""
import numpy as np
import pytest


def test_train_mix_runs_and_checks_correct(tiny_cell, measure_cpu):
    res = measure_cpu(tiny_cell("susy.train"))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["metrics"]["train_s"]["value"] > 0


def _state_unchanged(monkeypatch):
    from repro.core import admm

    real = admm.admm_boxqp

    def stuck(solver_mat, task, beta, max_it=10, tol=None, z0=None,
              mu0=None, **kw):
        state, trace = real(solver_mat, task, beta, max_it, tol=tol, z0=z0,
                            mu0=mu0, **kw)
        keep = np.zeros(state.z.shape, np.float32) if z0 is None else z0
        return state._replace(z=keep + 0 * state.z), trace
    monkeypatch.setattr(admm, "admm_boxqp", stuck)


def _half_batch(monkeypatch):
    from repro.core.engine import HSSSVMEngine

    real = HSSSVMEngine.prepare

    def half(self, x, y=None):
        n = x.shape[0] // 2
        return real(self, x[:n], None if y is None else y[:n])
    monkeypatch.setattr(HSSSVMEngine, "prepare", half)


def _answer_altered(monkeypatch):
    from repro.core.engine import EngineModel

    real = EngineModel.predict

    def altered(self, x_test, block=2048):
        pred = real(self, x_test, block=block)
        return pred.at[: pred.shape[0] // 10].multiply(-1)
    monkeypatch.setattr(EngineModel, "predict", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_train_check_catches_a_broken_timed_path(fault, monkeypatch,
                                                 tiny_cell, measure_cpu):
    fault(monkeypatch)
    res = measure_cpu(tiny_cell("susy.train"))
    assert res["correct"] is False, res["checks"]
