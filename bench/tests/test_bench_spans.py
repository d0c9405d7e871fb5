"""The readers of the program's span recorder (``bench/metrics/_spans.py``
and the six metrics on it), fed a recorder on a clock of the test's own."""
import sys

import pytest

from bench import run as bench_run

# seconds of each stage of one model, and its jit counters
STAGES = dict(far=0.1, near=4.0, build=0.5, factorize=0.2, admm=0.05,
              predict=0.02)
COUNTS = {"jit.compiles": 1, "jit.cache_reads": 2, "jit.traces": 40}
MODEL_S = sum(STAGES.values())

EXPECTED = {
    "near_search_s.train": STAGES["near"],
    "compress_build_s.train": STAGES["build"],
    "admm_s.train": STAGES["admm"],
    "predict_s.train": STAGES["predict"],
    "jit_compiles.train": 3.0,
    "jit_traces.train": 40.0,
}


class Clock:
    def __init__(self):
        self.ns = 0

    def __call__(self) -> int:
        return self.ns

    def advance(self, seconds: float) -> None:
        self.ns += round(seconds * 1e9)


def train_model(rec, clock, scale=1.0):
    """One model's span tree, as ``fit_svm_grid`` records it."""
    def stage(name, key):
        with rec.span(name):
            clock.advance(STAGES[key] * scale)

    with rec.span("hss.fit"):
        with rec.span("hss.prepare"):
            with rec.span("hss.compress"):
                stage("hss.far_proxies", "far")
                stage("hss.near_search", "near")
                stage("hss.compress.levels", "build")
            stage("hss.factorize", "factorize")
        with rec.span("hss.train"):
            stage("hss.admm", "admm")
            for name, n in COUNTS.items():
                rec.count(name, n)
        stage("hss.predict", "predict")


@pytest.fixture
def recorder(monkeypatch):
    from repro import obs

    clock = Clock()
    rec = obs.Recorder(clock=clock)
    monkeypatch.setattr(obs, "recent_roots", rec.recent_roots)
    return rec, clock


def reader(name):
    return bench_run.load_module(bench_run.BENCH / "metrics" / f"{name}.py")


def window(n, scale=1.0):
    return {"models": [{"model_s": MODEL_S * scale} for _ in range(n)]}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_averages_the_window_models(name, recorder):
    rec, clock = recorder
    train_model(rec, clock, scale=3.0)        # set-up's model, not read
    for _ in range(3):
        train_model(rec, clock)
    assert reader(name).read(window(3)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_needs_a_root_per_model(name, recorder):
    rec, clock = recorder
    train_model(rec, clock)
    with rec.span("hss.prepare"):             # a root, but not a fit
        clock.advance(MODEL_S)
    assert reader(name).read(window(2)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_refuses_roots_that_are_not_the_window_models(name,
                                                              recorder):
    rec, clock = recorder
    train_model(rec, clock)
    train_model(rec, clock, scale=1.2)
    assert reader(name).read(window(2)) is None
    assert reader(name).read(window(1, scale=1.2)) is not None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_a_program_without_the_recorder(
        name, monkeypatch):
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert reader(name).read(window(1)) is None
    assert reader(name).read({"models": []}) is None
