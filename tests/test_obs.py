"""The span recorder (``repro.obs``) on a tiny fit through the launch
driver's path: the span tree, its timers against ``FitReport``, the jit
counters, per-thread nesting, and the spans on the profiler's host plane."""
import threading

import pytest

from repro import obs
from repro.data import synthetic
from repro.launch.train import build_svm_engine, fit_svm_grid

# span -> its parent, for one fit with one knob value
TREE = {
    "hss.fit": None,
    "hss.prepare": "hss.fit",
    "hss.pad": "hss.prepare",
    "hss.tree": "hss.prepare",
    "hss.labels": "hss.prepare",
    "hss.compress": "hss.prepare",
    "hss.far_proxies": "hss.compress",
    "hss.near_search": "hss.compress",
    "hss.near_search.knn": "hss.near_search",
    "hss.near_search.select": "hss.near_search",
    "hss.compress.leaves": "hss.compress",
    "hss.compress.levels": "hss.compress",
    "hss.shrink": "hss.compress",
    "hss.compress.wait": "hss.compress",
    "hss.factorize": "hss.prepare",
    "hss.upload": "hss.prepare",
    "hss.train": "hss.fit",
    "hss.admm": "hss.train",
    "hss.bias": "hss.train",
    "hss.predict": "hss.fit",
}


@pytest.fixture(scope="module")
def data():
    return synthetic.train_test("susy_like", 2048, 512, seed=0)


def _fit(data):
    engine = build_svm_engine("svm", 3.0, 16, 64)
    fit_svm_grid(engine, *data, [1.0], log=lambda _m: None)
    fit, = obs.recent_roots(1, name="hss.fit")
    return engine, fit


@pytest.fixture(scope="module")
def fitted(data):
    return _fit(data)


def test_every_stage_span_nests_under_one_fit(fitted):
    _, fit = fitted
    spans = list(fit.root.walk())
    by_id = {s.span_id: s for s in spans}
    got = {s.name: (by_id[s.parent_id].name if s.parent_id else None)
           for s in spans}
    assert got == TREE
    assert {s.trace_id for s in spans} == {fit.root.span_id}
    assert fit.root.attrs == {"rows": 2048, "features": 18, "knobs": 1}


def test_self_times_are_not_negative_and_spans_are_few(fitted):
    _, fit = fitted
    spans = list(fit.root.walk())
    assert len(spans) <= obs.MAX_ROOTS
    assert all(s.self_seconds >= 0 for s in spans)
    assert all(v >= 0 for v in fit.self_seconds.values())
    assert sum(fit.self_seconds.values()) == pytest.approx(fit.root.seconds)
    assert fit.counters["hss.kernel_evals"] > 0


def test_fit_report_timers_are_the_spans(fitted):
    engine, fit = fitted
    rep = engine.report
    assert rep.compression_s == fit.seconds["hss.compress"]
    assert rep.factorization_s == fit.seconds["hss.factorize"]
    assert rep.admm_s == fit.seconds["hss.admm"]
    assert rep.kernel_evals == fit.counters["hss.kernel_evals"]


def test_a_binary_fit_trains_one_dual_column(fitted):
    _, fit = fitted
    assert fit.counters["hss.dual_columns"] == 1


def test_a_seven_class_fit_trains_seven_dual_columns():
    from bench.data import covtype

    x, y = covtype.generate(2048 + 256, (3, 1))
    engine = build_svm_engine("svm", 0.5, 16, 256)
    fit_svm_grid(engine, x[:2048], y[:2048], x[2048:], y[2048:], [1.0, 2.0],
                 log=lambda _m: None)
    fit, = obs.recent_roots(1, name="hss.fit")
    assert fit.counters["hss.dual_columns"] == 14      # 7 columns, 2 knobs
    tree, = [s for s in fit.root.walk() if s.name == "hss.tree"]
    # the (wilderness, soil) pairs are split apart before any median split
    assert tree.attrs["split_onehot"] >= 20


def test_the_tree_span_counts_its_splits_by_column_kind(fitted):
    _, fit = fitted
    tree, = [s for s in fit.root.walk() if s.name == "hss.tree"]
    # 2,048 continuous rows in leaves of 64: 31 median splits, no 0/1 one
    assert tree.attrs == dict(split_onehot=0, split_continuous=31)


def test_a_fresh_engine_compiles_or_reads_the_cache(fitted, data):
    _, fit = _fit(data)
    c = fit.counters
    assert c.get("jit.compiles", 0) + c.get("jit.cache_reads", 0) >= 1
    assert c.get("jit.traces", 0) >= 1


def test_the_admm_program_is_traced_once_per_shape(fitted, data):
    """A second engine of the same shapes reuses the module-level ADMM
    program; an engine of another row count traces it once more."""
    _, again = _fit(data)
    assert again.counters.get("hss.admm.traces", 0) == 0
    admm, = [s for s in again.root.walk() if s.name == "hss.admm"]
    assert "jit.traces" not in admm.counters
    xtr, ytr, xte, yte = data
    engine = build_svm_engine("svm", 3.0, 16, 64)
    fit_svm_grid(engine, xtr[:1024], ytr[:1024], xte, yte, [1.0],
                 log=lambda _m: None)
    fit, = obs.recent_roots(1, name="hss.fit")
    assert fit.root.attrs["rows"] == 1024
    assert fit.counters["hss.admm.traces"] == 1


def test_the_local_build_searches_neighbours_on_the_device(fitted, data):
    _, fit = fitted
    assert fit.counters["hss.near_search.device"] == 1
    assert "hss.near_search.host" not in fit.counters
    # a second fresh engine reuses the module-level k-NN program
    _, again = _fit(data)
    knn, = [s for s in again.root.walk() if s.name == "hss.near_search.knn"]
    assert "jit.traces" not in knn.counters
    assert again.counters["hss.near_search.device"] == 1


def test_the_streamed_build_searches_neighbours_on_the_host(data):
    import numpy as np

    from repro.core import compression, tree as tree_mod
    from repro.core.kernelfn import KernelSpec

    x = np.asarray(data[0][:512])
    t = tree_mod.build_tree(x, leaf_size=64)
    with obs.span("test.streamed"):
        compression.compress_streamed(
            x[t.perm], t, KernelSpec(h=3.0),
            compression.CompressionParams(rank=8, n_near=16, n_far=16),
            stream=compression.StreamParams(batch_leaves=4))
    root, = obs.recent_roots(1, name="test.streamed")
    assert root.counters["hss.near_search.host"] == 1
    assert "hss.near_search.device" not in root.counters
    assert {"hss.near_search.kdtree", "hss.near_search.query",
            "hss.near_search.select"} <= set(root.seconds)


def test_a_thread_starts_its_own_root():
    rec = obs.Recorder()
    seen = {}

    def work():
        with rec.span("tick") as s:
            rec.count("ticks")
        seen["tick"] = s

    with rec.span("fit") as fit:
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        rec.count("fits")
    tick = seen["tick"]
    assert tick.parent_id is None and tick.trace_id == tick.span_id
    assert fit.children == []
    assert fit.counters == {"fits": 1} and tick.counters == {"ticks": 1}
    assert [r.root.name for r in rec.recent_roots(5)] == ["tick", "fit"]
    assert rec.total("ticks") == rec.total("fits") == 1


def test_recent_roots_keeps_the_last_roots_on_its_clock():
    clock = iter(range(0, 10_000_000, 10))
    rec = obs.Recorder(clock=lambda: next(clock))
    names = [f"r{i}" for i in range(obs.MAX_ROOTS + 2)]
    for name in names:
        with rec.span(name):
            with rec.span(name + ".child"):
                pass
    roots = rec.recent_roots(obs.MAX_ROOTS + 5)
    assert [r.root.name for r in roots] == names[2:]
    last = roots[-1]
    assert last.seconds == {names[-1]: 30e-9, names[-1] + ".child": 10e-9}
    assert last.self_seconds == {names[-1]: 20e-9,
                                 names[-1] + ".child": 10e-9}
    assert rec.recent_roots(1, name="r5")[0].root.name == "r5"
    assert rec.recent_roots(0) == []


def test_stage_spans_sit_on_the_profiler_host_plane(data, tmp_path):
    import jax

    from bench import trace

    with jax.profiler.trace(str(tmp_path)):
        _fit(data)
    ev = trace.read_events(trace.newest_xplane(str(tmp_path)))
    names = {name for name, _, _ in ev.host}
    assert {"hss.fit", "hss.compress", "hss.near_search",
            "hss.factorize"} <= names
