"""bench/trace.py: busy union, idle share, device time per module, top ops
and idle gaps named by the host."""
import pytest

from bench import trace


def test_union_clips_and_merges_overlaps():
    iv = [(0, 10), (5, 15), (20, 30), (28, 29), (40, 50)]
    assert trace.union_length(iv, 0, 100) == 15 + 10 + 10
    assert trace.union_length(iv, 8, 45) == 7 + 10 + 5
    assert trace.union_length([], 0, 10) == 0


def test_gaps_are_the_uncovered_stretches():
    iv = [(5, 15), (0, 10), (20, 30)]
    assert trace.gaps(iv, 0, 40) == [(15, 20), (30, 40)]
    assert trace.gaps(iv, -5, 12) == [(-5, 0)]


def test_module_name_drops_the_runtime_id():
    assert trace.module_name("jit__score_entry(1234)") == "jit__score_entry"
    assert trace.module_name("jit_foo") == "jit_foo"


def test_op_names_are_cut_at_the_hlo_text():
    assert trace.op_name("%fusion.5 = f32[256]{0} fusion(f32[256,18] %a)") \
        == "fusion.5"
    assert trace.op_name('%custom-call.6 = f32[8,8] custom-call(%x), '
                         'custom_call_target="Cholesky"') \
        == "custom-call.6:Cholesky"


def test_reduce_synthetic_events():
    ev = trace.Events(
        ops=[("fusion.1", 100, 300), ("fusion.2", 250, 400),
             ("dot", 600, 700)],
        modules=[("jit_a", 100, 400), ("jit_b", 600, 700),
                 ("jit_a", 900, 1200)],
        host=[("bench_window", 0, 1000), ("tick", 400, 600),
              ("decode", 450, 500), ("wait", 700, 1000)],
        n_devices=1)
    lo, hi = trace.window_bounds(ev, "bench_window")
    assert (lo, hi) == (0, 1000)
    red = trace.reduce_events(ev, lo, hi, unnamed=("bench_window",))
    assert red.window_s == pytest.approx(1e-6)
    assert red.busy_s == pytest.approx(400e-9)
    assert red.idle_share == pytest.approx(0.6)
    assert red.module_s["jit_a"] == pytest.approx(400e-9)
    assert red.module_calls == {"jit_a": 2, "jit_b": 1}
    assert [n for n, _ in red.top_ops] == ["jit_a/fusion.1",
                                           "jit_a/fusion.2", "jit_b/dot"]
    # longest gap first, named by the host event covering most of it
    assert red.idle_gaps[0] == ["wait", pytest.approx(300e-9)]
    assert red.idle_gaps[1][0] == "tick"
    assert red.idle_gaps[2] == ["no host event", pytest.approx(100e-9)]


def test_a_trace_without_device_work_is_refused():
    ev = trace.Events([], [], [("bench_window", 0, 10)], 0)
    with pytest.raises(ValueError):
        trace.reduce_events(ev, 0, 10)


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: four scorer launches (buckets 64,
    4096, 64, 1024 against 262,144 support rows), one jitted matmul and one
    eager add."""
    from pathlib import Path

    path = Path(__file__).parent / "data" / "serve_probe.xplane.pb"
    ev = trace.read_events(str(path))
    assert ev.n_devices == 1
    lo = min(s for _, s, _ in ev.ops)
    hi = max(e for _, _, e in ev.ops)
    red = trace.reduce_events(ev, lo, hi)
    assert red.module_calls == {"jit__score_entry": 4, "jit_foo_named": 1,
                                "jit_add": 1}
    assert red.module_s["jit__score_entry"] == pytest.approx(2.530169e-3)
    assert sum(red.module_s.values()) * 0.95 <= red.busy_s \
        <= sum(red.module_s.values())
    assert 0.9 < red.idle_share < 1.0
    assert red.top_ops[0][0].startswith("jit__score_entry/")
    assert len(red.idle_gaps) == 10
