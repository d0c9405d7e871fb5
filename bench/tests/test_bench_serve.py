"""susy.serve at a size a test run holds: open-loop requests through the
serving engine's ticks, checked against the plain reference; then the
timed path broken.  A tick has no state to leave unchanged, and one chip
has no exchange between chips, so those faults do not apply here."""
import numpy as np
import pytest

from bench.drivers import open_loop


def test_schedule_is_one_multiset_reordered_by_the_seed(tiny_cell):
    tr = tiny_cell("susy.serve").traffic
    d1, s1 = open_loop._schedule(tr, 1, 20.0)
    d2, s2 = open_loop._schedule(tr, 2**33 + 5, 20.0)
    assert s1.shape == (int(tr["requests_per_s"] * 20),)
    assert not np.array_equal(s1, s2)
    assert np.array_equal(np.sort(s1), np.sort(s2))
    g1, g2 = (np.sort(np.diff(d, prepend=0.0)) for d in (d1, d2))
    # one gap of the fixed multiset falls after the close, which one is
    # the seed's draw: every other gap is common to both
    assert np.isclose(g1[:, None], g2[None, :], rtol=1e-9).any(1).sum() \
        >= g1.size - 1
    assert s1.min() >= tr["rows_min"] and s1.max() <= tr["rows_max"]
    assert np.all(np.diff(d1) >= 0) and d1[-1] < 20.0


def _half_batch(monkeypatch):
    from repro.serve import engine

    real = engine.batched_scores

    def half(xq, xs, zy, biases, **kw):
        import jax.numpy as jnp

        n = max(xq.shape[0] // 2, 1)
        top = real(xq[:n], xs, zy, biases, **kw)
        rest = jnp.broadcast_to(top.mean(axis=0, keepdims=True),
                                (xq.shape[0] - n, top.shape[1]))
        return jnp.concatenate([top, rest])
    monkeypatch.setattr(engine, "batched_scores", half)


def _answer_altered(monkeypatch):
    from repro.serve import engine

    real = engine.decode_predictions

    def altered(scores, **kw):
        vals, preds = real(scores, **kw)
        return -vals, -preds
    monkeypatch.setattr(engine, "decode_predictions", altered)


@pytest.mark.parametrize("fault", [_half_batch, _answer_altered],
                         ids=["half_batch", "answer_altered"])
def test_serve_check_catches_a_broken_timed_path(fault, monkeypatch,
                                                 tiny_cell, measure_cpu):
    fault(monkeypatch)
    res = measure_cpu(tiny_cell("susy.serve"))
    assert res["correct"] is False, res["checks"]
