"""Backend compiles and persistent-cache reads per trained model: the
recorder's ``jit.compiles`` + ``jit.cache_reads`` under ``hss.fit``, mean
over the window's models."""
from bench.metrics._spans import per_model


def _compiles(t) -> float:
    c = t.counters
    return float(c.get("jit.compiles", 0) + c.get("jit.cache_reads", 0))


def read(rec: dict) -> float | None:
    return per_model(rec, _compiles)
