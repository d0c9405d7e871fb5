"""Functions traced to a jaxpr per trained model: the recorder's
``jit.traces`` under ``hss.fit``, mean over the window's models."""
from bench.metrics._spans import per_model


def read(rec: dict) -> float | None:
    return per_model(rec, lambda t: float(t.counters.get("jit.traces", 0)))
