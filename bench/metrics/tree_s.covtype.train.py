"""Cluster tree of a trained model on the host (the order of its rows and
the perfect tree over it): the ``hss.tree`` span, mean over the window's
models."""
from bench.metrics._spans import per_model


def read(rec: dict) -> float | None:
    return per_model(rec, lambda t: t.seconds.get("hss.tree"))
