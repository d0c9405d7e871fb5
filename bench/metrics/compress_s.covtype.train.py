"""Compression of a trained model, its NEAR search and FAR proxies
included: the ``hss.compress`` span, mean over the window's models."""
from bench.metrics._spans import per_model


def read(rec: dict) -> float | None:
    return per_model(rec, lambda t: t.seconds.get("hss.compress"))
