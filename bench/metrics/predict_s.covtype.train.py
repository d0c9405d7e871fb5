"""Holdout scoring of a trained model, the argmax over its one-vs-rest
columns included, up to the accuracy's host read: the ``hss.predict`` span,
mean over the window's models."""
from bench.metrics._spans import per_model


def read(rec: dict) -> float | None:
    return per_model(rec, lambda t: t.seconds.get("hss.predict"))
