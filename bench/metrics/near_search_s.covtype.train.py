"""NEAR-proxy search of a trained model (the k-NN program on the device
and the host's candidate selection): the ``hss.near_search`` span, mean
over the window's models."""
from bench.metrics._spans import per_model


def read(rec: dict) -> float | None:
    return per_model(rec, lambda t: t.seconds.get("hss.near_search"))
