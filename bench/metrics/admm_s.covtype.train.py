"""ADMM of a trained model's one-vs-rest columns up to its
``block_until_ready``, the fresh engine's trace and compile or cache read
included: the ``hss.admm`` span, mean over the window's models."""
from bench.metrics._spans import per_model


def read(rec: dict) -> float | None:
    return per_model(rec, lambda t: t.seconds.get("hss.admm"))
