"""Compression of a trained model less its host index searches: the
``hss.compress`` span less its ``hss.near_search`` and ``hss.far_proxies``
children, i.e. the leaf and level dispatch, the shrink and the wait for
the device; mean over the window's models."""
from bench.metrics._spans import per_model


def _build(t) -> float | None:
    if "hss.compress" not in t.seconds:
        return None
    return (t.seconds["hss.compress"] - t.seconds.get("hss.near_search", 0.0)
            - t.seconds.get("hss.far_proxies", 0.0))


def read(rec: dict) -> float | None:
    return per_model(rec, _build)
