"""Factorization of a trained model: `FitReport.factorization_s`, mean over
the window's models."""


def read(rec: dict) -> float | None:
    ms = rec.get("models")
    if not ms:
        return None
    return sum(m["factorization_s"] for m in ms) / len(ms)
