"""Compression of a trained model, host NEAR search included:
`FitReport.compression_s`, mean over the window's models."""


def read(rec: dict) -> float | None:
    ms = rec.get("models")
    if not ms:
        return None
    return sum(m["compression_s"] for m in ms) / len(ms)
