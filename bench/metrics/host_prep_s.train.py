"""Host prep of a trained model: the wall time of `prepare` (pad, cluster
tree, labels, compression, factorization) less the program's own
compression and factorization timers, mean over the window's models."""


def read(rec: dict) -> float | None:
    ms = rec.get("models")
    if not ms:
        return None
    return sum(m["prepare_s"] - m["compression_s"]
               - m["factorization_s"] for m in ms) / len(ms)
