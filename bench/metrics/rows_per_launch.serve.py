"""Query rows per scorer launch over the window: the growth of
``ServingEngine.stats()['queries']`` over that of ``['launches']``."""


def read(rec: dict) -> float | None:
    if not rec.get("launches"):
        return None
    return rec["queries"] / rec["launches"]
