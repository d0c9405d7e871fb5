"""Share of the traced window in which no operation ran on the device, in
percent: 100 (1 - busy / window), busy being the union of the device's op
intervals (bench/trace.py)."""


def read(rec: dict) -> float | None:
    red = rec.get("trace")
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * red.idle_share
