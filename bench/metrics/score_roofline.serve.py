"""Share of its roofline that the serving scorer reaches, in percent: the
least time the window's scoring work could take on this chip, over the
device time of the scorer's module (``_score_entry``) in the trace.

The work is counted by ``bench.roofline.score_work`` from the window's
counters: the rows scored (``stats()['queries']``, padding left out) and
the launches, each of which must read the support set once.  The least
time is the larger of flops over peak FLOP/s and bytes over HBM bandwidth,
taken over the window's totals; the bound that applies is printed.
"""
import sys

from bench import roofline

MODULE = "_score_entry"


def read(rec: dict) -> float | None:
    red = rec.get("trace")
    if red is None or not rec.get("launches"):
        return None
    device_s = sum(s for name, s in red.module_s.items() if MODULE in name)
    if device_s <= 0:
        return None
    flops, nbytes = roofline.score_work(
        rec["queries"], rec["launches"], rec["support_rows"],
        rec["features"], rec["columns"])
    least, bound = roofline.least_time(flops, nbytes, rec["peaks"])
    print(f"score_roofline.serve: {flops:.4g} flops, {nbytes:.4g} bytes, "
          f"least {least:.6g} s ({bound} bound), device {device_s:.6g} s",
          file=sys.stderr, flush=True)
    return 100.0 * least / device_s
