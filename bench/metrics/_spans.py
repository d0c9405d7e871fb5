"""Per-model readings of the program's span recorder (``repro.obs``).

Each model the window trained is one ``hss.fit`` root of the recorder, and
the reading is the mean over the window's models.  It is None where the
program keeps no recorder, where fewer roots than models are kept, where a
root lacks what is read, or where a root's duration differs from its
model's ``model_s`` by more than 5%: both time the same ``fit_svm_grid``
call, so then the roots are not the window's models.
"""
from __future__ import annotations

MATCH = 0.05


def per_model(rec: dict, quantity) -> float | None:
    """Mean over the window's models of ``quantity(totals)``, where
    ``totals`` is the model's ``repro.obs.RootTotals``; None where the
    quantity is None for any model."""
    models = rec.get("models")
    if not models:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    roots = obs.recent_roots(len(models), name="hss.fit")
    if len(roots) < len(models):
        return None
    for r, m in zip(roots, models):
        if abs(r.root.seconds - m["model_s"]) > MATCH * m["model_s"]:
            return None
    values = [quantity(r) for r in roots]
    if None in values:
        return None
    return sum(values) / len(values)
