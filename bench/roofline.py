"""Operations and bytes of the algorithms the per-layer rooflines read,
counted from shapes: the work the algorithm needs, not what an
implementation happens to do (padding, materialized intermediates)."""
from __future__ import annotations


def score_work(rows: int, launches: int, n_support: int, features: int,
               columns: int) -> tuple[float, float]:
    """(flops, bytes) of scoring ``rows`` query rows in ``launches`` launches
    against a Gaussian-kernel model of ``n_support`` rows of ``features``
    features and ``columns`` coefficient columns, in float32.

    Per kernel entry: the cross term x.s (2 f flops), the squared distance
    from it and the two norms (3), the exp (1), and the coefficient product
    (2 P).  Per launch the support rows and coefficients are read once; per
    row the query is read and its P scores written.
    """
    f, p, n = features, columns, n_support
    flops = float(rows) * n * (2 * f + 4 + 2 * p)
    nbytes = 4.0 * (launches * n * (f + p) + rows * (f + p))
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict
               ) -> tuple[float, str]:
    """The least time the chip could take, and the bound that sets it."""
    t_c = flops / peaks["flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
