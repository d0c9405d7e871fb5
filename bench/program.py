"""The system under test, as the drivers call it: the launch driver's engine
for a configuration file, checked against what the file states."""
from __future__ import annotations

import numpy as np

SV_TOL = 1e-6          # a dual coefficient above this is a support vector


def build_engine(config: dict):
    """``repro.launch.train.build_svm_engine`` for ``config``; raises where
    the engine it builds differs from the configuration's preset."""
    from repro.launch.train import build_svm_engine

    comp = config["compression"]
    eng = build_svm_engine(config["task"], config["h"], comp["rank"],
                           config["leaf_size"])
    got = dict(kernel=eng.spec.name, rank=eng.comp.rank,
               n_near=eng.comp.n_near, n_far=eng.comp.n_far,
               max_it=eng.max_it, leaf_size=eng.leaf_size,
               strategy=eng.strategy, beta=eng.beta)
    want = dict(kernel=config["kernel"], rank=comp["rank"],
                n_near=comp["n_near"], n_far=comp["n_far"],
                max_it=config["max_it"], leaf_size=config["leaf_size"],
                strategy=config.get("strategy", "ovr"), beta=None)
    if got != want:
        raise ValueError(f"the launch driver's engine {got} is not the "
                         f"configuration's preset {want}")
    return eng


def support_counts(zy) -> np.ndarray:
    """Support vectors per problem column of a (d, P) coefficient block."""
    zy = np.asarray(zy)
    if zy.ndim == 1:
        zy = zy[:, None]
    return np.sum(np.abs(zy) > SV_TOL, axis=0)


def count_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Support vectors the program has too many or too few, summed over the
    problem columns, as a share of the reference's total."""
    return float(np.sum(np.abs(prog - ref)) / max(np.sum(ref), 1))


def support_mismatch(x_perm, x_train: np.ndarray) -> float:
    """Share of the training rows that are not rows of the model's support
    set (its padded, permuted training points, pads left out)."""
    xp = np.asarray(x_perm)
    real = xp[xp[:, 0] <= x_train[:, 0].max()]
    if real.shape[0] != x_train.shape[0]:
        return 1.0
    a = real[np.lexsort(real.T[::-1])]
    b = x_train[np.lexsort(x_train.T[::-1])]
    return float(np.mean(np.any(a != b, axis=1)))
