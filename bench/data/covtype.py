"""Rows of the LIBSVM "covtype" shape: 54 features in [0, 1] (10 continuous,
then 4 wilderness-area and 40 soil-type one-hot columns) and 7 classes in
the published proportions of UCI Covertype.

Each class has its own mean for the continuous columns and its own
categorical distribution over wilderness areas and soil types.  Those
class profiles are fixed (drawn once from ``PROFILE_SEED``), so every run
serves the same deployment; ``key`` draws only the rows.
"""
from __future__ import annotations

import numpy as np

N_CONTINUOUS, N_WILDERNESS, N_SOIL = 10, 4, 40
N_FEATURES = N_CONTINUOUS + N_WILDERNESS + N_SOIL
CLASS_COUNTS = np.array([211_840, 283_301, 35_754, 2_747, 9_493, 17_367,
                         20_510])
CLASSES = np.arange(1, 8)
PROFILE_SEED = 581_012
CONTINUOUS_STD = 0.2


def _profiles():
    r = np.random.default_rng(PROFILE_SEED)
    k = CLASSES.shape[0]
    means = r.uniform(0.3, 0.7, size=(k, N_CONTINUOUS))
    wild = r.dirichlet(np.full(N_WILDERNESS, 1.0), size=k)
    soil = r.dirichlet(np.full(N_SOIL, 0.5), size=k)
    return means, wild, soil


def generate(n: int, key: tuple) -> tuple[np.ndarray, np.ndarray]:
    """(x, y): n rows from the random stream named by the integers ``key``;
    labels are the class numbers 1..7."""
    means, wild, soil = _profiles()
    r = np.random.default_rng([int(k) for k in key])
    cls = r.choice(CLASSES.shape[0], size=n,
                   p=CLASS_COUNTS / CLASS_COUNTS.sum())
    x = np.zeros((n, N_FEATURES), np.float32)
    x[:, :N_CONTINUOUS] = np.clip(
        means[cls] + CONTINUOUS_STD * r.normal(size=(n, N_CONTINUOUS)), 0, 1)
    u = r.uniform(size=(n, 1))
    w = (u > np.cumsum(wild[cls], axis=1)).sum(axis=1)
    s = (r.uniform(size=(n, 1)) > np.cumsum(soil[cls], axis=1)).sum(axis=1)
    x[np.arange(n), N_CONTINUOUS + np.minimum(w, N_WILDERNESS - 1)] = 1.0
    x[np.arange(n), N_CONTINUOUS + N_WILDERNESS
      + np.minimum(s, N_SOIL - 1)] = 1.0
    return x, CLASSES[cls].astype(np.float32)
