"""Rows of the LIBSVM "SUSY" shape: 18 dense features, +-1 labels, half
signal and half background.

Signal rows are correlated Gaussians, background rows broader and shifted,
so the classes overlap partly (holdout accuracy ~0.96 with the Gaussian
kernel at h = 3).  The same draw as the program's ``susy_like`` generator,
kept here so that the benchmark's inputs do not move when the program's
own generators change.
"""
from __future__ import annotations

import numpy as np

N_FEATURES = 18


def generate(n: int, key: tuple) -> tuple[np.ndarray, np.ndarray]:
    """(x, y): n rows from the random stream named by the integers ``key``."""
    r = np.random.default_rng([int(k) for k in key])
    half = n // 2
    cov = 0.6 * np.eye(N_FEATURES) + 0.4
    la = np.linalg.cholesky(cov)
    xa = r.normal(size=(half, N_FEATURES)) @ la.T
    xb = 1.4 * r.normal(size=(n - half, N_FEATURES)) + 0.8
    x = np.concatenate([xa, xb]).astype(np.float32)
    y = np.concatenate([np.ones(half), -np.ones(n - half)]).astype(np.float32)
    p = r.permutation(n)
    return x[p], y[p]
