"""The paper's data set: Mei, Xu & Xu (2016), section 5, data points uniform
at random in a square.  The first two points sit on its corners (0, 0) and
(1, 1), so every seed has the same bounding box: the grid the service plans
on it, whose extent and cell width are compiled into its programs, is then
the same for every seed.  No parameters."""

from __future__ import annotations

import numpy as np

from bench import data


def points(n: int, gen: np.random.Generator) -> np.ndarray:
    """(n, 3) float32 data points uniform in the unit square, the two
    corners first."""
    xy = gen.random((n, 2))
    xy[:2] = [[0.0, 0.0], [1.0, 1.0]]
    return np.concatenate([xy, data.surface(xy[:, 0], xy[:, 1])[:, None]],
                          axis=1).astype(np.float32)


def inserts(n: int, gen: np.random.Generator) -> np.ndarray:
    """(n, 3) float32 new data points uniform in [0.01, 0.99]^2."""
    xy = data.inner(gen.random((n, 2)))
    return np.concatenate([xy, data.surface(xy[:, 0], xy[:, 1])[:, None]],
                          axis=1).astype(np.float32)
