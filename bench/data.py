"""Data of a deployment, made from the seed: the paper's test protocol.

Mei, Xu & Xu (2016), section 5: data points uniform at random in a square,
as many interpolated points.  The values come from a smooth analytic
surface, and every query and insert lies in ``[0.01, 0.99]^2``, inside the
data's bounding box, so the grid planned on the data is the study area of
the system and of the reference alike.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose; any integer seed, negative or
    wider than 32 bits, is taken modulo 2**63."""
    return np.random.default_rng([seed % 2 ** 63, stream])


def surface(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """The analytic terrain z(x, y) of every data point."""
    return (np.sin(3.1 * x) * np.cos(2.3 * y)
            + 0.5 * np.sin(7.9 * x * y) + 0.1 * x - 0.2 * y)


def points(n: int, gen: np.random.Generator) -> np.ndarray:
    """(n, 3) float32 data points uniform in the unit square.  The first two
    sit on its corners (0, 0) and (1, 1), so every seed has the same
    bounding box: the grid the service plans on it, whose extent and cell
    width are compiled into its programs, is then the same for every seed."""
    xy = gen.random((n, 2))
    xy[:2] = [[0.0, 0.0], [1.0, 1.0]]
    return np.concatenate([xy, surface(xy[:, 0], xy[:, 1])[:, None]],
                          axis=1).astype(np.float32)


def inner(xy: np.ndarray) -> np.ndarray:
    """Map unit-square coordinates into [0.01, 0.99]^2."""
    return (0.01 + 0.98 * xy).astype(np.float32)


def queries(n: int, gen: np.random.Generator) -> np.ndarray:
    """(n, 2) float32 query points inside the data's bounding box."""
    return inner(gen.random((n, 2)))


def inserts(n: int, gen: np.random.Generator) -> np.ndarray:
    """(n, 3) float32 new data points inside the bounding box."""
    xy = inner(gen.random((n, 2)))
    return np.concatenate([xy, surface(xy[:, 0], xy[:, 1])[:, None]],
                          axis=1).astype(np.float32)
