"""Data and queries of a deployment, made from the seed, by files found by
name.

A configuration names its data set, ``"data": {"name": <set>, ...}``, made
by ``bench/datasets/<set>.py``; a traffic mix names its query pattern,
``"queries": {"name": <pattern>, ...}``, made by
``bench/queries/<pattern>.py``.  Every other key of either object is a
parameter of that file's functions.  The contract of each file is in the
docstring of ``bench/run.py``.  What the data sets share is here: the
analytic surface that gives every data point its value, and the map into
``[0.01, 0.99]^2``, inside the data's bounding box, where queries and
inserts lie so that the grid planned on the data is the study area of the
system and of the reference alike.
"""

from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose; any integer seed, negative or
    wider than 32 bits, is taken modulo 2**63."""
    return np.random.default_rng([seed % 2 ** 63, stream])


def surface(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """The analytic terrain z(x, y) of every data point."""
    return (np.sin(3.1 * x) * np.cos(2.3 * y)
            + 0.5 * np.sin(7.9 * x * y) + 0.1 * x - 0.2 * y)


def inner(xy: np.ndarray) -> np.ndarray:
    """Map unit-square coordinates into [0.01, 0.99]^2."""
    return (0.01 + 0.98 * xy).astype(np.float32)


def load(kind: str, name: str, bench: Path = BENCH):
    """The module ``bench/<kind>/<name>.py``: a metric reader, a data set
    or a query pattern.  A name with no file is an error."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _checked(points: np.ndarray, n: int, name: str) -> np.ndarray:
    """Refuse data that breaks the data-set contract: (n, 3) float32 in the
    unit square, with both of its corners, so that every seed has the same
    bounding box and so the same compiled grid."""
    xy = points[..., :2]
    if (points.shape != (n, 3) or points.dtype != np.float32
            or xy.min() < 0.0 or xy.max() > 1.0
            or not (xy == 0.0).all(axis=1).any()
            or not (xy == 1.0).all(axis=1).any()):
        raise ValueError(f"data set {name!r} breaks its contract: (n, 3) "
                         "float32 in the unit square, with (0, 0) and (1, 1)")
    return points


def data_set(spec: dict, bench: Path = BENCH):
    """``(points, inserts)`` of the data set that ``spec`` (a
    configuration's ``"data"``) names, each ``f(n, gen)`` with the set's
    parameters bound; ``points`` checks the contract of what it returns."""
    params = dict(spec)
    name = params.pop("name")
    module = load("datasets", name, bench)

    def points(n: int, gen: np.random.Generator) -> np.ndarray:
        return _checked(module.points(n, gen, **params), n, name)

    return points, functools.partial(module.inserts, **params)


def query_stream(spec: dict, gen: np.random.Generator, bench: Path = BENCH):
    """``next(n) -> (n, 2) float32`` of the query pattern that ``spec`` (a
    mix's ``"queries"``) names, drawing from ``gen``."""
    params = dict(spec)
    return load("queries", params.pop("name"), bench).stream(gen, **params)
