"""The comparison that decides ``correct``: the timed path's answers against
the plain reference (``bench/reference.py``) on a sample drawn from the seed.

Each number compared has its own limit, from the configuration's
``limits``; ``PERF.md`` gives the readings each limit was set from.
"""

from __future__ import annotations

import numpy as np

from bench import reference


def _max(a: np.ndarray) -> float:
    """Largest entry; a NaN anywhere reads as infinitely wrong."""
    a = np.asarray(a, np.float64)
    return float("inf") if np.isnan(a).any() else float(a.max(initial=0.0))


def gaps(got: dict, ref: dict) -> dict:
    """Worst gaps of one sample: values (absolute), and where the path
    returns them, r_obs (relative) and alpha (absolute)."""
    out = {"values_abs": _max(np.abs(got["values"] - ref["values"]))}
    if "r_obs" in got:
        out["r_obs_rel"] = _max(np.abs(got["r_obs"] - ref["r_obs"])
                                / ref["r_obs"])
        out["alpha_abs"] = _max(np.abs(got["alpha"] - ref["alpha"]))
    return out


def worst(samples: list[dict]) -> dict:
    """Per-number maximum over several samples' :func:`gaps`."""
    out: dict = {}
    for s in samples:
        for k, v in s.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and ``{name: {"value", "limit"}}`` for every number; a
    number without a limit of its own is an error, not a pass."""
    table = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return all(t["value"] <= t["limit"] for t in table.values()), table


def epochs(points: np.ndarray, updates: list[dict]) -> list[np.ndarray]:
    """The data set after each of ``updates`` in order (index 0: before
    any): deletes index the live points, inserts append, as the service's
    update contract states."""
    out = [points]
    for u in updates:
        keep = np.ones(out[-1].shape[0], bool)
        keep[u["deletes"]] = False
        out.append(np.concatenate([out[-1][keep], u["inserts"]]))
    return out


def reference_for(points, queries, config: dict, area: float,
                  dtype=None) -> dict:
    """The reference's answers for ``queries`` on the data set ``points``."""
    kw = {} if dtype is None else {"dtype": dtype}
    return reference.aidw(points, queries, area=area, k=config["k"],
                          local=config["stage2"] == "local", **kw)
