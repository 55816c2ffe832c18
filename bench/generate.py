"""The one traffic generator: a mix's data file -> the operations of a run.

A mix (``bench/traffic/<mix>.json``) is parameters only:

- ``"loop": "open"``: independent clients.  ``rate_per_s`` reads a second
  arrive on a Poisson schedule whatever the server does; ``read_queries``
  gives their sizes (``lo``..``hi`` queries, log-uniform, rounded to a
  multiple of ``quantum``); ``update_share`` of all operations are dataset
  updates of ``update.inserts`` new points and ``update.deletes`` deletions
  of live points; ``server`` holds the server's batch limits.
- ``"loop": "closed"``: one client that sends its next call of
  ``call_queries`` random queries when the last one returns.

Every seed gets the same work: the same number of reads and updates, the
same multiset of sizes and of inter-arrival gaps (quantiles of their
distributions), in one order, the mix's own: ``schedule_seed`` draws when
each operation is due, which are updates and how large each read is, as a
recorded arrival trace is replayed.  The run's seed draws what the
operations hold: every query's position and every update's points.  So
runs with different seeds differ in their data and not in how much work
they hold or how it bunches, which sets the queue's tail.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from bench import data


def load_mix(root: Path, name: str) -> dict:
    path = root / "traffic" / f"{name}.json"
    mix = json.loads(path.read_text())
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    return mix


@dataclass
class Op:
    """One open-loop operation, due ``t`` seconds after the window opens."""

    t: float
    kind: str                          # "read" or "update"
    queries: np.ndarray | None = None  # read: (n, 2)
    inserts: np.ndarray | None = None  # update: (u, 3)
    deletes: np.ndarray | None = None  # update: indices of live points


def read_sizes(spec: dict, count: int, gen: np.random.Generator) -> np.ndarray:
    """``count`` request sizes: log-uniform quantiles over ``lo``..``hi``,
    rounded to ``quantum``, in an order drawn from ``gen``."""
    lo, hi, quantum = spec["lo"], spec["hi"], spec["quantum"]
    u = (np.arange(count) + 0.5) / count
    sizes = np.rint(lo * (hi / lo) ** u / quantum).astype(int) * quantum
    return gen.permutation(np.clip(sizes, lo, hi))


def arrivals(count: int, rate: float, gen: np.random.Generator) -> np.ndarray:
    """Poisson arrival times (s): exponential-gap quantiles at ``rate`` a
    second, in an order drawn from ``gen``; the first is due at 0."""
    u = (np.arange(count) + 0.5) / count
    gaps = gen.permutation(-np.log1p(-u) / rate)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def open_loop(mix: dict, seconds: float, n_points: int,
              gen: np.random.Generator) -> list[Op]:
    """The operations of one open-loop window of ``seconds``: their schedule
    from the mix's ``schedule_seed``, their contents from ``gen``."""
    share = mix.get("update_share", 0.0)
    n_reads = max(1, round(mix["rate_per_s"] * seconds))
    n_updates = round(n_reads * share / (1.0 - share))
    sched = data.rng(mix["schedule_seed"], 0)
    kinds = sched.permutation(["read"] * n_reads + ["update"] * n_updates)
    times = arrivals(len(kinds), mix["rate_per_s"] / (1.0 - share), sched)
    sizes = iter(read_sizes(mix["read_queries"], n_reads, sched))
    ops, m = [], n_points
    for t, kind in zip(times, kinds):
        if kind == "read":
            ops.append(Op(float(t), "read",
                          queries=data.queries(int(next(sizes)), gen)))
        else:
            u = mix["update"]
            ops.append(Op(float(t), "update",
                          inserts=data.inserts(u["inserts"], gen),
                          deletes=gen.choice(m, u["deletes"], replace=False)))
            m += u["inserts"] - u["deletes"]
    return ops


def batch_sizes(mix: dict) -> list[int]:
    """Every batch size an open-loop mix can make the server dispatch: each
    multiple of ``quantum`` from the smallest read up to ``max_batch``."""
    q = mix["read_queries"]
    return list(range(q["lo"], mix["server"]["max_batch"] + 1, q["quantum"]))
