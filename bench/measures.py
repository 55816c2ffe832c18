"""Arithmetic the metric readers share: percentiles over all requests, the
rate over a whole window, a histogram's percentile over a window, and the
device's shares from the trace.  A reader that finds nothing to read gets
``None`` back and reports nothing; nothing here returns 0 for "no data"."""

from __future__ import annotations

import math

import numpy as np

from bench import trace_reduce

# JAX name-stack scopes of the two stages in the served executable (the
# program's nested jits and kernels; see PERF.md, "How the trace names the
# stages").  A stage whose scope is not in the trace reads nothing.
STAGE_SCOPES = {
    "stage1": ("jit(grid_knn)",),
    "stage2": ("jit(weighted_partial_sums)", "jit(tiled_interpolate)",
               "jit(fused_stage2)", "jit(local_interpolate)"),
}


def percentile(values, p: float):
    """The ``p``-th percentile (linear interpolation) of every value, or
    None when there are none."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, p)) if v.size else None


def read_latency_ms(run) -> list[float]:
    """Each read's latency from its due time to its answer; a read that
    never came counts at the time it was given up."""
    return [(r["t_done"] - r["due"]) * 1e3 for r in run.reads]


def update_visible_ms(run) -> list[float]:
    return [(u["t_done"] - u["due"]) * 1e3 for u in run.updates]


def queries_per_s(run):
    """Queries of every call over the time from the first call's start to
    the last call's end."""
    if not run.calls:
        return None
    wall = run.calls[-1]["t2"] - run.calls[0]["t1"]
    return sum(len(c["queries"]) for c in run.calls) / wall


def hist_percentile(run, name: str, p: float):
    """``p``-th percentile of the program histogram ``name`` over the
    window's observations only: the upper edge of the log bin holding that
    rank (10 bins a decade, so within 26% above)."""
    before, after = (s["hists"].get(name) for s in run.registry)
    if after is None:
        return None
    counts = list(after["counts"])
    if before is not None:
        counts = [a - b for a, b in zip(counts, before["counts"])]
    total = sum(counts)
    if total == 0:
        return None
    rank, seen = p / 100.0 * total, 0
    for i, c in enumerate(counts):
        seen += c
        if c and seen >= rank:
            return after["lo"] * 10.0 ** ((i + 1) / after["bins_per_decade"])
    return None


def stage_us_per_query(run, stage: str):
    """Device microseconds of ``stage``'s ops in the traced window over the
    queries answered in it."""
    if run.trace is None or not run.queries:
        return None
    ps = trace_reduce.stage_ps(run.trace, *run.trace_window,
                               STAGE_SCOPES[stage])
    return ps / 1e6 / run.queries if ps else None


def device_idle_pct(run):
    """100 x (1 - device busy / traced window)."""
    if run.trace is None or not run.trace.device:
        return None
    lo, hi = run.trace_window
    busy = trace_reduce.busy_ps(run.trace, lo, hi)
    return 100.0 * (1.0 - busy / (hi - lo)) if busy else None


def finite(x):
    return x if x is not None and math.isfinite(x) else None
