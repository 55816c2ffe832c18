"""Readers of the program's own spans, scopes and counters.

The program marks its work in three ways that a traced run can read (the
``repro.obs`` package docstring lists them):

* the ``aidw.stage1`` / ``aidw.stage2`` name-stack scopes on every device
  op of the executables that serve (the op's ``tf_op``);
* profiler annotations on the serving worker's thread (``serving.*``) and
  in the ingest path (``ingest.merge``, ``ingest.stage``): host events of
  the trace, on the same clock as the device ops;
* registry counters (``session/queries``, ``session/bucket_slots``,
  ``ingest/pulled_bytes_total``) and the ``ingest/staged_bytes_total``
  gauge, read from the registry's state before and after the window.

A reader that finds none of its scopes, spans or counters returns ``None``
(a program without them reports nothing); none returns 0 for missing data.
"""

from __future__ import annotations

import numpy as np

from bench import trace_reduce

STAGE2_SCOPE = "aidw.stage2"
WORKER_PREFIX = "serving."
WORKER_IDLE = "serving.wait_work"   # the worker blocked, nothing pending


def scope_us_per_query(run, scope: str):
    """Device microseconds of the ops whose name stack holds ``scope``, in
    the traced window, over the queries answered in it."""
    if run.trace is None or not run.queries:
        return None
    ps = trace_reduce.stage_ps(run.trace, *run.trace_window, (scope,))
    return ps / 1e6 / run.queries if ps else None


def idle_worker_busy_pct(run):
    """100 x the window time in which no op runs on the device while the
    serving worker is in one of its phases other than waiting for work,
    over the window: the device idle the host causes while it holds work."""
    if run.trace is None or not run.trace.device:
        return None
    lo, hi = run.trace_window
    busy = trace_reduce.union(
        (max(e.start_ps, lo), min(e.end_ps, hi))
        for line in run.trace.host.values() for e in line
        if e.name.startswith(WORKER_PREFIX) and e.name != WORKER_IDLE
        and e.end_ps > lo and e.start_ps < hi)
    if not busy:
        return None
    worker = sum(e - s for s, e in busy)
    planes = list(run.trace.device.values())
    idle = sum(worker - _overlap(busy, trace_reduce.union(
        (e.start_ps, e.end_ps) for e in evs)) for evs in planes)
    return 100.0 * idle / len(planes) / (hi - lo)


def _overlap(a, b) -> int:
    """Length of the intersection of two sorted lists of disjoint
    ``(start, end)`` intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def span_p50_ms(run, name: str):
    """Median duration (ms) of the host events ``name`` that start in the
    traced window (exact: each event's own duration)."""
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    durs = [(e.end_ps - e.start_ps) / 1e9
            for line in run.trace.host.values() for e in line
            if e.name == name and lo <= e.start_ps < hi]
    return float(np.median(durs)) if durs else None


def _delta(run, kind: str, name: str):
    """Change of a registry counter or gauge over the window, or ``None``
    when the registry after the window lacks it."""
    before, after = run.registry
    if name not in after.get(kind, {}):
        return None

    def value(state):
        v = state.get(kind, {}).get(name, 0)
        return v["value"] if isinstance(v, dict) else v

    return value(after) - value(before)


def bucket_fill_pct(run):
    """100 x real query rows over padded bucket rows dispatched in the
    window: the share of Stage-1 work spent on queries someone asked."""
    queries = _delta(run, "counters", "session/queries")
    slots = _delta(run, "counters", "session/bucket_slots")
    if queries is None or not slots:
        return None
    return 100.0 * queries / slots


def moved_mb_per_update(run):
    """Megabytes pulled from and staged to the device per update applied
    in the window."""
    pulled = _delta(run, "counters", "ingest/pulled_bytes_total")
    staged = _delta(run, "gauges", "ingest/staged_bytes_total")
    updates = sum(bool(u.get("ok")) for u in run.updates)
    if pulled is None or staged is None or not updates:
        return None
    return (pulled + staged) / 1e6 / updates
