"""Stage-attributed benchmark: per-stage walls from the obs registry.

Every row here is read out of the SAME :class:`repro.obs.Registry`
histograms the production paths populate — not from bench-local stopwatch
code — so the bench doubles as an end-to-end check that the instrumentation
itself is honest.  Two workloads drive the stack:

* a **session** workload on a mesh over every visible device: a
  ``replicated`` session's profiled queries split the warm wall into
  ``session/stage1_s`` / ``session/stage2_s`` (separately-jitted, fenced
  halves — ``profile=True`` needs the binned plan that layout carries),
  its construction and 1%-churn incremental updates populate
  ``session/plan_s`` / ``session/bin_s`` / ``session/staging_s``, and a
  ``grid_ring`` session's explicit compactions populate
  ``session/compact_s`` (the LSM fold only exists on that layout);
* a **serving** workload (``AsyncAidwServer`` with tracing at sample rate
  1.0): a burst of odd-sized requests populates ``serving/queue_wait_s`` /
  ``serving/coalesce_s`` / ``serving/execute_s`` / ``serving/total_s`` /
  ``serving/scatter_s``, and the tracer's spans give a second,
  independently-recorded view of the same intervals.

Rows (CSV schema ``name,us_per_call,derived`` plus an
``includes_compile`` stamp — ``staging``/``compact`` hold first-and-only
observations so XLA compile time is inside them, and benchmarks/run.py
excludes stamped rows from the regression gate): ``stage/stage1``,
``stage/stage2``, ``stage/staging``, ``stage/compact``,
``stage/queue_wait``, ``stage/coalesce`` — each with at least one RAISING
acceptance gate:

* **stage1/stage2 — fence honesty + e2e reconciliation.**  Each profiled
  stage must carry >= 2% of the profiled query wall (an unfenced stage
  would report only its ~µs dispatch cost), and the profiled sum
  (stage1 + stage2) must reconcile with the separately measured UNPROFILED
  warm query wall within ``E2E_TOL`` = 3x either way.  The tolerance is
  deliberately wide — the profiled path pays an extra dispatch + fence
  between the halves and CPU CI boxes are noisy — but it still catches
  gross misattribution (a missing fence puts ~100% of the wall on one
  stage and ~0% on the other, which the 2%-floor gate trips first).
* **staging — span nesting.**  ``bin + staging <= plan`` per the span
  taxonomy (both are sub-spans of the plan/update wall), checked on the
  construction update where all three histograms hold exactly one
  observation of the SAME update; a sub-wall exceeding its parent means
  the clock domains diverged.  The row itself reports the delta-path
  staging mean (the wall serving updates actually pay).
* **compact — count exactness.**  ``session/compact_s`` must hold exactly
  as many observations as ``compact()`` calls issued.
* **queue_wait — telemetry identity.**  ``mean(queue) + mean(execute)``
  must equal ``mean(total)`` within 1% (the three are stamped from the
  same request timestamps; drift means a recording path diverged).
* **coalesce — span/metric agreement.**  Every completed traced request
  must have produced exactly one ``coalesce`` span, and the mean of the
  ``execute`` SPANS must agree with the ``serving/execute_s`` histogram
  mean within 10% (spans and metrics are two views of one measurement).

Standalone: ``PYTHONPATH=src python benchmarks/stage_bench.py [--json]``
(CI runs it via ``benchmarks/run.py --json`` so the rows land in
``BENCH_<tag>.json``).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import AidwConfig, InterpolationSession
from repro.data.pipeline import spatial_points, spatial_queries
from repro.serving import AsyncAidwServer

# (m data points, query batch, profiled repetitions)
SIZES = (16384, 1024, 5)
E2E_TOL = 3.0          # profiled-sum vs unprofiled-wall reconciliation band
STAGE_FLOOR = 0.02     # min fraction of the profiled wall per fenced stage
SPAN_METRIC_TOL = 0.10  # tracer-span mean vs registry-histogram mean


def _hist(reg_snapshot: dict, name: str) -> dict:
    h = reg_snapshot["histograms"].get(name)
    if h is None or not h["count"]:
        raise RuntimeError(f"stage bench: no observations under {name!r} — "
                           f"the instrumentation path did not run")
    return h


def session_stage_rows(sizes=SIZES) -> list[tuple]:
    """``stage/stage1`` / ``stage/stage2`` / ``stage/staging`` /
    ``stage/compact`` rows + their gates (see module docstring)."""
    import jax

    m, base, reps = sizes
    pts = spatial_points(m, seed=0)
    mesh = jax.make_mesh((len(jax.devices()),), ("q",))
    sess = InterpolationSession(pts, AidwConfig(), mesh=mesh,
                                layout="replicated",
                                query_domain=spatial_queries(base, seed=1))
    # gate: sub-spans nest inside their parent wall — checked on the
    # construction update, where plan/bin/staging are exactly one
    # observation each of the SAME update
    ctor = sess.registry.snapshot()
    plan = _hist(ctor, "session/plan_s")
    binh = _hist(ctor, "session/bin_s")
    stg0 = _hist(ctor, "session/staging_s")
    if binh["mean_s"] + stg0["mean_s"] > plan["mean_s"] * 1.01:
        raise RuntimeError(
            f"stage bench gate: bin {binh['mean_s'] * 1e6:.1f}us + staging "
            f"{stg0['mean_s'] * 1e6:.1f}us exceeds their parent plan wall "
            f"{plan['mean_s'] * 1e6:.1f}us — clock domains diverged?")

    qs = spatial_queries(base, seed=2)
    sess.query(qs).values.block_until_ready()        # compile both paths
    sess.query(qs, profile=True)
    for name in ("session/query_s", "session/stage1_s", "session/stage2_s",
                 "session/staging_s"):
        sess.registry.reset_histogram(name)

    # unprofiled end-to-end warm wall (the reconciliation target)
    e2e = []
    for _ in range(reps):
        t0 = time.perf_counter()
        sess.query(qs).values.block_until_ready()
        e2e.append(time.perf_counter() - t0)
    e2e_s = float(np.mean(e2e))

    for _ in range(reps):
        sess.query(qs, profile=True)

    # incremental churn -> the delta-path staging wall (CSR patch + mesh
    # re-place, fenced)
    d = max(m // 100, 1)
    rng = np.random.default_rng(3)
    sess.update(inserts=spatial_points(d, seed=4),
                deletes=rng.choice(m, d, replace=False))

    # compaction only exists on the grid_ring LSM layout
    ring = InterpolationSession(pts, AidwConfig(), mesh=mesh,
                                layout="grid_ring",
                                query_domain=spatial_queries(base, seed=1))
    ring.update(inserts=spatial_points(d, seed=5),
                deletes=rng.choice(m, d, replace=False))
    n_compacts = 2
    for _ in range(n_compacts):
        ring.compact()

    snap = sess.registry.snapshot()
    s1 = _hist(snap, "session/stage1_s")
    s2 = _hist(snap, "session/stage2_s")
    prof = s1["mean_s"] + s2["mean_s"]

    # gate: fence honesty — each separately-jitted half carries real work
    for name, h in (("stage1", s1), ("stage2", s2)):
        if h["mean_s"] < STAGE_FLOOR * prof:
            raise RuntimeError(
                f"stage bench gate: {name} mean {h['mean_s'] * 1e6:.1f}us is "
                f"< {STAGE_FLOOR:.0%} of the profiled query wall "
                f"{prof * 1e6:.1f}us — stage output not fenced?")
    # gate: profiled split reconciles with the unprofiled end-to-end wall
    ratio = prof / max(e2e_s, 1e-12)
    if not (1.0 / E2E_TOL <= ratio <= E2E_TOL):
        raise RuntimeError(
            f"stage bench gate: profiled stage1+stage2 "
            f"{prof * 1e6:.1f}us vs unprofiled query {e2e_s * 1e6:.1f}us "
            f"({ratio:.2f}x) outside the {E2E_TOL}x reconciliation band")

    stg = _hist(snap, "session/staging_s")
    cmp_h = _hist(ring.registry.snapshot(), "session/compact_s")
    # gate: every compact() call produced exactly one observation
    if cmp_h["count"] != n_compacts:
        raise RuntimeError(
            f"stage bench gate: {n_compacts} compact() calls but "
            f"{cmp_h['count']} session/compact_s observations")

    # 4th element: includes_compile — stage1/stage2 walls are measured on
    # warmed executables; the staging and compact walls each hold their
    # FIRST (and only) observations, so XLA trace+compile time is inside
    # them.  run.py excludes stamped rows from the regression gate: a
    # compile-contaminated wall regressing 1.25x says nothing about the
    # production path (and a persistent-cache hit would "improve" it 10x).
    tag = f"{m}x{base}"
    return [
        (f"stage/stage1/{tag}", s1["mean_s"] * 1e6,
         f"{s1['mean_s'] / prof:.0%} of profiled query "
         f"({prof * 1e6:.0f}us; e2e {e2e_s * 1e6:.0f}us, "
         f"{ratio:.2f}x within {E2E_TOL}x band)", False),
        (f"stage/stage2/{tag}", s2["mean_s"] * 1e6,
         f"{s2['mean_s'] / prof:.0%} of profiled query, n={s2['count']}",
         False),
        (f"stage/staging/{tag}", stg["mean_s"] * 1e6,
         f"delta-path staging, n={stg['count']}; construction nesting "
         f"bin {binh['mean_s'] * 1e6:.0f}us + staging "
         f"{stg0['mean_s'] * 1e6:.0f}us <= plan {plan['mean_s'] * 1e6:.0f}us",
         True),
        (f"stage/compact/{tag}", cmp_h["mean_s"] * 1e6,
         f"{cmp_h['count']} grid_ring compactions observed "
         f"(count gate exact)", True),
    ]


def serving_stage_rows(points: int = 16384, req_queries: int = 96,
                       n_requests: int = 24) -> list[tuple]:
    """``stage/queue_wait`` / ``stage/coalesce`` rows + the telemetry
    identity and span/metric-agreement gates (see module docstring)."""
    pts = spatial_points(points, seed=0)
    with AsyncAidwServer(pts, max_batch=4096, trace_sample_rate=1.0,
                         query_domain=spatial_queries(1024, seed=1)) as srv:
        srv.submit(spatial_queries(req_queries, seed=2))
        srv.flush(timeout=600)
        srv.telemetry.reset()
        srv.spans()                       # drop warmup spans
        reqs = [srv.submit(spatial_queries(req_queries - (i % 7), seed=3 + i),
                           block=False)
                for i in range(n_requests)]
        srv.flush(timeout=600)
        snap = srv.metrics_snapshot()
        spans = srv.spans()
        done = sum(r.status == "done" for r in reqs)

    qw = _hist(snap, "serving/queue_wait_s")
    ex = _hist(snap, "serving/execute_s")
    tot = _hist(snap, "serving/total_s")
    co = _hist(snap, "serving/coalesce_s")
    # gate: the telemetry identity queue + execute == total (same stamps)
    drift = abs(qw["mean_s"] + ex["mean_s"] - tot["mean_s"])
    if drift > 0.01 * max(tot["mean_s"], 1e-12):
        raise RuntimeError(
            f"stage bench gate: mean(queue_wait)+mean(execute) drifts "
            f"{drift * 1e6:.1f}us from mean(total) "
            f"{tot['mean_s'] * 1e6:.1f}us (> 1%)")
    # gate: one coalesce span per completed traced request, none lost
    co_spans = [s for s in spans if s["name"] == "coalesce"]
    if len(co_spans) != done:
        raise RuntimeError(
            f"stage bench gate: {done} completed traced requests but "
            f"{len(co_spans)} coalesce spans")
    # gate: spans and histograms are two views of ONE measurement
    ex_spans = [s["dur"] for s in spans if s["name"] == "execute"]
    span_mean = float(np.mean(ex_spans)) if ex_spans else 0.0
    if abs(span_mean - ex["mean_s"]) > SPAN_METRIC_TOL * ex["mean_s"]:
        raise RuntimeError(
            f"stage bench gate: execute span mean {span_mean * 1e6:.1f}us vs "
            f"serving/execute_s mean {ex['mean_s'] * 1e6:.1f}us differ by "
            f"> {SPAN_METRIC_TOL:.0%}")

    tag = f"{points}x{req_queries}"
    return [
        (f"stage/queue_wait/{tag}", qw["mean_s"] * 1e6,
         f"queue+execute-total drift {drift * 1e6:.2f}us (<1% gate), "
         f"n={qw['count']}", False),
        (f"stage/coalesce/{tag}", co["mean_s"] * 1e6,
         f"{len(co_spans)} spans == {done} completed requests; execute "
         f"span/metric agree within {SPAN_METRIC_TOL:.0%}", False),
    ]


def stage_rows() -> list[tuple]:
    """All stage-attributed rows (wired into benchmarks/run.py)."""
    return session_stage_rows() + serving_stage_rows()


def main() -> None:
    import argparse
    import json

    p = argparse.ArgumentParser()
    p.add_argument("--json", action="store_true")
    args = p.parse_args()
    rows = stage_rows()
    if args.json:
        print(json.dumps([{"name": r[0], "us_per_call": r[1],
                           "derived": r[2],
                           "includes_compile": bool(r[3])
                           if len(r) > 3 else False}
                          for r in rows], indent=1))
        return
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r[0]},{r[1]:.1f},{r[2]}")


if __name__ == "__main__":
    main()
