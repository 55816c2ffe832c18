"""The program's own spans and counters on the device trace's clock.

* every executable that serves carries the ``aidw.stage1`` /
  ``aidw.stage2`` name-stack scopes, whichever Stage-2 path runs;
* a ``Tracer.span`` lands in the JAX profiler's trace as a host event, and
  the Tracer's exported start agrees with the profiler's within 1 ms;
* the serving worker's phases are ``serving.*`` spans on one host line,
  without overlap, with the ingest spans nested in the update's;
* the row, bucket-slot and byte counters match hand counts.
"""

from __future__ import annotations

import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace_reduce
from repro.core import AidwConfig, InterpolationSession
from repro.core import pipeline as P
from repro.data.pipeline import spatial_points, spatial_queries
from repro.obs import Tracer, span
from repro.serving import AsyncAidwServer

WORKER_SPANS = {"serving.wait_work", "serving.coalesce", "serving.launch",
                "serving.materialize", "serving.scatter",
                "serving.apply_update"}


def _profiled(tmp_path, body):
    """Run ``body()`` under the JAX profiler; return the ``.xplane.pb``."""
    with jax.profiler.trace(str(tmp_path)):
        body()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    return path


def _host_events(path, names):
    """``{line: [Event]}`` of the host events whose name is in ``names``."""
    tr = trace_reduce.read(path)
    out = {}
    for line, evs in tr.host.items():
        mine = [e for e in evs if e.name in names]
        if mine:
            out[line] = sorted(mine, key=lambda e: e.start_ps)
    return out


@pytest.mark.parametrize("stage2", ["global", "tiled", "local"])
def test_execute_scopes_in_lowered_ops(stage2):
    pts = spatial_points(512, seed=0)
    sess = InterpolationSession(pts, AidwConfig(stage2=stage2))
    pln = sess.plan
    qp = jnp.asarray(spatial_queries(64, seed=1))
    text = P._session_execute.lower(
        pln.spec, pln.cfg, pln.area, pln.table, pln.points_xy, pln.values,
        qp, pln.n_points).as_text(debug_info=True)
    for scope in ("aidw.stage1", "aidw.stage2"):
        assert scope in text
    # the nested jits the accepted stage metrics read stay in place
    assert "aidw.stage1/jit(grid_knn)" in text


def test_tracer_span_lands_in_profiler_trace(tmp_path):
    tr = Tracer(sample_rate=1.0)

    def body():
        with tr.span("probe.span", trace_id=tr.new_trace()):
            time.sleep(0.02)
        with span("probe.bare"):
            pass

    path = _profiled(tmp_path, body)
    ((_, (ev,)),) = _host_events(path, {"probe.span"}).items()
    assert _host_events(path, {"probe.bare"})
    # the .xplane.pb keeps host times relative to the profile's start
    env = next(p for p in jax.profiler.ProfileData.from_file(path).planes
               if p.name == "Task Environment")
    start_ns = dict(env.stats)["profile_start_time"]
    (exported,) = tr.spans()
    assert abs(start_ns / 1e9 + ev.start_ps / 1e12
               - exported["t0"]) < 1e-3
    assert exported["dur"] == pytest.approx(
        (ev.end_ps - ev.start_ps) / 1e12, abs=1e-3)


def test_unprofiled_span_records_nothing():
    tr = Tracer(sample_rate=0.0)
    with tr.span("x", trace_id=tr.new_trace()) as s:
        assert s.span_id is None
    with span("y") as s:
        assert s.trace_id is None
    assert tr.spans() == []


def test_served_worker_spans_on_one_line(tmp_path):
    pts = spatial_points(2048, seed=0)
    q = spatial_queries(100, seed=1)
    rng = np.random.default_rng(0)
    ins = np.concatenate([spatial_queries(20, seed=2),
                          rng.random((20, 1), np.float32)], axis=1)
    srv = AsyncAidwServer(pts, AidwConfig(), max_batch=256, min_bucket=64,
                          query_domain=q)
    try:
        srv.result(srv.submit(q), timeout=120)        # compile outside
        srv.update_dataset(inserts=ins, deletes=np.arange(20), timeout=120)

        def body():
            srv.result(srv.submit(q), timeout=120)
            srv.update_dataset(inserts=ins, deletes=np.arange(20, 40),
                               timeout=120)
            srv.result(srv.submit(q), timeout=120)
            time.sleep(0.3)         # the idle worker waits for work

        path = _profiled(tmp_path, body)
    finally:
        srv.close(timeout=60)
    worker = _host_events(path, WORKER_SPANS)
    (line,) = worker                # every worker phase on one thread
    evs = worker[line]
    assert {e.name for e in evs} == WORKER_SPANS
    for a, b in zip(evs, evs[1:]):
        assert a.end_ps <= b.start_ps, (a, b)
    (upd,) = [e for e in evs if e.name == "serving.apply_update"]
    ingest = _host_events(path, {"ingest.merge", "ingest.stage"})
    assert set(ingest) == {line}
    assert [e.name for e in ingest[line]] == ["ingest.merge", "ingest.stage"]
    for e in ingest[line]:
        assert upd.start_ps <= e.start_ps <= e.end_ps <= upd.end_ps


def test_query_counts_rows_and_bucket_slots():
    pts = spatial_points(2048, seed=0)
    sess = InterpolationSession(pts, AidwConfig(), min_bucket=64)
    sess.query(spatial_queries(100, seed=1))
    counters = sess.registry.state()["counters"]
    assert counters["session/queries"] == 100
    assert counters["session/bucket_slots"] == 128


def test_delta_update_counts_pulled_and_staged_bytes():
    pts = spatial_points(2048, seed=0)
    qd = spatial_queries(256, seed=1)
    sess = InterpolationSession(pts, AidwConfig(), query_domain=qd)
    pulled = sum(a.nbytes for a in sess.plan.table)
    ins = np.concatenate([spatial_queries(30, seed=2),
                          np.ones((30, 1), np.float32)], axis=1)
    sess.update(inserts=ins, deletes=np.arange(10))
    new = sess.plan
    staged = new.points_xy.nbytes + new.values.nbytes + sum(
        a.nbytes for a in new.table)
    state = sess.registry.state()
    assert state["counters"]["ingest/pulled_bytes_total"] == pulled
    gauges = {k: g["value"] for k, g in state["gauges"].items()}
    assert gauges["ingest/staged_bytes"] == staged
    assert gauges["ingest/staged_bytes_total"] == staged
    assert sess.stats["staged_bytes_total"] == staged
    # the staging wall covers the fenced upload of the new plan
    assert state["hists"]["session/staging_s"]["count"] == 1
