"""Observability spine (ISSUES 8+9): tracer, metrics registry, trace
propagation, flight recorder, SLO monitor, tail attribution, and the
report()/state() schema contracts.

Acceptance criteria covered here:
(a) span recording is retroactive, sampled once at the root, and a no-op
    when the trace is unsampled (the <2% overhead story);
(b) Chrome ``trace_event`` export is structurally valid and multi-host
    span collections land in per-host lanes;
(c) the registry merges bin-exactly across hosts and exports Prometheus
    text under the documented ``aidw_<slash_name>`` scheme — with an
    EXACT-exposition regression (``# HELP``/``# TYPE`` per family);
(d) fleet QPS is computed over the UNION wall window (fake-clock exact),
    with the legacy summed rate exposed as ``queries_per_s_summed``;
(e) ``AsyncAidwServer.report()`` keeps its schema (the keys downstream
    dashboards and ``merge_reports`` read), now including ``stages``,
    ``registry``, ``slo`` and ``recorder`` blocks;
(f) session timing aliases: ``stats['last_plan_s']`` and
    ``res.timings['query']`` mirror the newest registry observations, and
    the session's plan/bin/staging/query spans nest;
(g) PR 9: flight-recorder retention is DETERMINISTIC under fake clocks
    (anomaly classes, FIFO ring eviction, explicit dropped counters, the
    prior-window slow rule), SLO burn rates match hand-computed
    arithmetic with edge-triggered breach events, the tail attribution
    decomposes p99-p50 into per-stage contributions that SUM to the gap,
    and histogram exemplars merge bin-exactly.
The 2-host kill-mid-batch trace-propagation test and the fleet debugz
bundle tests live in tests/test_cluster.py next to the other
fleet-death coverage.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from repro.data.pipeline import spatial_points, spatial_queries
from repro.obs import (FlightRecorder, Registry, SloMonitor, Tracer,
                       chrome_trace, fleet_epoch_events, new_span_id,
                       tail_attribution)
from repro.obs.metrics import Histogram
from repro.serving import AsyncAidwServer, Telemetry
from repro.serving.cluster import merge_reports


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_tracer_retroactive_record_and_wall_anchor():
    clk, wall = FakeClock(5.0), FakeClock(105.0)
    tr = Tracer(clock=clk, wall=wall, sample_rate=1.0, host="h9")
    tid = tr.new_trace()
    assert tid is not None
    root = tr.record("plan", 5.0, 7.5, trace_id=tid)
    child = tr.record("bin", 5.0, 6.0, trace_id=tid, parent_id=root)
    assert root and child and root != child
    spans = tr.spans()
    assert [s["name"] for s in spans] == ["plan", "bin"]
    # wall anchoring: offset = wall - clock at construction (100.0)
    assert spans[0]["t0"] == pytest.approx(105.0)
    assert spans[0]["dur"] == pytest.approx(2.5)
    assert spans[1]["parent_id"] == root
    assert all(s["trace_id"] == tid and s["host"] == "h9" for s in spans)


def test_tracer_rate_zero_is_total_noop():
    tr = Tracer(clock=FakeClock(), wall=None, sample_rate=0.0)
    assert tr.new_trace() is None
    # record with an unsampled trace: returns None, stores nothing — every
    # call site's cost is exactly this one if
    assert tr.record("x", 0.0, 1.0, trace_id=None) is None
    with tr.span("y", trace_id=None) as sp:
        assert sp.span_id is None
    assert tr.spans() == []


def test_tracer_sampling_is_decided_at_the_root():
    tr = Tracer(clock=FakeClock(), wall=None, sample_rate=0.5, seed=7)
    decisions = [tr.new_trace() is not None for _ in range(200)]
    assert 40 < sum(decisions) < 160            # probabilistic, seeded
    # children never re-decide: a sampled trace records everything
    tid = next(t for t in iter(tr.new_trace, "") if t is not None)
    assert tr.record("child", 0.0, 1.0, trace_id=tid) is not None


def test_tracer_span_context_manager_and_drain():
    clk = FakeClock(0.0)
    tr = Tracer(clock=clk, wall=None, sample_rate=1.0)
    tid = tr.new_trace()
    with tr.span("phase1", trace_id=tid) as sp:
        clk.t += 0.25
        tr.record("inner", 0.1, 0.2, trace_id=tid, parent_id=sp.span_id)
    spans = tr.drain()
    assert {s["name"] for s in spans} == {"inner", "phase1"}
    ph1 = next(s for s in spans if s["name"] == "phase1")
    assert ph1["dur"] == pytest.approx(0.25)
    inner = next(s for s in spans if s["name"] == "inner")
    assert inner["parent_id"] == ph1["span_id"]
    assert tr.spans() == []                     # drain cleared the buffer


def test_tracer_retention_cap_counts_drops():
    tr = Tracer(clock=FakeClock(), wall=None, sample_rate=1.0, max_spans=2)
    tid = tr.new_trace()
    for i in range(5):
        tr.record(f"s{i}", 0.0, 1.0, trace_id=tid)
    assert len(tr.spans()) == 2 and tr.dropped == 3


def test_pregenerated_root_ids_parent_before_record():
    # the fleet-router pattern: children are parented on a root id that is
    # only recorded (retroactively) after they already completed
    tr = Tracer(clock=FakeClock(), wall=None, sample_rate=1.0)
    tid, root = tr.new_trace(), new_span_id()
    tr.record("queue_wait", 0.0, 0.5, trace_id=tid, parent_id=root)
    assert tr.record("route", 0.0, 1.0, trace_id=tid, span_id=root) == root
    spans = tr.spans()
    ids = {s["span_id"] for s in spans}
    assert all(s["parent_id"] in ids | {None} for s in spans)


def test_chrome_trace_export_is_structurally_valid(tmp_path):
    tr = Tracer(clock=FakeClock(1.0), wall=None, sample_rate=1.0, host="3")
    tid = tr.new_trace()
    tr.record("stage1", 1.0, 1.5, trace_id=tid, args={"queries": 64})
    path = tmp_path / "trace.json"
    tr.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X" and ev["cat"] == "aidw"
    assert ev["ts"] == pytest.approx(1.0 * 1e6)     # microseconds
    assert ev["dur"] == pytest.approx(0.5 * 1e6)
    assert ev["pid"] == "host-3"
    assert ev["args"]["trace_id"] == tid and ev["args"]["queries"] == 64


def test_chrome_trace_merges_hosts_into_lanes():
    dicts = [{"name": "route", "trace_id": "t", "span_id": "a",
              "parent_id": None, "t0": 0.0, "dur": 1.0, "host": "router"},
             {"name": "execute", "trace_id": "t", "span_id": "b",
              "parent_id": "a", "t0": 0.2, "dur": 0.5, "host": "1"}]
    doc = chrome_trace(dicts)
    assert {e["pid"] for e in doc["traceEvents"]} \
        == {"host-router", "host-1"}
    assert json.dumps(doc)                          # serializable as-is


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_merge_is_bin_exact_and_gauge_mode_aware():
    a, b = Registry(), Registry()
    for reg, vals in ((a, (0.001, 0.01)), (b, (0.1, 1.0))):
        for v in vals:
            reg.observe("session/query_s", v)
        reg.inc("serving/batches", 2)
    a.set("ingest/staged_bytes", 100, merge="sum")
    b.set("ingest/staged_bytes", 50, merge="sum")
    a.set("ingest/ring_occupancy", 0.2, merge="max")
    b.set("ingest/ring_occupancy", 0.7, merge="max")
    fleet = Registry.merge_states([a.state(), b.state()])
    snap = fleet.snapshot()
    assert snap["counters"]["serving/batches"] == 4
    assert snap["gauges"]["ingest/staged_bytes"] == 150
    assert snap["gauges"]["ingest/ring_occupancy"] == pytest.approx(0.7)
    h = snap["histograms"]["session/query_s"]
    # bin-exact: identical to one histogram fed all four observations
    one = Registry()
    for v in (0.001, 0.01, 0.1, 1.0):
        one.observe("session/query_s", v)
    assert h == one.snapshot()["histograms"]["session/query_s"]


def test_registry_prometheus_text_naming_scheme():
    reg = Registry()
    reg.observe("serving/queue_wait_s", 0.004)
    reg.inc("serving/batches")
    reg.set("ingest/ring_occupancy", 0.5)
    text = reg.prometheus_text()
    assert "# TYPE aidw_serving_batches_total counter" in text
    assert "aidw_serving_batches_total 1" in text
    assert "# TYPE aidw_ingest_ring_occupancy gauge" in text
    assert "# TYPE aidw_serving_queue_wait_s summary" in text
    assert 'aidw_serving_queue_wait_s{quantile="0.99"}' in text
    assert "aidw_serving_queue_wait_s_count 1" in text


def test_reset_histogram_keeps_registration_and_binning():
    reg = Registry()
    reg.histogram("x", lo=1e-3, hi=1e2, bins_per_decade=5).record(0.5)
    h = reg.reset_histogram("x")
    assert h.count == 0 and (h.lo, h.hi, h.bins_per_decade) == (1e-3, 1e2, 5)
    reg.observe("x", 0.1)
    assert reg.snapshot()["histograms"]["x"]["count"] == 1


# ---------------------------------------------------------------------------
# fleet QPS: union wall window (satellite b)
# ---------------------------------------------------------------------------


class _Req:
    queries_xy = np.zeros((100, 2), np.float32)
    overflow = 0
    t_submit, t_dispatch, t_done = 1.0, 1.5, 2.0


def _host_report(wall_at: float, host_id: int) -> dict:
    t = Telemetry(clock=FakeClock(10.0), wall=FakeClock(wall_at))
    t.record_batch([_Req()], 0.5)
    return {"merge": t.state(), "epoch": 0, "host_id": host_id}


def test_fleet_qps_uses_union_wall_window_not_summed_rates():
    # two hosts each serve 100 queries over a 1s window, but the windows
    # are DISJOINT in wall time: true fleet throughput is 200/2s = 100 q/s,
    # while the pre-PR-8 summed rate over-reports 200 q/s
    reports = [_host_report(1000.0, 0), _host_report(1001.0, 1)]
    fleet = merge_reports(reports)
    assert fleet["queries_per_s"] == pytest.approx(100.0)
    assert fleet["queries_per_s_summed"] == pytest.approx(200.0)


def test_fleet_qps_identical_windows_match_summed():
    reports = [_host_report(1000.0, 0), _host_report(1000.0, 1)]
    fleet = merge_reports(reports)
    assert fleet["queries_per_s"] == pytest.approx(200.0)
    assert fleet["queries_per_s_summed"] == pytest.approx(200.0)


def test_fleet_qps_falls_back_to_summed_without_windows():
    reports = [_host_report(1000.0, 0), _host_report(1001.0, 1)]
    for r in reports:                       # legacy per-host state shape
        del r["merge"]["window"]
    fleet = merge_reports(reports)
    assert fleet["queries_per_s"] == pytest.approx(200.0)


# ---------------------------------------------------------------------------
# server report schema + serving spans (needs jax; small shapes)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_server_report():
    pts = spatial_points(2048, seed=0)
    with AsyncAidwServer(pts, max_batch=512, trace_sample_rate=1.0,
                         query_domain=spatial_queries(256, seed=1)) as srv:
        reqs = [srv.submit(spatial_queries(32 + i, seed=2 + i), block=False)
                for i in range(4)]
        srv.update_dataset(inserts=spatial_points(8, seed=9),
                           deletes=np.arange(8), timeout=300)
        srv.flush(timeout=300)
        yield srv.report(), srv.spans(), reqs, srv.metrics_text()


def test_server_report_schema_regression(traced_server_report):
    rep, _, reqs, _ = traced_server_report
    assert all(r.status == "done" for r in reqs)
    # the stable top-level surface: telemetry counters + rate + latency,
    # server attribution, and (PR 8) the stages/registry blocks
    for key in ("submitted", "completed", "shed", "rejected_full",
                "batches", "queries", "overflow_queries", "dataset_updates",
                "queries_per_s", "latency", "epoch", "admission",
                "queue_depth", "session", "merge", "stages", "registry",
                "slo", "recorder"):
        assert key in rep, f"report() lost key {key!r}"
    # the PR 9 blocks: SLO evaluation + flight-recorder counters
    assert {"targets", "windows_s", "rates", "gauges", "events"} \
        <= set(rep["slo"])
    assert {"requests", "retained", "dropped", "events",
            "events_dropped", "anomalies"} <= set(rep["recorder"])
    assert rep["recorder"]["requests"] == len(reqs)
    for axis in ("queue", "execute", "total", "shed"):
        snap = rep["latency"][axis]
        assert {"count", "mean_s", "p50_s", "p95_s", "p99_s",
                "max_s"} <= set(snap)
    # the mergeable block: counters + rate + wall window + full hist states
    assert {"counters", "queries_per_s", "window", "hists"} \
        <= set(rep["merge"])
    assert {"t0_wall", "t1_wall", "queries"} == set(rep["merge"]["window"])
    assert rep["merge"]["window"]["queries"] == rep["queries"]
    # the stage block: serving + session walls from ONE registry
    hists = rep["stages"]["histograms"]
    for name in ("serving/queue_wait_s", "serving/execute_s",
                 "serving/total_s", "serving/coalesce_s",
                 "serving/scatter_s", "session/plan_s",
                 "serving/epoch_barrier_s"):
        assert name in hists, f"stages block lost {name!r}"
    assert hists["serving/queue_wait_s"]["count"] == len(reqs)
    # the update_dataset barrier in the fixture observed its FIFO hold
    assert hists["serving/epoch_barrier_s"]["count"] == 1
    json.dumps(rep)                             # stays JSON-serializable


def test_serving_spans_cover_every_traced_request(traced_server_report):
    _, spans, reqs, _ = traced_server_report
    by_trace: dict = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s)
    req_traces = [by_trace[r.trace_id] for r in reqs]
    for trace in req_traces:
        names = sorted(s["name"] for s in trace)
        assert names == ["coalesce", "execute", "queue_wait", "scatter"]
        assert all(s["parent_id"] == trace[0]["parent_id"] for s in trace)
    # the epoch barrier got its own trace with an apply_epoch span
    assert any(any(s["name"] == "apply_epoch" for s in t)
               for t in by_trace.values())


def test_server_prometheus_endpoint(traced_server_report):
    _, _, _, text = traced_server_report
    assert "# TYPE aidw_serving_queue_wait_s summary" in text
    assert "aidw_serving_coalesce_s" in text
    assert "aidw_session_plan_s" in text


def test_server_without_tracer_serves_and_reports_no_spans():
    pts = spatial_points(2048, seed=0)
    with AsyncAidwServer(pts, max_batch=512,
                         query_domain=spatial_queries(256, seed=1)) as srv:
        r = srv.submit(spatial_queries(32, seed=2))
        srv.flush(timeout=300)
        assert r.status == "done" and r.trace_id is None
        assert srv.spans() == []
        assert srv.report()["stages"]["histograms"][
            "serving/queue_wait_s"]["count"] == 1


# ---------------------------------------------------------------------------
# session timing aliases (satellite a)
# ---------------------------------------------------------------------------


def test_session_timing_aliases_mirror_registry():
    from repro.core import AidwConfig, InterpolationSession

    pts = spatial_points(2048, seed=0)
    qs = spatial_queries(256, seed=1)
    sess = InterpolationSession(pts, AidwConfig(), query_domain=qs)
    # stats["last_plan_s"] is the documented alias of the newest
    # session/plan_s observation
    snap = sess.registry.snapshot()["histograms"]
    assert snap["session/plan_s"]["count"] == 1
    assert snap["session/plan_s"]["mean_s"] \
        == pytest.approx(sess.stats["last_plan_s"])

    sess.query(qs)                                  # compile the bucket
    sess.registry.reset_histogram("session/query_s")
    res = sess.query(qs, timings=True)
    h = sess.registry.snapshot()["histograms"]["session/query_s"]
    assert h["count"] == 1
    # res.timings["query"] is the alias of the same wall
    assert h["mean_s"] == pytest.approx(res.timings["query"])
    assert res.timings["bucket"] == 256
    # the timed query returns the untimed query's values
    assert np.array_equal(np.asarray(res.values),
                          np.asarray(sess.query(qs).values))

    # a delta update: the alias follows the newest plan_s observation, and
    # its fenced staging is a part of it
    sess.registry.reset_histogram("session/plan_s")
    sess.update(inserts=np.asarray(pts[:8]), deletes=np.arange(8))
    h = sess.registry.snapshot()["histograms"]
    assert h["session/plan_s"]["count"] == 1
    assert h["session/plan_s"]["mean_s"] \
        == pytest.approx(sess.stats["last_plan_s"])
    assert h["session/staging_s"]["count"] == 1
    assert h["session/staging_s"]["max_s"] <= sess.stats["last_plan_s"]


def test_session_spans_nest_plan_and_profiled_query():
    from repro.core import AidwConfig, InterpolationSession

    pts = spatial_points(2048, seed=0)
    qs = spatial_queries(256, seed=1)
    tr = Tracer(sample_rate=1.0, host="s")
    sess = InterpolationSession(pts, AidwConfig(), query_domain=qs,
                                tracer=tr)
    sess.query(qs)                  # untimed: no span
    res = sess.query(qs, timings=True)
    spans = tr.spans()
    names = [s["name"] for s in spans]
    assert sorted(names) == ["bin", "plan", "query"]
    plan = next(s for s in spans if s["name"] == "plan")
    binsp = next(s for s in spans if s["name"] == "bin")
    assert binsp["parent_id"] == plan["span_id"]
    assert binsp["trace_id"] == plan["trace_id"]
    assert binsp["dur"] <= plan["dur"]
    query = next(s for s in spans if s["name"] == "query")
    assert query["parent_id"] is None
    assert query["dur"] == pytest.approx(res.timings["query"])

    # a delta update: one plan root with its staging sub-span
    tr.drain()
    sess.update(inserts=np.asarray(pts[:8]), deletes=np.arange(8))
    spans = tr.spans()
    assert sorted(s["name"] for s in spans) == ["plan", "staging"]
    plan = next(s for s in spans if s["name"] == "plan")
    stage = next(s for s in spans if s["name"] == "staging")
    assert stage["parent_id"] == plan["span_id"]
    assert plan["t0"] <= stage["t0"]
    assert stage["t0"] + stage["dur"] <= plan["t0"] + plan["dur"] + 1e-9


# ---------------------------------------------------------------------------
# flight recorder: deterministic tail-sampling retention (ISSUE 9)
# ---------------------------------------------------------------------------


class _RecReq:
    """Minimal request stub for the recorder: stamped timestamps only."""

    def __init__(self, uid, *, deadline=None, overflow=0, zero_weight=0,
                 t_submit=0.0, t_dispatch=None, t_done=None,
                 trace_id=None, epoch=None):
        self.uid = uid
        self.deadline = deadline
        self.overflow = overflow
        self.zero_weight = zero_weight
        self.t_submit = t_submit
        self.t_dispatch = t_dispatch
        self.t_done = t_done
        self.trace_id = trace_id
        self.epoch = epoch


def _observe_fast(rec, uid, *, total=0.01, **kw):
    """One in-SLO request: queue_wait 10% of total, execute the rest."""
    qw = 0.1 * total
    req = _RecReq(uid, t_submit=0.0, t_dispatch=qw, t_done=total, **kw)
    return rec.observe_request(req, t0=qw, t1=total, t2=total,
                               last_submit=0.0)


def test_recorder_in_slo_requests_leave_no_trace():
    rec = FlightRecorder(clock=FakeClock(), wall=None, host="h",
                         top_percentile=None)
    assert _observe_fast(rec, 1) is None
    assert rec.retained() == []
    snap = rec.snapshot()
    assert snap["requests"] == 1 and snap["retained"] == 0
    assert all(v == 0 for v in snap["anomalies"].values())
    # the coarse breakdown still folded into the running histograms
    assert rec.state()["hists"]["total"]["count"] == 1


def test_recorder_classifies_and_retains_each_anomaly_class():
    rec = FlightRecorder(clock=FakeClock(), wall=None, host="h",
                         top_percentile=None)
    # served past its deadline, plus overflow + zero-weight queries
    req = _RecReq(7, deadline=0.02, overflow=2, zero_weight=1,
                  t_submit=0.0, t_dispatch=0.01, t_done=0.04)
    rid = rec.observe_request(req, t0=0.01, t1=0.04, t2=0.05,
                              last_submit=0.0)
    assert rid == "req-7"
    (rec_record,) = rec.retained()
    assert rec_record["anomalies"] == ["deadline_miss", "overflow",
                                       "zero_weight"]
    bd = rec_record["breakdown"]
    assert bd["queue_wait"] == pytest.approx(0.01)
    assert bd["execute"] == pytest.approx(0.03)
    assert bd["scatter"] == pytest.approx(0.01)
    assert bd["total"] == pytest.approx(0.04)
    # additive identity: queue_wait + execute == total (scatter lands
    # after t_done; coalesce overlaps queue_wait)
    assert bd["queue_wait"] + bd["execute"] == pytest.approx(bd["total"])
    names = sorted(s["name"] for s in rec_record["spans"])
    assert names == ["coalesce", "execute", "queue_wait", "request",
                     "scatter"]
    # deterministic span ids: derived from the uid, never uuid4
    assert {s["span_id"] for s in rec_record["spans"]} \
        == {"req-7/r", "req-7/queue_wait", "req-7/coalesce",
            "req-7/execute", "req-7/scatter"}
    assert rec.snapshot()["anomalies"]["deadline_miss"] == 1


def test_recorder_retention_is_bitwise_deterministic():
    def run():
        rec = FlightRecorder(clock=FakeClock(), wall=None, host="h",
                             top_percentile=None)
        _observe_fast(rec, 1)
        req = _RecReq(2, deadline=0.01, t_submit=0.0, t_dispatch=0.005,
                      t_done=0.03)
        rec.observe_request(req, t0=0.005, t1=0.02, t2=0.03,
                            last_submit=0.0)
        rec.observe_shed(_RecReq(3, deadline=0.001, t_submit=0.0,
                                 t_done=0.002))
        return rec.state()

    assert run() == run()                   # replays bit-identically


def test_recorder_shed_retained_but_censored_from_histograms():
    rec = FlightRecorder(clock=FakeClock(5.0), wall=None, host="h",
                         top_percentile=None)
    rec.observe_shed(_RecReq(4, deadline=0.01, t_submit=0.0, t_done=0.02))
    (r,) = rec.retained()
    assert r["anomalies"] == ["shed", "deadline_miss"]
    assert r["breakdown"]["queue_wait"] == pytest.approx(0.02)
    # censoring: folding time-to-shed into the total histogram would
    # IMPROVE percentiles as traffic is dropped
    assert rec.state()["hists"]["total"]["count"] == 0
    assert rec.snapshot()["anomalies"]["shed"] == 1


def test_recorder_ring_evicts_fifo_and_counts_drops():
    rec = FlightRecorder(clock=FakeClock(), wall=None, host="h",
                         ring=2, top_percentile=None)
    for uid in (1, 2, 3):
        rec.observe_shed(_RecReq(uid, deadline=0.01, t_submit=0.0,
                                 t_done=0.02))
    assert [r["id"] for r in rec.retained()] == ["req-2", "req-3"]
    assert rec.dropped == 1                  # explicit, not silent
    assert rec.snapshot()["dropped"] == 1


def test_recorder_slow_class_reads_the_prior_window():
    rec = FlightRecorder(clock=FakeClock(), wall=None, host="h",
                         top_percentile=50.0, min_window=2)
    # below min_window the class is unarmed, however slow the request
    assert _observe_fast(rec, 1, total=5.0) is None
    assert _observe_fast(rec, 2, total=0.01) is None
    # armed: 5ms is below the prior-window p50 (~10ms) -> not slow
    assert _observe_fast(rec, 3, total=0.005) is None
    # 10x the prior-window p50 -> slow, retained
    rid = _observe_fast(rec, 4, total=6.0)
    assert rid == "req-4"
    assert rec.snapshot()["anomalies"]["slow"] == 1
    # top_percentile=None disables the class entirely
    off = FlightRecorder(clock=FakeClock(), wall=None, min_window=0,
                         top_percentile=None)
    for uid in range(8):
        assert _observe_fast(off, uid, total=float(uid + 1)) is None


def test_recorder_event_ring_bounded_with_drop_counter():
    rec = FlightRecorder(clock=FakeClock(1.0), wall=None, host="h",
                         event_ring=2)
    for i in range(3):
        rec.event(f"e{i}", severity="warning", data={"i": i})
    evs = rec.events()
    assert [e["kind"] for e in evs] == ["e1", "e2"]
    assert rec.events_dropped == 1


# ---------------------------------------------------------------------------
# SLO monitor: burn-rate arithmetic + edge-triggered breaches (ISSUE 9)
# ---------------------------------------------------------------------------


def _slo(clk, rec=None, windows=(10.0,), miss_target=0.05):
    return SloMonitor(clock=clk, windows=windows, recorder=rec,
                      targets={"deadline_miss_rate": miss_target,
                               "shed_rate": None,
                               "queue_depth_frac": None,
                               "ring_occupancy": None})


def test_slo_burn_rate_matches_hand_computed_rates():
    clk = FakeClock(0.0)
    mon = _slo(clk)
    mon.sample({"requests": 0, "deadline_miss": 0})
    clk.t = 10.0
    mon.sample({"requests": 200, "deadline_miss": 20})
    ev = mon.evaluate()
    w = ev["rates"]["deadline_miss_rate"]["10"]
    # hand-computed: 20 bad / 200 total = 10% observed, target 5% -> burn 2
    assert w["rate"] == pytest.approx(0.1)
    assert w["burn"] == pytest.approx(2.0)
    assert (w["bad"], w["total"]) == (20, 200)
    assert w["span_s"] == pytest.approx(10.0)
    assert ev["rates"]["deadline_miss_rate"]["windows_evaluated"] == 1
    (breach,) = ev["events"]
    assert breach["slo"] == "deadline_miss_rate" and breach["burn"] == 2.0


def test_slo_breach_events_are_edge_triggered():
    clk = FakeClock(0.0)
    rec = FlightRecorder(clock=clk, wall=None)
    mon = _slo(clk, rec)
    mon.sample({"requests": 0, "deadline_miss": 0})
    clk.t = 10.0
    mon.sample({"requests": 100, "deadline_miss": 50})
    assert len(mon.evaluate()["events"]) == 1     # crossing emits once
    assert mon.evaluate()["events"] == []         # sustained: no re-emit
    (ev,) = rec.events()
    assert ev["kind"] == "slo_breach" and ev["severity"] == "critical"
    # recovery clears the latch; a NEW burn re-emits
    clk.t = 20.0
    mon.sample({"requests": 300, "deadline_miss": 50})
    assert mon.evaluate()["events"] == []         # window rate back to 0
    clk.t = 30.0
    mon.sample({"requests": 500, "deadline_miss": 150})
    assert len(mon.evaluate()["events"]) == 1


def test_slo_needs_two_samples_spanning_a_window():
    clk = FakeClock(0.0)
    mon = _slo(clk)
    assert mon.evaluate()["rates"] == {}          # no samples at all
    mon.sample({"requests": 100, "deadline_miss": 100})
    assert mon.evaluate()["rates"] == {}          # one sample: no window


def test_slo_gauge_thresholds_and_events():
    clk = FakeClock(0.0)
    mon = SloMonitor(clock=clk, windows=(10.0,),
                     targets={"deadline_miss_rate": None, "shed_rate": None,
                              "queue_depth_frac": 0.9,
                              "ring_occupancy": 0.8})
    mon.sample({}, gauges={"queue_depth_frac": 0.95, "ring_occupancy": 0.5})
    ev = mon.evaluate()
    assert ev["gauges"]["queue_depth_frac"]["breaching"] is True
    assert ev["gauges"]["ring_occupancy"]["breaching"] is False
    assert [e["slo"] for e in ev["events"]] == ["queue_depth_frac"]


def test_fleet_epoch_staleness_derived_at_the_merge_point():
    assert fleet_epoch_events({"a": {"epoch": 3}, "b": {"epoch": 4}}) == []
    (ev,) = fleet_epoch_events({"a": {"epoch": 3}, "b": {"epoch": 5}})
    assert ev["slo"] == "epoch_staleness" and ev["window"] == "fleet"
    assert (ev["min_epoch"], ev["max_epoch"], ev["lag"]) == (3, 5, 2)
    assert ev["stale_hosts"] == ["a"]
    assert fleet_epoch_events({"a": {"epoch": 1}}) == []   # 1 host: no view


# ---------------------------------------------------------------------------
# tail-latency attribution: the decomposition identity (ISSUE 9)
# ---------------------------------------------------------------------------


def _fed_recorder(host="0", n_fast=100, n_slow=2):
    """A recorder fed ``n_fast`` 10ms in-SLO requests and ``n_slow``
    1s deadline-missers whose excess is ALL queue_wait."""
    rec = FlightRecorder(clock=FakeClock(), wall=None, host=host,
                         top_percentile=None)
    for uid in range(n_fast):
        _observe_fast(rec, uid, total=0.01)
    for uid in range(n_fast, n_fast + n_slow):
        req = _RecReq(uid, deadline=0.5, t_submit=0.0, t_dispatch=0.99,
                      t_done=1.0)
        rec.observe_request(req, t0=0.99, t1=1.0, t2=1.0, last_submit=0.0)
    return rec


def test_attribution_identity_decomposes_the_gap():
    attr = tail_attribution([_fed_recorder().state()])
    assert attr["n_total"] == 102 and attr["tail_n"] == 2
    assert not attr["tail_is_fallback"]
    gap = attr["gap_s"]
    assert gap > 0
    # THE acceptance identity: per-stage contributions sum to the gap
    # (well within the 15% bar — exact by construction with excess > 0)
    assert attr["attributed_s"] == pytest.approx(gap)
    assert attr["unattributed_s"] == pytest.approx(0.0)
    assert attr["share_basis"] == "excess"
    st = attr["stages"]
    # the tail's excess is queue_wait by construction
    assert st["queue_wait"]["share"] > 0.95
    assert st["queue_wait"]["attributed_s"] == pytest.approx(
        gap * st["queue_wait"]["share"])
    assert sum(s["share"] for n, s in st.items() if s["additive"]) \
        == pytest.approx(1.0)
    # overlay stages are reported but never attributed (they overlap)
    assert st["coalesce"]["attributed_s"] is None
    assert st["scatter"]["share"] is None


def test_attribution_fleet_merge_and_stall_block():
    reg = Registry()
    reg.observe("session/compact_stall_s", 0.25, exemplar="upd-1")
    reg.observe("serving/epoch_barrier_s", 0.1)
    attr = tail_attribution(
        [_fed_recorder("0").state(), _fed_recorder("1").state()],
        registry_state=reg.state())
    # two hosts merged bin-exactly: counts double, identity still exact
    assert attr["n_total"] == 204 and attr["tail_n"] == 4
    assert attr["attributed_s"] == pytest.approx(attr["gap_s"])
    # the stall block reads Registry.state()'s "hists" key
    stalls = attr["stalls"]
    assert stalls["session/compact_stall_s"]["count"] == 1
    assert stalls["session/compact_stall_s"]["max_s"] \
        == pytest.approx(0.25)
    assert stalls["serving/epoch_barrier_s"]["p99_s"] > 0


def test_attribution_tail_mean_basis_when_no_stage_exceeds_baseline():
    # bimodal population with NO retained record above the baselines:
    # excess-based shares would attribute nothing; the report degrades to
    # tail-mean mass so a positive gap still decomposes
    rec = FlightRecorder(clock=FakeClock(), wall=None,
                         top_percentile=None)
    for uid in range(60):
        _observe_fast(rec, uid, total=0.01)
    for uid in range(60, 100):
        _observe_fast(rec, uid, total=1.0)     # slow but in-SLO: not kept
    req = _RecReq(100, overflow=1, t_submit=0.0, t_dispatch=1e-4,
                  t_done=1.0)
    rec.observe_request(req, t0=1e-4, t1=3e-4, t2=3e-4, last_submit=0.0)
    attr = tail_attribution([rec.state()])
    assert attr["gap_s"] > 0 and attr["tail_n"] == 1
    assert attr["share_basis"] == "tail_mean"
    assert attr["attributed_s"] == pytest.approx(attr["gap_s"])


def test_attribution_empty_states_are_harmless():
    attr = tail_attribution([])
    assert attr["n_total"] == 0 and attr["gap_s"] == 0.0
    assert attr["attributed_s"] == 0.0 and attr["stalls"] == {}


# ---------------------------------------------------------------------------
# histogram exemplars: bucket -> trace links (ISSUE 9)
# ---------------------------------------------------------------------------


def test_histogram_exemplars_latest_wins_and_merge_is_bin_exact():
    a, b = Histogram(), Histogram()
    a.record(0.004, exemplar="t-old")
    a.record(0.0042, exemplar="t-new")      # same log bin: latest wins
    a.record(0.5, exemplar="t-big")
    b.record(0.0041, exemplar="t-peer")     # same bin as t-new, other host
    b.record(20.0, exemplar="t-huge")
    st = a.state()
    assert set(st["exemplars"].values()) == {"t-new", "t-big"}
    merged = Histogram.from_states([st, b.state()])
    ex = merged.state()["exemplars"]
    # bin-exact: the shared bin took the LAST-merged host's exemplar, the
    # disjoint bins kept their own
    assert set(ex.values()) == {"t-peer", "t-big", "t-huge"}
    # snapshot keys by upper bin edge (human-facing latency bound)
    snap_ex = merged.snapshot()["exemplars"]
    assert all(float(k) > 0 for k in snap_ex)
    # a pre-exemplar peer state (no "exemplars" key) still merges
    legacy = Histogram()
    legacy.record(1.0)
    merged.merge_state(legacy.state())
    assert merged.count == 6


def test_exemplars_absent_when_unused_and_not_in_prometheus_text():
    h = Histogram()
    h.record(0.01)
    assert "exemplars" not in h.state()
    assert "exemplars" not in h.snapshot()
    reg = Registry()
    reg.observe("serving/total_s", 0.01, exemplar="trace-xyz")
    text = reg.prometheus_text()
    # the 0.0.4 text format has no exemplar syntax: exposition unchanged
    assert "trace-xyz" not in text and "exemplar" not in text


def test_telemetry_exemplars_link_buckets_to_request_traces(
        traced_server_report):
    rep, _, reqs, _ = traced_server_report
    ex = rep["merge"]["hists"]["total"].get("exemplars", {})
    assert ex, "total-latency histogram lost its exemplars"
    assert set(ex.values()) <= {r.trace_id for r in reqs}


# ---------------------------------------------------------------------------
# Prometheus exposition: exact-format regression (ISSUE 9)
# ---------------------------------------------------------------------------


def test_prometheus_exposition_exact_format():
    reg = Registry()
    reg.inc("serving/batches", 3)
    reg.set("ingest/ring_occupancy", 0.5)
    reg.observe("serving/queue_wait_s", 0.004)
    assert reg.prometheus_text() == (
        "# HELP aidw_serving_batches_total cumulative count of "
        "serving/batches\n"
        "# TYPE aidw_serving_batches_total counter\n"
        "aidw_serving_batches_total 3\n"
        "# HELP aidw_ingest_ring_occupancy gauge ingest/ring_occupancy\n"
        "# TYPE aidw_ingest_ring_occupancy gauge\n"
        "aidw_ingest_ring_occupancy 0.5\n"
        "# HELP aidw_serving_queue_wait_s summary of serving/queue_wait_s "
        "in seconds\n"
        "# TYPE aidw_serving_queue_wait_s summary\n"
        'aidw_serving_queue_wait_s{quantile="0.5"} 0.004\n'
        'aidw_serving_queue_wait_s{quantile="0.95"} 0.004\n'
        'aidw_serving_queue_wait_s{quantile="0.99"} 0.004\n'
        "aidw_serving_queue_wait_s_sum 0.004\n"
        "aidw_serving_queue_wait_s_count 1\n"
        "aidw_serving_queue_wait_s_max 0.004\n")


def test_every_prometheus_family_has_help_and_type(traced_server_report):
    _, _, _, text = traced_server_report
    lines = text.splitlines()
    families = {ln.split()[0].split("{")[0]
                for ln in lines if ln and not ln.startswith("#")}
    helped = {ln.split()[2] for ln in lines if ln.startswith("# HELP")}
    typed = {ln.split()[2] for ln in lines if ln.startswith("# TYPE")}
    for fam in families:
        base = fam
        for suffix in ("_sum", "_count", "_max"):
            if base.endswith(suffix) and base.removesuffix(suffix) in typed:
                base = base.removesuffix(suffix)
                break
        assert base in typed, f"{fam} has no # TYPE"
        assert base in helped, f"{fam} has no # HELP"


# ---------------------------------------------------------------------------
# compaction-stall histogram: the FIFO-barrier hold (ISSUE 9 satellite)
# ---------------------------------------------------------------------------


def test_compact_stall_histogram_covers_the_fifo_hold():
    pts = spatial_points(2048, seed=0)
    with AsyncAidwServer(pts, max_batch=512,
                         query_domain=spatial_queries(256, seed=1)) as srv:
        srv.submit(spatial_queries(32, seed=2))
        srv.compact(timeout=300)
        srv.flush(timeout=300)
        hists = srv.report()["stages"]["histograms"]
        stall = hists["session/compact_stall_s"]
        assert stall["count"] == 1
        # the stall covers the WHOLE hold (enqueue -> applied), so it can
        # never undershoot the device fold wall the session records
        if "session/compact_s" in hists and hists["session/compact_s"][
                "count"]:
            assert stall["max_s"] >= hists["session/compact_s"]["max_s"] \
                - 1e-6
