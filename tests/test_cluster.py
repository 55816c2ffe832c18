"""Multi-host AIDW serving cluster: epoch protocol, routing + draining,
fleet telemetry merge, and multi-process jax.distributed fleets.

Acceptance criteria covered here (ISSUE 4):
(a) a 2-host cluster serving an interleaved query+churn workload (3
    CONCURRENT ``update_dataset`` calls) returns results bit-identical to a
    single ``AsyncAidwServer`` applying the same epochs sequentially;
(b) a host dying mid-stream is drained by the router with no lost or
    duplicated request;
(c) per-host telemetry merges into fleet p50/p95/p99 + QPS;
plus the slow-marked 2-process x 4-forced-host-device test that runs the
whole stack — ``jax.distributed`` bootstrap, socket control plane, epoch
broadcast — across REAL process boundaries (the CI cluster-suite job).
"""

from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from conftest import REPO
from repro.data.pipeline import spatial_points, spatial_queries
from repro.serving import (AdmissionQueueFull, AsyncAidwServer,
                           LatencyHistogram, Telemetry)
from repro.serving.cluster import (AidwCluster, EpochApplier,
                                   EpochCoordinator, EpochUpdate,
                                   NoLiveHosts, Router, merge_reports)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# epoch protocol
# ---------------------------------------------------------------------------


def test_epoch_coordinator_monotonic_under_concurrency():
    coord = EpochCoordinator()
    got: list[int] = []
    lock = threading.Lock()

    def assign(k):
        for _ in range(50):
            e = coord.assign(inserts=k).epoch
            with lock:
                got.append(e)

    ts = [threading.Thread(target=assign, args=(k,)) for k in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # 200 assignments -> epochs 1..200, each exactly once, log in order
    assert sorted(got) == list(range(1, 201))
    assert [u.epoch for u in coord.log] == list(range(1, 201))
    assert coord.epoch == 200
    assert [u.epoch for u in coord.since(197)] == [198, 199, 200]


def test_epoch_applier_orders_buffers_and_dedups():
    applied: list[int] = []

    def enqueue(upd):
        applied.append(upd.epoch)
        return object()

    ap = EpochApplier(enqueue)
    h2 = ap.offer(EpochUpdate(epoch=2))          # early: buffered
    assert applied == [] and not h2.wait_bound(0)
    h1 = ap.offer(EpochUpdate(epoch=1))          # fills the gap: 1 then 2
    assert applied == [1, 2]
    assert h1.wait_bound(0) and h2.wait_bound(0)
    dup = ap.offer(EpochUpdate(epoch=1))         # stale: idempotent drop
    assert dup.duplicate and applied == [1, 2]
    assert ap.counters == {"enqueued": 2, "buffered": 1, "duplicates": 1}
    ap.offer(EpochUpdate(epoch=3))
    assert applied == [1, 2, 3] and ap.next_epoch == 4


def test_server_epoch_stamping_and_order_guard(spatial_data):
    """Server hooks: requests are stamped with the epoch they were served
    under; explicit (cluster) epochs pin the counter and must increase."""
    pts, qs = spatial_data
    with AsyncAidwServer(pts, max_batch=256, query_domain=qs) as srv:
        r0 = srv.submit(qs[:32])
        srv.flush(timeout=120)
        assert srv.epoch == 0 and r0.epoch == 0
        srv.update_dataset(inserts=spatial_points(8, seed=3), timeout=120)
        r1 = srv.submit(qs[:32])
        srv.flush(timeout=120)
        assert srv.epoch == 1 and r1.epoch == 1
        srv.update_dataset(inserts=spatial_points(8, seed=4), epoch=7,
                           timeout=120)
        assert srv.epoch == 7
        with pytest.raises(RuntimeError, match="epoch"):
            srv.update_dataset(inserts=spatial_points(8, seed=5), epoch=7,
                               timeout=120)
        r2 = srv.submit(qs[:32])                 # worker survived the guard
        assert srv.result(r2, timeout=120).epoch == 7


def test_withdrawn_epoch_update_leaves_detectable_gap(spatial_data):
    """Review regression: a withdrawn (timed-out) explicit-epoch barrier is
    a HOLE in the host's update order — later deltas must refuse (the
    monotonicity guard alone cannot see the gap), a full refresh heals it,
    and a retried wait on the skipped op must not read as success."""
    from repro.serving.server import _UpdateOp

    pts, qs = spatial_data
    with AsyncAidwServer(pts, max_batch=256, query_domain=qs) as srv:
        op = _UpdateOp(inserts=spatial_points(8, seed=3), epoch=1,
                       cancelled=True)     # withdrawn before the worker ran
        srv._apply_update(op)              # worker skip path, deterministic
        assert op.skipped and op.applied.is_set()
        with pytest.raises(TimeoutError, match="withdrawn"):
            srv.wait_update(op, timeout=1.0)
        with pytest.raises(RuntimeError, match="missed epoch 1"):
            srv.update_dataset(inserts=spatial_points(8, seed=4), epoch=2,
                               timeout=120)
        srv.update_dataset(pts, epoch=3, timeout=120)   # full re-sync heals
        r = srv.submit(qs[:16])
        assert srv.result(r, timeout=120).epoch == 3
        srv.update_dataset(inserts=spatial_points(8, seed=5), epoch=4,
                           timeout=120)    # deltas flow again post-heal
        assert srv.epoch == 4


# ---------------------------------------------------------------------------
# fleet telemetry merge
# ---------------------------------------------------------------------------


def test_histogram_merge_matches_single_histogram():
    rng = np.random.default_rng(0)
    samples = rng.exponential(0.05, 400)
    one = LatencyHistogram()
    parts = [LatencyHistogram() for _ in range(3)]
    for i, s in enumerate(samples):
        one.record(s)
        parts[i % 3].record(s)
    merged = LatencyHistogram.from_states(p.state() for p in parts)
    got, want = merged.snapshot(), one.snapshot()
    # mean sums floats in a different order; everything else is exact
    assert got["mean_s"] == pytest.approx(want["mean_s"])
    for k in ("count", "p50_s", "p95_s", "p99_s", "max_s"):
        assert got[k] == want[k], k
    with pytest.raises(ValueError):              # mismatched bins are loud
        one.merge_state(LatencyHistogram(bins_per_decade=5).state())


def test_merge_reports_sums_counters_and_rates():
    class _R:
        queries_xy = np.zeros((4, 2), np.float32)
        overflow = 1
        t_submit, t_dispatch, t_done = 1.0, 2.0, 3.0

    reports = []
    for host_id in range(2):
        t = Telemetry()
        t.record_submit(_R())
        t.record_batch([_R()], 0.5)
        reports.append({"merge": t.state(), "epoch": 2 + host_id,
                        "host_id": host_id, "admission": {"admitted": 3}})
    fleet = merge_reports(reports)
    assert fleet["hosts"] == 2 and fleet["host_ids"] == [0, 1]
    assert fleet["completed"] == 2 and fleet["queries"] == 8
    assert fleet["overflow_queries"] == 2
    assert fleet["admission"] == {"admitted": 6}
    assert fleet["epoch_min"] == 2 and fleet["epoch_max"] == 3
    # fleet QPS = sum(queries) over the UNION wall window (PR 8); the
    # legacy summed rate stays observable as queries_per_s_summed
    ws = [r["merge"]["window"] for r in reports]
    t0 = min(w["t0_wall"] for w in ws)
    t1 = max(w["t1_wall"] for w in ws)
    assert fleet["queries_per_s"] == pytest.approx(
        sum(w["queries"] for w in ws) / (t1 - t0))
    assert fleet["queries_per_s_summed"] == pytest.approx(
        sum(r["merge"]["queries_per_s"] for r in reports))
    assert fleet["latency"]["total"]["count"] == 2


# ---------------------------------------------------------------------------
# router (stub hosts: policy + heartbeat draining without jax in the loop)
# ---------------------------------------------------------------------------


class StubRequest:
    def __init__(self, queries_xy, deadline_s):
        self.queries_xy = queries_xy
        self.deadline_s = deadline_s
        self.done = False
        self.status = "queued"
        self.values = None
        self.overflow = 0
        self.epoch = 0


class StubHost:
    """Scriptable host: instant serve unless ``hold`` / ``dead`` /
    ``full`` (backpressure: submit raises AdmissionQueueFull)."""

    def __init__(self, host_id, depth=0):
        self.host_id = host_id
        self.depth = depth
        self.dead = False
        self.hold = False
        self.full = False
        self.submitted: list[StubRequest] = []

    def submit(self, queries_xy, *, deadline_s=None, uid=None, timeout=None):
        if self.dead:
            raise RuntimeError("stub host is dead")
        if self.full:
            raise AdmissionQueueFull("stub queue full")
        req = StubRequest(queries_xy, deadline_s)
        self.submitted.append(req)
        if not self.hold:
            req.done, req.status = True, "done"
            req.values = np.zeros(len(queries_xy), np.float32)
        return req

    def wait(self, req, timeout=None):
        if self.dead:
            raise RuntimeError("stub host is dead")
        if not req.done:
            raise TimeoutError("stub pending")
        return req

    def queue_depth(self):
        if self.dead:
            raise RuntimeError("stub host is dead")
        return self.depth

    def probe(self):
        return self.queue_depth()


def _q(n=4):
    return np.zeros((n, 2), np.float32)


def test_router_round_robin_alternates_and_least_loaded_prefers_shallow():
    a, b = StubHost(0), StubHost(1)
    rr = Router([a, b], clock=FakeClock())
    for _ in range(4):
        rr.route(_q())
    assert [len(a.submitted), len(b.submitted)] == [2, 2]

    a2, b2 = StubHost(0, depth=5), StubHost(1, depth=0)
    ll = Router([a2, b2], policy="least_loaded", clock=FakeClock())
    for _ in range(4):
        ll.route(_q())
    assert len(b2.submitted) == 4 and len(a2.submitted) == 0
    with pytest.raises(ValueError):
        Router([a, b], policy="random")


def test_router_least_loaded_drains_host_that_fails_depth_probe():
    """Review regression: a dead host raising from its queue_depth() probe
    is drained inside host selection, not allowed to wedge every route."""
    a, b = StubHost(0), StubHost(1)
    r = Router([a, b], policy="least_loaded", clock=FakeClock())
    a.dead = True
    req = r.route(_q())
    assert r.live_hosts() == [1] and r.counters["drained_hosts"] == 1
    assert req.status == "done" and req.attempts[0][0] == 1


def test_router_validates_queries_without_draining():
    a, b = StubHost(0), StubHost(1)
    r = Router([a, b], clock=FakeClock())
    for bad in (np.zeros((4, 3), np.float32), np.zeros((0, 2), np.float32),
                np.zeros((4, 2), np.int32)):
        with pytest.raises(ValueError):
            r.route(bad)
    assert r.live_hosts() == [0, 1]              # malformed input != death


def test_router_heartbeat_timeout_probes_then_drains_and_resubmits():
    clock = FakeClock()
    a, b = StubHost(0), StubHost(1)
    a.hold = True                                # a accepts but never serves
    r = Router([a, b], heartbeat_timeout_s=10.0, clock=clock)
    stuck = r.route(_q())                        # round-robin -> host 0
    assert stuck.attempts[0][0] == 0 and not stuck.done
    clock.t = 11.0
    r.beat(1)                                    # b is alive, a went silent
    # stale heartbeat alone is NOT death: a still answers its probe
    assert r.check() == [] and r.live_hosts() == [0, 1]
    clock.t = 23.0
    a.dead = True                                # now the probe fails too
    assert r.check() == [0]
    assert r.live_hosts() == [1]
    # the stuck request was resubmitted to b, which serves instantly
    assert stuck.attempts[-1][0] == 1
    assert r.wait(stuck, timeout=5.0).status == "done"
    assert r.counters["resubmitted"] == 1 and r.counters["drained_hosts"] == 1


def test_router_idle_fleet_not_drained_by_quiet_period():
    """Review regression: hosts untouched for > heartbeat_timeout_s pass
    their probe and stay in rotation — an idle fleet must not silently
    collapse (there is no re-admission path yet)."""
    clock = FakeClock()
    a, b = StubHost(0), StubHost(1)
    r = Router([a, b], heartbeat_timeout_s=10.0, clock=clock)
    clock.t = 120.0                              # long quiet period
    assert r.check() == [] and r.live_hosts() == [0, 1]
    req = r.route(_q())                          # still serves normally
    assert r.wait(req, timeout=5.0).status == "done"


def test_router_backpressure_is_not_death():
    """Review regression: AdmissionQueueFull routes around the full host
    without draining it; an all-full fleet surfaces backpressure to the
    caller like a single server would."""
    a, b = StubHost(0), StubHost(1)
    r = Router([a, b], clock=FakeClock())
    a.full = True
    for _ in range(3):
        assert r.wait(r.route(_q()), timeout=5.0).status == "done"
    assert len(b.submitted) == 3 and len(a.submitted) == 0
    assert r.live_hosts() == [0, 1]              # a stayed in rotation
    b.full = True
    with pytest.raises(AdmissionQueueFull):
        r.route(_q())
    assert r.live_hosts() == [0, 1]


def test_router_fleet_wide_death_fails_requests_not_hangs():
    a, b = StubHost(0), StubHost(1)
    a.hold = b.hold = True
    r = Router([a, b], clock=FakeClock())
    req = r.route(_q())
    a.dead = b.dead = True
    r.drain(0)                                   # cascade: resubmit hits b,
    assert req.status == "failed" and req.done   # b dead too -> failed, not
    assert r.live_hosts() == []                  # an exception or a hang
    with pytest.raises(NoLiveHosts):
        r.route(_q())


# ---------------------------------------------------------------------------
# 2-host cluster: bit-identity + host death (in-process, CI-fast)
# ---------------------------------------------------------------------------


def _replay_reference(pts, qd, log, pre, post, max_batch=256):
    """Single AsyncAidwServer applying the coordinator's epoch log between
    the same two query waves; returns (pre_results, post_results)."""
    with AsyncAidwServer(pts, max_batch=max_batch, query_domain=qd) as ref:
        r_pre = [ref.submit(q) for q in pre]
        ref.flush(timeout=300)
        for u in log:
            ref.update_dataset(u.points_xyz, inserts=u.inserts,
                               deletes=u.deletes, timeout=300)
        r_post = [ref.submit(q) for q in post]
        ref.flush(timeout=300)
    return r_pre, r_post


def test_cluster_bit_identical_to_single_server_across_concurrent_updates(
        spatial_data):
    """Acceptance (a): interleaved queries + 3 CONCURRENT update_dataset
    calls; every result bit-identical to one server applying the same
    epochs sequentially, on both waves and on every host."""
    pts, qs = spatial_data
    qd = spatial_queries(1024, seed=1)
    pre = [qs[64 * i:64 * (i + 1)] for i in range(4)]
    post = [qs[64 * i:64 * (i + 1)] for i in range(4, 8)]
    with AidwCluster(pts, n_hosts=2, max_batch=256, query_domain=qd) as cl:
        w0 = [cl.submit(q) for q in pre]

        def upd(k):
            cl.update_dataset(
                inserts=spatial_points(16, seed=40 + k),
                deletes=np.arange(k * 16, (k + 1) * 16), timeout=300)

        ts = [threading.Thread(target=upd, args=(k,)) for k in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        w1 = [cl.submit(q) for q in post]
        cl.flush(timeout=300)
        log = list(cl.coordinator.log)
        rep = cl.report()
    assert len(log) == 3 and [u.epoch for u in log] == [1, 2, 3]
    # both hosts applied all three epochs (fleet-wide consistency)
    assert rep["fleet"]["epoch_min"] == rep["fleet"]["epoch_max"] == 3
    # queries were actually spread over both hosts
    assert sorted({r.host_id for r in w0 + w1}) == [0, 1]
    # epoch stamps witness the contract: wave 0 pre-churn, wave 1 post
    assert all(r.epoch == 0 for r in w0)
    assert all(r.epoch == 3 for r in w1)

    r0, r1 = _replay_reference(pts, qd, log, pre, post)
    for got, want in zip(w0 + w1, r0 + r1):
        assert got.status == "done"
        assert np.array_equal(np.asarray(got.values),
                              np.asarray(want.values))
    # exactly-once: every uid distinct, every request terminal
    assert len({r.uid for r in w0 + w1}) == 8


def test_cluster_host_death_mid_stream_no_lost_or_duplicated(spatial_data):
    """Acceptance (b): a host dies mid-stream; the router drains it,
    resubmits its unserved requests, and results still match the
    single-server reference (same epochs)."""
    pts, qs = spatial_data
    qd = spatial_queries(1024, seed=1)
    batches = [qs[32 * i:32 * (i + 1)] for i in range(8)]
    with AidwCluster(pts, n_hosts=2, max_batch=256, query_domain=qd) as cl:
        warm = [cl.submit(q) for q in batches[:2]]
        cl.flush(timeout=300)
        epoch = cl.update_dataset(inserts=spatial_points(16, seed=9),
                                  deletes=np.arange(16), timeout=300)
        assert epoch == 1

        def boom(*a, **k):
            raise RuntimeError("injected host fault")

        cl.hosts[1].server.session.query = boom   # dies on next dispatch
        reqs = [cl.submit(q) for q in batches]
        cl.flush(timeout=300)
        rep = cl.report()
        assert rep["routing"]["live_hosts"] == [0]
        assert rep["routing"]["drained_hosts"] == 1
        assert rep["routing"]["resubmitted"] >= 1
        # no lost (all terminal, served), no duplicated (distinct uids,
        # resolved exactly once)
        assert all(r.status == "done" and r.values is not None
                   for r in warm + reqs)
        assert len({r.uid for r in warm + reqs}) == 10
        log = list(cl.coordinator.log)
    with AsyncAidwServer(pts, max_batch=256, query_domain=qd) as ref:
        for u in log:
            ref.update_dataset(u.points_xyz, inserts=u.inserts,
                               deletes=u.deletes, timeout=300)
        want = [ref.submit(q) for q in batches]
        ref.flush(timeout=300)
    for got, w in zip(reqs, want):
        assert np.array_equal(np.asarray(got.values), np.asarray(w.values))


def test_cluster_kill_mid_batch_keeps_one_connected_trace(spatial_data):
    """ISSUE 8 acceptance: a host killed mid-batch with tracing on.  The
    drain-resubmission records a ``resubmit`` span as a CHILD of the
    original request's route root on the SAME trace — one connected trace
    per request, zero lost spans (every done request has exactly one
    serving span set) and zero duplicated ones (the dead host never
    scattered, so it contributed none)."""
    pts, qs = spatial_data
    qd = spatial_queries(1024, seed=1)
    batches = [qs[32 * i:32 * (i + 1)] for i in range(6)]
    with AidwCluster(pts, n_hosts=2, max_batch=256, query_domain=qd,
                     trace_sample_rate=1.0) as cl:
        warm = [cl.submit(q) for q in batches[:2]]
        cl.flush(timeout=300)
        cl.collect_spans()                     # drop the warmup spans

        def boom(*a, **k):
            raise RuntimeError("injected host fault")

        cl.hosts[1].server.session.query = boom   # dies on next dispatch
        reqs = [cl.submit(q) for q in batches]
        cl.flush(timeout=300)
        spans = cl.collect_spans()
        rep = cl.report()
    assert rep["routing"]["resubmitted"] >= 1
    assert all(r.status == "done" for r in warm + reqs)

    by_trace: dict = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s)
    # every request kept ONE trace end to end: its root route span, its
    # serving spans, and (for drained requests) the resubmit child
    assert all(r.trace_id in by_trace for r in reqs)
    assert len({r.trace_id for r in reqs}) == len(reqs)
    resubmits = [s for s in spans if s["name"] == "resubmit"]
    assert resubmits, "drain-resubmission recorded no spans"
    for trace_id, trace in by_trace.items():
        roots = [s for s in trace if s["name"] == "route"]
        assert len(roots) == 1, f"trace {trace_id} has {len(roots)} roots"
        root = roots[0]
        for s in trace:
            if s["name"] == "resubmit":
                # the resubmission is a child of the ORIGINAL route span —
                # the kill shows up inside the request's trace, not as a
                # disconnected second trace
                assert s["parent_id"] == root["span_id"]
                assert s["args"]["attempt"] >= 1
        # zero lost / zero duplicated serving spans: exactly one full
        # queue_wait/coalesce/execute/scatter set per completed request
        for name in ("queue_wait", "coalesce", "execute", "scatter"):
            got = [s for s in trace if s["name"] == name]
            assert len(got) == 1, \
                f"trace {trace_id}: {len(got)} {name} spans"
            assert got[0]["parent_id"] == root["span_id"]
    # the dead host contributed no serving spans (it never scattered) —
    # all serving-side spans come from the surviving host or the router
    serving = [s for s in spans if s["name"] in
               ("queue_wait", "coalesce", "execute", "scatter")]
    assert {s["host"] for s in serving} == {"0"}


def test_cluster_least_loaded_policy_serves_all(spatial_data):
    pts, qs = spatial_data
    qd = spatial_queries(1024, seed=1)
    with AidwCluster(pts, n_hosts=2, max_batch=256, query_domain=qd,
                     policy="least_loaded") as cl:
        reqs = [cl.submit(qs[32 * i:32 * (i + 1)]) for i in range(8)]
        cl.flush(timeout=300)
        assert all(r.status == "done" for r in reqs)
        assert cl.report()["fleet"]["completed"] == 8


# ---------------------------------------------------------------------------
# multi-process fleets (slow: subprocess spawning; the CI cluster-suite gate)
# ---------------------------------------------------------------------------


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_cluster_two_process_jax_distributed_bit_identical():
    """The acceptance workload across REAL process boundaries: 2 processes
    x 4 forced host devices each, jax.distributed initialized on both, the
    socket control plane carrying routed queries + 3 epoch-broadcast
    updates, and results bit-identical to a single in-process server
    replaying the coordinator's epoch log."""
    import os
    import subprocess
    import sys

    jax_port, ctrl_port = _free_port(), _free_port()
    code = f"""
import os, numpy as np
from repro.data.pipeline import spatial_points, spatial_queries
from repro.serving import AsyncAidwServer
from repro.serving.cluster import (AidwCluster, ClusterConfig, HostServer,
                                   RemoteHost, bootstrap)
from repro.serving.cluster.rpc import spawn_worker

import jax
# spawn the worker FIRST: jax.distributed.initialize barriers until every
# fleet process registers with the coordination service
env = dict(os.environ)
proc = spawn_worker(1, 2, points=2048, seed=0, control_port={ctrl_port},
                    max_batch=256,
                    jax_coordinator="127.0.0.1:{jax_port}", env=env)
ctx = bootstrap(ClusterConfig(
    n_hosts=2, host_id=0, jax_coordinator="127.0.0.1:{jax_port}",
    control_port={ctrl_port}))
assert ctx.jax_distributed and jax.process_count() == 2
assert len(jax.local_devices()) == 4 and len(jax.devices()) == 8
assert ctx.mesh is not None and ctx.mesh.devices.size == 4

pts = spatial_points(2048, seed=0)
qs = spatial_queries(512, seed=1)
qd = spatial_queries(1024, seed=1)
local = HostServer(0, pts, max_batch=256, query_domain=qd, mesh=ctx.mesh)
remote = RemoteHost(1, ("127.0.0.1", {ctrl_port} + 1), connect_timeout_s=300)

pre = [qs[64*i:64*(i+1)] for i in range(4)]
post = [qs[64*i:64*(i+1)] for i in range(4, 8)]
with AidwCluster(hosts=[local, remote]) as cl:
    w0 = [cl.submit(q) for q in pre]
    for k in range(3):
        cl.update_dataset(inserts=spatial_points(16, seed=40 + k),
                          deletes=np.arange(k*16, (k+1)*16), timeout=300)
    w1 = [cl.submit(q) for q in post]
    cl.flush(timeout=600)
    rep = cl.report()
    log = list(cl.coordinator.log)
ctx.shutdown()       # join the fleet shutdown barrier with the worker
proc.wait(timeout=120)
assert proc.returncode == 0, proc.returncode
assert rep["fleet"]["hosts"] == 2
assert rep["fleet"]["epoch_min"] == rep["fleet"]["epoch_max"] == 3
assert rep["fleet"]["latency"]["total"]["p99_s"] > 0
assert sorted({{r.host_id for r in w0 + w1}}) == [0, 1]
assert local.server.session.stats["devices"] == 4

with AsyncAidwServer(pts, max_batch=256, query_domain=qd) as ref:
    r0 = [ref.submit(q) for q in pre]
    ref.flush(timeout=300)
    for u in log:
        ref.update_dataset(inserts=u.inserts, deletes=u.deletes, timeout=300)
    r1 = [ref.submit(q) for q in post]
    ref.flush(timeout=300)
for got, want in zip(w0 + w1, r0 + r1):
    assert got.status == "done"
    assert np.array_equal(np.asarray(got.values), np.asarray(want.values))
print("2proc cluster ok", rep["fleet"]["completed"])
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env, timeout=600,
                         capture_output=True, text=True)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "2proc cluster ok 8" in out.stdout


@pytest.mark.slow
def test_load_gen_cluster_procs_merged_report():
    """The CI fleet-latency artifact path: load_gen --cluster 2
    --cluster-procs --json produces a merged report with summed counters
    and fleet percentiles, and loses nothing."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "load_gen.py"),
         "--cluster", "2", "--cluster-procs", "--json", "--requests", "24",
         "--rate", "150", "--points", "4096"],
        env=env, timeout=600, capture_output=True, text=True)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    rep = json.loads(out.stdout)
    assert rep["lost"] == 0 and rep["duplicated"] == 0
    fleet = rep["report"]
    assert fleet["hosts"] == 2 and len(rep["hosts"]) == 2
    assert fleet["completed"] == sum(h["completed"] for h in rep["hosts"])
    assert fleet["latency"]["total"]["p99_s"] > 0
    assert fleet["queries_per_s"] > 0
    # per-host histograms really merged: fleet count = sum of host counts
    assert fleet["latency"]["total"]["count"] == sum(
        h["latency"]["total"]["count"] for h in rep["hosts"])


# ---------------------------------------------------------------------------
# fleet data partitioning (ShardedAidwCluster; PR 5 acceptance gate)
# ---------------------------------------------------------------------------


def test_sharded_cluster_matches_replica():
    """Acceptance: a 2-shard cluster (points PARTITIONED, not replicated)
    answers a query batch within f32 accumulation tolerance of a 1-host
    full-replica server — the client-side k-way merge over per-shard grid
    kNN + Eq. (1) partial sums."""
    from repro.serving.cluster import ShardedAidwCluster

    pts = spatial_points(8192, seed=0)
    qd = spatial_queries(1024, seed=1)
    qs = spatial_queries(500, seed=2)
    with AsyncAidwServer(pts, query_domain=qd) as replica, \
            ShardedAidwCluster(pts, n_hosts=2, query_domain=qd) as fleet:
        want = replica.result(replica.submit(qs))
        got = fleet.query(qs, timeout=300)
        assert got.epoch == 0
        err = np.abs(np.asarray(want.values) - got.values).max()
        assert err < 1e-4, err
        rep = fleet.report()
        assert rep["n_points"] == pts.shape[0]
        assert sum(rep["shard_sizes"]) == pts.shape[0]
        assert min(rep["shard_sizes"]) > 0       # really partitioned


def test_sharded_cluster_delta_routing_and_epochs():
    """Deltas split by owning shard under one epoch (empty pieces keep the
    per-host epoch streams dense); post-delta results still match the
    replica applying the same global delta; concurrent churn retries keep
    every merged batch on ONE epoch."""
    from repro.serving.cluster import ShardedAidwCluster

    pts = spatial_points(8192, seed=0)
    qd = spatial_queries(1024, seed=1)
    qs = spatial_queries(300, seed=2)
    rng = np.random.default_rng(5)
    with AsyncAidwServer(pts, query_domain=qd) as replica, \
            ShardedAidwCluster(pts, n_hosts=2, query_domain=qd) as fleet:
        dels = rng.choice(pts.shape[0], 120, replace=False)
        ins = spatial_points(100, seed=9)
        replica.update_dataset(inserts=ins, deletes=dels)
        assert fleet.update_dataset(inserts=ins, deletes=dels,
                                    timeout=300) == 1
        assert fleet.m == pts.shape[0] - 120 + 100
        # every host saw epoch 1 (even if its piece was small/empty)
        assert all(h.epoch == 1 for h in fleet.hosts)
        want = replica.result(replica.submit(qs))
        got = fleet.query(qs, timeout=300)
        assert got.epoch == 1
        err = np.abs(np.asarray(want.values) - got.values).max()
        assert err < 1e-4, err

        # interleave queries with churn: merged batches stay epoch-pure
        done = []

        def churn():
            for i in range(3):
                fleet.update_dataset(
                    inserts=spatial_points(40, seed=20 + i),
                    deletes=np.arange(40) * 2, timeout=300)

        t = threading.Thread(target=churn)
        t.start()
        for i in range(6):
            out = fleet.query(spatial_queries(80, seed=40 + i), timeout=300)
            assert np.isfinite(out.values).all()
            done.append(out.epoch)
        t.join()
        assert fleet.epoch == 4
        assert all(e in range(0, 5) for e in done)


def test_sharded_cluster_validates_queries_like_the_router():
    """The shard fan-out shares validate_queries with the server/router
    admission surfaces: malformed arrays bounce at the boundary instead of
    reaching (and killing) shard workers."""
    from repro.serving.cluster import ShardedAidwCluster

    pts = spatial_points(2048, seed=0)
    with ShardedAidwCluster(pts, n_hosts=2,
                            query_domain=spatial_queries(256, seed=1)) as fl:
        for bad in (np.zeros((0, 2), np.float32),
                    np.zeros((4, 3), np.float32),
                    np.zeros((4, 2), np.int32)):
            with pytest.raises(ValueError):
                fl.query(bad)
        # a shard op reaching the server directly hits the same check
        with pytest.raises(ValueError):
            fl.hosts[0].shard_knn(np.zeros((4, 3), np.float32))


@pytest.mark.slow
def test_sharded_cluster_subprocess_shard_worker():
    """The fleet-partitioned deployment shape across a REAL process
    boundary: host 1 is a subprocess serving shard 1 of the deterministic
    fleet_partition (rpc --shard-of), shard ops travel the socket control
    plane, and the merged results still match the full-replica server."""
    import os

    from repro.serving.cluster import (HostServer as HS, RemoteHost,
                                       ShardedAidwCluster, fleet_partition)
    from repro.serving.cluster.rpc import free_port_base, spawn_worker

    n_pts, seed = 4096, 0
    pts = spatial_points(n_pts, seed=seed)
    qd = spatial_queries(1024, seed=1)
    qs = spatial_queries(300, seed=2)
    _, _, members = fleet_partition(pts, 2, query_domain=qd)
    base = free_port_base(2)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    worker = spawn_worker(1, 2, points=n_pts, seed=seed, control_port=base,
                          shard_of=2, env=env)
    try:
        hosts = [HS(0, pts[members[0]], query_domain=qd),
                 RemoteHost(1, ("127.0.0.1", base + 1),
                            connect_timeout_s=300)]
        with AsyncAidwServer(pts, query_domain=qd) as replica, \
                ShardedAidwCluster(pts, n_hosts=2, hosts=hosts,
                                   query_domain=qd) as fleet:
            want = replica.result(replica.submit(qs))
            got = fleet.query(qs, timeout=300)
            err = np.abs(np.asarray(want.values) - got.values).max()
            assert err < 1e-4, err
            # delta routed across the process boundary under one epoch
            dels = np.arange(0, 200, 2)
            ins = spatial_points(64, seed=9)
            replica.update_dataset(inserts=ins, deletes=dels)
            assert fleet.update_dataset(inserts=ins, deletes=dels,
                                        timeout=300) == 1
            want2 = replica.result(replica.submit(qs))
            got2 = fleet.query(qs, timeout=300)
            assert got2.epoch == 1
            err2 = np.abs(np.asarray(want2.values) - got2.values).max()
            assert err2 < 1e-4, err2
    finally:
        try:
            worker.wait(timeout=60)
        except Exception:
            worker.kill()


def test_sharded_cluster_rejected_update_consumes_no_epoch():
    """Review-driven regression: a REJECTED update (bad delete index /
    empty-shard full refresh) must not consume an epoch — a gap would
    wedge every host's EpochApplier forever.  Validation runs before
    assignment, so the fleet stays fully usable."""
    from repro.serving.cluster import ShardedAidwCluster

    pts = spatial_points(4096, seed=0)
    with ShardedAidwCluster(pts, n_hosts=2,
                            query_domain=spatial_queries(256, seed=1)) as fl:
        with pytest.raises(IndexError):
            fl.update_dataset(deletes=[10**6])
        with pytest.raises(ValueError):      # all points into one shard
            fl.update_dataset(points_xyz=np.concatenate(
                [np.zeros((64, 2), np.float32) + 0.01,
                 np.ones((64, 1), np.float32)], axis=1))
        assert fl.epoch == 0                 # nothing consumed
        assert fl.update_dataset(inserts=spatial_points(32, seed=5),
                                 deletes=np.arange(32), timeout=300) == 1
        out = fl.query(spatial_queries(64, seed=2), timeout=300)
        assert out.epoch == 1
        assert np.isfinite(out.values).all()


def test_sharded_cluster_full_refresh_replans_and_bbox_guard():
    """Review-driven regression: a FULL refresh re-plans the fleet grid
    (study area + shard routing track the new data like a full-replica
    re-plan), while an out-of-bbox DELTA insert is rejected without
    consuming an epoch (the fleet spec is frozen across deltas, like
    plan_delta's bbox fallback)."""
    from repro.serving.cluster import ShardedAidwCluster

    pts = spatial_points(8192, seed=0)
    qd = spatial_queries(512, seed=1)
    with AsyncAidwServer(pts, query_domain=qd) as rep, \
            ShardedAidwCluster(pts, n_hosts=2, query_domain=qd) as fl:
        with pytest.raises(ValueError):
            fl.update_dataset(
                inserts=np.array([[9.0, 9.0, 1.0]], np.float32))
        assert fl.epoch == 0
        old_area = fl.area
        pts2 = spatial_points(8192, seed=7) \
            * np.array([2.0, 2.0, 1.0], np.float32)
        rep.update_dataset(points_xyz=pts2)
        assert fl.update_dataset(points_xyz=pts2, timeout=300) == 1
        assert fl.area > 2 * old_area        # spec really re-planned
        qs2 = (spatial_queries(200, seed=8) * 2.0).astype(np.float32)
        want = rep.result(rep.submit(qs2))
        got = fl.query(qs2, timeout=300)
        assert got.epoch == 1
        err = np.abs(np.asarray(want.values) - got.values).max()
        assert err < 1e-4, err


def test_sharded_cluster_churn_with_compaction_matches_replay():
    """ISSUE 7 acceptance: a sharded fleet under CONCURRENT writer churn
    plus a fleet-wide COMPACTION epoch matches a single grid_ring server
    replaying the coordinator's epoch log — compaction epochs replayed AS
    compactions (they carry no delta payload; replaying them through
    update_dataset would corrupt the replay), everything else in epoch
    order."""
    from repro.serving.cluster import ShardedAidwCluster

    pts = spatial_points(8192, seed=0)
    qd = spatial_queries(1024, seed=1)
    qs = spatial_queries(300, seed=2)
    lo, hi = pts[:, :2].min(axis=0), pts[:, :2].max(axis=0)

    def _ins(seed, n=32):
        # clip into the frozen bbox: both the fleet spec and the replay
        # server's plan_delta freeze the grid across deltas
        ins = spatial_points(n, seed=seed)
        ins[:, :2] = np.clip(ins[:, :2], lo, hi)
        return ins

    with ShardedAidwCluster(pts, n_hosts=2, query_domain=qd) as fleet:

        def churn(k):
            fleet.update_dataset(inserts=_ins(60 + k),
                                 deletes=np.arange(k * 32, (k + 1) * 32),
                                 timeout=300)

        ts = [threading.Thread(target=churn, args=(k,)) for k in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert fleet.compact(timeout=300) == 4   # fleet-wide ring fold
        fleet.update_dataset(inserts=_ins(99), deletes=np.arange(16),
                             timeout=300)
        got = fleet.query(qs, timeout=300)
        assert got.epoch == 5
        log = list(fleet.coordinator.log)
    assert [u.compact for u in log] == [False, False, False, True, False]
    assert log[3].points_xyz is None and log[3].inserts is None \
        and log[3].deletes is None               # compact carries no delta
    # the replay reference runs the grid_ring layout so the compaction
    # epoch really folds hot rings into the slab CSR mid-log
    mesh = jax.make_mesh((1,), ("q",))
    with AsyncAidwServer(pts, query_domain=qd, mesh=mesh,
                         layout="grid_ring", ring_cap=512) as ref:
        for u in log:
            if u.compact:
                ref.compact(timeout=300)
            else:
                ref.update_dataset(u.points_xyz, inserts=u.inserts,
                                   deletes=u.deletes, timeout=300)
        assert ref.session.stats["compactions"] >= 1
        assert ref.session.stats["ring_points"] == 32   # post-compact delta
        want = ref.result(ref.submit(qs))
    # sharded merge is f32-accumulation tolerant of a replica (1e-4) and
    # the grid_ring layout adds its own documented 1-ulp Stage-2 caveat
    err = np.abs(np.asarray(want.values) - got.values).max()
    assert err < 5e-4, err


# ---------------------------------------------------------------------------
# ISSUE 9: fleet-wide debugz bundles
# ---------------------------------------------------------------------------


class _AnomalyReq:
    """Stamped-timestamp stub for injecting deterministic anomalies into a
    host's live flight recorder (the debugz merge is what's under test,
    not the serving path that normally feeds it)."""

    def __init__(self, uid, *, deadline=None, t_submit=0.0,
                 t_dispatch=None, t_done=None):
        self.uid = uid
        self.deadline = deadline
        self.overflow = 0
        self.zero_weight = 0
        self.t_submit = t_submit
        self.t_dispatch = t_dispatch
        self.t_done = t_done
        self.trace_id = None
        self.epoch = None


def _inject_tail(rec, base_uid):
    """50 in-SLO 10ms requests + one 1s deadline-misser whose excess is
    all queue_wait — a deterministic p99-p50 gap with a retained tail."""
    for i in range(50):
        r = _AnomalyReq(base_uid + i, t_submit=0.0, t_dispatch=0.001,
                        t_done=0.01)
        rec.observe_request(r, t0=0.001, t1=0.01, t2=0.01, last_submit=0.0)
    slow = _AnomalyReq(base_uid + 50, deadline=0.5, t_submit=0.0,
                       t_dispatch=0.99, t_done=1.0)
    rec.observe_request(slow, t0=0.99, t1=1.0, t2=1.0, last_submit=0.0)


def test_cluster_debugz_merged_bundle_schema_and_attribution(spatial_data):
    """ISSUE 9 acceptance: ``AidwCluster.debugz()`` on a 2-host fleet
    returns ONE merged bundle — per-host sections, bin-exact fleet stage
    registry, fleet SLO events, and a tail-latency attribution whose
    per-stage contributions sum within 15% of the p99-p50 gap."""
    import json

    pts, qs = spatial_data
    qd = spatial_queries(1024, seed=1)
    with AidwCluster(pts, n_hosts=2, max_batch=256, query_domain=qd) as cl:
        reqs = [cl.submit(qs[32 * i:32 * (i + 1)]) for i in range(4)]
        cl.update_dataset(inserts=spatial_points(16, seed=9),
                          deletes=np.arange(16), timeout=300)
        cl.flush(timeout=300)
        assert all(r.status == "done" for r in reqs)
        # deterministic anomaly injection into the LIVE recorders: each
        # host retains one queue_wait-dominated deadline-misser
        for k, host in enumerate(cl.hosts):
            _inject_tail(host.server.recorder, base_uid=1000 * (k + 1))
        bundle = cl.debugz()

    assert set(bundle) == {"epoch", "hosts", "unreachable", "routing",
                           "fleet", "slo", "attribution"}
    assert sorted(bundle["hosts"]) == ["0", "1"] \
        and bundle["unreachable"] == []
    assert bundle["epoch"] == 1
    for hid, hb in bundle["hosts"].items():
        assert hb["host_id"] == int(hid) and hb["alive"]
        assert hb["recorder"]["requests"] >= 51
        assert {"targets", "rates", "gauges", "events"} <= set(hb["slo"])
    fleet = bundle["fleet"]
    assert fleet["epochs"] == {"min": 1, "max": 1,
                               "by_host": {"0": 1, "1": 1}}
    # bin-exact fleet merge: both hosts' serving walls in one histogram
    served = sum(b["recorder"]["anomalies"]["deadline_miss"]
                 for b in bundle["hosts"].values())
    assert served == 2
    assert "serving/queue_wait_s" in fleet["stages"]["histograms"]

    # THE acceptance identity, on the merged fleet attribution
    attr = bundle["attribution"]
    # 102 injected + the real served traffic also folded by the recorder
    assert attr["n_total"] >= 102 and attr["tail_n"] >= 2
    gap = attr["gap_s"]
    assert gap > 0
    assert abs(attr["attributed_s"] - gap) <= 0.15 * gap
    assert attr["stages"]["queue_wait"]["share"] > 0.9
    json.dumps(bundle)                       # one JSON artifact, as shipped


def test_cluster_debugz_partial_bundle_when_host_unreachable(spatial_data):
    """Diagnostics must never drain a host: a host whose debugz pull
    FAILS lands in ``unreachable`` — it is not drained, the other host's
    bundle and the fleet merge still come back whole (the bundle stays
    useful mid-incident, which is exactly when it is pulled)."""
    import json

    pts, qs = spatial_data
    qd = spatial_queries(1024, seed=1)
    with AidwCluster(pts, n_hosts=2, max_batch=256, query_domain=qd) as cl:
        reqs = [cl.submit(qs[32 * i:32 * (i + 1)]) for i in range(4)]
        cl.flush(timeout=300)

        def boom(*a, **k):
            raise RuntimeError("injected debugz fault")

        cl.hosts[1].server.debugz = boom
        bundle = cl.debugz()
        # the pull failure did NOT drain the host: it still serves
        assert cl.router.live_hosts() == [0, 1]
        after = cl.submit(qs[:16])
        cl.flush(timeout=300)
        assert after.status == "done"

    assert sorted(bundle["hosts"]) == ["0"]
    assert bundle["unreachable"] == ["1"]
    assert bundle["hosts"]["0"]["alive"]
    assert bundle["fleet"]["epochs"]["by_host"] == {"0": 0}
    assert bundle["attribution"]["n_total"] >= 0
    assert all(r.status == "done" for r in reqs)
    json.dumps(bundle)
