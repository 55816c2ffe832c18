"""Async AIDW serving subsystem: admission queue, deadline-aware coalescing,
telemetry, sync/async drive-mode equivalence, and write-path serialization.

Acceptance criteria covered here (ISSUE 3):
(a) AsyncAidwServer results bit-identical to the synchronous engine for the
    same request set with no deadlines;
(b) p99 latency reported and no lost/duplicated requests across >= 3
    interleaved delta updates;
(c) deadline-aware mode sheds expired requests instead of serving them late;
plus the satellite regressions: per-call vs cumulative engine stats,
per-request overflow propagation, and no-deadline FIFO coalescing
byte-for-byte compatibility.

The whole module also runs under the CI serving-suite job's 8-forced-host-
device config (``XLA_FLAGS=--xla_force_host_platform_device_count=8``): the
mesh tests below pick up every visible device, and the slow-marked
subprocess test forces the 8-device mesh regardless of this process's
device count.
"""

from __future__ import annotations

from collections import deque

import numpy as np
import pytest

from conftest import run_multidevice
from repro.core import AidwConfig, execute
from repro.data.pipeline import spatial_points, spatial_queries
from repro.serving import (AdmissionQueue, AdmissionQueueFull, AidwEngine,
                           AsyncAidwServer, DeadlineCoalescer,
                           ExecuteTimeModel, InterpolationRequest,
                           LatencyHistogram)


def _requests(qs, n_reqs, per=64, deadline=None):
    return [InterpolationRequest(uid=i, queries_xy=qs[per * i:per * (i + 1)],
                                 deadline=deadline)
            for i in range(n_reqs)]


# ---------------------------------------------------------------------------
# admission queue
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def test_admission_queue_fifo_bound_and_shed():
    clock = FakeClock()
    q = AdmissionQueue(max_depth=3, clock=clock)
    a = InterpolationRequest(uid=0, queries_xy=np.zeros((1, 2), np.float32))
    b = InterpolationRequest(uid=1, queries_xy=np.zeros((1, 2), np.float32))
    assert q.put(a) and q.put(b)
    # expired on arrival: refused admission, counted, NOT enqueued
    ex = InterpolationRequest(uid=2, queries_xy=np.zeros((1, 2), np.float32),
                              deadline=-1.0)
    assert q.put(ex) is False
    assert q.counters["shed_expired"] == 1
    assert len(q) == 2
    # bounded depth: non-blocking put raises once full
    q.put(InterpolationRequest(uid=3,
                               queries_xy=np.zeros((1, 2), np.float32)))
    with pytest.raises(AdmissionQueueFull):
        q.put(InterpolationRequest(uid=4,
                                   queries_xy=np.zeros((1, 2), np.float32)),
              block=False)
    assert q.counters["rejected_full"] == 1
    # blocking put with timeout also rejects loudly (clock never advances the
    # consumer, so use a real-time-free zero timeout)
    with pytest.raises(AdmissionQueueFull):
        q.put(InterpolationRequest(uid=5,
                                   queries_xy=np.zeros((1, 2), np.float32)),
              timeout=0.0)
    # FIFO pop order
    assert q.get().uid == 0
    assert q.get().uid == 1
    assert [r.uid for r in q.drain()] == [3]
    q.close()
    assert q.get() is None
    with pytest.raises(Exception):
        q.put(a)


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


def test_telemetry_reset_isolates_warmup():
    from repro.serving import Telemetry

    class _R:
        queries_xy = np.zeros((4, 2), np.float32)
        overflow = 0
        t_submit = 1.0
        t_dispatch = 2.0
        t_done = 3.0

    t = Telemetry()
    t.record_submit(_R())
    t.record_batch([_R()], 0.5)
    assert t.counters["completed"] == 1
    t.reset()                                # post-warmup: a clean window
    assert t.counters["completed"] == t.counters["submitted"] == 0
    assert t.total.count == 0 and t.queries_per_s() == 0.0
    t.record_batch([_R()], 0.5)              # still records after reset
    assert t.counters["completed"] == 1


def test_latency_histogram_percentiles():
    h = LatencyHistogram()
    assert h.percentile(99) == 0.0
    for ms in range(1, 101):                 # 1..100 ms uniform
        h.record(ms / 1000.0)
    snap = h.snapshot()
    assert snap["count"] == 100
    assert 0.040 <= snap["p50_s"] <= 0.070   # log-binned upper-edge estimate
    assert 0.090 <= snap["p95_s"] <= 0.110
    assert 0.095 <= snap["p99_s"] <= 0.100   # clamped to observed max
    assert snap["max_s"] == pytest.approx(0.1)
    assert snap["p50_s"] <= snap["p95_s"] <= snap["p99_s"] <= snap["max_s"]


# ---------------------------------------------------------------------------
# deadline-aware coalescing (deterministic: fake clock + primed estimator)
# ---------------------------------------------------------------------------


def _greedy_reference(requests, max_batch):
    """The pre-subsystem FIFO coalescing (PR 1 engine loop), verbatim."""
    groups, i = [], 0
    while i < len(requests):
        group = [requests[i]]
        size = group[0].queries_xy.shape[0]
        i += 1
        while i < len(requests) and \
                size + requests[i].queries_xy.shape[0] <= max_batch:
            group.append(requests[i])
            size += requests[i].queries_xy.shape[0]
            i += 1
        groups.append(group)
    return groups


def test_no_deadline_coalescing_matches_greedy_byte_for_byte():
    """Satellite: a no-deadline workload reproduces the classic FIFO
    coalescing exactly — same groups, same member order — across random
    request-size mixes."""
    rng = np.random.default_rng(0)
    for trial in range(20):
        sizes = rng.integers(1, 400, size=rng.integers(1, 30))
        reqs = [InterpolationRequest(uid=i,
                                     queries_xy=np.zeros((s, 2), np.float32))
                for i, s in enumerate(sizes)]
        max_batch = int(rng.choice([256, 512, 1024]))
        coal = DeadlineCoalescer(max_batch, ExecuteTimeModel(),
                                 clock=FakeClock())
        groups, shed = coal.coalesce(reqs)
        assert shed == []
        ref = _greedy_reference(reqs, max_batch)
        assert [[r.uid for r in g] for g in groups] == \
            [[r.uid for r in g] for g in ref], (trial, sizes, max_batch)


def test_near_deadline_closes_batch_early():
    """A measured execute-time estimate + a tight deadline close the batch
    before max_batch; without the deadline the same requests coalesce."""
    clock = FakeClock(100.0)
    est = ExecuteTimeModel(min_bucket=64)
    est.record(64, 0.010)        # 64-bucket measured at 10ms
    est.record(128, 0.050)       # crossing into the 128 bucket costs 50ms
    coal = DeadlineCoalescer(1024, est, clock=clock)

    def reqs(deadline):
        return [InterpolationRequest(
            uid=i, queries_xy=np.zeros((48, 2), np.float32),
            deadline=deadline) for i in range(4)]

    # no deadline: all four coalesce (48*4=192 <= max_batch)
    groups, _ = coal.coalesce(reqs(None), now=clock())
    assert [len(g) for g in groups] == [4]
    # 30ms deadline: 48 fits (64-bucket, 10ms) but growing to 96 queries
    # crosses into the 128 bucket (50ms > 30ms) -> close early at one request
    groups, shed = coal.coalesce(reqs(clock() + 0.030), now=clock())
    assert shed == []
    assert [len(g) for g in groups] == [1, 1, 1, 1]
    # 80ms deadline: 96 queries (128 bucket, 50ms) still meets it, growing to
    # 144 (256-bucket extrapolation ~100ms) does not -> pairs
    groups, _ = coal.coalesce(reqs(clock() + 0.080), now=clock())
    assert [len(g) for g in groups] == [2, 2]


def test_estimator_keys_on_dataset_size():
    """Satellite: the execute-time model keys on (query bucket, n_points
    bucket), so deadline early-close stays calibrated right after a large
    delta update instead of trusting EWMAs measured at the old size."""
    est = ExecuteTimeModel(min_bucket=64, n_points=4096)
    est.record(64, 0.010)                    # small dataset: 10ms
    est.n_points = 65536                     # large delta update lands
    assert est.estimate(64) == pytest.approx(0.010)   # fallback: nearest m
    est.record(64, 0.080)                    # measured at the new size
    assert est.estimate(64) == pytest.approx(0.080)
    est.n_points = 4096                      # shrink back: old key still live
    assert est.estimate(64) == pytest.approx(0.010)
    # unseen query bucket: nearest n at the SAME dataset size, scaled in n
    assert est.estimate(128) == pytest.approx(0.020)


def test_deadline_close_recalibrates_after_resize():
    """Satellite regression (primed estimator + fake clock): after a large
    update the coalescer's early-close uses the estimate measured AT the
    new dataset size, not the stale small-dataset EWMA."""
    clock = FakeClock(100.0)
    est = ExecuteTimeModel(min_bucket=64, n_points=4096)
    est.record(64, 0.005)                    # 64-bucket cheap when small
    est.record(128, 0.008)
    est.n_points = 65536                     # resize
    est.record(64, 0.020)
    est.record(128, 0.200)                   # 128-bucket now blows the SLO
    coal = DeadlineCoalescer(1024, est, clock=clock)

    def reqs(deadline):
        return [InterpolationRequest(
            uid=i, queries_xy=np.zeros((48, 2), np.float32),
            deadline=deadline) for i in range(4)]

    # 50ms deadline at the LARGE size: growing 48 -> 96 queries crosses into
    # the 128 bucket (200ms > 50ms) -> singles.  The stale small-dataset
    # model (8ms) would have coalesced and missed the deadline.
    groups, shed = coal.coalesce(reqs(clock() + 0.050), now=clock())
    assert shed == [] and [len(g) for g in groups] == [1, 1, 1, 1]
    est.n_points = 4096                      # back at the small size: the
    groups, _ = coal.coalesce(reqs(clock() + 0.050), now=clock())
    assert [len(g) for g in groups] == [4]   # old calibration still applies


def test_engine_update_refreshes_estimator_n_points(spatial_data):
    """The engine keeps the estimator's dataset key in sync with the
    session across full and delta updates."""
    pts, qs = spatial_data
    eng = AidwEngine(pts, max_batch=256, query_domain=qs)
    assert eng.estimator.n_points == eng.session.plan.n_points
    eng.update_dataset(inserts=spatial_points(32, seed=5))
    assert eng.estimator.n_points == eng.session.plan.n_points \
        == pts.shape[0] + 32


def test_expired_requests_shed_at_dispatch():
    clock = FakeClock(10.0)
    coal = DeadlineCoalescer(1024, ExecuteTimeModel(), clock=clock)
    live = InterpolationRequest(uid=0,
                                queries_xy=np.zeros((8, 2), np.float32))
    dead = InterpolationRequest(uid=1,
                                queries_xy=np.zeros((8, 2), np.float32),
                                deadline=9.0)
    groups, shed = coal.coalesce([dead, live], now=clock())
    assert [r.uid for g in groups for r in g] == [0]
    assert [r.uid for r in shed] == [1]
    assert shed[0].status == "shed" and shed[0].done
    assert shed[0].values is None            # never served late


def test_coalescer_stops_at_update_barrier():
    class Barrier:                            # no queries_xy attribute
        deadline = None

    reqs = [InterpolationRequest(uid=i,
                                 queries_xy=np.zeros((8, 2), np.float32))
            for i in range(3)]
    pending = deque([reqs[0], reqs[1], Barrier(), reqs[2]])
    coal = DeadlineCoalescer(1024, ExecuteTimeModel(), clock=FakeClock())
    group, shed = coal.next_batch(pending)
    assert [r.uid for r in group] == [0, 1] and not shed
    assert not hasattr(pending[0], "queries_xy")   # barrier left for caller
    # the list-drive mode has no barrier handler: reject loudly, never hang
    with pytest.raises(ValueError):
        coal.coalesce([reqs[0], Barrier(), reqs[2]])


# ---------------------------------------------------------------------------
# synchronous engine facade (stats split + deadline semantics + overflow)
# ---------------------------------------------------------------------------


def test_engine_stats_per_call_vs_cumulative(spatial_data):
    """Satellite regression: run() reports THIS call; self.stats accumulates
    — the two were previously mixed in one dict."""
    pts, qs = spatial_data
    eng = AidwEngine(pts, max_batch=256, query_domain=qs)
    r1 = eng.run(_requests(qs, 4))
    assert (r1["requests"], r1["queries"]) == (4, 256)
    assert "wall_s" in r1 and "queries_per_s" in r1
    r2 = eng.run(_requests(qs, 2))
    # per-call report counts ONLY the second call...
    assert (r2["requests"], r2["queries"]) == (2, 128)
    assert r2["batches"] <= r1["batches"]
    # ...while the cumulative counters sum both and carry no timing keys
    assert eng.stats["requests"] == 6
    assert eng.stats["queries"] == 384
    assert eng.stats["batches"] == r1["batches"] + r2["batches"]
    assert "wall_s" not in eng.stats and "queries_per_s" not in eng.stats


def test_engine_sheds_expired_serves_rest(spatial_data):
    pts, qs = spatial_data
    eng = AidwEngine(pts, max_batch=256, query_domain=qs)
    now = eng.clock()
    reqs = _requests(qs, 4)
    reqs[1].deadline = now - 1.0             # expired on arrival
    reqs[3].deadline = now + 60.0            # comfortably live
    rep = eng.run(reqs)
    assert rep["shed"] == 1 and rep["requests"] == 4
    assert reqs[1].status == "shed" and reqs[1].values is None
    assert all(r.status == "done" and r.values is not None
               for i, r in enumerate(reqs) if i != 1)
    assert eng.stats["shed"] == 1
    assert eng.telemetry.counters["shed"] == 1


class StepClock:
    """Monotonic fake clock that advances by ``step`` on every read."""

    def __init__(self, step: float = 0.1):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        now = self.t
        self.t += self.step
        return now


def test_engine_sheds_requests_that_expire_mid_run(spatial_data):
    """Regression: the sync engine forms batches with a FRESH clock per
    batch (like the async worker) — a request whose deadline expires while
    earlier groups execute is shed at dispatch time, not served late."""
    pts, qs = spatial_data
    clock = StepClock(0.1)
    eng = AidwEngine(pts, max_batch=64, query_domain=qs, clock=clock)
    reqs = _requests(qs, 3)                  # 64 queries each: 3 batches
    reqs[2].deadline = 0.05                  # expires after the first read
    rep = eng.run(reqs)
    assert reqs[2].status == "shed" and reqs[2].values is None
    assert rep["shed"] == 1 and rep["batches"] == 2
    assert all(r.status == "done" for r in reqs[:2])


def test_throughput_window_anchored_at_submit(spatial_data):
    """Regression: a single-batch run must report sane q/s — the window
    opens at the first submit, not at the first completion (which would be
    zero-width and divide by epsilon)."""
    pts, qs = spatial_data
    eng = AidwEngine(pts, max_batch=512, query_domain=qs)
    eng.run(_requests(qs, 2))                # coalesces into ONE batch
    assert eng.telemetry.counters["batches"] == 1
    qps = eng.telemetry.queries_per_s()
    assert 0 < qps < 1e9, qps                # epsilon window would be ~1e11


def test_async_submit_validates_queries(spatial_data):
    """Malformed arrays are rejected at the submit() boundary (a ValueError
    for the offending caller), never admitted to crash the shared worker."""
    pts, qs = spatial_data
    with AsyncAidwServer(pts, query_domain=qs) as srv:
        for bad in (np.zeros((4, 3), np.float32),     # wrong width
                    np.zeros((4,), np.float32),       # 1-D
                    np.zeros((0, 2), np.float32),     # empty
                    np.zeros((4, 2), np.int32)):      # non-float
            with pytest.raises(ValueError):
                srv.submit(bad)
        ok = srv.submit(qs[:8])                       # server still healthy
        assert srv.result(ok, timeout=120).status == "done"
        # auto-uids skip caller-supplied ones instead of colliding
        with_uid = srv.submit(qs[:8], uid=1)
        auto = [srv.submit(qs[:8]) for _ in range(3)]
        assert len({r.uid for r in [with_uid] + auto}) == 4
        srv.flush(timeout=120)


def test_async_worker_death_fails_fast_not_hangs(spatial_data):
    """Regression: a dead worker resolves queued update barriers and closes
    the admission queue, so update_dataset/submit raise instead of hanging
    forever (and close() surfaces the crash)."""
    pts, qs = spatial_data
    srv = AsyncAidwServer(pts, query_domain=qs)
    try:
        good = srv.submit(qs[:16])
        srv.result(good, timeout=120)

        def boom(*a, **k):
            raise RuntimeError("injected session fault")

        srv.session.query = boom             # next dispatch kills the worker
        srv.submit(qs[:8])
        with pytest.raises(Exception):
            srv.update_dataset(inserts=spatial_points(4, seed=1),
                               timeout=60)
        with pytest.raises(Exception):               # worker died or closed
            for _ in range(100):
                srv.submit(qs[:8])
        # a request that COMPLETED before the crash stays retrievable
        assert srv.result(good, timeout=10).status == "done"
    finally:
        with pytest.raises(RuntimeError):    # close() surfaces the crash
            srv.close()


def test_per_request_overflow_propagation():
    """Satellite: per-batch overflow attributes back to the OWNING requests
    (summing the per-query mask per slice), not just engine-wide."""
    pts = spatial_points(2048, seed=0, clustered=True)
    qs = spatial_queries(256, seed=1)
    cfg = AidwConfig(window=64)              # clustered cells overflow w=64
    eng = AidwEngine(pts, cfg, max_batch=512, query_domain=qs)
    reqs = _requests(qs, 4)
    rep = eng.run(reqs)
    res = execute(eng.session.plan, qs)
    mask = np.asarray(res.overflow_mask)
    assert 0 < mask.sum() < len(qs)          # partial overflow: informative
    for i, r in enumerate(reqs):
        assert r.overflow == int(mask[64 * i:64 * (i + 1)].sum()), i
    assert rep["overflow"] == sum(r.overflow for r in reqs) == mask.sum()


def test_engine_no_deadline_results_unchanged(spatial_data):
    """The refactored engine serves a no-deadline workload bit-identically
    to one execute over the same concatenation (the PR 1 contract)."""
    pts, qs = spatial_data
    eng = AidwEngine(pts, max_batch=256, query_domain=qs)
    reqs = _requests(qs, 6)
    eng.run(reqs)
    got = np.concatenate([r.values for r in reqs])
    want = np.asarray(execute(eng.session.plan, qs[:384]).values)
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# async server
# ---------------------------------------------------------------------------


def test_async_matches_sync_bitwise(spatial_data):
    """Acceptance (a): same request set, no deadlines -> async results are
    bit-identical to the synchronous engine's."""
    pts, qs = spatial_data
    eng = AidwEngine(pts, max_batch=256, query_domain=qs)
    sync_reqs = _requests(qs, 8, per=48)
    eng.run(sync_reqs)
    with AsyncAidwServer(pts, max_batch=256, query_domain=qs) as srv:
        async_reqs = [srv.submit(qs[48 * i:48 * (i + 1)]) for i in range(8)]
        srv.flush(timeout=120)
    for s, a in zip(sync_reqs, async_reqs):
        assert a.status == "done"
        assert np.array_equal(s.values, a.values), (s.uid, a.uid)
        assert s.overflow == a.overflow


def test_async_no_lost_or_dup_across_delta_updates(spatial_data):
    """Acceptance (b): >= 3 interleaved incremental dataset updates; every
    request resolves exactly once, updates are FIFO barriers (requests after
    an update see the new dataset), and p99 latency is reported."""
    pts, qs = spatial_data
    m = pts.shape[0]
    with AsyncAidwServer(pts, max_batch=512, query_domain=qs) as srv:
        waves = []
        rng = np.random.default_rng(7)
        for wave in range(4):                # u.q.q.q | u.q.q.q | ... x3 upd
            if wave:
                srv.update_dataset(
                    inserts=spatial_points(16, seed=40 + wave),
                    deletes=rng.choice(m - 32, 16, replace=False))
            waves.append([srv.submit(qs[32 * i:32 * (i + 1)])
                          for i in range(8)])
        srv.flush(timeout=240)
        report = srv.report()
        # no lost or duplicated requests: 32 submitted, 32 distinct uids,
        # every one terminal with exactly one result
        all_reqs = [r for w in waves for r in w]
        assert len({r.uid for r in all_reqs}) == 32
        assert all(r.status == "done" and r.values is not None
                   for r in all_reqs)
        assert report["completed"] == 32 and report["shed"] == 0
        assert report["queries"] == 32 * 32
        assert srv.session.stats["delta_updates"] == 3
        # p99 is reported for all three latency axes
        for axis in ("queue", "execute", "total"):
            assert report["latency"][axis]["count"] > 0
            assert report["latency"][axis]["p99_s"] > 0.0
    # post-update correctness: last wave matches a synchronous engine that
    # applied the same updates in the same order
    eng = AidwEngine(pts, max_batch=512, query_domain=qs)
    rng = np.random.default_rng(7)
    for wave in range(1, 4):
        eng.update_dataset(inserts=spatial_points(16, seed=40 + wave),
                           deletes=rng.choice(m - 32, 16, replace=False))
    ref = _requests(qs, 8, per=32)
    eng.run(ref)
    for a, b in zip(waves[-1], ref):
        assert np.array_equal(np.asarray(a.values), b.values)


def test_async_sheds_expired_instead_of_serving_late(spatial_data):
    """Acceptance (c): deadline-aware mode sheds expired requests with the
    distinct 'shed' status; live requests in the same stream still serve."""
    pts, qs = spatial_data
    with AsyncAidwServer(pts, max_batch=256, query_domain=qs) as srv:
        dead = srv.submit(qs[:64], deadline_s=-0.5)   # expired on arrival
        live = srv.submit(qs[64:128], deadline_s=600.0)
        srv.flush(timeout=120)
        assert dead.status == "shed" and dead.values is None and dead.done
        assert live.status == "done" and live.values is not None
        rep = srv.report()
        assert rep["shed"] == 1 and rep["completed"] == 1
        assert rep["admission"]["shed_expired"] == 1


def test_async_update_error_propagates_to_caller(spatial_data):
    pts, qs = spatial_data
    with AsyncAidwServer(pts, query_domain=qs) as srv:
        with pytest.raises(IndexError):      # delete index out of range
            srv.update_dataset(deletes=[pts.shape[0] + 5], timeout=120)
        # the worker survives a poisoned update: queries still serve
        r = srv.submit(qs[:32])
        srv.result(r, timeout=120)
        assert r.status == "done"


def test_async_flush_under_rapid_submit_cycles(spatial_data):
    """Regression: in-flight accounting must count a request BEFORE the
    worker can complete it — a late increment strands flush() forever when
    the worker wins the race between put() and the bookkeeping."""
    pts, qs = spatial_data
    with AsyncAidwServer(pts, max_batch=128, query_domain=qs) as srv:
        for _ in range(5):                   # warm executables => fast worker
            reqs = [srv.submit(qs[16 * i:16 * (i + 1)]) for i in range(8)]
            srv.flush(timeout=120)
            assert all(r.status == "done" for r in reqs)


def test_async_result_reap_and_duplicate_uid(spatial_data):
    pts, qs = spatial_data
    with AsyncAidwServer(pts, query_domain=qs) as srv:
        r = srv.submit(qs[:32], uid=77)
        assert srv.result(77, timeout=120).status == "done"
        with pytest.raises(ValueError):
            srv.submit(qs[:32], uid=77)      # duplicate uid rejected
        assert srv.reap() == 1               # terminal request dropped
        r2 = srv.submit(qs[:32], uid=77)     # uid reusable after reap
        assert srv.result(r2, timeout=120).status == "done"


def test_async_server_on_mesh(spatial_data):
    """One async server serving every visible device (1 in the fast gate,
    8 under the CI serving-suite job): results bit-identical to the
    single-device synchronous engine."""
    import jax

    pts, qs = spatial_data
    mesh = jax.make_mesh((len(jax.devices()),), ("q",))
    eng = AidwEngine(pts, max_batch=256, query_domain=qs)
    ref = _requests(qs, 4)
    eng.run(ref)
    with AsyncAidwServer(pts, max_batch=256, query_domain=qs,
                         mesh=mesh) as srv:
        got = [srv.submit(qs[64 * i:64 * (i + 1)]) for i in range(4)]
        srv.flush(timeout=240)
    assert srv.session.stats["devices"] == len(jax.devices())
    for a, b in zip(got, ref):
        assert np.array_equal(np.asarray(a.values), b.values)


@pytest.mark.slow
def test_async_server_forced_8device_mesh():
    """Acceptance (a)+(b)+(c) on a REAL 8-lane host mesh (subprocess with
    forced host devices, like tests/test_distributed.py)."""
    out = run_multidevice("""
import numpy as np, jax
from repro.data.pipeline import spatial_points, spatial_queries
from repro.serving import AidwEngine, AsyncAidwServer, InterpolationRequest

assert len(jax.devices()) == 8
pts = spatial_points(2048, seed=0)
qs = spatial_queries(512, seed=1)
mesh = jax.make_mesh((8,), ("q",))

eng = AidwEngine(pts, max_batch=256, query_domain=qs)
ref = [InterpolationRequest(uid=i, queries_xy=qs[64*i:64*(i+1)])
       for i in range(8)]
eng.run(ref)

srv = AsyncAidwServer(pts, max_batch=256, query_domain=qs, mesh=mesh)
subs = [srv.submit(qs[64*i:64*(i+1)]) for i in range(4)]
srv.update_dataset(inserts=spatial_points(8, seed=3), deletes=[0, 1])
post = [srv.submit(qs[64*i:64*(i+1)]) for i in range(4, 8)]
dead = srv.submit(qs[:64], deadline_s=-1.0)
srv.flush(timeout=300)
assert all(np.array_equal(np.asarray(a.values), b.values)
           for a, b in zip(subs, ref[:4])), 'pre-update mismatch'
assert all(r.status == 'done' for r in post)
assert dead.status == 'shed'
assert srv.session.stats['devices'] == 8
assert srv.session.stats['delta_updates'] == 1
rep = srv.report()
assert rep['latency']['total']['p99_s'] > 0
assert rep['completed'] == 8 and rep['shed'] == 1
srv.close()
print('8dev async ok', rep['completed'], rep['shed'])
""")
    assert "8dev async ok 8 1" in out
