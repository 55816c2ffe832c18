"""The readers of the program's own spans, scopes and counters, on a
synthetic trace written in the XPlane format: each returns the value
computed by hand, and ``None`` where its spans or counters are absent."""

from types import SimpleNamespace

import pytest

from bench import run as R
from bench import trace_reduce as T

PS = 10 ** 6   # picoseconds per microsecond

DEVICE = [  # (tf_op, start_us, dur_us)
    ("jit(_execute_core)/aidw.stage1/jit(grid_knn)/gather", 10, 30),
    ("jit(_execute_core)/aidw.stage2/mul", 40, 10),
    ("jit(_execute_core)/aidw.stage2/jit(weighted_partial_sums)/add", 70,
     10),
]
WORKER = [  # (name, start_us, dur_us), the worker thread's line
    ("serving.wait_work", 0, 5), ("serving.coalesce", 5, 3),
    ("serving.launch", 8, 42), ("serving.materialize", 50, 2),
    ("serving.scatter", 52, 8),
    ("serving.apply_update", 60, 10), ("ingest.merge", 61, 4),
    ("ingest.stage", 65, 4),
    ("serving.wait_work", 70, 15),
    ("serving.apply_update", 85, 10), ("ingest.merge", 86, 2),
    ("ingest.stage", 88, 6),
    ("serving.wait_work", 95, 5),
]


def _trace(tmp_path, device=DEVICE, worker=WORKER):
    """A TPU plane with ``device`` ops and a host plane with the
    benchmark's window on one line and ``worker`` on another."""
    space = T._xspace()()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].id, dev.stat_metadata[1].name = 1, "tf_op"
    line = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    for i, (tf_op, s, d) in enumerate(device, start=1):
        md = dev.event_metadata[i]
        md.id, md.name = i, f"%fusion.{i} = f32[8] fusion(...)"
        md.stats.add(metadata_id=1, str_value=tf_op)
        line.events.add(metadata_id=i, offset_ps=s * PS, duration_ps=d * PS)
    cpu = space.planes.add(name="/host:CPU")
    names = ["bench.window"] + sorted({n for n, _, _ in worker})
    for i, n in enumerate(names, start=1):
        cpu.event_metadata[i].id, cpu.event_metadata[i].name = i, n
    ids = {n: i for i, n in enumerate(names, start=1)}
    main = cpu.lines.add(name="python", id=1, timestamp_ns=1000)
    main.events.add(metadata_id=ids["bench.window"], offset_ps=0,
                    duration_ps=100 * PS)
    wl = cpu.lines.add(name="aidw-serving-worker", id=2, timestamp_ns=1000)
    for n, s, d in worker:
        wl.events.add(metadata_id=ids[n], offset_ps=s * PS,
                      duration_ps=d * PS)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    tr = T.read(path)
    return SimpleNamespace(trace=tr, trace_window=T.window_of(
        tr, "bench.window"), queries=4, updates=[], registry=None)


def _state(counters=None, gauges=None):
    return {"counters": dict(counters or {}),
            "gauges": {k: {"value": v, "merge": "sum"}
                       for k, v in (gauges or {}).items()},
            "hists": {}}


def test_stage2_scope_per_query(tmp_path):
    run = _trace(tmp_path)
    # aidw.stage2 ops: [40, 50] and [70, 80] -> 20 us over 4 queries
    for name in ("stage2_us_per_query.batch", "stage2_us_per_query.churn"):
        assert R.load_reader(name)(run) == pytest.approx(5.0)
    run = _trace(tmp_path, device=DEVICE[:1])
    assert R.load_reader("stage2_us_per_query.batch")(run) is None


def test_idle_while_the_worker_holds_work(tmp_path):
    run = _trace(tmp_path)
    # worker busy (not waiting): [5, 70] and [85, 95] = 75 us; the device
    # runs in [10, 50] of it -> 35 us idle of a 100-us window
    for name in ("idle_worker_busy_pct.served", "idle_worker_busy_pct.churn"):
        assert R.load_reader(name)(run) == pytest.approx(35.0)
    waits = [w for w in WORKER if w[0] == "serving.wait_work"]
    run = _trace(tmp_path, worker=waits)
    assert R.load_reader("idle_worker_busy_pct.served")(run) is None


def test_ingest_span_medians(tmp_path):
    run = _trace(tmp_path)
    # merges of 4 and 2 us, stages of 4 and 6 us
    assert R.load_reader("ingest_merge_p50_ms")(run) == pytest.approx(0.003)
    assert R.load_reader("ingest_stage_p50_ms")(run) == pytest.approx(0.005)
    run = _trace(tmp_path, worker=WORKER[:5])
    assert R.load_reader("ingest_merge_p50_ms")(run) is None
    assert R.load_reader("ingest_stage_p50_ms")(run) is None


def test_bucket_fill_from_counters():
    before = _state({"session/queries": 100, "session/bucket_slots": 128})
    after = _state({"session/queries": 300, "session/bucket_slots": 384})
    run = SimpleNamespace(registry=[before, after])
    for name in ("bucket_fill_pct.served", "bucket_fill_pct.churn"):
        assert R.load_reader(name)(run) == pytest.approx(100 * 200 / 256)
    run = SimpleNamespace(registry=[_state(), _state()])
    assert R.load_reader("bucket_fill_pct.served")(run) is None
    run = SimpleNamespace(registry=[before, before])     # nothing dispatched
    assert R.load_reader("bucket_fill_pct.served")(run) is None


def test_bytes_moved_per_update():
    before = _state({"ingest/pulled_bytes_total": 0},
                    {"ingest/staged_bytes_total": 1e6})
    after = _state({"ingest/pulled_bytes_total": 3e6},
                   {"ingest/staged_bytes_total": 3e6})
    updates = [{"ok": True}, {"ok": True}, {"ok": False}]
    run = SimpleNamespace(registry=[before, after], updates=updates)
    read = R.load_reader("ingest_moved_mb_per_update")
    assert read(run) == pytest.approx((3e6 + 2e6) / 1e6 / 2)
    # a program that mirrors no staged bytes reports nothing
    run = SimpleNamespace(registry=[_state(), _state(
        {"ingest/pulled_bytes_total": 3e6})], updates=updates)
    assert read(run) is None
    run = SimpleNamespace(registry=[before, after], updates=[])
    assert read(run) is None
