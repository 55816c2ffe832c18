"""The reduction from a profiler trace to busy time, stage times and the
breakdown: on synthetic device planes written in the XPlane format, and on
a small trace recorded on the CPU."""

import glob

import jax
import jax.numpy as jnp
import pytest

from bench import measures, trace_reduce as T

PS = 10 ** 6   # picoseconds per microsecond


def _space(events, host=()):
    """An XSpace with one TPU plane (``XLA Ops`` line) and one host line.
    ``events``: (name, tf_op, start_us, dur_us); ``host``: (name, start_us,
    dur_us)."""
    space = T._xspace()()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].id, dev.stat_metadata[1].name = 1, "tf_op"
    line = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    for i, (name, tf_op, s, d) in enumerate(events, start=1):
        md = dev.event_metadata[i]
        md.id, md.name = i, f"%{name} = f32[8] fusion(...)"
        if tf_op:
            md.stats.add(metadata_id=1, str_value=tf_op)
        line.events.add(metadata_id=i, offset_ps=s * PS, duration_ps=d * PS)
    cpu = space.planes.add(name="/host:CPU")
    hl = cpu.lines.add(name="python", id=7, timestamp_ns=1000)
    for i, (name, s, d) in enumerate(host, start=1):
        cpu.event_metadata[i].id, cpu.event_metadata[i].name = i, name
        hl.events.add(metadata_id=i, offset_ps=s * PS, duration_ps=d * PS)
    return space


@pytest.fixture
def synthetic(tmp_path):
    events = [("while.1", "", 10, 50),                      # holds 2 and 3
              ("fusion.2", "jit(f)/jit(grid_knn)/gather", 10, 30),
              ("fusion.3", "jit(f)/jit(grid_knn)/top_k", 30, 20),
              ("fusion.4", "jit(f)/jit(weighted_partial_sums)/mul", 70, 10),
              ("fusion.5", "jit(f)/jit(weighted_partial_sums)/mul", 75, 15)]
    host = [("bench.window", 0, 100), ("bench.sleep", 60, 10),
            ("bench.submit", 90, 10)]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space(events, host).SerializeToString())
    return T.read(path)


def test_union_of_overlapping_intervals():
    assert T.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert T.covered([(0, 10), (5, 20), (30, 40)], 8, 35) == 17


def test_busy_stage_and_window(synthetic):
    lo, hi = T.window_of(synthetic, "bench.window")
    assert (hi - lo) == 100 * PS
    assert T.busy_ps(synthetic, lo, hi) == (50 + 20) * PS
    assert T.stage_ps(synthetic, lo, hi, ["jit(grid_knn)"]) == 40 * PS
    assert T.stage_ps(synthetic, lo, hi,
                      ["jit(weighted_partial_sums)"]) == 20 * PS


def test_top_ops_and_idle_gaps(synthetic):
    lo, hi = T.window_of(synthetic, "bench.window")
    top = T.top_ops(synthetic, lo, hi, 2)
    assert top[0] == ["while.1", 50e-6] and top[1][0] == "fusion.2"
    gaps = T.idle_gaps(synthetic, lo, hi, {"bench.sleep", "bench.submit"})
    # device busy over [10, 60] and [70, 90]: three 10-us gaps, labelled by
    # the benchmark's annotation over each, else by any host event
    assert sorted(((s - lo) // PS, (e - s) // PS, label)
                  for label, s, e in gaps) == [
        (0, 10, "bench.window"), (60, 10, "bench.sleep"),
        (90, 10, "bench.submit")]


def test_stage_metric_per_query(synthetic):
    from types import SimpleNamespace

    lo, hi = T.window_of(synthetic, "bench.window")
    run = SimpleNamespace(trace=synthetic, trace_window=(lo, hi), queries=4)
    assert measures.stage_us_per_query(run, "stage1") == pytest.approx(10.0)
    assert measures.stage_us_per_query(run, "stage2") == pytest.approx(5.0)
    assert measures.device_idle_pct(run) == pytest.approx(30.0)
    run.trace = T.Trace()
    assert measures.stage_us_per_query(run, "stage1") is None
    assert measures.device_idle_pct(run) is None


def test_cpu_recorded_trace(tmp_path):
    """A real trace: the host annotation is found; the CPU has no TPU
    plane, so nothing is read as device time."""
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones(256)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                f(x).block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    tr = T.read(path)
    lo, hi = T.window_of(tr, "bench.window")
    assert hi > lo
    assert tr.device == {} and T.busy_ps(tr, lo, hi) == 0.0
