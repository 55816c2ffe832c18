"""Metric arithmetic on synthetic inputs: percentiles over all requests
(one that never came counts at its give-up time), the rate over a whole
window, and a program histogram's percentile over the window alone."""

from types import SimpleNamespace

import pytest

from bench import measures
from bench import run as R


def test_percentiles_over_all_reads():
    reads = [{"due": 0.0, "t_done": t, "ok": True} for t in
             (0.010, 0.020, 0.030, 0.040)]
    reads.append({"due": 1.0, "t_done": 61.0, "ok": False})  # never came
    run = SimpleNamespace(reads=reads, updates=[], calls=[])
    lat = measures.read_latency_ms(run)
    assert lat[-1] == pytest.approx(60000.0)
    assert R.load_reader("latency_p50_ms")(run) == pytest.approx(30.0)
    assert R.load_reader("latency_p95_ms")(run) > 40.0
    assert R.load_reader("update_visible_p50_ms")(run) is None


def test_rate_over_the_whole_window():
    calls = [{"queries": [0] * 100, "t1": 0.0, "t2": 1.0},
             {"queries": [0] * 100, "t1": 1.5, "t2": 2.0}]
    run = SimpleNamespace(calls=calls)
    assert measures.queries_per_s(run) == pytest.approx(100.0)
    assert measures.queries_per_s(SimpleNamespace(calls=[])) is None


def test_histogram_percentile_over_the_window():
    from repro.obs import Registry

    reg = Registry()
    for s in (0.5, 0.5, 0.5):                # before the window
        reg.observe("session/plan_s", s)
    before = reg.state()
    for s in (0.001, 0.002, 0.003, 0.2):
        reg.observe("session/plan_s", s)
    run = SimpleNamespace(registry=[before, reg.state()])
    p50 = measures.hist_percentile(run, "session/plan_s", 50)
    assert 0.002 <= p50 <= 0.002 * 1.26
    assert R.load_reader("ingest_apply_p50_ms")(run) == pytest.approx(
        p50 * 1e3)
    assert measures.hist_percentile(run, "serving/none_s", 50) is None
    run = SimpleNamespace(registry=[before, before])
    assert measures.hist_percentile(run, "session/plan_s", 50) is None


def test_queue_wait_from_request_stamps():
    reads = [{"t_submit_server": 0.0, "t_dispatch": d / 1e3}
             for d in range(1, 21)]
    run = SimpleNamespace(reads=reads)
    assert R.load_reader("queue_wait_p95_ms")(run) == pytest.approx(19.05)
