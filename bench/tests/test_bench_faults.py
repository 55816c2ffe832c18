"""The harness end to end on the CPU at a tiny size, with the chip check
skipped: sound runs come out correct, and runs whose timed path is broken
underneath come out not correct."""

import dataclasses

import pytest

from bench import run as R
from bench.tests import tiny

CELLS = ["global-served", "local-batch", "local-churn"]


def _run(name, seed=2 ** 31 + 11):
    spec, cell, config, mix = tiny.cell(name)
    return R.execute(spec, cell, config, mix, seed=seed, seconds=1.0,
                     trace=False)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out["checks"])[-1] in ("values_abs", "alpha_abs")
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]


@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_is_caught(name, monkeypatch):
    """Every 64th answer of each call of the timed path is moved by 0.01
    where the session produces it."""
    from repro.core.session import InterpolationSession

    query = InterpolationSession.query

    def altered(self, *a, **k):
        res = query(self, *a, **k)
        bad = res.values.at[::64].add(0.01)
        return dataclasses.replace(res, values=bad)

    monkeypatch.setattr(InterpolationSession, "query", altered)
    out = _run(name)
    assert not out["correct"]
    assert out["checks"]["values_abs"]["value"] > 0.005


def test_dropped_update_is_caught(monkeypatch):
    """An ingest path that acknowledges a delta but never applies it."""
    from repro.core.session import InterpolationSession

    update = InterpolationSession.update

    def dropped(self, points_xyz=None, **k):
        if points_xyz is not None:
            return update(self, points_xyz)

    monkeypatch.setattr(InterpolationSession, "update", dropped)
    out = _run("local-churn")
    assert not out["correct"]
    assert out["checks"]["values_abs"]["value"] > \
        out["checks"]["values_abs"]["limit"]
