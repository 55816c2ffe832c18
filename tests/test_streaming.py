"""Streaming ingest: LSM hot-ring interleaving, O(Δ) staging, zero-retrace.

ISSUE 7 test gates for the per-slab donation-aliased delta staging + hot
append ring:

* interleaving property — any mix of appends, deletes and COMPACTION
  epochs on a :class:`~repro.core.slab.SlabPartition`, compacted at the
  end, is element-identical to a fresh build of the surviving dataset
  (hypothesis-driven, with a fixed-seed variant that runs on minimal
  containers too);
* zero-retrace regression — in-ring churn on a ``grid_ring`` session must
  reuse BOTH the one compiled executor signature AND the cached staging
  fns (``SlabStaging._fns``): a retrace or a fresh jit per update would
  hide O(compile) work inside the O(Δ) ingest path;
* staged-bytes reduction — the unit-sized mirror of the
  ``ingest/staged_reduction`` benchmark gate: a 1% balanced delta must
  stage >= 10x fewer bytes than the construction-time full packet.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from hypcompat import given, settings, st
from repro.core import grid as G
from repro.core.slab import SlabPartition
from repro.data.pipeline import spatial_points, spatial_queries


def _apply_interleaved(part, cur, rng, ops):
    """Apply (kind, payload) ops to ``part`` and the numpy shadow ``cur``."""
    for kind in ops:
        if kind == "compact":
            part.compact()                       # mid-stream compaction epoch
            continue
        n_del = int(rng.integers(0, max(cur.shape[0] // 6, 1)))
        dels = rng.choice(cur.shape[0], n_del, replace=False)
        n_ins = int(rng.integers(1, 9))
        ins = np.concatenate([rng.random((n_ins, 2)),
                              rng.random((n_ins, 1))], 1).astype(np.float32)
        part.apply_delta(inserts=ins, deletes=dels)
        keep = np.ones(cur.shape[0], bool)
        keep[dels] = False
        cur = np.concatenate([cur[keep], ins], 0)
    return cur


def _assert_element_identical(part, fresh, p):
    assert part.m == fresh.m
    for s in range(p):
        for name in ("sx", "sy", "sz", "cell_start", "order"):
            a = np.asarray(getattr(part.tables[s], name))
            b = np.asarray(getattr(fresh.tables[s], name))
            assert a.shape == b.shape and np.array_equal(a, b), (s, name)
        assert np.array_equal(part.members[s], fresh.members[s])


@settings(max_examples=15, deadline=None)
@given(st.integers(200, 1200), st.integers(2, 5), st.integers(0, 10_000),
       st.lists(st.sampled_from(["delta", "compact"]), min_size=1,
                max_size=6))
def test_interleaved_deltas_and_compactions_element_identical(
        m, p, seed, ops):
    """Property: ANY interleaving of delta updates and compaction epochs,
    followed by a final compact, leaves every slab table array and member
    list element-identical to a fresh build of the surviving dataset —
    compaction is a pure tier move, never a reorder the fresh build would
    not produce."""
    rng = np.random.default_rng(seed)
    pts = np.concatenate([rng.random((m, 2)), rng.random((m, 1))],
                         1).astype(np.float32)
    spec = G.plan_grid(pts[:, :2])
    part = SlabPartition.build(spec, pts, p, halo=3)
    cur = _apply_interleaved(part, pts.copy(), rng, ops)
    part.compact()
    assert part.ring_size() == 0 and part.tombstone_frac() == 0.0
    _assert_element_identical(part, SlabPartition.build(spec, cur, p,
                                                        halo=3), p)


@pytest.mark.parametrize("seed,ops", [
    (0, ["delta", "compact", "delta"]),
    (7, ["compact", "delta", "delta", "compact", "delta"]),
    (42, ["delta", "delta", "compact"]),
])
def test_interleaved_deltas_and_compactions_fixed_seeds(seed, ops):
    """Fixed-seed interleavings of the property above (runs on minimal
    containers where hypothesis is absent)."""
    rng = np.random.default_rng(seed)
    m, p = 700, 3
    pts = np.concatenate([rng.random((m, 2)), rng.random((m, 1))],
                         1).astype(np.float32)
    spec = G.plan_grid(pts[:, :2])
    part = SlabPartition.build(spec, pts, p, halo=3)
    cur = _apply_interleaved(part, pts.copy(), rng, ops)
    part.compact()
    assert part.ring_size() == 0 and part.tombstone_frac() == 0.0
    _assert_element_identical(part, SlabPartition.build(spec, cur, p,
                                                        halo=3), p)


def _grid_ring_session(m, *, ring_cap=512, seed=3):
    from repro.core import InterpolationSession
    mesh = jax.make_mesh((len(jax.devices()),), ("q",))
    pts = spatial_points(m, seed=seed)
    qd = spatial_queries(256, seed=seed + 1)
    sess = InterpolationSession(pts, query_domain=qd, mesh=mesh,
                                layout="grid_ring", ring_cap=ring_cap)
    sess.query(qd)
    return sess, pts, qd


def test_in_ring_churn_zero_retrace_and_stable_staging_fns():
    """Zero-retrace regression (ISSUE 7): while churn stays inside the
    ring capacity, every delta reuses (a) the ONE compiled grid-ring
    executor signature and (b) the cached donation-aliased staging fns —
    after the first delta has populated the scatter-fn cache, further
    same-bucket deltas add ZERO new jitted signatures of either kind."""
    from repro.core import pipeline as P

    sess, pts, qd = _grid_ring_session(3301)          # size unique to test
    lo, hi = pts[:, :2].min(axis=0), pts[:, :2].max(axis=0)
    sp = sess.sharded_plan
    fn = P.grid_ring_session_execute(
        sp.mesh, sp.ring_axis, sess.plan.cfg, sess.plan.spec, sp.rps,
        sp.halo, sp.max_level)
    n_exec = fn._cache_size()
    assert n_exec >= 1

    rng = np.random.default_rng(11)

    def delta(i):
        ins = spatial_points(16, seed=70 + i)
        ins[:, :2] = np.clip(ins[:, :2], lo, hi)
        # delete only from the CSR-resident head so every insert stays
        # ring-resident (a ring delete would be exact, but the 64-point
        # occupancy assertion below wants all inserts alive)
        sess.update(inserts=ins, deletes=rng.choice(3000, 16, replace=False))
        sess.query(qd)

    delta(0)                        # populates the scatter-side fn cache
    n_fns = len(sess.sharded_plan.staging._fns)
    for i in range(1, 4):
        delta(i)
    assert fn._cache_size() == n_exec            # zero executor retraces
    assert len(sess.sharded_plan.staging._fns) == n_fns   # zero staging fns
    assert sess.stats["delta_updates"] == 4
    assert sess.stats["full_restages"] == 1      # construction only
    assert sess.stats["spilled_updates"] == 0
    assert sess.stats["ring_points"] == 64       # all churn stayed in-ring
    # a compaction epoch may compile its own one-time staging signatures
    # (full-row folds at slab capacity) — but the EXECUTOR never retraces,
    # and a second churn+compact round adds zero new signatures of any kind
    sess.compact()
    sess.query(qd)
    assert sess.stats["ring_points"] == 0
    assert fn._cache_size() == n_exec
    n_post = len(sess.sharded_plan.staging._fns)
    delta(4)
    sess.compact()
    sess.query(qd)
    assert fn._cache_size() == n_exec
    assert len(sess.sharded_plan.staging._fns) == n_post


def test_delta_staging_bytes_reduction_unit():
    """Unit-sized mirror of the ``ingest/staged_reduction`` benchmark
    gate: at 1% balanced churn a grid-ring delta stages >= 10x fewer
    bytes than the construction-time full-packet upload, touching only
    the slabs the delta landed in."""
    m = 8192
    sess, pts, qd = _grid_ring_session(m)
    full_bytes = sess.stats["staged_bytes"]       # construction upload
    assert full_bytes > 0
    lo, hi = pts[:, :2].min(axis=0), pts[:, :2].max(axis=0)
    d = m // 100
    rng = np.random.default_rng(13)
    staged = []
    for i in range(2):
        ins = spatial_points(d, seed=80 + i)
        ins[:, :2] = np.clip(ins[:, :2], lo, hi)
        sess.update(inserts=ins, deletes=rng.choice(m, d, replace=False))
        sess.query(qd)
        staged.append(sess.stats["staged_bytes"])
    assert sess.stats["delta_updates"] == 2
    assert sess.stats["full_restages"] == 1
    assert sess.stats["spilled_updates"] == 0
    reduction = full_bytes / max(float(np.mean(staged)), 1.0)
    assert reduction >= 10.0, (reduction, staged, full_bytes)
    assert sess.stats["staged_bytes_total"] >= full_bytes + sum(staged)
    assert 1 <= sess.stats["slabs_touched"] <= len(jax.devices())
