"""Grid-aware sharded Stage 1 (PR 5): slab/halo correctness, the k-way
merge's equivalence with the replicated grid search (the halo's whole job,
exercised hardest by queries NEAR slab boundaries), delta updates staying
element-identical to a fresh plan, the analytic candidate census, and the
8-device grid-ring session (slow, subprocess — the CI mesh-suite gate).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from conftest import run_multidevice
from hypcompat import given, settings, st
from repro.core import grid as G
from repro.core import knn as K
from repro.core.slab import SlabPartition
from repro.data.pipeline import spatial_points, spatial_queries


def _merge_slabs(spec, part, queries, k, max_level, window=256):
    """Host-side mirror of the ring step: per-slab slab_knn + running
    top-k merge + the overflow-excuse certificate."""
    dev = part.device_tables()
    n = queries.shape[0]
    topk = np.full((n, k), np.inf, np.float32)
    excuse = np.full(n, np.inf, np.float32)
    cand = np.zeros(n, np.int64)
    for s in range(part.p):
        res = K.slab_knn(
            spec, part.rps, part.halo, jnp.asarray(dev["cell_start"][s]),
            jnp.asarray(dev["sx"][s]), jnp.asarray(dev["sy"][s]),
            jnp.zeros(dev["sx"].shape[1], jnp.int32),
            jnp.int32(dev["row_lo"][s]), jnp.asarray(queries), k, max_level,
            window, 4096)
        topk = np.sort(np.concatenate([topk, np.asarray(res.d2)], 1), 1)[:, :k]
        excuse = np.minimum(excuse, np.asarray(res.excuse))
        cand += np.asarray(res.n_candidates)
    overflow = np.sqrt(np.maximum(topk[:, -1], 0.0)) > excuse
    return topk, overflow, cand


def _boundary_queries(spec, p, n, rng):
    """Queries concentrated within a couple of cells of slab boundaries."""
    from repro.core.slab import slab_rows

    rps = slab_rows(spec, p)
    cw = spec.cell_width
    edges = [spec.min_y + s * rps * cw for s in range(1, p)]
    ys = rng.choice(edges, n) + rng.uniform(-2 * cw, 2 * cw, n)
    xs = spec.min_x + rng.uniform(0, spec.n_cols * cw, n)
    return np.stack([xs, ys], 1).astype(np.float32)


def test_slab_merge_matches_grid_knn_fixed():
    """Fixed-seed exactness: merged per-slab top-k == replicated grid_knn
    d2 VALUES on every certified query, incl. boundary-hugging queries."""
    rng = np.random.default_rng(0)
    pts = spatial_points(4096, seed=0)
    qs = np.concatenate([spatial_queries(256, seed=1),
                         _boundary_queries(
                             G.plan_grid(pts[:, :2]), 4, 256, rng)])
    spec = G.plan_grid(pts[:, :2], qs)
    table = G.bin_points(spec, jnp.array(pts[:, 0]), jnp.array(pts[:, 1]),
                         jnp.array(pts[:, 2]))
    k = 15
    max_level = K.auto_max_level(spec, pts.shape[0], k)
    ref = K.grid_knn(spec, table, jnp.array(qs), k, max_level, 256, 4096,
                     True)
    part = SlabPartition.build(spec, pts, 4, halo=max_level)
    topk, overflow, cand = _merge_slabs(spec, part, qs, k, max_level)
    ok = ~np.asarray(ref.overflow) & ~overflow
    assert ok.mean() > 0.95                       # window generous here
    assert np.array_equal(np.sort(np.asarray(ref.d2), 1)[ok], topk[ok])
    # the O(window) claim: way fewer candidate distances than brute m
    assert cand.mean() < pts.shape[0] / 10


@settings(max_examples=25, deadline=None)
@given(st.integers(100, 900), st.integers(2, 6), st.integers(0, 10_000),
       st.integers(1, 20))
def test_slab_merge_matches_brute_near_boundaries(m, p, seed, k):
    """Property: for boundary-hugging queries, the merged slab search
    equals brute-force kNN wherever the merge certifies exactness."""
    rng = np.random.default_rng(seed)
    xy = rng.random((m, 2)).astype(np.float32)
    pts = np.concatenate([xy, rng.random((m, 1))], 1).astype(np.float32)
    spec = G.plan_grid(xy)
    qs = _boundary_queries(spec, p, 24, rng)
    max_level = K.auto_max_level(spec, m, k)
    part = SlabPartition.build(spec, pts, p, halo=max_level)
    topk, overflow, _ = _merge_slabs(spec, part, qs, k, max_level,
                                     window=512)
    bd2, _ = K.brute_knn(jnp.array(xy), jnp.array(qs), k)
    want = np.sort(np.asarray(bd2), 1)
    certified = ~overflow
    assert certified.any()
    np.testing.assert_allclose(topk[certified],
                               want[certified][:, :topk.shape[1]],
                               atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(200, 1200), st.integers(2, 5), st.integers(0, 10_000))
def test_slab_partition_delta_element_identical(m, p, seed):
    """apply_delta + compact == fresh build of the reconstructed dataset,
    every array of every slab table (the grid-ring delta-update contract:
    deltas tier through the hot rings / tombstones, and compaction folds
    them back to exactly the fresh-build arrays)."""
    rng = np.random.default_rng(seed)
    pts = np.concatenate([rng.random((m, 2)), rng.random((m, 1))],
                         1).astype(np.float32)
    spec = G.plan_grid(pts[:, :2])
    part = SlabPartition.build(spec, pts, p, halo=3)
    cur = pts.copy()
    for it in range(2):
        n_del = rng.integers(0, max(cur.shape[0] // 5, 1))
        dels = rng.choice(cur.shape[0], n_del, replace=False)
        ins = np.concatenate([rng.random((7, 2)), rng.random((7, 1))],
                             1).astype(np.float32)
        part.apply_delta(inserts=ins, deletes=dels)
        keep = np.ones(cur.shape[0], bool)
        keep[dels] = False
        cur = np.concatenate([cur[keep], ins], 0)
    part.compact()                      # fold rings + purge tombstones
    assert part.ring_size() == 0 and part.tombstone_frac() == 0.0
    fresh = SlabPartition.build(spec, cur, p, halo=3)
    assert part.m == fresh.m == cur.shape[0]
    for s in range(p):
        for name in ("sx", "sy", "sz", "cell_start", "order"):
            a = np.asarray(getattr(part.tables[s], name))
            b = np.asarray(getattr(fresh.tables[s], name))
            assert a.shape == b.shape and np.array_equal(a, b), (s, name)
        assert np.array_equal(part.members[s], fresh.members[s])


def test_ring_stage1_census_reduction():
    """The analytic census confirms the candidate-count drop at fixed
    (m, P): O(window) grid candidates vs O(m) brute."""
    from repro.launch.analytic import aidw_ring_stage1_census

    c = aidw_ring_stage1_census(100_000, 8)
    assert c.brute_candidates == 100_000
    assert c.grid_candidates <= 256                # bounded by the window
    assert c.reduction > 100                       # >= two orders at 100k
    small = aidw_ring_stage1_census(4096, 8)
    assert small.reduction > 10


def test_grid_ring_session_single_device_mesh():
    """A 1-device mesh degenerates to one slab covering the whole grid —
    the grid-ring session must still serve, delta-update incrementally,
    and stay element-identical to a fresh plan after churn."""
    import jax

    from repro.core import InterpolationSession

    mesh = jax.make_mesh((len(jax.devices()),), ("q",))
    pts = spatial_points(2048, seed=0)
    qs = spatial_queries(333, seed=1)
    sess = InterpolationSession(pts, query_domain=qs, mesh=mesh,
                                layout="grid_ring")
    single = InterpolationSession(pts, query_domain=qs)
    a, b = single.query(qs), sess.query(qs)
    assert np.array_equal(np.asarray(a.r_obs), np.asarray(b.r_obs))
    assert np.array_equal(np.asarray(a.alpha), np.asarray(b.alpha))
    assert np.abs(np.asarray(a.values) - np.asarray(b.values)).max() < 1e-4

    rng = np.random.default_rng(3)
    dels = rng.choice(2048, 40, replace=False)
    ins = spatial_points(40, seed=9)
    sess.update(inserts=ins, deletes=dels)
    assert sess.stats["delta_updates"] == 1
    assert sess.stats["stage1_builds"] == 1        # executor survived
    assert sess.stats["ring_points"] == 40         # inserts tiered in-ring
    assert sess.stats["staged_bytes"] > 0
    keep = np.ones(2048, bool)
    keep[dels] = False
    fresh = InterpolationSession(
        np.concatenate([pts[keep], ins.astype(pts.dtype)], 0),
        query_domain=qs, mesh=mesh, layout="grid_ring")
    # ring-resident: within the documented 1-ulp FMA caveat of fresh
    np.testing.assert_allclose(np.asarray(sess.query(qs).values),
                               np.asarray(fresh.query(qs).values),
                               rtol=1e-6, atol=1e-6)
    # post-compaction: BITWISE a fresh plan's (same m -> same GridSpec)
    sess.compact()
    assert sess.stats["ring_points"] == 0
    assert sess.stats["compactions"] == 1
    assert np.array_equal(np.asarray(sess.query(qs).values),
                          np.asarray(fresh.query(qs).values))


# ---------------------------------------------------------------------------
# multi-device (slow: subprocess with 8 forced host devices)
# ---------------------------------------------------------------------------

pytestmark_slow = pytest.mark.slow


@pytest.mark.slow
def test_grid_ring_session_matches_replicated_8dev():
    """Acceptance: on an 8-device mesh the grid-ring session serves within
    documented tolerance of the replicated layout — bit-identical
    r_obs/alpha on certified queries, ~1e-5 values — at O(window)
    candidates per query, and an incremental delta stays element-identical
    to a fresh plan."""
    out = run_multidevice("""
import numpy as np, jax
from repro.core import InterpolationSession
from repro.data.pipeline import spatial_points, spatial_queries

pts = spatial_points(16384, seed=0)
qs = spatial_queries(1000, seed=1)       # odd size: padded buckets
mesh = jax.make_mesh((8,), ("q",))
single = InterpolationSession(pts, query_domain=qs)
sess = InterpolationSession(pts, query_domain=qs, mesh=mesh,
                            layout="grid_ring")
assert sess.sharded_plan.layout == "grid_ring"
a, b = single.query(qs), sess.query(qs)
assert np.array_equal(np.asarray(a.r_obs), np.asarray(b.r_obs))
assert np.array_equal(np.asarray(a.alpha), np.asarray(b.alpha))
err = np.abs(np.asarray(a.values) - np.asarray(b.values)).max()
assert err < 1e-4, err
cand = np.asarray(sess.last_stage1_candidates)
assert cand.mean() < pts.shape[0] / 20, cand.mean()   # O(window) not O(m)

# brute ring on the same mesh: tolerance only (never bitwise)
ring = InterpolationSession(pts, query_domain=qs, mesh=mesh, layout="ring")
rerr = np.abs(np.asarray(ring.query(qs).values)
              - np.asarray(a.values)).max()
assert rerr < 1e-4, rerr

# incremental delta: inserts tier through the hot rings (O(Delta) staging),
# deletes tombstone in place; ring-resident answers stay within 1 ulp of
# the physically-rebinned single session, and COMPACTION restores
# element-identity with a fresh plan (bitwise values, same m -> same spec)
dels = np.random.default_rng(3).choice(16384, 160, replace=False)
ins = spatial_points(160, seed=9)
for s in (single, sess):
    s.update(inserts=ins, deletes=dels)
assert sess.stats["delta_updates"] == 1 and sess.stats["stage1_builds"] == 1
assert sess.stats["ring_points"] == 160
a2, b2 = single.query(qs), sess.query(qs)
np.testing.assert_allclose(np.asarray(a2.r_obs), np.asarray(b2.r_obs),
                           rtol=1e-6, atol=1e-6)
sess.compact()
assert sess.stats["ring_points"] == 0 and sess.stats["compactions"] == 1
b2 = sess.query(qs)
assert np.array_equal(np.asarray(a2.r_obs), np.asarray(b2.r_obs))
keep = np.ones(16384, bool); keep[dels] = False
fresh = InterpolationSession(
    np.concatenate([pts[keep], ins.astype(pts.dtype)], 0),
    query_domain=qs, mesh=mesh, layout="grid_ring")
assert np.array_equal(np.asarray(b2.values), np.asarray(fresh.query(qs).values))
print("grid-ring-8dev-ok", float(cand.mean()))
""")
    assert "grid-ring-8dev-ok" in out


@pytest.mark.slow
def test_grid_ring_async_serving_8dev():
    """The async server can run the grid-ring layout: same results as the
    synchronous grid-ring session, churn serialized through the FIFO."""
    out = run_multidevice("""
import numpy as np, jax
from repro.core import InterpolationSession
from repro.data.pipeline import spatial_points, spatial_queries
from repro.serving import AsyncAidwServer

pts = spatial_points(8192, seed=0)
qd = spatial_queries(1024, seed=1)
mesh = jax.make_mesh((8,), ("q",))
qs = [spatial_queries(96, seed=10 + i) for i in range(6)]
sess = InterpolationSession(pts, query_domain=qd, mesh=mesh,
                            layout="grid_ring")
with AsyncAidwServer(pts, query_domain=qd, mesh=mesh,
                     layout="grid_ring") as srv:
    reqs = [srv.submit(q) for q in qs[:3]]
    srv.update_dataset(inserts=spatial_points(50, seed=99),
                       deletes=np.arange(50), timeout=300)
    reqs += [srv.submit(q) for q in qs[3:]]
    srv.flush(timeout=600)
# values: allclose, not bitwise — the worker may coalesce the requests
# into one batch, and the ring Stage-2 tile shape (hence XLA's f32
# reduction strategy) varies with the padded bucket (~1 ulp)
for i, r in enumerate(reqs[:3]):
    assert r.status == "done" and r.epoch == 0
    ref = np.asarray(sess.query(qs[i]).values)
    assert np.abs(r.values - ref).max() < 1e-5
sess.update(inserts=spatial_points(50, seed=99), deletes=np.arange(50))
for i, r in enumerate(reqs[3:]):
    assert r.status == "done" and r.epoch == 1
    ref = np.asarray(sess.query(qs[3 + i]).values)
    assert np.abs(r.values - ref).max() < 1e-5
print("grid-ring-async-ok")
""")
    assert "grid-ring-async-ok" in out

@pytest.mark.slow
def test_grid_ring_local_stage2_8dev():
    """Exact-k local Stage 2 on the real 8-device grid-ring mesh: bit-identical
    r_obs/alpha to the global grid-ring session (Stage 1 untouched), values
    within the truncation tolerance, and the fused Pallas gather+weighting
    path agrees with the unfused local path within the documented 5e-7
    (bitwise stats; XLA FMA contraction under jit shifts jnp values ~1 ulp)."""
    out = run_multidevice("""
import numpy as np, jax
from repro.core import AidwConfig, InterpolationSession
from repro.data.pipeline import spatial_points, spatial_queries

pts = spatial_points(16384, seed=0)
qs = spatial_queries(1000, seed=1)       # odd size: padded buckets
mesh = jax.make_mesh((8,), ("q",))
kw = dict(query_domain=qs, mesh=mesh, layout="grid_ring")
glob = InterpolationSession(pts, **kw)
loc = InterpolationSession(pts, AidwConfig(stage2="local"), **kw)
fused = InterpolationSession(
    pts, AidwConfig(stage2="local", fused=True, interpret=True), **kw)

g, l, f = glob.query(qs), loc.query(qs), fused.query(qs)
assert np.array_equal(np.asarray(g.r_obs), np.asarray(l.r_obs))
assert np.array_equal(np.asarray(g.alpha), np.asarray(l.alpha))
err = np.abs(np.asarray(g.values) - np.asarray(l.values)).max()
assert err < 5e-2, err                   # truncated far-field tail
assert not np.isnan(np.asarray(l.values)).any()

assert np.array_equal(np.asarray(f.alpha), np.asarray(l.alpha))
np.testing.assert_allclose(np.asarray(f.values), np.asarray(l.values),
                           rtol=5e-7, atol=5e-7)
print("grid-ring-local-8dev-ok", float(err))
""")
    assert "grid-ring-local-8dev-ok" in out
