"""InterpolationSession: amortization counters, bucketing, bit-identity,
dataset refresh, fused Stage-2, and the session-backed serving engine."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AidwConfig, InterpolationSession, aidw_improved,
                        bucket_size, execute)
from repro.core import grid as G
from repro.core import pipeline as P
from repro.data.pipeline import spatial_points, spatial_queries


def test_bucket_size_powers_of_two():
    assert bucket_size(1) == 64          # floor
    assert bucket_size(63) == 64
    assert bucket_size(64) == 64
    assert bucket_size(65) == 128
    assert bucket_size(2048) == 2048
    assert bucket_size(2049) == 4096
    assert bucket_size(5, min_bucket=8) == 8
    with pytest.raises(ValueError):
        bucket_size(0)


def test_bucket_size_non_pow2_min_bucket():
    """Regression: a non-power-of-two ``min_bucket`` must round UP to a power
    of two, not seed a 48 -> 96 -> 192 doubling chain."""
    assert bucket_size(5, min_bucket=48) == 64
    assert bucket_size(100, min_bucket=48) == 128
    assert bucket_size(1, min_bucket=1) == 1
    assert bucket_size(3, min_bucket=3) == 4
    for mb in (1, 3, 7, 48, 100, 64):
        for n in (1, 5, 97, 1000):
            b = bucket_size(n, min_bucket=mb)
            assert b >= n and (b & (b - 1)) == 0, (n, mb, b)


def test_warm_query_bit_identical_to_cold(spatial_data):
    """Core acceptance: session.query == one-shot aidw_improved, bitwise."""
    pts, qs = spatial_data
    cold = aidw_improved(pts, qs)
    sess = InterpolationSession(pts, query_domain=qs)
    warm = sess.query(qs)
    assert np.array_equal(np.asarray(cold.values), np.asarray(warm.values))
    assert np.array_equal(np.asarray(cold.alpha), np.asarray(warm.alpha))
    assert np.array_equal(np.asarray(cold.r_obs), np.asarray(warm.r_obs))
    assert cold.overflow == warm.overflow == 0


def test_no_rebuild_or_retrace_across_same_bucket_queries(spatial_data):
    """Repeated odd-sized batches in one bucket: the jit cache is hit and
    Stage-1 (bin_points) is neither re-traced nor re-run."""
    pts, _ = spatial_data
    sess = InterpolationSession(pts, min_bucket=64)
    sess.query(spatial_queries(512, seed=2))        # compile the 512 bucket
    traces0, bins0 = P.execute_traces(), G.bin_traces()
    builds0 = sess.stats["stage1_builds"]
    for i in range(5):
        n = 512 - 3 * i
        res = sess.query(spatial_queries(n, seed=10 + i))
        assert res.values.shape == (n,)
    assert P.execute_traces() == traces0            # zero execute retraces
    assert G.bin_traces() == bins0                  # zero Stage-1 rebinning
    assert sess.stats["stage1_builds"] == builds0 == 1
    assert sess.stats["bucket_misses"] == 1
    assert sess.stats["bucket_hits"] == 5


def test_new_bucket_traces_exactly_once():
    # a dataset size unique to THIS test: n_points is a static jit arg, so no
    # other test file can have pre-compiled these signatures (the trace-delta
    # assertions below are only valid against a cold compile cache)
    pts = spatial_points(2051, seed=12)
    sess = InterpolationSession(pts, min_bucket=64)
    sess.query(spatial_queries(100, seed=0))        # 128 bucket
    t0 = P.execute_traces()
    sess.query(spatial_queries(200, seed=1))        # 256 bucket: one trace
    assert P.execute_traces() == t0 + 1
    sess.query(spatial_queries(255, seed=2))        # 256 again: cache hit
    assert P.execute_traces() == t0 + 1


def test_bucket_boundary_shapes_roundtrip(spatial_data):
    """n in {1, block-1, block, block+1} all pad, execute, and un-pad to
    results bit-identical to an unpadded execute on the same plan."""
    pts, qs = spatial_data
    block = 64
    sess = InterpolationSession(pts, min_bucket=block, query_domain=qs)
    for n in (1, block - 1, block, block + 1):
        warm = sess.query(qs[:n])
        want = execute(sess.plan, qs[:n])
        assert warm.values.shape == (n,)
        assert np.array_equal(np.asarray(warm.values), np.asarray(want.values))
        assert np.array_equal(np.asarray(warm.alpha), np.asarray(want.alpha))
        assert warm.overflow == want.overflow


def test_update_refreshes_dataset(spatial_data):
    pts, qs = spatial_data
    sess = InterpolationSession(pts, query_domain=qs)
    v_old = np.asarray(sess.query(qs).values)
    pts2 = spatial_points(pts.shape[0], seed=9)
    sess.update(pts2)
    v_new = np.asarray(sess.query(qs).values)
    cold2 = np.asarray(aidw_improved(pts2, qs).values)
    assert np.array_equal(v_new, cold2)             # serving == one-shot
    assert not np.array_equal(v_new, v_old)         # dataset really changed
    assert sess.stats["stage1_builds"] == 2


def _fixed_spec_plan(sess, pts_updated):
    """A plan from a FULL re-bin on the session's retained spec (the
    incremental path's equivalence reference)."""
    spec = sess.plan.spec
    table = G.bin_points(spec, jnp.asarray(pts_updated[:, 0]),
                         jnp.asarray(pts_updated[:, 1]),
                         jnp.asarray(pts_updated[:, 2]))
    return P.pad_plan(P.AidwPlan(spec=spec, table=table,
                                 points_xy=jnp.asarray(pts_updated[:, :2]),
                                 values=jnp.asarray(pts_updated[:, 2]),
                                 n_points=pts_updated.shape[0],
                                 area=sess.plan.area, cfg=sess.cfg))


def test_delta_update_matches_full_rebin(spatial_data):
    """update(inserts/deletes) == full re-bin at the retained spec, bitwise;
    Stage-1 is never rebuilt (delta_updates counts instead)."""
    pts, qs = spatial_data
    m = pts.shape[0]
    sess = InterpolationSession(pts, query_domain=qs)
    sess.query(qs)
    bins0 = G.bin_traces()
    dels = np.random.default_rng(0).choice(m, 25, replace=False)
    ins = spatial_points(30, seed=21)
    sess.update(inserts=ins, deletes=dels)
    assert sess.stats["delta_updates"] == 1
    assert sess.stats["stage1_builds"] == 1          # no full rebuild
    assert G.bin_traces() == bins0                   # sort core untouched

    keep = np.ones(m, bool)
    keep[dels] = False
    upd = np.concatenate([pts[keep], ins], axis=0)
    warm = sess.query(qs)
    want = execute(_fixed_spec_plan(sess, upd), qs)
    assert np.array_equal(np.asarray(warm.values), np.asarray(want.values))
    assert np.array_equal(np.asarray(warm.alpha), np.asarray(want.alpha))


def test_delta_update_deltas_tuple_and_engine(spatial_data):
    """The ``deltas=(inserts, deletes)`` spelling and the engine passthrough."""
    from repro.serving import AidwEngine

    pts, qs = spatial_data
    sess = InterpolationSession(pts, query_domain=qs)
    sess.update(deltas=(spatial_points(10, seed=3),
                        np.arange(10)))
    assert sess.stats["delta_updates"] == 1

    eng = AidwEngine(pts, query_domain=qs)
    eng.update_dataset(inserts=spatial_points(10, seed=4), deletes=[0, 1])
    assert eng.session.stats["delta_updates"] == 1
    assert eng.session.stats["stage1_builds"] == 1


def test_update_argument_validation(spatial_data):
    """Bad update() spellings fail loudly instead of silently diverging."""
    pts, qs = spatial_data
    sess = InterpolationSession(pts, query_domain=qs)
    with pytest.raises(ValueError):
        sess.update()                                # nothing to update
    with pytest.raises(ValueError):
        sess.update(pts, inserts=pts[:1])            # full AND delta
    with pytest.raises(IndexError):
        sess.update(deletes=[-1])                    # would wrap silently
    with pytest.raises(IndexError):
        sess.update(deletes=[pts.shape[0]])
    with pytest.raises(ValueError):                  # layout typo
        InterpolationSession(pts, mesh=jax.make_mesh((1,), ("q",)),
                             layout="auto")


def test_delta_update_fallback_paths(spatial_data):
    """Oversized deltas and out-of-bbox inserts fall back to a full re-plan."""
    pts, qs = spatial_data
    m = pts.shape[0]
    sess = InterpolationSession(pts, query_domain=qs)
    sess.update(inserts=spatial_points(m, seed=7))   # > max_delta_frac * m
    assert sess.stats["stage1_builds"] == 2
    assert sess.stats["delta_updates"] == 0

    out = np.array([[50.0, 50.0, 1.0]], np.float32)  # far outside the grid
    sess.update(inserts=out)
    assert sess.stats["stage1_builds"] == 3          # bbox fallback
    assert sess.stats["delta_updates"] == 0
    # ... and the re-planned session still answers (the degenerate geometry
    # overflows the candidate window, where only tolerance — not bitwise —
    # equality is contractual)
    want = execute(sess.plan, qs)
    got = sess.query(qs)
    assert got.overflow == want.overflow
    np.testing.assert_allclose(np.asarray(got.values),
                               np.asarray(want.values), rtol=1e-5, atol=1e-6)


def test_sharded_session_single_device_mesh(spatial_data):
    """mesh= on a 1-device mesh: same API, bit-identical results, shard-aware
    stats.  (The real 8-lane partition runs in tests/test_distributed.py.)"""
    pts, qs = spatial_data
    mesh = jax.make_mesh((1,), ("q",))
    single = InterpolationSession(pts, query_domain=qs)
    sharded = InterpolationSession(pts, query_domain=qs, mesh=mesh)
    assert sharded.stats["devices"] == 1
    assert sharded.sharded_plan.layout == "replicated"
    a, b = single.query(qs), sharded.query(qs)
    assert np.array_equal(np.asarray(a.values), np.asarray(b.values))
    assert np.array_equal(np.asarray(a.r_obs), np.asarray(b.r_obs))
    assert a.overflow == b.overflow
    # delta update keeps working through the sharded placement
    sharded.update(inserts=spatial_points(8, seed=5), deletes=[0, 1, 2])
    single.update(inserts=spatial_points(8, seed=5), deletes=[0, 1, 2])
    a, b = single.query(qs), sharded.query(qs)
    assert np.array_equal(np.asarray(a.values), np.asarray(b.values))
    assert sharded.stats["delta_updates"] == 1


def test_auto_axes_view_of_a_bare_mesh():
    """A bare ``jax.make_mesh`` types its axes Explicit; the library runs on
    an Auto-typed view with the same devices and axis names."""
    from jax.sharding import AxisType

    from repro.core.distributed import auto_axes

    mesh = jax.make_mesh((1,), ("q",))
    assert mesh.axis_types == (AxisType.Explicit,)
    view = auto_axes(mesh)
    assert view.axis_types == (AxisType.Auto,)
    assert view.axis_names == mesh.axis_names
    assert (view.devices == mesh.devices).all()
    assert auto_axes(view) is view


def test_fused_session_matches_unfused(spatial_data):
    """AidwConfig(fused=True) routes Stage 2 through the alpha-in-kernel
    Pallas path; predictions agree with the two-launch path within 1e-5."""
    pts, qs = spatial_data
    unfused = InterpolationSession(pts, query_domain=qs)
    fused_cfg = AidwConfig(stage2="tiled", fused=True, interpret=True,
                           tile_q=128, tile_d=256)
    fused = InterpolationSession(pts, fused_cfg, query_domain=qs)
    ref = np.asarray(unfused.query(qs).values)
    got = np.asarray(fused.query(qs).values)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_aidw_engine_coalesces_and_matches(spatial_data):
    from repro.serving import AidwEngine, InterpolationRequest

    pts, qs = spatial_data
    eng = AidwEngine(pts, max_batch=256, query_domain=qs)
    reqs = [InterpolationRequest(uid=i, queries_xy=qs[64 * i:64 * (i + 1)])
            for i in range(6)]
    stats = eng.run(reqs)
    assert all(r.done for r in reqs)
    got = np.concatenate([r.values for r in reqs])
    want = np.asarray(execute(eng.session.plan, qs[:384]).values)
    assert np.array_equal(got, want)
    assert stats["batches"] < len(reqs)             # FIFO coalescing happened
    assert stats["queries"] == 384
    assert eng.session.stats["stage1_builds"] == 1  # zero per-request rebuilds


def test_aidw_engine_dataset_refresh(spatial_data):
    from repro.serving import AidwEngine, InterpolationRequest

    pts, qs = spatial_data
    eng = AidwEngine(pts, query_domain=qs)
    r1 = InterpolationRequest(uid=0, queries_xy=qs[:128])
    eng.run([r1])
    eng.update_dataset(spatial_points(pts.shape[0], seed=11))
    r2 = InterpolationRequest(uid=1, queries_xy=qs[:128])
    eng.run([r2])
    assert eng.session.stats["stage1_builds"] == 2
    assert not np.array_equal(r1.values, r2.values)


# ---------------------------------------------------------------------------
# n_points-churn retrace regression (the PR 6 bugfix): n_points is a TRACED
# scalar and plan arrays are capacity-padded, so dataset-RESIZING deltas that
# stay inside one 64-row capacity bucket must never retrace any executor.
# ---------------------------------------------------------------------------


def _churn(sess, sizes=(10, -5, 20, -25)):
    """Apply resizing deltas (net n_points change each step)."""
    from repro.data.pipeline import spatial_points

    for i, d in enumerate(sizes):
        if d > 0:
            sess.update(inserts=spatial_points(d, seed=50 + i))
        else:
            sess.update(deletes=np.arange(-d))


def test_churn_within_capacity_bucket_never_retraces():
    """Single layout: +10/-5/+20/-25 point churn (all inside the 3072-row
    capacity bucket) keeps the execute trace count frozen while the served
    values actually change."""
    from repro.data.pipeline import spatial_points, spatial_queries

    # dataset size unique to THIS test (see test_new_bucket_traces_exactly_once)
    pts = spatial_points(3037, seed=30)
    qs = spatial_queries(256, seed=31)
    sess = InterpolationSession(pts, query_domain=qs)
    v0 = np.asarray(sess.query(qs).values)
    t0, b0 = P.execute_traces(), G.bin_traces()
    _churn(sess)
    assert sess.plan.points_xy.shape[0] == 3072     # capacity bucket held
    v1 = np.asarray(sess.query(qs).values)
    assert P.execute_traces() == t0                 # ZERO retraces on churn
    assert G.bin_traces() == b0                     # delta path, no re-bin
    assert sess.stats["delta_updates"] == 4
    assert not np.array_equal(v0, v1)               # dataset really changed


def test_churn_replicated_mesh_never_retraces():
    """Replicated mesh layout: the shard_map body is _execute_core, so the
    same counter proves the mesh executor survived resizing churn."""
    from repro.data.pipeline import spatial_points, spatial_queries

    pts = spatial_points(3101, seed=32)             # unique size
    qs = spatial_queries(256, seed=33)
    sess = InterpolationSession(pts, query_domain=qs,
                                mesh=jax.make_mesh((1,), ("q",)))
    sess.query(qs)
    t0 = P.execute_traces()
    _churn(sess)
    sess.query(qs)
    assert P.execute_traces() == t0
    assert sess.stats["delta_updates"] == 4


@pytest.mark.parametrize("layout", ["ring", "grid_ring"])
def test_churn_ring_layouts_never_retrace(layout):
    """Ring layouts: n_points rides through the ring executors as a traced
    scalar and the packet arrays are capacity-padded, so resizing churn
    reuses the ONE compiled signature (jit cache size stays 1)."""
    from repro.data.pipeline import spatial_points, spatial_queries

    pts = spatial_points(3163 if layout == "ring" else 3217, seed=34)
    qs = spatial_queries(256, seed=35)
    mesh = jax.make_mesh((1,), ("q",))
    sess = InterpolationSession(pts, query_domain=qs, mesh=mesh,
                                layout=layout)
    sess.query(qs)
    sp = sess.sharded_plan
    if layout == "ring":
        fn = P.ring_session_execute(sp.mesh, sp.ring_axis, sess.plan.cfg)
    else:
        fn = P.grid_ring_session_execute(
            sp.mesh, sp.ring_axis, sess.plan.cfg, sess.plan.spec, sp.rps,
            sp.halo, sp.max_level)
    # the cached executor is shared process-wide (keyed by mesh/cfg), so
    # other suites may have compiled other shapes already — the invariant
    # is that churn adds ZERO new signatures, not an absolute count
    n0 = fn._cache_size()
    assert n0 >= 1
    _churn(sess)
    sess.query(qs)
    assert fn._cache_size() == n0                   # zero retraces on churn
    assert sess.stats["delta_updates"] == 4
