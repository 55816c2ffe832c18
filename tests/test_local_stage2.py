"""Local (exact-k) Stage-2 error contract vs the global Eq. (1) path.

The contract (``repro.core.aidw`` module docstring): with
``AidwConfig(stage2='local')`` Stage 1 is untouched, so ``r_obs``/``alpha``
are BIT-IDENTICAL to global mode by construction; the predicted values
differ exactly by the truncated far-field tail, which is bounded by the
tail's weight-mass fraction, shrinks as k grows, and vanishes (to f32
accumulation tolerance) at k = m.  Tightest on clustered data, loosest on
uniform data — both regimes are pinned here, plus the fused Pallas kernel's
bitwise equivalence, the zero-weight sentinel, and the fleet's single-phase
local merge.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from hypcompat import given, settings, st
from repro.core import (AidwConfig, InterpolationSession, aidw_improved,
                        brute_knn)
from repro.core import aidw as A
from repro.data.pipeline import spatial_points, spatial_queries


def _pair(pts, qs, **cfg_kw):
    """(global, local) results for the same dataset/queries."""
    g = aidw_improved(pts, qs, AidwConfig(**cfg_kw))
    l = aidw_improved(pts, qs, AidwConfig(stage2="local", **cfg_kw))
    return g, l


def _tail_bound(pts, qs, k, alpha):
    """f64 oracle: the far-field tail's weight-mass error bound per query.

    |Z_local - Z_global| <= (tail_w / total_w) * spread(z): dropping the
    tail moves the weighted average by at most the dropped mass times the
    data's value range.
    """
    d2 = ((qs[:, None, :] - pts[None, :, :2]) ** 2).sum(-1).astype(np.float64)
    w = np.maximum(d2, A.EPS_D2) ** (-0.5 * alpha[:, None].astype(np.float64))
    order = np.argsort(d2, axis=1, kind="stable")
    wsorted = np.take_along_axis(w, order, axis=1)
    tail = wsorted[:, k:].sum(axis=1)
    spread = pts[:, 2].max() - pts[:, 2].min()
    return tail / wsorted.sum(axis=1) * spread


def test_local_stats_bitwise_and_values_within_tail_bound():
    """Acceptance: r_obs/alpha bitwise vs global; |values delta| within the
    analytic truncated-tail bound (+ f32 accumulation slack)."""
    pts = spatial_points(4096, seed=0)
    qs = spatial_queries(512, seed=1)
    g, l = _pair(pts, qs, k=15)
    assert np.array_equal(np.asarray(g.r_obs), np.asarray(l.r_obs))
    assert np.array_equal(np.asarray(g.alpha), np.asarray(l.alpha))
    err = np.abs(np.asarray(g.values) - np.asarray(l.values))
    bound = _tail_bound(pts, qs, 15, np.asarray(g.alpha))
    assert (err <= bound + 1e-4).all(), float((err - bound).max())
    assert not np.asarray(l.zero_weight_mask).any()


@pytest.mark.parametrize("clustered", [False, True])
def test_local_converges_to_global_as_k_grows(clustered):
    """k -> m convergence: the tail error shrinks with k and reaches f32
    accumulation tolerance at k = m (the whole dataset is "local")."""
    m = 512
    pts = spatial_points(m, seed=2, clustered=clustered)
    qs = spatial_queries(128, seed=3)
    errs = []
    for k in (4, 16, 64, m):
        g, l = _pair(pts, qs, k=k, window=4 * m)
        assert np.array_equal(np.asarray(g.alpha), np.asarray(l.alpha)), k
        errs.append(np.abs(np.asarray(g.values) - np.asarray(l.values)).max())
    assert errs[-1] < 1e-5, errs            # k = m: only accumulation order
    assert errs[-1] <= errs[0] + 1e-7, errs  # tail error really shrank


def test_local_tolerance_uniform_tighter_than_clustered():
    """The documented regime split (``repro.core.aidw``): the tail mass is
    set by the alpha Eq. (6) picks, so UNIFORM patterns (alpha >= 2, fast
    decay) truncate tightly while CLUSTERED patterns (alpha ~ 0.5 near the
    clusters) carry a heavy far-field tail — local mode is loosest there."""
    rng = np.random.default_rng(4)

    def stats(clustered):
        pts = spatial_points(4096, seed=5, clustered=clustered)
        # queries co-located with the data: jittered data sites
        qs = (pts[rng.integers(0, 4096, 256), :2]
              + rng.normal(0, 0.005, (256, 2))).astype(np.float32)
        g, l = _pair(pts, qs, k=15)
        err = float(np.median(np.abs(np.asarray(g.values)
                                     - np.asarray(l.values))))
        return err, float(np.median(np.asarray(g.alpha)))

    uni_err, uni_alpha = stats(False)
    clu_err, clu_alpha = stats(True)
    assert clu_alpha < uni_alpha        # Eq. (6): clustered -> small alpha
    assert uni_err < clu_err            # ... hence the heavier tail


def test_session_local_fused_vs_unfused(spatial_data):
    """AidwConfig(stage2='local', fused=True) — the Pallas gather+weighting
    kernel — matches the unfused jnp top-k path end to end: Stage-1 stats
    and masks bitwise, values within 1 ulp (XLA contracts the compiled jnp
    path's mul+add into an FMA the interpreter doesn't use; the eager
    bitwise contract is pinned in tests/test_kernels.py)."""
    pts, qs = spatial_data
    unf = InterpolationSession(pts, AidwConfig(stage2="local"),
                               query_domain=qs).query(qs)
    fus = InterpolationSession(
        pts, AidwConfig(stage2="local", fused=True, interpret=True),
        query_domain=qs).query(qs)
    vu, vf = np.asarray(unf.values), np.asarray(fus.values)
    np.testing.assert_allclose(vf, vu, rtol=5e-7, atol=5e-7)
    assert np.array_equal(np.asarray(unf.alpha), np.asarray(fus.alpha))
    assert np.array_equal(np.asarray(unf.r_obs), np.asarray(fus.r_obs))
    assert np.array_equal(np.asarray(unf.zero_weight_mask),
                          np.asarray(fus.zero_weight_mask))


def test_session_local_matches_global_stats(spatial_data):
    """Session-level contract: local sessions report bitwise-identical
    Stage-1 stats (r_obs/alpha/overflow) to the global session."""
    pts, qs = spatial_data
    g = InterpolationSession(pts, query_domain=qs).query(qs)
    l = InterpolationSession(pts, AidwConfig(stage2="local"),
                             query_domain=qs).query(qs)
    assert np.array_equal(np.asarray(g.r_obs), np.asarray(l.r_obs))
    assert np.array_equal(np.asarray(g.alpha), np.asarray(l.alpha))
    assert np.array_equal(np.asarray(g.overflow_mask),
                          np.asarray(l.overflow_mask))
    assert np.abs(np.asarray(g.values) - np.asarray(l.values)).max() < 0.2


@settings(max_examples=20, deadline=None)
@given(st.integers(100, 600), st.integers(1, 30), st.integers(0, 10_000),
       st.booleans())
def test_local_error_contract_property(m, k, seed, clustered):
    """Property (hypothesis): for any cloud/k, the top-k truncation of
    Eq. (1) stays within the f64 tail bound and keeps alpha bitwise."""
    pts = spatial_points(m, seed=seed, clustered=clustered)
    qs = spatial_queries(32, seed=seed + 1)
    g, l = _pair(pts, qs, k=k, window=4 * m)
    assert np.array_equal(np.asarray(g.alpha), np.asarray(l.alpha))
    err = np.abs(np.asarray(g.values) - np.asarray(l.values))
    bound = _tail_bound(pts, qs, k, np.asarray(g.alpha))
    assert (err <= bound + 1e-3).all(), float((err - bound).max())


def test_topk_partial_sums_pad_invariance():
    """Appending inf-distance slots to the k axis is a bitwise no-op — the
    sequential accumulation contract."""
    rng = np.random.default_rng(7)
    d2 = jnp.asarray(np.sort(rng.random((64, 9)), axis=1), jnp.float32)
    z = jnp.asarray(rng.normal(0, 1, (64, 9)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.5, 4.0, 64), jnp.float32)
    swz, sw = A.topk_weighted_partial_sums(d2, z, a)
    d2p = jnp.pad(d2, ((0, 0), (0, 7)), constant_values=jnp.inf)
    zp = jnp.pad(z, ((0, 0), (0, 7)))
    swzp, swp = A.topk_weighted_partial_sums(d2p, zp, a)
    assert np.array_equal(np.asarray(swz), np.asarray(swzp))
    assert np.array_equal(np.asarray(sw), np.asarray(swp))


def test_local_zero_weight_far_query(spatial_data):
    """A query so far that every neighbour weight underflows: 0.0 sentinel +
    raised mask, never NaN — through the full local session path."""
    pts, qs = spatial_data
    far = np.array([[1e18, 1e18]], np.float32)
    batch = np.concatenate([qs[:7], far]).astype(np.float32)
    for fused in (False, True):
        sess = InterpolationSession(
            pts, AidwConfig(stage2="local", fused=fused, interpret=True),
            query_domain=qs)
        res = sess.query(batch)
        vals = np.asarray(res.values)
        mask = np.asarray(res.zero_weight_mask)
        assert not np.isnan(vals).any()
        assert mask[-1] and vals[-1] == 0.0
        assert not mask[:-1].any()


def test_fleet_local_single_phase_matches_replica():
    """ShardedAidwCluster(stage2='local'): the merged (d2, z) heap finishes
    the query client-side (no phase-2 fan-out) and matches a full-replica
    local session within merge-order tolerance, with bitwise alpha."""
    from repro.serving.cluster import ShardedAidwCluster

    pts = spatial_points(4096, seed=0)
    qd = spatial_queries(512, seed=1)
    qs = spatial_queries(300, seed=2)
    cfg = AidwConfig(stage2="local")
    replica = InterpolationSession(pts, cfg, query_domain=qd)
    want = replica.query(qs)
    with ShardedAidwCluster(pts, n_hosts=2, cfg=cfg,
                            query_domain=qd) as fleet:
        got = fleet.query(qs, timeout=300)
        assert got.epoch == 0
        assert np.array_equal(got.alpha.astype(np.float32),
                              np.asarray(want.alpha))
        err = np.abs(got.values - np.asarray(want.values)).max()
        assert err < 1e-5, err
        assert not got.zero_weight_mask.any()


def test_grid_ring_local_matches_global_one_device():
    """grid_ring + stage2='local' on a 1-device mesh: bitwise Stage-1 stats
    vs the global grid-ring session, values within the tail tolerance, and
    no Stage-2 rotation needed to serve."""
    import jax

    mesh = jax.make_mesh((len(jax.devices()),), ("q",))
    pts = spatial_points(2048, seed=0)
    qs = spatial_queries(256, seed=1)
    g = InterpolationSession(pts, query_domain=qs, mesh=mesh,
                             layout="grid_ring").query(qs)
    l = InterpolationSession(pts, AidwConfig(stage2="local"),
                             query_domain=qs, mesh=mesh,
                             layout="grid_ring").query(qs)
    assert np.array_equal(np.asarray(g.r_obs), np.asarray(l.r_obs))
    assert np.array_equal(np.asarray(g.alpha), np.asarray(l.alpha))
    assert np.array_equal(np.asarray(g.overflow_mask),
                          np.asarray(l.overflow_mask))
    bound = _tail_bound(pts, qs, 15, np.asarray(g.alpha))
    err = np.abs(np.asarray(g.values) - np.asarray(l.values))
    assert (err <= bound + 1e-4).all()


def test_ring_local_matches_global_one_device():
    """ring + stage2='local' on a 1-device mesh: same contract through the
    brute-force ring executor (co-merged (d2, z) carry)."""
    import jax

    mesh = jax.make_mesh((len(jax.devices()),), ("q",))
    pts = spatial_points(1024, seed=0)
    qs = spatial_queries(256, seed=1)
    g = InterpolationSession(pts, query_domain=qs, mesh=mesh,
                             layout="ring").query(qs)
    l = InterpolationSession(pts, AidwConfig(stage2="local"),
                             query_domain=qs, mesh=mesh,
                             layout="ring").query(qs)
    assert np.array_equal(np.asarray(g.r_obs), np.asarray(l.r_obs))
    assert np.array_equal(np.asarray(g.alpha), np.asarray(l.alpha))
    bound = _tail_bound(pts, qs, 15, np.asarray(g.alpha))
    err = np.abs(np.asarray(g.values) - np.asarray(l.values))
    assert (err <= bound + 1e-4).all()
