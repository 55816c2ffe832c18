"""Grid kNN: exactness vs brute force (the paper's central data structure)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypcompat import given, settings, st  # guarded: skips, never dies, without hypothesis

from repro.core import bin_points, brute_knn, grid_knn, mean_nn_distance, plan_grid
from repro.core import knn as K
from repro.core.slab import SlabPartition
from repro.data.pipeline import spatial_points, spatial_queries


def _setup(pts, qs):
    spec = plan_grid(pts[:, :2], qs)
    table = bin_points(spec, jnp.array(pts[:, 0]), jnp.array(pts[:, 1]),
                       jnp.array(pts[:, 2]))
    return spec, table


def test_grid_knn_exact_matches_brute():
    rng = np.random.default_rng(0)
    pts = rng.random((3000, 3)).astype(np.float32)
    qs = rng.random((700, 2)).astype(np.float32)
    spec, table = _setup(pts, qs)
    res = grid_knn(spec, table, jnp.array(qs), 15, None, 1024, 512, True)
    bd2, _ = brute_knn(jnp.array(pts[:, :2]), jnp.array(qs), 15)
    assert int(res.overflow.sum()) == 0
    np.testing.assert_allclose(np.sort(np.asarray(res.d2), 1),
                               np.sort(np.asarray(bd2), 1), atol=1e-6)


def test_paper_heuristic_mode_close_but_flagged():
    """exact=False is the paper's +1-ring heuristic: nearly exact on uniform
    data (the paper's own test protocol) — mismatches are rare and small."""
    rng = np.random.default_rng(1)
    pts = rng.random((3000, 3)).astype(np.float32)
    qs = rng.random((1000, 2)).astype(np.float32)
    spec, table = _setup(pts, qs)
    res = grid_knn(spec, table, jnp.array(qs), 15, None, 1024, 512, False)
    bd2, _ = brute_knn(jnp.array(pts[:, :2]), jnp.array(qs), 15)
    bad = (np.abs(np.sort(np.asarray(res.d2), 1)
                  - np.sort(np.asarray(bd2), 1)).max(1) > 1e-6).sum()
    assert bad <= 20  # < 2% of queries on uniform data


@settings(max_examples=20, deadline=None)
@given(st.integers(30, 500), st.integers(1, 25), st.integers(0, 10_000),
       st.booleans())
def test_grid_knn_exactness_property(m, k, seed, clustered):
    rng = np.random.default_rng(seed)
    if clustered:
        centers = rng.random((3, 2))
        xy = np.clip(centers[rng.integers(0, 3, m)]
                     + rng.normal(0, 0.05, (m, 2)), 0, 1)
    else:
        xy = rng.random((m, 2))
    pts = np.concatenate([xy, rng.random((m, 1))], 1).astype(np.float32)
    qs = rng.random((64, 2)).astype(np.float32)
    spec, table = _setup(pts, qs)
    res = grid_knn(spec, table, jnp.array(qs), k, None, 4096, 64, True)
    bd2, _ = brute_knn(jnp.array(pts[:, :2]), jnp.array(qs), k)
    no_ovf = ~np.asarray(res.overflow)
    got = np.sort(np.asarray(res.d2), 1)[no_ovf]
    want = np.sort(np.asarray(bd2), 1)[no_ovf]
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_k_larger_than_m():
    rng = np.random.default_rng(3)
    pts = rng.random((8, 3)).astype(np.float32)
    qs = rng.random((5, 2)).astype(np.float32)
    spec, table = _setup(pts, qs)
    res = grid_knn(spec, table, jnp.array(qs), 15, None, 64, 8, True)
    # first 8 finite, rest inf
    d2 = np.sort(np.asarray(res.d2), 1)
    assert np.isfinite(d2[:, :8]).all()
    assert np.isinf(d2[:, 8:]).all()


def test_mean_nn_distance_defers_sqrt():
    d2 = jnp.array([[4.0, 9.0, 16.0]])
    assert float(mean_nn_distance(d2)[0]) == (2 + 3 + 4) / 3


def test_knn_indices_point_to_true_neighbors():
    rng = np.random.default_rng(4)
    pts = rng.random((500, 3)).astype(np.float32)
    qs = rng.random((50, 2)).astype(np.float32)
    spec, table = _setup(pts, qs)
    res = grid_knn(spec, table, jnp.array(qs), 5, None, 512, 64, True)
    idx = np.asarray(res.idx)
    d2 = np.asarray(res.d2)
    for i in range(len(qs)):
        d = (pts[idx[i], 0] - qs[i, 0]) ** 2 + (pts[idx[i], 1] - qs[i, 1]) ** 2
        np.testing.assert_allclose(np.sort(d), np.sort(d2[i]), rtol=1e-5)


# --- the window-slot -> source map (``knn._slot_map``) -----------------------

def _search_slot_map(r_start, r_len, window):
    """Reference: the binary-search slot map, as Stage 1 first wrote it."""
    n_band = r_len.shape[0]
    offsets = jnp.cumsum(r_len)
    slots = jnp.arange(window, dtype=jnp.int32)
    row_of = jnp.searchsorted(offsets, slots, side="right").astype(jnp.int32)
    row_of = jnp.minimum(row_of, n_band - 1)
    prev = jnp.where(row_of > 0, offsets[jnp.maximum(row_of - 1, 0)], 0)
    src = r_start[row_of] + (slots - prev)
    return src, offsets[-1]


def _bands(case, n_band, window, rng, n=300):
    """``n`` ragged bands of ``n_band`` rows: row starts and lengths."""
    r_start = rng.integers(0, 1_000_000, (n, n_band))
    if case == "ragged":     # empty rows among short ones, totals on both sides of window
        r_len = rng.integers(0, 2 * window // n_band + 2, (n, n_band))
        r_len[rng.random((n, n_band)) < 0.3] = 0
    elif case == "empty":
        r_len = np.zeros((n, n_band), np.int64)
    else:                    # "overfull": more than the window, one row empty
        r_len = rng.integers(window // max(n_band - 1, 1) + 1, 2 * window,
                             (n, n_band))
        if n_band > 1:
            r_len[:, rng.integers(0, n_band)] = 0
    return (jnp.asarray(r_start, jnp.int32), jnp.asarray(r_len, jnp.int32))


@pytest.mark.parametrize("case", ["ragged", "empty", "overfull"])
@pytest.mark.parametrize("n_band", [1, 3, 23, 69, 255, 256, 301])
def test_slot_map_matches_search(n_band, case):
    """The dense slot map gives the binary search's source index and band
    total for every slot: empty rows, slots past the band's end and a
    band larger than the window included."""
    window = 256
    r_start, r_len = _bands(case, n_band, window,
                            np.random.default_rng(n_band * 7 + len(case)))
    got = jax.jit(jax.vmap(lambda a, b: K._slot_map(a, b, window)))(r_start, r_len)
    want = jax.vmap(lambda a, b: _search_slot_map(a, b, window))(r_start, r_len)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if case == "overfull":
        assert bool((want[1] > window).all())


@pytest.fixture
def search_slot_map(monkeypatch):
    """Stage 1 with the binary-search slot map in place of ``_slot_map``
    (every trace made while it is on is dropped on both sides)."""
    jax.clear_caches()
    monkeypatch.setattr(K, "_slot_map", _search_slot_map)
    yield
    monkeypatch.undo()
    jax.clear_caches()


def _data(clustered):
    pts = spatial_points(3000, seed=5, clustered=clustered)
    qs = spatial_queries(600, seed=6)
    spec = plan_grid(pts[:, :2], qs)
    return pts, qs, spec


@pytest.mark.parametrize("clustered", [False, True])
def test_grid_knn_bitwise_with_search_slot_map(clustered, request):
    """Every ``grid_knn`` output is bitwise what the search form gives."""
    pts, qs, spec = _data(clustered)
    table = bin_points(spec, *(jnp.asarray(pts[:, i]) for i in range(3)))

    def run():
        return [np.asarray(a) for a in
                grid_knn(spec, table, jnp.asarray(qs), 15, None, 256, 256, True)]

    got = run()
    request.getfixturevalue("search_slot_map")
    want = run()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("clustered", [False, True])
def test_slab_knn_bitwise_with_search_slot_map(clustered, request):
    """Every ``slab_knn`` output, slab by slab, is bitwise the search form's."""
    pts, qs, spec = _data(clustered)
    k = 15
    max_level = K.auto_max_level(spec, pts.shape[0], k)
    part = SlabPartition.build(spec, pts, 3, halo=max_level)
    dev = part.device_tables()

    def run():
        out = []
        for s in range(part.p):
            res = K.slab_knn(
                spec, part.rps, part.halo, jnp.asarray(dev["cell_start"][s]),
                jnp.asarray(dev["sx"][s]), jnp.asarray(dev["sy"][s]),
                jnp.arange(dev["sx"].shape[1], dtype=jnp.int32),
                jnp.int32(dev["row_lo"][s]), jnp.asarray(qs), k, max_level,
                256, 256)
            out += [np.asarray(a) for a in res]
        return out

    got = run()
    request.getfixturevalue("search_slot_map")
    want = run()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def _search_loops():
    """``while`` instructions of the compiled ``grid_knn`` that carry an
    ``s32[4096,256]`` operand (the binary search's slots, block x window)
    at the cells' density: 0.25 points per Eq. (2) cell, n_band = 23."""
    rng = np.random.default_rng(7)
    pts = rng.random((40_000, 3)).astype(np.float32)
    qs = rng.random((4096, 2)).astype(np.float32)
    spec = plan_grid(pts[:, :2], qs)
    table = bin_points(spec, *(jnp.asarray(pts[:, i]) for i in range(3)))
    assert 2 * K.auto_max_level(spec, pts.shape[0], 15) + 1 == 23
    hlo = grid_knn.lower(spec, table, jnp.asarray(qs), 15, None, 256, 4096,
                         True).compile().as_text()
    return [ln for ln in hlo.splitlines()
            if " while(" in ln and "s32[4096,256]" in ln]


def test_grid_knn_has_no_slot_search_loop():
    """The dense slot map leaves no binary-search loop in Stage 1."""
    assert _search_loops() == []


def test_slot_search_loop_detected(search_slot_map):
    """Control for the test above: the search form's loops are found."""
    assert len(_search_loops()) == 2
