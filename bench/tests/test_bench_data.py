"""Data sets and query patterns, found by name: the uniform ones draw
bitwise what the harness drew before they were files of their own, and
the raster pattern sweeps its lattice row by row."""

import hashlib

import numpy as np
import pytest

from bench import data, generate, readings
from bench import run as R

UNIFORM = {"name": "uniform"}


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# sha256 prefixes of what the harness drew when points, inserts and queries
# were functions of bench/data.py (points(1000), inserts(500), queries(700))
@pytest.mark.parametrize("seed,want", [
    (2 ** 33 + 7, ("5b7d08021c45b53e", "b6e0360c0915fa15", "60d8c52cf2e80f98")),
    (-5, ("40b6e546e06f8a10", "d14af215bcaea9a4", "68800a9dcbed4747"))])
def test_uniform_draws_what_it_drew_before(seed, want):
    points, inserts = data.data_set(UNIFORM)
    got = (_digest(points(1000, data.rng(seed, 0))),
           _digest(inserts(500, data.rng(seed, 1))),
           _digest(data.query_stream(UNIFORM, data.rng(seed, 1))(700)))
    assert got == want


# every array a run of seed 3140000303 makes at the cell's full size (the
# data set, the window's queries and updates, two calls of a closed loop),
# and the set-up's warm-up, as drawn before the move
@pytest.mark.parametrize("cell,want", [
    ("global-served", "c5d84fc6633e8ef3"), ("local-batch", "40a60f679e45e5c1"),
    ("local-churn", "911758328a5ebb63")])
def test_existing_cells_draw_what_they_drew_before(cell, want):
    entry = R.find(R.load_spec()["workloads"], cell)
    config = R.load_config(entry["config"])
    mix = generate.load_mix(R.BENCH, entry["traffic"])
    pts, rec = readings.control_record(config, mix, 3140000303, 51.0, 2)
    arrays = [pts] + [c["queries"] for c in rec.win.calls]
    arrays += [r["queries"] for r in rec.win.reads]
    for u in rec.setup_updates + rec.win.updates:
        arrays += [u["inserts"], np.asarray(u["deletes"])]
    assert _digest(*arrays) == want


@pytest.mark.parametrize("mix_name,updates,want", [
    ("served-128-2048", False, "9a2cd9e713d5cc80"),
    ("churn-64-1024", True, "591b0b13026609b1")])
def test_warm_up_draws_what_it_drew_before(mix_name, updates, want):
    mix = generate.load_mix(R.BENCH, mix_name)
    reads, ups = R.warm_plan(mix, 1_000_000, updates,
                             data.rng(3140000303, 2),
                             data.data_set(UNIFORM)[1])
    arrays = reads + [a for u in ups
                      for a in (u["inserts"], np.asarray(u["deletes"]))]
    assert _digest(*arrays) == want


def test_data_set_contract_is_checked(tmp_path):
    (tmp_path / "datasets").mkdir()
    (tmp_path / "datasets" / "cornerless.py").write_text(
        "import numpy as np\n"
        "def points(n, gen):\n"
        "    return (0.5 * gen.random((n, 3))).astype(np.float32)\n"
        "def inserts(n, gen):\n"
        "    return points(n, gen)\n")
    points, _ = data.data_set({"name": "cornerless"}, tmp_path)
    with pytest.raises(ValueError):
        points(100, data.rng(1, 0))


# -- the raster pattern ---------------------------------------------------

H, W = 64, 96


def _raster(seed, height=H, width=W):
    spec = {"name": "raster", "height": height, "width": width}
    return data.query_stream(spec, data.rng(seed, 1))


def _pixels(q, seed):
    """Row and column of each query, and the seed's sub-pixel origin."""
    ox, oy = data.rng(seed, 1).random(2) - 0.5
    j = (q[:, 0].astype(np.float64) - 0.01) / 0.98 * W - 0.5 - ox
    i = (q[:, 1].astype(np.float64) - 0.01) / 0.98 * H - 0.5 - oy
    ri, rj = np.rint(i).astype(int), np.rint(j).astype(int)
    assert np.abs(i - ri).max() < 1e-3 and np.abs(j - rj).max() < 1e-3
    return ri, rj


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 3])
def test_raster_sweeps_every_pixel_once(seed):
    nxt = _raster(seed)
    q = np.concatenate([nxt(W * 4) for _ in range(H // 4)])   # one sweep
    assert q.dtype == np.float32 and q.shape == (H * W, 2)
    assert q.min() >= 0.01 and q.max() <= 0.99
    ri, rj = _pixels(q, seed)
    assert ri.min() == 0 and ri.max() == H - 1
    assert rj.min() == 0 and rj.max() == W - 1
    assert len(set(zip(ri.tolist(), rj.tolist()))) == H * W
    # the next sweep starts over at the same pixel
    assert np.array_equal(nxt(W), q[:W])


def test_raster_pixels_in_row_major_order():
    seed = 5
    q = _raster(seed)(H * W)
    ri, rj = _pixels(q, seed)
    order = ri * W + rj
    assert order[0] % W == 0                         # starts at a whole row
    assert np.array_equal(order, (order[0] + np.arange(H * W)) % (H * W))


@pytest.mark.parametrize("rows", [1, 3, 16])
def test_raster_call_of_whole_rows_is_a_strip(rows):
    seed = 9
    nxt = _raster(seed)
    nxt(W * 2)
    ri, rj = _pixels(nxt(W * rows), seed)
    assert np.array_equal(rj, np.tile(np.arange(W), rows))
    assert len(set(ri.tolist())) == rows


def test_raster_wraps_mid_call():
    seed = 11
    nxt = _raster(seed)
    first = _pixels(nxt(W), seed)[0][0]
    nxt(W * (H - 2))                                 # all but one row
    ri, _ = _pixels(nxt(W * 2), seed)                # the last row, then
    assert set(ri[:W].tolist()) == {(first - 1) % H}  # the first again
    assert set(ri[W:].tolist()) == {first}


def test_raster_seed_fixes_the_stream_and_moves_the_lattice():
    a, b = _raster(7), _raster(7)
    for _ in range(3):
        assert np.array_equal(a(W), b(W))
    x, y = _raster(7)(W * 2), _raster(8)(W * 2)
    assert x.shape == y.shape                        # the same work ...
    assert not np.isin(x, y).any()                   # ... elsewhere


@pytest.mark.parametrize("n", [0, -W])
def test_raster_refuses_an_empty_call(n):
    with pytest.raises(ValueError):
        _raster(1)(n)


@pytest.mark.parametrize("height,width", [(0, W), (H, 0)])
def test_raster_refuses_a_lattice_with_no_pixels(height, width):
    with pytest.raises(ValueError):
        _raster(1, height=height, width=width)
