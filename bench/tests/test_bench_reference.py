"""The plain reference against the float64 serial AIDW of
``benchmarks/serial_ref.py`` and a NumPy brute force, at a small size."""

import numpy as np
import pytest

from benchmarks.serial_ref import serial_aidw
from bench import data, reference


@pytest.fixture(scope="module")
def case():
    pts = data.data_set({"name": "uniform"})[0](3000, data.rng(7, 0))
    q = data.query_stream({"name": "uniform"}, data.rng(7, 1))(300)
    return pts, q, reference.study_area(pts[:, :2], 1e-6)


def test_global_matches_serial_float64(case):
    pts, q, area = case
    got = reference.aidw(pts, q, area=area)
    want = serial_aidw(pts, q, k=15, area=area)
    np.testing.assert_allclose(got["values"], want, atol=2e-5)


def test_local_matches_numpy_brute_force(case):
    pts, q, area = case
    got = reference.aidw(pts, q, area=area, local=True)
    p = pts.astype(np.float64)
    d2 = ((q[:, None, :].astype(np.float64) - p[None, :, :2]) ** 2).sum(-1)
    idx = np.argsort(d2, axis=1)[:, :15]
    nd2 = np.take_along_axis(d2, idx, axis=1)
    r_obs = np.sqrt(nd2).mean(1)
    np.testing.assert_allclose(got["r_obs"], r_obs, rtol=1e-5)
    w = nd2 ** (-got["alpha"][:, None].astype(np.float64) / 2)
    want = (w * p[idx, 2]).sum(1) / w.sum(1)
    np.testing.assert_allclose(got["values"], want, atol=2e-5)


def test_study_area_is_the_planned_grid():
    pts = np.array([[0.0, 0.0], [1.0, 0.5]])
    # box 1 x 0.5, cell 1 / (2 sqrt(2 / 0.5)) = 0.25: 5 x 3 cells
    assert reference.study_area(pts, 0.0) == pytest.approx(1.25 * 0.75)


def test_block_size_does_not_change_answers(case):
    pts, q, area = case
    a = reference.aidw(pts, q[:77], area=area, block=128)
    b = reference.aidw(pts, q[:77], area=area, block=32)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-6)
