"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles (interpret)."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.aidw import ops as aidw_ops, ref as aidw_ref
from repro.kernels.knn import ops as knn_ops, ref as knn_ref


def _data(n, m, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.random((n, 2)), dtype)
    p = jnp.asarray(rng.random((m, 2)), dtype)
    z = jnp.asarray(np.sin(rng.random(m) * 7), dtype)
    a = jnp.asarray(rng.uniform(0.5, 4.0, n), dtype)
    return q, p, z, a


@pytest.mark.parametrize("n,m,tq,td", [
    (256, 512, 256, 512),     # exact tile fit
    (700, 1300, 256, 512),    # ragged both axes
    (64, 100, 8, 128),        # tiny tiles
    (1024, 256, 512, 128),    # more queries than data
    (1, 1, 8, 128),           # degenerate
])
def test_aidw_kernel_shapes_f32(n, m, tq, td):
    q, p, z, a = _data(n, m, jnp.float32)
    out, zero = aidw_ops.tiled_interpolate(q, p, z, a, tile_q=tq, tile_d=td,
                                           interpret=True)
    want = aidw_ref.interpolate_ref(q, p, z, a)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(zero).any()


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 5e-2)])
def test_aidw_kernel_dtypes(dtype, tol):
    q, p, z, a = _data(300, 600, dtype)
    out, _ = aidw_ops.tiled_interpolate(q, p, z, a, tile_q=128, tile_d=256,
                                        interpret=True)
    want = aidw_ref.interpolate_ref(q, p, z, a)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_aidw_fused_alpha_kernel():
    q, p, z, _ = _data(300, 600, jnp.float32, seed=3)
    r_obs = jnp.asarray(np.random.default_rng(4).uniform(0, 0.1, 300), jnp.float32)
    out, _ = aidw_ops.fused_stage2(q, p, z, r_obs, n_points=600, area=1.0,
                                   tile_q=128, tile_d=256, interpret=True)
    want = aidw_ref.fused_stage2_ref(q, p, z, r_obs, n_points=600, area=1.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,m,k", [
    (256, 512, 15), (300, 600, 7), (33, 90, 15), (1, 64, 3),
])
def test_local_kernel_bitwise_vs_jnp_topk(n, m, k):
    """The local weighting kernel is BITWISE the jnp top-k path on the
    same gathered values (sequential k-axis accumulation)."""
    from repro.core import aidw as A, brute_knn

    q, p, z, a = _data(n, m, jnp.float32, seed=5)
    d2, idx = brute_knn(p, q, k)
    out, zero = aidw_ops.local_interpolate(d2, idx, z, a, tile_q=64,
                                           interpret=True)
    swz, sw = A.topk_weighted_partial_sums(d2, z[idx], a)
    want, wzero = A.guarded_values(swz, sw)
    assert (np.asarray(out) == np.asarray(want)).all()
    assert (np.asarray(zero) == np.asarray(wzero)).all()
    assert not np.isnan(np.asarray(out)).any()


def test_fused_local_kernel_bitwise_vs_unfused():
    """In-kernel alpha (Eqs. 2/4/5/6 from the SMEM stats block) is bitwise
    the host-side adaptive_alpha -> unfused local kernel chain."""
    from repro.core import aidw as A, brute_knn

    q, p, z, _ = _data(300, 600, jnp.float32, seed=6)
    d2, idx = brute_knn(p, q, 15)
    r_obs = jnp.sqrt(jnp.maximum(d2, 0.0)).mean(axis=1)
    alpha = A.adaptive_alpha(r_obs, jnp.float32(600), jnp.float32(1.0))
    fused, fzero = aidw_ops.fused_local_stage2(
        d2, idx, z, r_obs, n_points=jnp.float32(600), area=jnp.float32(1.0),
        tile_q=128, interpret=True)
    unf, uzero = aidw_ops.local_interpolate(d2, idx, z, alpha, tile_q=128,
                                            interpret=True)
    assert (np.asarray(fused) == np.asarray(unf)).all()
    assert (np.asarray(fzero) == np.asarray(uzero)).all()


def test_tiled_kernel_zero_weight_sentinel():
    """Global Pallas path: a query beyond f32 range from all data underflows
    every weight — 0.0 sentinel + raised mask bit, never NaN."""
    q = jnp.array([[1e18, 1e18], [0.5, 0.5]], jnp.float32)
    p = jnp.asarray(np.random.default_rng(8).random((64, 2)), jnp.float32)
    z = jnp.ones((64,), jnp.float32)
    out, zero = aidw_ops.tiled_interpolate(q, p, z, 4.0, tile_q=8,
                                           tile_d=128, interpret=True)
    assert not np.isnan(np.asarray(out)).any()
    assert np.asarray(zero)[0] and np.asarray(out)[0] == 0.0
    assert not np.asarray(zero)[1]


def test_local_kernel_zero_weight_sentinel():
    """All-inf neighbour distances (every weight underflows) must yield the
    0.0 sentinel + raised mask bit — never NaN."""
    d2 = jnp.full((4, 8), jnp.inf, jnp.float32)
    idx = jnp.zeros((4, 8), jnp.int32)
    z = jnp.ones((16,), jnp.float32)
    out, zero = aidw_ops.local_interpolate(d2, idx, z, 2.0, tile_q=8,
                                           interpret=True)
    assert np.asarray(zero).all()
    assert (np.asarray(out) == 0.0).all()


@pytest.mark.parametrize("n,m,k", [
    (256, 512, 15), (100, 300, 1), (70, 40, 8), (128, 128, 32), (33, 9, 15),
])
def test_knn_kernel_shapes(n, m, k):
    q, p, _, _ = _data(n, m, jnp.float32, seed=k)
    out = knn_ops.knn_d2(p, q, k=k, tile_q=64, tile_d=128, interpret=True)
    want = knn_ref.knn_d2_ref(p, q, k=k)
    fin = np.isfinite(np.asarray(want))
    np.testing.assert_allclose(np.asarray(out)[fin], np.asarray(want)[fin],
                               rtol=1e-5, atol=1e-7)
    assert (np.isfinite(np.asarray(out)) == fin).all()


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 5e-2)])
def test_knn_kernel_dtypes(dtype, tol):
    q, p, _, _ = _data(200, 400, dtype, seed=9)
    out = knn_ops.knn_d2(p, q, k=10, tile_q=64, tile_d=128, interpret=True)
    want = knn_ref.knn_d2_ref(p, q, k=10)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_knn_kernel_duplicate_points():
    """k-pass masked-min must handle exact duplicate distances."""
    p = jnp.array([[0.5, 0.5]] * 20 + [[0.1, 0.1]] * 5, jnp.float32)
    q = jnp.array([[0.5, 0.5]], jnp.float32)
    out = knn_ops.knn_d2(p, q, k=21, tile_q=8, tile_d=128, interpret=True)
    want = knn_ref.knn_d2_ref(p, q, k=21)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-7)


def test_kernel_mean_distance_matches_core():
    from repro.core import brute_knn

    q, p, _, _ = _data(150, 350, jnp.float32, seed=11)
    d2k = knn_ops.knn_d2(p, q, k=15, interpret=True)
    d2c, _ = brute_knn(p, q, 15)
    np.testing.assert_allclose(np.asarray(knn_ops.mean_nn_distance(d2k)),
                               np.asarray(knn_ops.mean_nn_distance(d2c)),
                               rtol=1e-5)


@pytest.mark.parametrize("backend,arg,want", [
    ("tpu", None, False), ("cpu", None, True), ("gpu", None, True),
    ("tpu", True, True), ("cpu", False, False),
])
def test_interpret_resolves_from_backend(monkeypatch, backend, arg, want):
    """``interpret=None`` compiles on a TPU and interprets elsewhere; an
    explicit bool is kept as given."""
    import jax

    from repro.kernels import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_interpret(arg) is want
