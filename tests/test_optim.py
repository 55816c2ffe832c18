"""Optimizer + gradient compression: convergence and invariants."""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.optim import adamw, compression


def _fit(opt_cfg, steps=200, compress=False):
    """Fit y = Xw on a fixed problem; returns final loss."""
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(0, 1, (64, 8)), jnp.float32)
    w_true = jnp.asarray(rng.normal(0, 1, (8,)), jnp.float32)
    y = X @ w_true
    params = {"w": jnp.zeros((8,), jnp.float32)}
    state = adamw.init_state(opt_cfg, params)
    err = compression.init_error(params) if compress else None

    def loss_fn(p):
        return jnp.mean((X @ p["w"] - y) ** 2)

    @jax.jit
    def step(p, s, e):
        l, g = jax.value_and_grad(loss_fn)(p)
        if compress:
            g, e = compression.compress_with_feedback(g, e)
        p, s, _ = adamw.apply_updates(opt_cfg, p, s, g)
        return p, s, e, l

    for _ in range(steps):
        params, state, err, l = step(params, state, err)
    return float(l)


def test_adamw_converges():
    assert _fit(adamw.AdamWConfig(lr=0.05, weight_decay=0.0,
                                  warmup_steps=5, total_steps=200)) < 1e-3


def test_compressed_grads_converge():
    """Error feedback keeps int8-quantized gradients unbiased over time."""
    assert _fit(adamw.AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=5,
                                  total_steps=200), compress=True) < 1e-2


def test_no_master_weights_mode():
    cfg = adamw.AdamWConfig(lr=0.05, weight_decay=0.0, master_weights=False,
                            warmup_steps=5, total_steps=200)
    assert "master" not in adamw.init_state(cfg, {"w": jnp.zeros(3)})
    assert _fit(cfg) < 1e-3


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0), "b": jnp.full((3,), -10.0)}
    clipped, norm = adamw.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(700), rel=1e-5)
    assert float(adamw.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    # below threshold: untouched
    same, _ = adamw.clip_by_global_norm(g, 1e9)
    np.testing.assert_allclose(np.asarray(same["a"]), np.asarray(g["a"]))


def test_lr_schedule_shape():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            min_lr_ratio=0.1)
    lrs = [float(adamw.lr_schedule(cfg, jnp.int32(s))) for s in range(101)]
    assert lrs[0] == 0.0
    assert lrs[10] == pytest.approx(1.0, rel=1e-3)
    assert max(lrs) <= 1.0 + 1e-6
    assert lrs[100] == pytest.approx(0.1, rel=1e-3)
    assert all(b <= a + 1e-6 for a, b in zip(lrs[10:], lrs[11:]))  # decays


def test_quantize_roundtrip_error_bounded(rng):
    g = jnp.asarray(rng.normal(0, 3, (1000,)), jnp.float32)
    q, s = compression._quantize(g)
    dq = compression._dequantize(q, s)
    assert float(jnp.abs(g - dq).max()) <= float(s) * 0.5 + 1e-6


def test_error_feedback_accumulates():
    g = {"w": jnp.asarray([1e-4] * 8, jnp.float32)}  # below 1-step resolution
    err = compression.init_error(g)
    total = jnp.zeros(8)
    for _ in range(50):
        dq, err = compression.compress_with_feedback(g, err)
        total = total + dq["w"]
    # over many steps the quantized stream must deliver the true mass
    np.testing.assert_allclose(np.asarray(total), 50 * 1e-4, rtol=0.2)


def test_compressed_psum_shardmap(rng):
    """int8-quantize -> psum -> dequantize inside shard_map (1 device)."""
    mesh = jax.make_mesh((1,), ("d",))
    g = jnp.asarray(rng.normal(0, 1, (16,)), jnp.float32)

    fn = jax.shard_map(lambda x: compression.compressed_psum(x, "d"),
                   mesh=mesh, in_specs=jax.sharding.PartitionSpec("d"),
                   out_specs=jax.sharding.PartitionSpec("d"))
    out = fn(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=0.05)
