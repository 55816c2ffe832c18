"""Multi-device tests (subprocess with forced host devices): ring AIDW,
sharded train step, production-mesh construction."""

from __future__ import annotations

import pytest

from conftest import run_multidevice

# subprocess-spawning (8 forced host devices per test)
pytestmark = pytest.mark.slow


def test_ring_aidw_matches_single_device():
    out = run_multidevice("""
import numpy as np, jax
from repro.core import aidw_improved
from repro.core.distributed import ring_aidw, query_sharded_aidw

rng = np.random.default_rng(0)
pts = rng.random((1024, 3)).astype(np.float32)
q = rng.random((512, 2)).astype(np.float32)
mesh = jax.make_mesh((4, 2), ("data", "model"))
ref = np.asarray(aidw_improved(pts, q).values)
ring = np.asarray(ring_aidw(mesh, "data", pts, q))
qsh = np.asarray(query_sharded_aidw(mesh, pts, q))
assert np.abs(ring - ref).max() < 1e-5, np.abs(ring - ref).max()
assert np.abs(qsh - ref).max() < 1e-6, np.abs(qsh - ref).max()
print("ring-ok")
""")
    assert "ring-ok" in out


def test_ring_aidw_unpadded_sizes():
    out = run_multidevice("""
import numpy as np, jax
from repro.core import aidw_improved
from repro.core.distributed import ring_aidw

rng = np.random.default_rng(1)
pts = rng.random((1000, 3)).astype(np.float32)   # not divisible by 8
q = rng.random((300, 2)).astype(np.float32)
mesh = jax.make_mesh((8,), ("data",))
ref = np.asarray(aidw_improved(pts, q).values)
ring = np.asarray(ring_aidw(mesh, "data", pts, q))
assert ring.shape == (300,)
assert np.abs(ring - ref).max() < 1e-5
print("pad-ok")
""")
    assert "pad-ok" in out


def test_sharded_train_step_runs_and_matches_single():
    out = run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs import get_config, reduced
from repro.models import api, sharding
from repro.nn.param import init_params, make_shardings
from repro.optim import adamw
from repro.training import trainer
from repro.data.pipeline import LMStreamConfig, lm_batch

cfg = reduced(get_config("deepseek-7b"))
ocfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
step = trainer.make_train_step(cfg, ocfg)
stream = LMStreamConfig(vocab=cfg.vocab, seq_len=32, global_batch=8)
batch = {k: jnp.asarray(v) for k, v in lm_batch(stream, 0).items()}

# single-device reference
params = init_params(api.param_defs(cfg), jax.random.PRNGKey(0))
opt = trainer.init_opt_state(ocfg, params)
p_ref, _, m_ref = jax.jit(step)(params, opt, batch)

# sharded on a (4,2) mesh; the LM sharding rules are written for Auto axes
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
defs = api.param_defs(cfg)
psh = make_shardings(defs, mesh, sharding.param_rules(mesh))
with mesh:
    params2 = jax.device_put(init_params(defs, jax.random.PRNGKey(0)), psh)
    opt2 = trainer.init_opt_state(ocfg, params2)
    p_sh, _, m_sh = jax.jit(step)(params2, opt2, batch)
assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-4
diff = jax.tree.reduce(max, jax.tree.map(
    lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()),
    p_ref, p_sh))
assert diff < 1e-3, diff
print("shard-ok", diff)
""")
    assert "shard-ok" in out


def test_production_mesh_shapes():
    out = run_multidevice("""
import jax
from repro.launch.mesh import make_production_mesh, make_ring_mesh
m = make_production_mesh()
assert m.devices.shape == (16, 16) and m.axis_names == ("data", "model")
mp = make_production_mesh(multi_pod=True)
assert mp.devices.shape == (2, 16, 16)
assert mp.axis_names == ("pod", "data", "model")
r = make_ring_mesh(512)
assert r.devices.shape == (512,)
print("mesh-ok")
""", n_devices=512)
    assert "mesh-ok" in out


def test_expert_parallel_moe_matches_pjit_dispatch():
    out = run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import AxisType
from repro.nn.moe import moe_apply, moe_apply_ep

rng = np.random.default_rng(0)
E, D, F, topk = 8, 16, 32, 2
x = jnp.asarray(rng.normal(0,1,(4,16,D)), jnp.float32)
wr = jnp.asarray(rng.normal(0,0.5,(D,E)), jnp.float32)
wg = jnp.asarray(rng.normal(0,0.1,(E,D,F)), jnp.float32)
wu = jnp.asarray(rng.normal(0,0.1,(E,D,F)), jnp.float32)
wd = jnp.asarray(rng.normal(0,0.1,(E,F,D)), jnp.float32)
mesh = jax.make_mesh((2,4), ("data","model"), axis_types=(AxisType.Auto,) * 2)
ref = moe_apply(x, wr, wg, wu, wd, top_k=topk, capacity_factor=8.0)
with mesh:
    sh = lambda a: jax.device_put(a, NamedSharding(mesh, P("model")))
    out = jax.jit(lambda *a: moe_apply_ep(*a, top_k=topk, capacity_factor=8.0))(
        x, wr, sh(wg), sh(wu), sh(wd))
    g = jax.grad(lambda w: moe_apply_ep(x, wr, w, sh(wu), sh(wd), top_k=topk,
                                        capacity_factor=8.0).astype(jnp.float32).sum())(sh(wg))
g_ref = jax.grad(lambda w: moe_apply(x, wr, w, wu, wd, top_k=topk,
                                     capacity_factor=8.0).astype(jnp.float32).sum())(wg)
assert float(jnp.abs(out - ref).max()) < 1e-6
assert float(jnp.abs(g - g_ref).max()) < 1e-5
print("ep-ok")
""")
    assert "ep-ok" in out


def test_sharded_session_matches_single_device():
    """Acceptance: on an 8-device host-platform mesh, sharded session.query
    is bit-identical per query to the single-device session on the same
    plan, across mesh shapes and odd (bucketed) batch sizes."""
    out = run_multidevice("""
import numpy as np, jax
from repro.core import InterpolationSession
from repro.data.pipeline import spatial_points, spatial_queries

pts = spatial_points(4096, seed=0)
qs = spatial_queries(1000, seed=1)       # odd size: exercises padded buckets
single = InterpolationSession(pts, query_domain=qs)
for shape, axes in (((8,), ("q",)), ((4, 2), ("data", "model"))):
    mesh = jax.make_mesh(shape, axes)
    sess = InterpolationSession(pts, query_domain=qs, mesh=mesh)
    assert sess.stats["devices"] == 8
    a, b = single.query(qs), sess.query(qs)
    assert np.array_equal(np.asarray(a.values), np.asarray(b.values)), shape
    assert np.array_equal(np.asarray(a.alpha), np.asarray(b.alpha))
    assert np.array_equal(np.asarray(a.r_obs), np.asarray(b.r_obs))
    assert a.overflow == b.overflow
    q2 = spatial_queries(997, seed=2)    # same bucket -> compile-cache hit
    assert np.array_equal(np.asarray(single.query(q2).values),
                          np.asarray(sess.query(q2).values))
    assert sess.stats["bucket_misses"] == 1 and sess.stats["bucket_hits"] == 1
print("sharded-session-ok")
""")
    assert "sharded-session-ok" in out


def test_sharded_session_delta_and_ring():
    """Delta updates re-place the sharded plan (still bit-identical), and the
    ring layout serves within brute-force-accumulation tolerance."""
    out = run_multidevice("""
import numpy as np, jax
from repro.core import InterpolationSession
from repro.data.pipeline import spatial_points, spatial_queries

pts = spatial_points(4096, seed=0)
qs = spatial_queries(512, seed=1)
mesh = jax.make_mesh((8,), ("q",))
single = InterpolationSession(pts, query_domain=qs)
sess = InterpolationSession(pts, query_domain=qs, mesh=mesh)
dels = np.random.default_rng(3).choice(4096, 40, replace=False)
ins = spatial_points(40, seed=9)
for s in (single, sess):
    s.update(inserts=ins, deletes=dels)
assert sess.stats["delta_updates"] == 1 and sess.stats["stage1_builds"] == 1
a, b = single.query(qs), sess.query(qs)
assert np.array_equal(np.asarray(a.values), np.asarray(b.values))

ring = InterpolationSession(pts, query_domain=qs, mesh=mesh, layout="ring")
assert ring.sharded_plan.layout == "ring"
err = np.abs(np.asarray(ring.query(qs).values)
             - np.asarray(InterpolationSession(pts, query_domain=qs)
                          .query(qs).values)).max()
assert err < 1e-4, err
print("delta-ring-ok", err)
""")
    assert "delta-ring-ok" in out


def test_ring_aidw_query_blocking():
    out = run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import aidw_improved
from repro.core.distributed import make_ring_aidw
rng = np.random.default_rng(0)
pts = rng.random((1024, 3)).astype(np.float32)
q = rng.random((512, 2)).astype(np.float32)
mesh = jax.make_mesh((8,), ("ring",))
ref = np.asarray(aidw_improved(pts, q).values)
for qb in (0, 17, 64):
    fn = make_ring_aidw(mesh, "ring", q_block=qb)
    out = fn(jnp.asarray(pts), jnp.asarray(q), jnp.float32(1024), jnp.float32(1.0))
    assert np.abs(np.asarray(out) - ref).max() < 1e-5, qb
print("qblock-ok")
""")
    assert "qblock-ok" in out


def test_slab_aidw_matches_single_device():
    out = run_multidevice("""
import numpy as np, jax
from repro.core import aidw_improved, AidwConfig
from repro.core.slab import slab_aidw

rng = np.random.default_rng(3)
pts = rng.random((8192, 3)).astype(np.float32)
q = rng.random((2048, 2)).astype(np.float32)
mesh = jax.make_mesh((8,), ("ring",))
ref = np.asarray(aidw_improved(pts, q, AidwConfig(k=15, cell_factor=4.0)).values)
out, ovf = slab_aidw(mesh, "ring", pts, q, k=15, cell_factor=4.0, window=512)
assert ovf == 0
assert np.abs(out - ref).max() < 1e-5
print("slab-ok")
""")
    assert "slab-ok" in out
