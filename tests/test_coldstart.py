"""Cold-start subsystem: persistent compilation cache, AOT bucket-ladder
precompile, server/fleet prewarm, and the zero-compile-after-prewarm
invariants.

Every zero-compile assertion uses a dataset size unique within the test
process (distinct 64-multiple capacity buckets), so the in-memory jit cache
cannot pre-satisfy the shapes under test and ``precompile`` provably does
the compiling.  Zero-compile is asserted on EXACT ladder-bucket query
sizes — odd sizes additionally pay tiny one-off pad/sum helper compiles by
design (see the AOT contract in ``core/pipeline.py``).
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import AidwConfig, InterpolationSession
from repro.core import pipeline as P
from repro.data.pipeline import spatial_points, spatial_queries
from repro.runtime import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _selftest(cache_dir, *extra) -> dict:
    """Run the compile_cache selftest CLI in a fresh interpreter."""
    import json

    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)   # it would win over --cache-dir
    out = subprocess.run(
        [sys.executable, "-m", "repro.runtime.compile_cache",
         "--cache-dir", str(cache_dir), *extra],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
    return json.loads(out.stdout)


def test_persistent_cache_second_process_hits(tmp_path):
    """The restart story end to end: a second process compiling the same
    canonical signature against the same cache directory deserializes
    instead of compiling (the CI cluster-suite assertion)."""
    first = _selftest(tmp_path / "cache")
    assert first["cache_dir"] == str(tmp_path / "cache")
    assert first["backend_compiles"] >= 1
    second = _selftest(tmp_path / "cache", "--min-hits", "1")
    assert second["persistent_cache_hits"] >= 1
    assert second["probe_s"] < first["probe_s"]


def test_enable_resolves_env_and_arg(tmp_path, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("AIDW_CACHE_DIR", raising=False)
    try:
        # nothing set: the fixed, git-ignored path inside the checkout
        assert compile_cache.enable(None) == str(REPO / ".jax_cache") \
            == compile_cache.DEFAULT_CACHE_DIR
        monkeypatch.setenv("AIDW_CACHE_DIR", str(tmp_path / "env"))
        assert compile_cache.enable(None) == str(tmp_path / "env")
        assert (tmp_path / "env").is_dir()
        # explicit argument wins over the env var
        assert compile_cache.enable(str(tmp_path / "arg")) \
            == str(tmp_path / "arg")
    finally:
        # leave the test process cache-less again
        jax.config.update("jax_compilation_cache_dir", None)


def test_enable_honours_jax_compilation_cache_dir(tmp_path, monkeypatch):
    """A cache directory placed from outside wins: neither the argument nor
    AIDW_CACHE_DIR overrides it, and no other directory is set."""
    placed = tmp_path / "placed"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    monkeypatch.setenv("AIDW_CACHE_DIR", str(tmp_path / "env"))
    try:
        assert compile_cache.enable(str(tmp_path / "arg")) == str(placed)
        assert jax.config.jax_compilation_cache_dir == str(placed)
        assert placed.is_dir()
        assert not (tmp_path / "arg").exists()
        assert not (tmp_path / "env").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", None)


@pytest.mark.parametrize("backend,refused", [("tpu", True), ("cpu", False)])
def test_refuse_child_if_tpu_held(monkeypatch, backend, refused):
    """A parent that has started JAX's TPU backend holds the chip: starting
    a chip-needing child is refused, naming the cause; elsewhere it is not."""
    from jax._src import xla_bridge

    from repro.runtime import refuse_child_if_tpu_held

    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: True)
    monkeypatch.setattr(xla_bridge, "default_backend", lambda: backend)
    if refused:
        with pytest.raises(RuntimeError, match="already holds the TPU"):
            refuse_child_if_tpu_held("a test child")
    else:
        refuse_child_if_tpu_held("a test child")


def test_sync_registry_folds_deltas_not_totals():
    """Counters fold as per-registry DELTAS: syncing twice adds only what
    happened in between, so fleet merge_states stays additive."""
    from repro.obs import Registry

    compile_cache.install_listeners()
    reg = Registry()
    compile_cache.sync_registry(reg)              # baseline fold
    h0 = reg.counter("compile_cache_hits").value
    b0 = reg.counter("backend_compiles").value
    with compile_cache._LOCK:
        compile_cache._COUNTS["persistent_cache_hits"] += 3
        compile_cache._COUNTS["cache_requests"] += 5
        compile_cache._COUNTS["backend_compiles"] += 2
    delta = compile_cache.sync_registry(reg)
    assert delta["persistent_cache_hits"] == 3
    assert reg.counter("compile_cache_hits").value == h0 + 3
    assert reg.counter("compile_cache_misses").value >= 2
    assert reg.counter("backend_compiles").value == b0 + 2
    # nothing new happened: a second sync folds zero
    delta2 = compile_cache.sync_registry(reg)
    assert delta2["backend_compiles"] == 0
    assert reg.counter("compile_cache_hits").value == h0 + 3


def _zero_compile_ladder(sess, buckets):
    """First post-prewarm query of every ladder bucket: no new execute
    trace, no dispatch reaching the XLA compile layer."""
    anchor = np.asarray(sess._host_pts[0, :2], dtype=np.float32)
    t0, c0 = P.execute_traces(), compile_cache.backend_compiles()
    for b in buckets:
        r = sess.query(np.tile(anchor, (b, 1)))
        assert np.asarray(r.values).shape == (b,)
    return P.execute_traces() - t0, compile_cache.backend_compiles() - c0


@pytest.mark.parametrize("layout,points", [
    ("single", 2243), ("replicated", 2371),
    ("ring", 2503), ("grid_ring", 2633),
])
def test_precompile_ladder_zero_compile_all_layouts(layout, points):
    compile_cache.install_listeners()
    mesh = None if layout == "single" else jax.make_mesh((1,), ("q",))
    kw = {} if layout == "single" else {"layout": layout}
    sess = InterpolationSession(spatial_points(points, seed=0), AidwConfig(),
                                mesh=mesh,
                                query_domain=spatial_queries(512, seed=1),
                                **kw)
    buckets = sess.precompile(max_queries=256, warm=True)
    assert buckets == [64, 128, 256]
    assert sess.stats["aot_buckets"] == len(buckets)
    assert sess.registry.counter is not None     # registry wired
    dt, dc = _zero_compile_ladder(sess, buckets)
    assert dt == 0, f"{layout}: {dt} new execute traces post-prewarm"
    assert dc == 0, f"{layout}: {dc} backend compiles post-prewarm"
    # compile observability landed: one wall per compiled executable
    hist = sess.registry.snapshot()["histograms"]["session/compile_s"]
    assert hist["count"] >= len(buckets)


def test_precompile_results_match_lazy_session():
    """The AOT executables are the SAME computation: bit-identical values
    against a fresh lazily-compiled session on the same data."""
    pts = spatial_points(2767, seed=0)
    qs = spatial_queries(128, seed=2)             # exact bucket size
    qd = spatial_queries(512, seed=1)
    aot = InterpolationSession(pts, AidwConfig(), query_domain=qd)
    aot.precompile(buckets=[128], warm=True)
    lazy = InterpolationSession(pts, AidwConfig(), query_domain=qd)
    np.testing.assert_array_equal(np.asarray(aot.query(qs).values),
                                  np.asarray(lazy.query(qs).values))


def test_delta_update_keeps_aot_full_refresh_invalidates():
    compile_cache.install_listeners()
    pts = spatial_points(2129, seed=0)
    sess = InterpolationSession(pts, AidwConfig(),
                                query_domain=spatial_queries(512, seed=1))
    buckets = sess.precompile(max_queries=128, warm=True)
    lo, hi = pts[:, :2].min(axis=0), pts[:, :2].max(axis=0)
    ins = spatial_points(16, seed=3)
    ins[:, :2] = np.clip(ins[:, :2], lo, hi)      # stay inside the bbox
    sess.update(inserts=ins,
                deletes=np.arange(16))            # balanced: same capacity
    assert sess.stats["aot_buckets"] == len(buckets)
    dt, dc = _zero_compile_ladder(sess, buckets)
    assert (dt, dc) == (0, 0), "delta update must keep the AOT ladder live"
    # a full dataset refresh replans: the ladder is stale and must drop
    sess.update(points_xyz=spatial_points(4201, seed=4))
    assert sess.stats["aot_buckets"] == 0


def test_server_sync_prewarm_zero_postwarm_compiles():
    from repro.serving import AsyncAidwServer

    pts = spatial_points(2113, seed=0)
    with AsyncAidwServer(pts, max_batch=256, prewarm="sync",
                         query_domain=spatial_queries(512, seed=1)) as srv:
        st = srv.prewarm(wait=True, timeout=600)
        assert st["prewarmed"] and st["mode"] == "sync"
        assert st["aot_buckets"] == 3             # ladder 64/128/256
        anchor = np.asarray(pts[0, :2], dtype=np.float32)
        for b in (64, 128, 256):
            srv.result(srv.submit(np.tile(anchor, (b, 1))), timeout=600)
        rep = srv.report()
        assert rep["compile"]["post_warmup_compiles"] == 0
        assert rep["compile"]["prewarmed"] is True
        gauges = srv.debugz()["slo"]["gauges"]
        assert gauges["post_warmup_compiles"]["breaching"] is False


def test_server_background_prewarm_serves_while_compiling():
    from repro.serving import AsyncAidwServer

    pts = spatial_points(2179, seed=0)
    with AsyncAidwServer(pts, max_batch=256, prewarm="background",
                         query_domain=spatial_queries(512, seed=1)) as srv:
        # serving works immediately — lazily while the ladder compiles
        r = srv.result(srv.submit(spatial_queries(64, seed=2)), timeout=600)
        assert r.status == "done"
        st = srv.prewarm(wait=True, timeout=600)
        assert st["prewarmed"] and st["mode"] == "background"
        assert srv.report()["compile"]["aot_buckets"] == 3


def test_hot_path_compile_after_prewarm_is_flagged():
    """A compile reaching the worker AFTER prewarm is an anomaly: counter,
    SLO gauge, and flight-recorder event all fire.  Odd-size queries pay
    eager pad/sum helper compiles on first sight, which makes a convenient
    trigger."""
    from repro.serving import AsyncAidwServer

    pts = spatial_points(2339, seed=0)
    with AsyncAidwServer(pts, max_batch=256, prewarm="sync",
                         query_domain=spatial_queries(512, seed=1)) as srv:
        srv.result(srv.submit(spatial_queries(61, seed=2)), timeout=600)
        rep = srv.report()
        assert rep["compile"]["post_warmup_compiles"] > 0
        bundle = srv.debugz()
        assert bundle["slo"]["gauges"]["post_warmup_compiles"]["breaching"]
        kinds = [e["kind"] for e in bundle["recorder"]["events"]]
        assert "hot_path_compile" in kinds


def test_fleet_prewarm_then_first_batch_no_compile():
    from repro.serving.cluster import AidwCluster

    pts = spatial_points(1907, seed=0)
    with AidwCluster(pts, n_hosts=2, max_batch=256,
                     query_domain=spatial_queries(512, seed=1)) as cl:
        statuses = cl.prewarm(timeout=600)
        assert sorted(statuses) == [0, 1]
        assert all(s["prewarmed"] for s in statuses.values())
        anchor = np.asarray(pts[0, :2], dtype=np.float32)
        for _ in range(4):                        # round-robin hits both
            req = cl.submit(np.tile(anchor, (64, 1)))
            assert cl.result(req, timeout=600).status == "done"
        for h in cl.report()["hosts"]:
            assert h["compile"]["post_warmup_compiles"] == 0
            assert h["compile"]["prewarmed"] is True


def test_rpc_prewarm_wire():
    """The fleet control-plane prewarm op over the socket transport: a
    joining (remote) host compiles its ladder before entering rotation and
    serves its first routed batch without a hot-path compile."""
    from repro.serving.cluster.host import HostServer
    from repro.serving.cluster.rpc import (RemoteHost, free_port_base,
                                           serve_host)

    pts = spatial_points(1733, seed=0)
    host = HostServer(0, pts, max_batch=256,
                      query_domain=spatial_queries(512, seed=1))
    port = free_port_base(1)
    ready = threading.Event()
    t = threading.Thread(target=serve_host,
                         args=(host, ("127.0.0.1", port)),
                         kwargs={"ready_event": ready}, daemon=True)
    t.start()
    assert ready.wait(30)
    rh = RemoteHost(0, ("127.0.0.1", port))
    try:
        st = rh.prewarm(wait=True, timeout=600)
        assert st["prewarmed"] and st["aot_buckets"] == 3
        req = rh.submit(np.tile(np.asarray(pts[0, :2], dtype=np.float32),
                                (64, 1)))
        rh.wait(req, timeout=600)
        assert rh.report()["compile"]["post_warmup_compiles"] == 0
    finally:
        rh.close()
        t.join(30)


def test_cluster_config_cache_dir_from_env(monkeypatch, tmp_path):
    from repro.serving.cluster.bootstrap import ClusterConfig

    monkeypatch.setenv("AIDW_CACHE_DIR", str(tmp_path / "fleet"))
    cfg = ClusterConfig.from_env()
    assert cfg.cache_dir == str(tmp_path / "fleet")
    monkeypatch.delenv("AIDW_CACHE_DIR")
    assert ClusterConfig.from_env().cache_dir is None
    assert ClusterConfig.from_env(cache_dir="/x").cache_dir == "/x"
