"""Serving driver.

LM mode (default): ``python -m repro.launch.serve --arch llama3.2-3b
--reduced`` runs the slot-based continuous-batching engine over synthetic
requests and reports prefill/decode throughput.

AIDW mode: ``python -m repro.launch.serve --aidw [--mesh] [--async]
[--cluster N]`` runs the session-backed interpolation engine over synthetic
spatial request traffic; ``--mesh`` shards the session's query path across
every visible device (simulate a pod slice on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), an incremental
``update_dataset(inserts=..., deletes=...)`` between waves exercises the
delta-rebinning path, and ``--async`` drives the same traffic through
:class:`repro.serving.AsyncAidwServer` (admission queue + worker thread +
deadline-aware coalescing) and prints the latency telemetry report.
``--cluster N`` serves the traffic from an N-host
:class:`repro.serving.cluster.AidwCluster` fleet instead (epoch-ordered
updates, query routing, merged fleet telemetry).
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.models import api
from repro.nn.param import init_params
from repro.serving.engine import Request, ServingEngine


def run_aidw(args) -> None:
    from repro.data.pipeline import spatial_points, spatial_queries
    from repro.serving.engine import AidwEngine, InterpolationRequest

    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("q",)) if args.mesh or \
        args.layout != "replicated" else None
    pts = spatial_points(args.points, seed=args.seed)
    if args.cluster:
        run_aidw_cluster(args, pts, mesh)
        return
    if args.async_:
        run_aidw_async(args, pts, mesh)
        return
    engine = AidwEngine(pts, max_batch=args.max_batch, mesh=mesh,
                        layout=args.layout,
                        query_domain=spatial_queries(1024, seed=1))

    def wave(wave_id: int) -> None:
        reqs = [InterpolationRequest(
            uid=wave_id * args.requests + i,
            queries_xy=spatial_queries(max(args.req_queries - 7 * i, 1),
                                       seed=wave_id * 100 + i))
            for i in range(args.requests)]
        report = engine.run(reqs)            # per-call report for THIS wave
        assert all(r.done for r in reqs)
        print(f"wave {wave_id}: {report['queries']} queries in "
              f"{report['batches']} coalesced batches "
              f"({report['queries_per_s']:.0f} q/s)")

    wave(0)
    # incremental churn: replace 1% of the dataset, Stage-1 stays resident
    rng = np.random.default_rng(args.seed + 1)
    n_delta = max(args.points // 100, 1)
    engine.update_dataset(
        inserts=spatial_points(n_delta, seed=args.seed + 2),
        deletes=rng.choice(args.points, n_delta, replace=False))
    wave(1)
    s = engine.session.stats
    print(f"aidw serve: devices={s['devices']} stage1_builds={s['stage1_builds']} "
          f"delta_updates={s['delta_updates']} buckets={s['bucket_misses']} "
          f"queries={s['queries']} (cumulative: {engine.stats})")


def run_aidw_async(args, pts, mesh) -> None:
    """The same two-wave traffic through the ASYNC server: admission queue,
    worker thread, deadline mix, delta update serialized mid-stream."""
    from repro.data.pipeline import spatial_points, spatial_queries
    from repro.serving import AsyncAidwServer

    with AsyncAidwServer(pts, max_batch=args.max_batch, mesh=mesh,
                         layout=args.layout, prewarm=args.prewarm,
                         query_domain=spatial_queries(1024, seed=1)) as srv:
        def wave(wave_id: int, deadline_s):
            return [srv.submit(
                spatial_queries(max(args.req_queries - 7 * i, 1),
                                seed=wave_id * 100 + i),
                deadline_s=deadline_s if i % 3 == 0 else None)
                for i in range(args.requests)]

        w0 = wave(0, deadline_s=30.0)
        rng = np.random.default_rng(args.seed + 1)
        n_delta = max(args.points // 100, 1)
        srv.update_dataset(                   # FIFO barrier inside the stream
            inserts=spatial_points(n_delta, seed=args.seed + 2),
            deletes=rng.choice(args.points, n_delta, replace=False))
        w1 = wave(1, deadline_s=30.0)
        srv.flush(timeout=600)
        rep = srv.report()
        done = sum(r.status == "done" for r in w0 + w1)
        print(f"async waves: {done}/{len(w0) + len(w1)} served, "
              f"{rep['shed']} shed, {rep['batches']} batches, "
              f"{rep['queries_per_s']:.0f} q/s")
        lat = rep["latency"]["total"]
        print(f"async latency: p50 {lat['p50_s'] * 1e3:.1f}ms "
              f"p95 {lat['p95_s'] * 1e3:.1f}ms p99 {lat['p99_s'] * 1e3:.1f}ms")
        s = srv.session.stats
        print(f"aidw serve: devices={s['devices']} "
              f"stage1_builds={s['stage1_builds']} "
              f"delta_updates={s['delta_updates']} queries={s['queries']}")
        _dump_debugz(args, srv.debugz())


def run_aidw_cluster(args, pts, mesh=None) -> None:
    """Two waves + a fleet-wide epoch-ordered update through an N-host
    in-process cluster; prints the MERGED fleet telemetry.  With ``mesh``
    every host serves its batches across the whole visible-device mesh
    (in-process hosts share the devices)."""
    import numpy as np

    from repro.data.pipeline import spatial_points, spatial_queries
    from repro.serving.cluster import AidwCluster

    with AidwCluster(pts, n_hosts=args.cluster, max_batch=args.max_batch,
                     query_domain=spatial_queries(1024, seed=1),
                     policy=args.policy, mesh=mesh,
                     layout=args.layout) as cl:
        def wave(wave_id: int):
            return [cl.submit(
                spatial_queries(max(args.req_queries - 7 * i, 1),
                                seed=wave_id * 100 + i),
                deadline_s=30.0 if i % 3 == 0 else None)
                for i in range(args.requests)]

        w0 = wave(0)
        rng = np.random.default_rng(args.seed + 1)
        n_delta = max(args.points // 100, 1)
        epoch = cl.update_dataset(       # epoch-ordered fleet-wide barrier
            inserts=spatial_points(n_delta, seed=args.seed + 2),
            deletes=rng.choice(args.points, n_delta, replace=False),
            timeout=600)
        w1 = wave(1)
        cl.flush(timeout=600)
        rep = cl.report()
        fleet = rep["fleet"]
        done = sum(r.status == "done" for r in w0 + w1)
        print(f"cluster[{args.cluster} hosts, {rep['routing']['policy']}]: "
              f"{done}/{len(w0) + len(w1)} served, epoch {epoch}, "
              f"{fleet['shed']} shed, {fleet['queries_per_s']:.0f} q/s fleet")
        lat = fleet["latency"]["total"]
        print(f"fleet latency: p50 {lat['p50_s'] * 1e3:.1f}ms "
              f"p95 {lat['p95_s'] * 1e3:.1f}ms p99 {lat['p99_s'] * 1e3:.1f}ms")
        for h in rep["hosts"]:
            print(f"  host {h['host_id']}: epoch {h['epoch']} "
                  f"completed {h['completed']} "
                  f"queries {h['queries']} (n_points "
                  f"{h['session']['n_points']})")
        _dump_debugz(args, cl.debugz())


def _dump_debugz(args, bundle: dict) -> None:
    """Write the diagnostics bundle for ``--debug-dump PATH`` and print
    the tail-latency attribution it carries (single-server bundles have
    per-host shape; fleet bundles are pre-merged)."""
    if not getattr(args, "debug_dump", None):
        return
    import json

    from repro.obs import render_attribution, tail_attribution

    attr = bundle.get("attribution")
    if attr is None and bundle.get("recorder"):
        attr = tail_attribution([bundle["recorder"]],
                                registry_state=bundle.get("registry"))
        bundle = {**bundle, "attribution": attr}
    with open(args.debug_dump, "w") as f:
        json.dump(bundle, f, indent=1)
    print(f"debugz bundle -> {args.debug_dump}")
    if attr is not None:
        print(render_attribution(attr))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--aidw", action="store_true",
                   help="serve AIDW interpolation instead of the LM engine")
    p.add_argument("--mesh", action="store_true",
                   help="AIDW: shard the session across all visible devices")
    p.add_argument("--layout", default="replicated",
                   choices=("replicated", "ring", "grid_ring"),
                   help="AIDW mesh layout: replicate the plan, brute-force "
                        "ring-shard the points, or grid-aware ring-shard "
                        "them (slab CSR + halo; implies --mesh)")
    p.add_argument("--async", dest="async_", action="store_true",
                   help="AIDW: drive traffic through the AsyncAidwServer "
                        "(admission queue + worker thread + deadlines)")
    p.add_argument("--cluster", type=int, default=0, metavar="N",
                   help="AIDW: serve from an N-host in-process fleet "
                        "(epoch-ordered updates + routing + fleet report)")
    p.add_argument("--policy", default="round_robin",
                   choices=("round_robin", "least_loaded"),
                   help="cluster routing policy")
    p.add_argument("--prewarm", choices=("background", "sync"), default=None,
                   help="AIDW --async: AOT-compile + warm the whole bucket "
                        "ladder at server construction ('sync' blocks, "
                        "'background' compiles off the worker thread while "
                        "serving lazily)")
    p.add_argument("--compilation-cache-dir", metavar="DIR", default=None,
                   help="persistent XLA compilation cache directory "
                        "(JAX_COMPILATION_CACHE_DIR env wins; default: "
                        "AIDW_CACHE_DIR env, else the checkout's .jax_cache; "
                        "a restart with the same directory deserializes "
                        "instead of recompiling)")
    p.add_argument("--debug-dump", metavar="PATH",
                   help="AIDW --async/--cluster: write the debugz "
                        "diagnostics bundle (queue/epoch state, SLO "
                        "events, flight-recorder traces, tail-latency "
                        "attribution) to PATH as JSON after the waves")
    p.add_argument("--points", type=int, default=16384)
    p.add_argument("--req-queries", type=int, default=384)
    p.add_argument("--max-batch", type=int, default=4096)
    p.add_argument("--arch", default="llama3.2-3b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    # before any compile (directory rules: compile_cache.enable)
    from repro.runtime import compile_cache
    compile_cache.enable(args.compilation_cache_dir)

    if args.aidw:
        run_aidw(args)
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.enc_dec:
        raise SystemExit("serve demo targets decoder-only archs")

    params = init_params(api.param_defs(cfg), jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]

    max_len = args.prompt_len + args.max_new + 8
    engine = ServingEngine(cfg, params, batch_size=args.batch, max_len=max_len)
    stats = engine.run(reqs)
    done = sum(r.done for r in reqs)
    print(f"arch={cfg.name} served={done}/{len(reqs)} "
          f"prefills={stats['prefills']} decode_steps={stats['decode_steps']} "
          f"tokens={stats['tokens']} ({stats['tokens_per_s']:.1f} tok/s)")
    for r in reqs[:3]:
        print(f"  req {r.uid}: {len(r.out_tokens)} tokens -> {r.out_tokens[:8]}...")


if __name__ == "__main__":
    main()
