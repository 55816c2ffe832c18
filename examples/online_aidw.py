"""Online AIDW serving — the async subsystem end to end.

Multiple client threads submit interpolation requests (some deadline-bound)
to one :class:`repro.serving.AsyncAidwServer` while the dataset churns
underneath via incremental delta updates; the admission queue serializes
churn against query batches, the deadline-aware coalescer forms microbatches
on the resident session's compiled executables, and telemetry reports the
latency distribution at the end.

Run single-device, or simulate a pod slice on CPU:

  PYTHONPATH=src python examples/online_aidw.py
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python examples/online_aidw.py --mesh
"""

from __future__ import annotations

import argparse
import threading

import numpy as np

from repro.data.pipeline import spatial_points, spatial_queries
from repro.serving import AsyncAidwServer


def client(srv: AsyncAidwServer, cid: int, n_requests: int, results: list):
    """One client: a stream of odd-sized requests, every third with an SLO."""
    reqs = []
    for i in range(n_requests):
        qs = spatial_queries(97 + 13 * ((cid + i) % 5), seed=cid * 100 + i)
        deadline_s = 10.0 if i % 3 == 0 else None
        reqs.append(srv.submit(qs, deadline_s=deadline_s))
    for r in reqs:
        srv.result(r, timeout=300)
    results.append(reqs)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--points", type=int, default=16384)
    p.add_argument("--clients", type=int, default=3)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--mesh", action="store_true")
    args = p.parse_args()

    mesh = None
    if args.mesh:
        import jax

        mesh = jax.make_mesh((len(jax.devices()),), ("q",))

    pts = spatial_points(args.points, seed=0)
    with AsyncAidwServer(pts, max_batch=4096, mesh=mesh,
                         query_domain=spatial_queries(1024, seed=1)) as srv:
        results: list = []
        threads = [threading.Thread(target=client,
                                    args=(srv, c, args.requests, results))
                   for c in range(args.clients)]
        for t in threads:
            t.start()
        # churn the dataset WHILE clients are in flight: the update is a FIFO
        # barrier on the worker, so it never races a query batch
        n_delta = max(args.points // 100, 1)
        srv.update_dataset(
            inserts=spatial_points(n_delta, seed=2),
            deletes=np.random.default_rng(3).choice(
                args.points, n_delta, replace=False))
        for t in threads:
            t.join()
        srv.flush(timeout=300)

        served = sum(r.status == "done" for reqs in results for r in reqs)
        total = sum(len(reqs) for reqs in results)
        rep = srv.report()
        lat = rep["latency"]["total"]
        print(f"served {served}/{total} requests from {args.clients} "
              f"client threads ({rep['shed']} shed, "
              f"{rep['dataset_updates']} dataset update mid-stream)")
        print(f"batches {rep['batches']}, {rep['queries_per_s']:.0f} q/s, "
              f"total-latency p50 {lat['p50_s'] * 1e3:.1f}ms / "
              f"p99 {lat['p99_s'] * 1e3:.1f}ms")
        s = srv.session.stats
        print(f"session: devices={s['devices']} "
              f"stage1_builds={s['stage1_builds']} "
              f"delta_updates={s['delta_updates']} "
              f"buckets={s['bucket_misses']}")


if __name__ == "__main__":
    main()
