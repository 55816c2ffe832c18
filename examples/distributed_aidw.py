"""Domain-decomposed (ring) AIDW across devices — the paper at pod scale.

Shards the DATA POINTS across a device ring and the queries across the whole
mesh, rotating data blocks with collective-permute so no chip ever holds the
full dataset (DESIGN.md §2 'ring AIDW').  The single-device reference runs
through :class:`repro.core.InterpolationSession` — the grid build happens
once and every query batch reuses it.  Run with forced host devices to
simulate a pod slice on CPU:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python examples/distributed_aidw.py
"""

import numpy as np
import jax

from repro.core import InterpolationSession
from repro.core.distributed import query_sharded_aidw, ring_aidw
from repro.data.pipeline import spatial_points, spatial_queries


def main() -> None:
    n_dev = len(jax.devices())
    print(f"devices: {n_dev}")
    pts = spatial_points(4096, seed=0)
    qs = spatial_queries(2048, seed=1)

    # plan once; every batch below is a warm session query (no grid rebuild)
    sess = InterpolationSession(pts, query_domain=qs)
    ref = np.asarray(sess.query(qs).values)
    for seed in (2, 3, 4):          # repeated odd-sized traffic, one executable
        sess.query(spatial_queries(2048 - seed * 7, seed=seed))
    print(f"session: {sess.stats['batches']} batches / "
          f"{sess.stats['queries']} queries on "
          f"{sess.stats['stage1_builds']} Stage-1 build(s), "
          f"{sess.stats['bucket_misses']} compiled bucket(s)")

    # incremental churn: replace ~1% of the dataset without a Stage-1 rebuild
    n_delta = pts.shape[0] // 100
    sess.update(inserts=spatial_points(n_delta, seed=5),
                deletes=np.random.default_rng(6).choice(
                    pts.shape[0], n_delta, replace=False))
    sess.query(qs)
    print(f"delta update: {sess.stats['delta_updates']} incremental / "
          f"{sess.stats['stage1_builds']} full Stage-1 build(s)")

    if n_dev >= 2:
        # ONE session serving the whole mesh: queries sharded over all axes,
        # plan replicated — results bit-identical to the single-device path
        smesh = jax.make_mesh((n_dev,), ("q",))
        ssess = InterpolationSession(pts, query_domain=qs, mesh=smesh)
        sharded = np.asarray(ssess.query(qs).values)
        print(f"sharded session ({n_dev} devices): bit-identical to "
              f"single-device = {np.array_equal(sharded, ref)}")

        axes = (n_dev // 2, 2)
        mesh = jax.make_mesh(axes, ("data", "model"))
        ring = np.asarray(ring_aidw(mesh, "data", pts, qs))
        qsh = np.asarray(query_sharded_aidw(mesh, pts, qs))
        print(f"mesh {axes}: ring-AIDW max|err| vs warm session "
              f"= {np.abs(ring - ref).max():.2e}")
        print(f"mesh {axes}: query-sharded max|err| = {np.abs(qsh - ref).max():.2e}")
        print(f"per-device data-point shard: {pts.shape[0] // axes[0]} of "
              f"{pts.shape[0]} (O(m/P) memory)")
    else:
        print("single device: ring reduces to the local session pipeline")
        print(f"AIDW values[:4] = {ref[:4]}")
    print("aidw distributed demo complete")


if __name__ == "__main__":
    main()
