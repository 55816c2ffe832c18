"""Distributed AIDW — the paper's algorithm at pod scale.

The paper parallelizes over queries on ONE GPU (one thread per interpolated
point) and replicates all data points.  At 1000+-chip scale neither the data
points nor the queries fit (or should sit) on one chip.  Two schemes:

* :func:`query_sharded_aidw` — queries sharded over the whole mesh, data
  points replicated.  Zero communication (embarrassingly parallel, the
  paper's own structure); right when m is small and n is huge.

* :func:`make_ring_aidw` — **domain-decomposed / ring AIDW** (beyond-paper,
  DESIGN.md §2): data points are sharded into P blocks along a ring axis;
  queries are sharded over the remaining mesh axes (and the ring axis).  Both
  stages then rotate the data blocks around the ring with
  ``lax.ppermute``:

    - Stage 1 (kNN): each device keeps a running top-k of squared distances
      between its local queries and the rotating data block — after P steps
      every query has seen every data point.  (Same merge pattern as the
      in-kernel k-selection.)
    - Stage 2 (Eq. 1): each device accumulates partial (sum w*z, sum w)
      against the rotating block — the numerator/denominator accumulation of
      ring attention, applied to inverse-distance weights.

  Per-chip memory is O(m/P + n/(P*Q)); the collective is a neighbour
  permute (contention-free on a TPU torus), and XLA overlaps the permute
  with the local distance/weight compute.  Padding points are placed at
  +PAD_COORD so they contribute inf distance / zero weight to both stages.

* :func:`make_grid_ring_aidw` — **grid-aware ring AIDW** (PR 5): same data
  decomposition and rotation as the ring scheme, but Stage 1 keeps the
  paper's GRID search.  Each rotating block ships its slab's CSR cell
  table (built by :class:`repro.core.slab.SlabPartition`: the global even
  grid cut into row slabs with a halo ring of boundary cells), and the
  ring step only evaluates candidates from the query's expanding search
  window instead of the whole block — O(window) candidate distances per
  query instead of O(m), restoring the paper's headline Stage-1 cost at
  O(m/P + boundary-halo) memory per device.  Per-slab top-k results are
  k-way merged into the running neighbour heap (the same
  concatenate-and-top-k merge as the brute step), with an exactly-once
  contribution contract and an overflow-excuse certificate
  (:func:`repro.core.knn._slab_query_knn`) so merged results match the
  replicated layout within the SAME certification story — bit-identical
  d2/r_obs/alpha for queries whose certified window closes inside one
  slab (incl. its halo), ~1e-5 f32 accumulation tolerance on the
  interpolated values (Stage 2 sums slab partials in rotation order).
  Comms per step: one neighbour permute of the slab packet — points, CSR
  offsets, row offset — O(m/P + boundary) bytes, same wire profile as the
  brute ring plus the O(n_cells/P) offset array.

Both ring builders accept ``stage2_local=True`` (the session's
``AidwConfig(stage2='local')``): Stage 1 co-merges the rotating blocks' data
VALUES alongside the distances through the same ``top_k`` selection, and
Eq. (1) is evaluated over just those k merged neighbours after the scan —
the Stage-2 rotation disappears entirely (O(window + k) per query in the
grid-aware ring).  r_obs/alpha are bit-identical to global mode by
construction; the interpolated values differ by the truncated far-field
tail (see ``repro.core.aidw``).
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from . import aidw as A

PAD_COORD = 1e30


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis typed ``Auto``.

    The library's placements (replicated plan arrays, query-sharded
    batches, eager indexing of sharded results) rely on the compiler
    propagating shardings.  A bare ``jax.make_mesh`` types its axes
    ``Explicit``, under which eager gathers and slices of a sharded array
    raise ``ShardingTypeError`` unless every call names an output
    sharding.  Entry points that take a caller's mesh run on this view of
    it: same devices, same axis names."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def pad_to_multiple(arr: jax.Array, multiple: int, axis: int = 0,
                    value: float = PAD_COORD) -> jax.Array:
    """Pad ``axis`` up to a multiple; AIDW-safe sentinel coordinates."""
    pad = (-arr.shape[axis]) % multiple
    if pad == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths, constant_values=value)


def query_sharded_aidw(mesh: Mesh, points_xyz, queries_xy, *, k: int = 15,
                       alphas=A.DEFAULT_ALPHAS, cfg=None):
    """Queries sharded over every mesh axis; data replicated (paper's scheme)."""
    from .pipeline import AidwConfig, aidw_improved

    cfg = cfg or AidwConfig(k=k, alphas=alphas)
    mesh = auto_axes(mesh)
    axes = tuple(mesh.axis_names)
    n_dev = mesh.devices.size
    qs = pad_to_multiple(jnp.asarray(queries_xy), n_dev)
    qs = jax.device_put(qs, NamedSharding(mesh, P(axes, None)))
    pts = jax.device_put(jnp.asarray(points_xyz), NamedSharding(mesh, P(None, None)))
    res = aidw_improved(pts, qs, cfg)
    return res.values[: queries_xy.shape[0]]


def _blocked_map(fn, qxy, block: int):
    """lax.map over query chunks of ``block`` (bounds the (q, m_loc) tiles)."""
    n = qxy[0].shape[0]
    if block <= 0 or block >= n:
        return fn(qxy)
    pad = (-n) % block
    padded = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                   for a in qxy)
    nb = (n + pad) // block
    chunked = tuple(a.reshape((nb, block) + a.shape[1:]) for a in padded)
    out = jax.lax.map(fn, chunked)
    return jax.tree.map(
        lambda a: a.reshape((nb * block,) + a.shape[2:])[:n], out)


def _ring_knn_step(ring_axis: str, perm, qx, qy, carry_d2, blk,
                   q_block: int = 0, carry_z=None):
    """Merge the rotating data block into the running top-k, then rotate.

    ``q_block`` chunks the queries so the (q, m_loc) distance tile stays
    VMEM/HBM-bounded (§Perf AIDW iteration: baseline materializes the full
    tile; blocked version fits at 1B-point scale).

    With ``carry_z`` (local Stage-2 mode) the block's data VALUES co-merge
    through the SAME ``top_k`` call — the selected distances (and hence
    r_obs/alpha) are bitwise what the distance-only merge selects."""
    bx, by = blk[:, 0], blk[:, 1]
    k = carry_d2.shape[1]

    if carry_z is None:
        def merge(args):
            cqx, cqy, ctop = args
            d2 = (cqx[:, None] - bx[None, :]) ** 2 + (cqy[:, None] - by[None, :]) ** 2
            cat = jnp.concatenate([ctop, d2], axis=1)
            neg_top, _ = jax.lax.top_k(-cat, k)
            return -neg_top

        carry_d2 = _blocked_map(merge, (qx, qy, carry_d2), q_block)
        blk = jax.lax.ppermute(blk, ring_axis, perm)
        return carry_d2, blk

    bz = blk[:, 2]

    def merge_z(args):
        cqx, cqy, ctop, ctz = args
        d2 = (cqx[:, None] - bx[None, :]) ** 2 + (cqy[:, None] - by[None, :]) ** 2
        cat = jnp.concatenate([ctop, d2], axis=1)
        catz = jnp.concatenate(
            [ctz, jnp.broadcast_to(bz[None, :], d2.shape)], axis=1)
        neg_top, sel = jax.lax.top_k(-cat, k)
        return -neg_top, jnp.take_along_axis(catz, sel, axis=1)

    carry_d2, carry_z = _blocked_map(
        merge_z, (qx, qy, carry_d2, carry_z), q_block)
    blk = jax.lax.ppermute(blk, ring_axis, perm)
    return (carry_d2, carry_z), blk


def _ring_interp_step(ring_axis: str, perm, qx, qy, alpha, carry, blk,
                      q_block: int = 0):
    """Accumulate partial (sum w*z, sum w) against the rotating block."""
    sum_wz, sum_w = carry
    bx, by, bz = blk[:, 0], blk[:, 1], blk[:, 2]

    def accum(args):
        cqx, cqy, calpha, cwz, cw = args
        d2 = (cqx[:, None] - bx[None, :]) ** 2 + (cqy[:, None] - by[None, :]) ** 2
        w = A.idw_weights_sq(d2, calpha[:, None])
        # padding sentinels: d2 = inf -> w = 0 exactly
        return cwz + (w * bz[None, :]).sum(axis=1), cw + w.sum(axis=1)

    sum_wz, sum_w = _blocked_map(accum, (qx, qy, alpha, sum_wz, sum_w), q_block)
    blk = jax.lax.ppermute(blk, ring_axis, perm)
    return (sum_wz, sum_w), blk


def make_ring_aidw(
    mesh: Mesh,
    ring_axis: str,
    *,
    k: int = 15,
    alphas=A.DEFAULT_ALPHAS,
    r_min: float = A.DEFAULT_R_MIN,
    r_max: float = A.DEFAULT_R_MAX,
    q_block: int = 0,
    stage2_local: bool = False,
    return_stats: bool = False,
):
    """Build the domain-decomposed AIDW step for ``mesh``.

    Returns ``fn(points_xyz, queries_xy, n_points, area) -> values`` operating
    on GLOBAL arrays whose leading dims are divisible by the mesh factors:
    data sharded along ``ring_axis`` only; queries sharded along every axis.
    ``n_points``/``area`` are the true (unpadded) study statistics for Eq.(2).
    With ``return_stats=True`` the step returns ``(values, alpha, r_obs,
    zero_weight_mask)`` instead — the per-query stats the sharded ring-layout
    session reports.

    ``stage2_local=True`` drops the Stage-2 rotation entirely: the Stage-1
    scan co-merges the blocks' data VALUES alongside the distances (same
    ``top_k`` selection — r_obs/alpha stay bitwise what global mode
    computes) and Eq. (1) is evaluated over just those k neighbours after
    the scan, O(k) per query instead of a second O(m) sweep.
    """
    mesh = auto_axes(mesh)
    all_axes = tuple(mesh.axis_names)
    p_ring = mesh.shape[ring_axis]
    perm = [(i, (i + 1) % p_ring) for i in range(p_ring)]

    def local_fn(points, queries, n_points, area):
        qx, qy = queries[:, 0], queries[:, 1]
        n_q = queries.shape[0]

        # ---- Stage 1: ring kNN (lax.scan: HLO is O(1) in ring size) ----
        def knn_step(carry, _):
            topk, blk = carry
            topk, blk = _ring_knn_step(ring_axis, perm, qx, qy, topk, blk,
                                       q_block)
            return (topk, blk), None

        def knn_z_step(carry, _):
            (topk, tz), blk = carry
            (topk, tz), blk = _ring_knn_step(ring_axis, perm, qx, qy, topk,
                                             blk, q_block, carry_z=tz)
            return ((topk, tz), blk), None

        # scan carries inherit the queries' full varying-axes set
        topk0 = jax.lax.pcast(jnp.full((n_q, k), jnp.inf, points.dtype),
                              all_axes, to="varying")
        if stage2_local:
            tz0 = jax.lax.pcast(jnp.zeros((n_q, k), points.dtype),
                                all_axes, to="varying")
            ((topk, topk_z), _), _ = jax.lax.scan(
                knn_z_step, ((topk0, tz0), points), None, length=p_ring)
        else:
            (topk, _), _ = jax.lax.scan(knn_step, (topk0, points), None,
                                        length=p_ring)
        r_obs = jnp.sqrt(jnp.maximum(topk, 0.0)).mean(axis=1)
        alpha = A.adaptive_alpha(r_obs, n_points, area,
                                 alphas=alphas, r_min=r_min, r_max=r_max)

        if stage2_local:
            # ---- Stage 2 (local): Eq. (1) over the merged k neighbours ----
            swz, sw = A.topk_weighted_partial_sums(topk, topk_z, alpha)
            vals, zero = A.guarded_values(swz, sw)
            return (vals, alpha, r_obs, zero) if return_stats else vals

        # ---- Stage 2 (global): ring weighted interpolation ----
        def interp_step(carry, _):
            acc, blk = carry
            acc, blk = _ring_interp_step(ring_axis, perm, qx, qy, alpha, acc,
                                         blk, q_block)
            return (acc, blk), None

        acc0 = (jnp.zeros_like(qx), jnp.zeros_like(qx))
        ((sum_wz, sum_w), _), _ = jax.lax.scan(
            interp_step, (acc0, points), None, length=p_ring)
        vals, zero = A.guarded_values(sum_wz, sum_w)
        return (vals, alpha, r_obs, zero) if return_stats else vals

    data_spec = P(ring_axis, None)
    query_spec = P(all_axes, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(data_spec, query_spec, P(), P()),
        out_specs=P(all_axes),
    )
    return jax.jit(fn)


def make_grid_ring_aidw(
    mesh: Mesh,
    ring_axis: str,
    *,
    spec,
    rps: int,
    halo: int,
    max_level: int,
    k: int = 15,
    window: int = 256,
    knn_block: int = 4096,
    alphas=A.DEFAULT_ALPHAS,
    r_min: float = A.DEFAULT_R_MIN,
    r_max: float = A.DEFAULT_R_MAX,
    q_block: int = 0,
    stage2_local: bool = False,
    return_stats: bool = False,
):
    """Build the grid-aware ring AIDW step for ``mesh`` (module docstring).

    Returns ``fn(sx, sy, sz, cell_start, row_lo, bx, by, bz, rx, ry, rz,
    queries, n_points, area)`` where the first eleven arguments are the
    stacked packets from :meth:`repro.core.slab.SlabPartition.device_tables`
    — the halo'd slab CSR tables Stage 1 rotates, the owned-only point
    blocks Stage 2 rotates, and the per-slab HOT APPEND RINGS (the LSM
    ingest tier, ``repro.core.slab`` module docstring) — all sharded along
    ``ring_axis``; queries are sharded over EVERY mesh axis.  ``spec`` is
    the GLOBAL grid spec and ``rps``/``halo``/``max_level`` the slab
    geometry — all static.

    Hot-ring search: each rotating packet's ring is scanned EXHAUSTIVELY
    (:func:`repro.core.knn.ring_candidate_d2`) and its candidates co-merge
    into the same per-step ``top_k`` as the slab's CSR result, so freshly
    staged inserts are query-visible without touching the CSR arrays.  A
    ring point lives ONLY in its owning slab's packet (never in a halo
    copy), so the exhaustive scan preserves the exactly-once contribution
    contract, needs no certification (it cannot overflow), and its d2
    arithmetic is bitwise the CSR gather's.  Stage 2 (global mode) rotates
    the ring points concatenated onto the owned block; empty ring slots
    carry ``PAD_COORD`` and contribute inf distance / zero weight.

    With ``return_stats=True`` the step returns ``(values, alpha, r_obs,
    overflow, n_candidates, zero_weight_mask)``: per-query overflow is the
    merged certification flag (kth merged distance vs the worst un-excused
    slab overflow), and ``n_candidates`` counts Stage-1 candidate distance
    evaluations per query summed over all slabs — the measured O(window)
    quantity the analytic census cross-checks against brute force's O(m).

    ``stage2_local=True`` drops the Stage-2 block rotation entirely: the
    Stage-1 packet additionally rotates the slab's sorted VALUES (``sz``),
    each slab's top-k indices gather them, and the (d2, z) pairs co-merge
    through the SAME ``top_k`` call — so r_obs/alpha (and the whole
    certification story) stay bitwise what global mode computes while
    per-query Stage-2 work drops from O(m) to O(k): O(window + k) total.
    """
    from . import knn as K

    mesh = auto_axes(mesh)
    all_axes = tuple(mesh.axis_names)
    p_ring = mesh.shape[ring_axis]
    perm = [(i, (i + 1) % p_ring) for i in range(p_ring)]

    def local_fn(sx, sy, sz, cell_start, row_lo, bx, by, bz, rx, ry, rz,
                 queries, n_points, area):
        qx, qy = queries[:, 0], queries[:, 1]
        n_q = queries.shape[0]

        # ---- Stage 1: grid-aware ring kNN -----------------------------
        # the rotating packet carries the slab's sorted points + CSR
        # offsets + row offset + hot append ring; `own` is consumed
        # locally by Stage 2 only.  Local mode rotates sz/rz too and
        # co-merges the gathered values.
        def knn_step(carry, _):
            if stage2_local:
                topk, topk_z, excuse, cand, pk = carry
                psx, psy, psz, pcs, prl, prx, pry, prz = pk
            else:
                topk, excuse, cand, pk = carry
                psx, psy, pcs, prl, prx, pry = pk
            # `order` = iota: res.idx indexes the slab's SORTED arrays,
            # which is exactly what the in-scan value gather wants (global
            # mode never reads idx, so zeros vs iota is indifferent there)
            res = K.slab_knn(spec, rps, halo, pcs[0], psx[0], psy[0],
                             jax.lax.iota(jnp.int32, psx.shape[1]), prl[0],
                             queries, k, max_level, window, knn_block)
            # hot ring: exhaustive scan of this slab's staged inserts
            # (tiny, exact, overflow-free — see make_grid_ring_aidw doc)
            rd2 = K.ring_candidate_d2(prx[0], pry[0], qx, qy)
            cat = jnp.concatenate([topk, res.d2, rd2], axis=1)
            neg, sel = jax.lax.top_k(-cat, k)
            ring_live = (prx[0] < PAD_COORD).sum().astype(jnp.int32)
            pk = jax.tree.map(
                lambda a: jax.lax.ppermute(a, ring_axis, perm), pk)
            if stage2_local:
                catz = jnp.concatenate(
                    [topk_z, psz[0][res.idx],
                     jnp.broadcast_to(prz[0][None, :], rd2.shape)], axis=1)
                topk_z = jnp.take_along_axis(catz, sel, axis=1)
                return (-neg, topk_z, jnp.minimum(excuse, res.excuse),
                        cand + res.n_candidates + ring_live, pk), None
            return (-neg, jnp.minimum(excuse, res.excuse),
                    cand + res.n_candidates + ring_live, pk), None

        topk0 = jax.lax.pcast(jnp.full((n_q, k), jnp.inf, queries.dtype),
                              all_axes, to="varying")
        excuse0 = jax.lax.pcast(jnp.full((n_q,), jnp.inf, queries.dtype),
                                all_axes, to="varying")
        cand0 = jax.lax.pcast(jnp.zeros((n_q,), jnp.int32), all_axes,
                              to="varying")
        if stage2_local:
            tz0 = jax.lax.pcast(jnp.zeros((n_q, k), sz.dtype), all_axes,
                                to="varying")
            packet0 = (sx, sy, sz, cell_start, row_lo, rx, ry, rz)
            (topk, topk_z, excuse, cand, _), _ = jax.lax.scan(
                knn_step, (topk0, tz0, excuse0, cand0, packet0), None,
                length=p_ring)
        else:
            packet0 = (sx, sy, cell_start, row_lo, rx, ry)
            (topk, excuse, cand, _), _ = jax.lax.scan(
                knn_step, (topk0, excuse0, cand0, packet0), None,
                length=p_ring)

        r_obs = jnp.sqrt(jnp.maximum(topk, 0.0)).mean(axis=1)
        overflow = jnp.sqrt(jnp.maximum(topk[:, -1], 0.0)) > excuse
        alpha = A.adaptive_alpha(r_obs, n_points, area, alphas=alphas,
                                 r_min=r_min, r_max=r_max)

        if stage2_local:
            # ---- Stage 2 (local): no rotation — the merged neighbour
            # carry already holds everything Eq. (1) needs ---------------
            swz, sw = A.topk_weighted_partial_sums(topk, topk_z, alpha)
            vals, zero = A.guarded_values(swz, sw)
            return (vals, alpha, r_obs, overflow, cand, zero) \
                if return_stats else vals

        # ---- Stage 2 (global): ring rotation over OWNED blocks plus the
        # slab's hot ring (ring points live only in their owner's packet,
        # so concatenating them keeps Eq. (1) exactly-once; halo copies
        # never enter: they would double-count, and their dead lanes
        # would widen every Stage-2 tile) ------------------------------
        blk0 = jnp.concatenate([
            jnp.stack([bx[0], by[0], bz[0]], axis=1),
            jnp.stack([rx[0], ry[0], rz[0]], axis=1),
        ], axis=0)

        def interp_step(carry, _):
            acc, blk = carry
            acc, blk = _ring_interp_step(ring_axis, perm, qx, qy, alpha,
                                         acc, blk, q_block)
            return (acc, blk), None

        acc0 = (jnp.zeros_like(qx), jnp.zeros_like(qx))
        ((swz, sw), _), _ = jax.lax.scan(interp_step, (acc0, blk0), None,
                                         length=p_ring)
        vals, zero = A.guarded_values(swz, sw)
        return (vals, alpha, r_obs, overflow, cand, zero) if return_stats \
            else vals

    data2 = P(ring_axis, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(data2, data2, data2, data2, P(ring_axis), data2, data2,
                  data2, data2, data2, data2, P(all_axes, None), P(), P()),
        out_specs=tuple(P(all_axes) for _ in range(6)) if return_stats
        else P(all_axes),
    )
    return jax.jit(fn)


def ring_aidw(mesh: Mesh, ring_axis: str, points_xyz, queries_xy, *,
              k: int = 15, alphas=A.DEFAULT_ALPHAS):
    """Convenience wrapper: pads, runs :func:`make_ring_aidw`, unpads."""
    points_xyz = jnp.asarray(points_xyz)
    queries_xy = jnp.asarray(queries_xy)
    n, m = queries_xy.shape[0], points_xyz.shape[0]
    # true study-area statistics from the unpadded data
    xs = jnp.concatenate([points_xyz[:, 0], queries_xy[:, 0]])
    ys = jnp.concatenate([points_xyz[:, 1], queries_xy[:, 1]])
    area = (xs.max() - xs.min()) * (ys.max() - ys.min())

    p_ring = mesh.shape[ring_axis]
    n_dev = mesh.devices.size
    pts = pad_to_multiple(points_xyz, p_ring)
    qs = pad_to_multiple(queries_xy, n_dev)
    fn = make_ring_aidw(mesh, ring_axis, k=k, alphas=alphas)
    return fn(pts, qs, jnp.float32(m), area.astype(jnp.float32))[:n]
