"""Fast kNN search over the even grid — Stage 1 of the improved AIDW algorithm.

Paper mapping (§3.2.4 / Fig. 5): per interpolated point,
  Step 1 locate the query in the grid          -> row/col computation
  Step 2 determine the level of cell expanding -> closed-form from ring counts
  Step 3 find neighbours within the local cells-> ragged window gather + top-k
  Step 4 average distance                      -> mean of k sqrt'd squared dists

TPU adaptation (DESIGN.md §2): the paper expands rings in a per-thread loop,
counting points until >= k are covered, then adds ONE safety ring (the Remark /
Fig. 4 exactness argument).  A per-lane data-dependent loop would serialize on
a TPU's (8, 128) vector unit, so we restructure it:

* Because cells of one grid row are contiguous in the flattened id, the points
  of a (2L+1)x(2L+1) block are, per row, ONE contiguous slice of the sorted
  point array.  Ring counts for ALL levels come from 2x(2L+1) gathers of the
  CSR ``cell_start`` array — no loop over points.
* The expansion level is then ``first L with count(L) >= k``, computed with a
  vectorized argmax over a static number of levels, + 1 safety ring (paper).
* Candidate gathering is a ragged->dense window gather: row slices are packed
  into a fixed-size window of ``window`` slots with masking, and the exact kNN
  are selected with a masked top-k.  Each slot finds its band row by counting
  the row offsets at or below it, a dense compare over the band with no
  gather or loop (``_slot_map``).
  Squared distances throughout; the sqrt is deferred to the final averaging
  step exactly as the paper prescribes.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .grid import CellTable, GridSpec, cell_ids


class KnnResult(NamedTuple):
    d2: jax.Array        # (n, k) squared distances, ascending
    idx: jax.Array       # (n, k) indices into the ORIGINAL point array
    n_candidates: jax.Array  # (n,) candidates examined per query
    overflow: jax.Array  # (n,) bool: window too small (result approximate)


def _slot_map(r_start, r_len, window):
    """Map the ``window`` slots of a ragged band to source indices.

    The band's rows are the ``n_band`` slices ``[r_start[r], r_start[r] +
    r_len[r])`` of the sorted point array, packed back to back into the
    window.  Returns ``(src, total)``: each slot's index into the sorted
    point array and the band's point count.  Slots at or past ``total``
    are not masked here (their row is clamped to the last one).

    A slot's band row is the number of row offsets at or below it, and its
    source is ``slot + base[row]`` with ``base = r_start - (offsets -
    r_len)``, picked by a one-hot sum: a dense compare over the band,
    about 5 * n_band vector integer ops per slot, with no gather and no
    loop.  The int32 results are those of a binary search over the offsets
    (``jnp.searchsorted``), which costs ceil(log2(n_band + 1)) + 2 gathered
    elements per slot; on a TPU a gathered element costs the time of
    thousands of vector ops, so the dense form serves every band width
    (the chip sweep by band width is in PERF.md, section 6).
    """
    n_band = r_len.shape[0]
    offsets = jnp.cumsum(r_len)                                       # (n_band,)
    slots = jnp.arange(window, dtype=jnp.int32)
    rows = jnp.arange(n_band, dtype=jnp.int32)
    # band rows on the major axis: each sum adds (window,)-wide vectors
    row_of = jnp.sum(offsets[:, None] <= slots[None, :], axis=0,
                     dtype=jnp.int32)
    row_of = jnp.minimum(row_of, n_band - 1)
    base = r_start - (offsets - r_len)
    src = slots + jnp.sum(
        jnp.where(rows[:, None] == row_of[None, :], base[:, None], 0),
        axis=0, dtype=jnp.int32)
    return src, offsets[-1]


def _gather_topk(spec, k, window, cell_start, sx, sy, order,
                 qx, qy, col0, dr, row_ok, row_base, lvl):
    """Gather the level-``lvl`` block's row slices and select the k nearest."""
    n_cols = spec.n_cols
    flo = jnp.clip(col0 - lvl, 0, n_cols - 1)
    fhi = jnp.clip(col0 + lvl, 0, n_cols - 1)
    active = (jnp.abs(dr) <= lvl) & row_ok                            # (n_band,)
    r_start = cell_start[row_base + flo]
    r_len = jnp.where(active, cell_start[row_base + fhi + 1] - r_start, 0)
    with jax.named_scope("knn.slot_map"):
        src, total = _slot_map(r_start, r_len, window)
    slots = jnp.arange(window, dtype=jnp.int32)
    valid = slots < jnp.minimum(total, window)
    src = jnp.clip(src, 0, sx.shape[0] - 1)

    # exact kNN among candidates (squared distances; sqrt deferred)
    d2 = (sx[src] - qx) ** 2 + (sy[src] - qy) ** 2
    d2 = jnp.where(valid, d2, jnp.inf)
    neg_top, top_i = jax.lax.top_k(-d2, k)
    return -neg_top, order[src[top_i]], total


def _query_knn(
    spec: GridSpec,
    k: int,
    max_level: int,
    window: int,
    exact: bool,
    cell_start: jax.Array,
    sx: jax.Array,
    sy: jax.Array,
    order: jax.Array,
    qx: jax.Array,
    qy: jax.Array,
):
    """kNN for a single query point (vmapped by :func:`grid_knn`)."""
    n_cols, n_rows = spec.n_cols, spec.n_rows
    col0 = jnp.clip(((qx - spec.min_x) / spec.cell_width).astype(jnp.int32), 0, n_cols - 1)
    row0 = jnp.clip(((qy - spec.min_y) / spec.cell_width).astype(jnp.int32), 0, n_rows - 1)

    n_band = 2 * max_level + 1
    dr = jnp.arange(-max_level, max_level + 1, dtype=jnp.int32)      # (n_band,)
    rows = row0 + dr
    row_ok = (rows >= 0) & (rows < n_rows)
    rows_c = jnp.clip(rows, 0, n_rows - 1)
    row_base = rows_c * n_cols                                        # (n_band,)

    # --- Step 2: ring counts for every level L in [0, max_level] ------------
    # count(L) = sum over rows |dr|<=L of points in columns [col0-L, col0+L].
    levels = jnp.arange(max_level + 1, dtype=jnp.int32)               # (n_lvl,)
    clo = jnp.clip(col0 - levels, 0, n_cols - 1)                      # (n_lvl,)
    chi = jnp.clip(col0 + levels, 0, n_cols - 1)
    # starts[l, r] = cell_start[row_base[r] + clo[l]]   (gather, no loops)
    start_idx = row_base[None, :] + clo[:, None]                      # (n_lvl, n_band)
    end_idx = row_base[None, :] + chi[:, None] + 1
    row_cnt = cell_start[end_idx] - cell_start[start_idx]             # (n_lvl, n_band)
    in_band = jnp.abs(dr)[None, :] <= levels[:, None]
    row_cnt = jnp.where(in_band & row_ok[None, :], row_cnt, 0)
    counts = row_cnt.sum(axis=1)                                      # (n_lvl,)

    # first level with >= k candidates; paper's Remark: expand one extra ring.
    # The true point count is cell_start[-1], NOT sx.shape[0]: capacity-padded
    # tables (pipeline plan padding) carry sentinel tail slots outside every
    # CSR range, and the count floor must ignore them.
    enough = counts >= jnp.minimum(k, jnp.maximum(cell_start[-1], 1))
    first = jnp.where(jnp.any(enough), jnp.argmax(enough), max_level)
    lvl = jnp.minimum(first.astype(jnp.int32) + 1, max_level)

    args = (spec, k, window, cell_start, sx, sy, order,
            qx, qy, col0, dr, row_ok, row_base)
    d2, idx, total = _gather_topk(*args, lvl)
    not_exact = total > window

    if exact:
        # Beyond-paper exactness pass (DESIGN.md §2): the paper's +1 ring is a
        # heuristic — the true kth NN can sit outside it (~0.5% of queries on
        # uniform data).  A level-L block centred on the query's cell is
        # GUARANTEED to cover radius L*cw, and pass-1's kth distance upper-
        # bounds the true kth distance, so re-gathering at ceil(d_k/cw)
        # certifies exactness.
        d_k = jnp.sqrt(jnp.maximum(d2[-1], 0.0))
        lvl2 = jnp.ceil(d_k / spec.cell_width).astype(jnp.int32)
        clamped = lvl2 > max_level
        lvl2 = jnp.clip(lvl2, lvl, max_level)
        d2b, idxb, totalb = _gather_topk(*args, lvl2)
        redo = lvl2 > lvl
        d2 = jnp.where(redo, d2b, d2)
        idx = jnp.where(redo, idxb, idx)
        total = jnp.where(redo, totalb, total)
        not_exact = (total > window) | clamped

    return KnnResult(d2=d2, idx=idx, n_candidates=total, overflow=not_exact)


class SlabKnnResult(NamedTuple):
    d2: jax.Array        # (n, k) squared distances to THIS slab's contribution
    idx: jax.Array       # (n, k) indices into the slab's original point order
    n_candidates: jax.Array  # (n,) candidates examined against this slab
    overflow: jax.Array  # (n,) bool: this slab's search was not certified
    excuse: jax.Array    # (n,) f32: radius within which an overflow is
    #                        irrelevant — any point this slab FAILED to
    #                        examine is farther than ``excuse`` from the
    #                        query, so a merged kth distance <= excuse keeps
    #                        the merged result exact despite the flag


def _slab_query_knn(
    spec: GridSpec,
    k: int,
    max_level: int,
    window: int,
    rps: int,
    halo: int,
    cell_start: jax.Array,
    sx: jax.Array,
    sy: jax.Array,
    order: jax.Array,
    row_lo: jax.Array,
    qx: jax.Array,
    qy: jax.Array,
):
    """kNN for one query against ONE slab of the global grid.

    The slab owns global rows ``[row_lo, row_lo + rps)`` and its CSR table
    additionally carries ``halo`` rows of boundary cells on each side
    (local row ``r`` is global row ``row_lo - halo + r``; the table has
    ``rps + 2*halo`` rows x ``spec.n_cols`` cells).  ``spec`` is the GLOBAL
    grid — column/row indices are computed exactly as the replicated search
    computes them, and ``sx``/``sy`` hold TRUE (unshifted) coordinates, so
    every distance is bitwise what the replicated path computes for the
    same (query, point) pair.  ``row_lo`` is dynamic: the slab rotates
    around a ring, so nothing about it may be baked into the trace.

    Ownership contract (the halo-width invariant; see ``repro.core.slab``):
    merging per-slab results must count every data point EXACTLY once, so
    each (query, point) pair is assigned to one slab —

    * the slab OWNING the query's row contributes its own rows plus halo
      rows within ``halo`` grid rows of the query (the halo exists so a
      query near a slab boundary finds its whole expanding search window
      in the owning slab's table: for certified levels <= halo the owner's
      result alone is the exact global answer, bit-identical to the
      replicated layout's candidate sequence);
    * every other slab contributes only rows it OWNS that lie MORE than
      ``halo`` rows from the query (outside the owner's covered band).

    Certification: the exact second gather pass re-runs at
    ``ceil(d_k / cell_width)`` like :func:`_query_knn`; clamping only moves
    the search centre CLOSER to any in-table cell, so the coverage argument
    survives queries whose row lies outside this slab.  When the pass
    cannot be certified (window overflow or level clamp) the result is
    flagged, and ``excuse`` reports the radius under which the flag cannot
    affect a MERGED top-k: every point this slab failed to examine is
    farther than ``excuse`` (its contributed rows start ``max(gap, halo+1)``
    rows away for non-owners; 0 for the owner, whose overflow is never
    excused).
    """
    n_cols, n_rows_g = spec.n_cols, spec.n_rows
    n_rows_local = rps + 2 * halo
    col0 = jnp.clip(((qx - spec.min_x) / spec.cell_width).astype(jnp.int32),
                    0, n_cols - 1)
    row_g = jnp.clip(((qy - spec.min_y) / spec.cell_width).astype(jnp.int32),
                     0, n_rows_g - 1)
    rr = row_g - row_lo                       # own-row-relative query row
    gap = jnp.maximum(0, jnp.maximum(-rr, rr - (rps - 1)))
    is_owner = gap == 0
    row0 = jnp.clip(rr + halo, 0, n_rows_local - 1)   # clamped local centre

    n_band = 2 * max_level + 1
    dr = jnp.arange(-max_level, max_level + 1, dtype=jnp.int32)
    rows_l = row0 + dr                                 # local band rows
    rows_global = rows_l + (row_lo - halo)
    owned = (rows_l >= halo) & (rows_l < halo + rps)
    in_band = jnp.abs(rows_global - row_g) <= halo
    contrib = jnp.where(is_owner, owned | in_band, owned & ~in_band)
    row_ok = (rows_l >= 0) & (rows_l < n_rows_local) \
        & (rows_global < n_rows_g) & contrib
    rows_c = jnp.clip(rows_l, 0, n_rows_local - 1)
    row_base = rows_c * n_cols

    # ring counts for every level (same gather pattern as _query_knn, with
    # the ownership mask folded into row validity)
    levels = jnp.arange(max_level + 1, dtype=jnp.int32)
    clo = jnp.clip(col0 - levels, 0, n_cols - 1)
    chi = jnp.clip(col0 + levels, 0, n_cols - 1)
    start_idx = row_base[None, :] + clo[:, None]
    end_idx = row_base[None, :] + chi[:, None] + 1
    row_cnt = cell_start[end_idx] - cell_start[start_idx]
    band_ok = jnp.abs(dr)[None, :] <= levels[:, None]
    row_cnt = jnp.where(band_ok & row_ok[None, :], row_cnt, 0)
    counts = row_cnt.sum(axis=1)

    n_slab = cell_start[-1]
    enough = counts >= jnp.minimum(k, jnp.maximum(n_slab, 1))
    first = jnp.where(jnp.any(enough), jnp.argmax(enough), max_level)
    lvl = jnp.minimum(first.astype(jnp.int32) + 1, max_level)

    args = (spec, k, window, cell_start, sx, sy, order,
            qx, qy, col0, dr, row_ok, row_base)
    d2, idx, total = _gather_topk(*args, lvl)

    # certified second pass (cap inf d_k BEFORE the int cast: a slab with
    # fewer than k contributed points yields d2[-1] = inf)
    d_k = jnp.sqrt(jnp.maximum(d2[-1], 0.0))
    d_cap = jnp.minimum(d_k, (max_level + 2.0) * spec.cell_width)
    lvl2 = jnp.ceil(d_cap / spec.cell_width).astype(jnp.int32)
    clamped = (lvl2 > max_level) | ~jnp.isfinite(d_k)
    lvl2 = jnp.clip(lvl2, lvl, max_level)
    d2b, idxb, totalb = _gather_topk(*args, lvl2)
    redo = lvl2 > lvl
    d2 = jnp.where(redo, d2b, d2)
    idx = jnp.where(redo, idxb, idx)
    total = jnp.where(redo, totalb, total)
    # a slab whose whole contributed point set fit in the gather window is
    # exact no matter what the level heuristics concluded
    exhausted = (total <= window) & (total >= n_slab)
    not_exact = ((total > window) | clamped) & ~exhausted

    # overflow excuse: non-owner slabs contribute nothing nearer than
    # max(gap, halo+1) rows, so their un-certified searches cannot corrupt
    # a merged top-k whose kth distance stays below (that - 1) cell widths.
    gap_eff = jnp.where(is_owner, 0, jnp.maximum(gap, halo + 1))
    excuse = jnp.where(
        not_exact,
        (gap_eff.astype(d_k.dtype) - 1.0) * spec.cell_width,
        jnp.inf)
    return SlabKnnResult(d2=d2, idx=idx, n_candidates=total,
                         overflow=not_exact, excuse=excuse)


def slab_knn(
    spec: GridSpec,
    rps: int,
    halo: int,
    cell_start: jax.Array,
    sx: jax.Array,
    sy: jax.Array,
    order: jax.Array,
    row_lo: jax.Array,
    queries_xy: jax.Array,
    k: int = 15,
    max_level: int | None = None,
    window: int = 256,
    block: int = 4096,
) -> SlabKnnResult:
    """Vectorized :func:`_slab_query_knn` over a query batch (the grid-aware
    ring step's Stage-1 kernel; NOT jitted here — it runs inside the traced
    ring rotation of :func:`repro.core.distributed.make_grid_ring_aidw`,
    and standalone callers wrap it themselves)."""
    n = queries_xy.shape[0]
    if max_level is None:
        max_level = auto_max_level(spec, max(int(sx.shape[0]), 1), k)
    block = min(block, max(n, 1))   # never pad a small shard up to a block
    qx, qy = queries_xy[:, 0], queries_xy[:, 1]
    f = partial(_slab_query_knn, spec, k, max_level, window, rps, halo,
                cell_start, sx, sy, order, row_lo)
    pad = (-n) % block
    qxp = jnp.pad(qx, (0, pad))
    qyp = jnp.pad(qy, (0, pad))
    nb = (n + pad) // block
    out = jax.lax.map(
        lambda ab: jax.vmap(f)(ab[0], ab[1]),
        (qxp.reshape(nb, block), qyp.reshape(nb, block)),
    )
    flat = jax.tree.map(lambda a: a.reshape((nb * block,) + a.shape[2:])[:n],
                        out)
    return SlabKnnResult(*flat)


def ring_candidate_d2(rx: jax.Array, ry: jax.Array,
                      qx: jax.Array, qy: jax.Array) -> jax.Array:
    """Exhaustive squared distances from a query batch to a slab's hot ring.

    The hot append ring (``repro.core.slab`` LSM ingest contract) is a tiny
    fixed-capacity buffer of freshly inserted points that have not yet been
    folded into the slab's CSR table.  It is searched EXHAUSTIVELY — every
    query against every slot — because its capacity is a few hundred slots,
    far below the CSR gather window, and an exhaustive scan needs no level
    heuristic, no certification pass, and cannot overflow.

    The arithmetic is element-for-element the CSR path's
    ``(sx[src] - qx)**2 + (sy[src] - qy)**2`` (squaring makes the operand
    order bitwise-irrelevant: ``x*x`` and ``(-x)*(-x)`` are identical
    floats), so merging ring candidates into a slab top-k preserves the
    bitwise Stage-1 contract.  Empty slots carry the ``PAD_COORD`` sentinel
    (1e30): their d2 overflows f32 to +inf and is never selected.

    Shapes: ``rx``/``ry`` are (ring_cap,); ``qx``/``qy`` are (nq,); the
    result is (nq, ring_cap).
    """
    return ((qx[:, None] - rx[None, :]) ** 2
            + (qy[:, None] - ry[None, :]) ** 2)


def auto_max_level(spec: GridSpec, m: int, k: int) -> int:
    """Expansion-level bound from expected point density (points/cell).

    Need (2L+1)^2 * ppc >= k at the count level, plus the safety ring and
    certified-pass headroom; clamped to the grid radius.
    """
    ppc = max(m / spec.n_cells, 1e-3)
    lvl = int(math.ceil(0.5 * (math.sqrt(4.0 * k / ppc) - 1.0))) + 3
    return max(2, min(lvl, max(spec.n_rows, spec.n_cols)))


@partial(jax.jit, static_argnums=(0, 3, 4, 5, 6, 7))
def grid_knn(
    spec: GridSpec,
    table: CellTable,
    queries_xy: jax.Array,
    k: int = 15,
    max_level: int | None = None,
    window: int = 256,
    block: int = 4096,
    exact: bool = True,
) -> KnnResult:
    """kNN for every query via local grid search (paper Stage 1).

    ``exact=False`` is the paper-faithful heuristic (count-based level + one
    safety ring); ``exact=True`` (default) adds the certified second gather
    pass (see ``_query_knn``).  ``window`` bounds the candidate set per query;
    with the paper's Eq.(2) cell width the expected candidate count at the
    safety level is ~(2L+3)^2 / 4 << 256, so the default is generous for
    near-uniform data.  ``overflow`` reports queries whose window overflowed
    or whose certified level exceeded ``max_level`` (result approximate).
    ``block`` chunks queries through ``lax.map`` to bound peak memory.
    """
    n = queries_xy.shape[0]
    if max_level is None:
        max_level = auto_max_level(spec, table.sx.shape[0], k)
    qx, qy = queries_xy[:, 0], queries_xy[:, 1]
    f = partial(
        _query_knn, spec, k, max_level, window, exact,
        table.cell_start, table.sx, table.sy, table.order,
    )
    pad = (-n) % block
    qxp = jnp.pad(qx, (0, pad))
    qyp = jnp.pad(qy, (0, pad))
    nb = (n + pad) // block
    out = jax.lax.map(
        lambda ab: jax.vmap(f)(ab[0], ab[1]),
        (qxp.reshape(nb, block), qyp.reshape(nb, block)),
    )
    flat = jax.tree.map(lambda a: a.reshape((nb * block,) + a.shape[2:])[:n], out)
    return KnnResult(*flat)


@partial(jax.jit, static_argnums=(2, 3))
def brute_knn(points_xy: jax.Array, queries_xy: jax.Array, k: int = 15,
              block: int = 1024) -> tuple[jax.Array, jax.Array]:
    """Brute-force kNN (the 'original' algorithm's global search, §3.1).

    Returns (d2, idx) with d2 ascending.  Blocked over queries so the (n, m)
    distance matrix never materializes in full.
    """
    n = queries_xy.shape[0]
    px, py = points_xy[:, 0], points_xy[:, 1]
    k = min(k, points_xy.shape[0])

    def one_block(qb):
        d2 = (qb[:, 0:1] - px[None, :]) ** 2 + (qb[:, 1:2] - py[None, :]) ** 2
        neg_top, idx = jax.lax.top_k(-d2, k)
        return -neg_top, idx

    pad = (-n) % block
    qp = jnp.pad(queries_xy, ((0, pad), (0, 0)))
    nb = (n + pad) // block
    d2, idx = jax.lax.map(one_block, qp.reshape(nb, block, 2))
    return d2.reshape(-1, k)[:n], idx.reshape(-1, k)[:n]


def mean_nn_distance(d2: jax.Array) -> jax.Array:
    """Eq. (3): r_obs = mean of the k NN distances (sqrt deferred until here)."""
    return jnp.sqrt(jnp.maximum(d2, 0.0)).mean(axis=-1)
