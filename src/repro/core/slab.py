"""Slab-decomposed AIDW: grid kNN with halo exchange + ring Stage 2.

The final §Perf iteration for the paper's technique at pod scale
(EXPERIMENTS.md cell 3): the ring variant's brute-force kNN doubles the step
FLOPs.  Here Stage 1 keeps the paper's GRID search, domain-decomposed:

* the study area is cut into P horizontal **slabs** of whole grid rows
  (slab s owns rows [s*rps, (s+1)*rps) of the global grid); data points and
  queries arrive pre-partitioned by slab (the natural layout of tiled
  geospatial ingestion);
* each shard receives its two neighbour slabs via collective-permute (the
  halo — one ring hop each way) and builds a LOCAL grid over
  [prev | own | next] with static dims (3*rps rows x global cols); the only
  dynamic quantity is the slab's y-offset, folded into the point/query
  coordinates, so the existing static-spec `bin_points`/`grid_knn` machinery
  applies unchanged;
* kNN is exact while the certified expansion level stays within one slab
  (max_level <= rps; overflow flags report violations — with Eq.(2)x4 cells
  and k=15 the certified level is ~5 vs rps=32 at 1B points / 512 chips);
* Stage 2 is the ring rotation from `distributed.make_ring_aidw` (the global
  Eq.(1) sum needs every data block regardless of where kNN happened).

Per-chip cost at m=n=2^30, P=512: kNN drops from O(n_loc * m) ~ 1.7e16 FLOPs
(ring brute force) to O(n_loc * window) ~ 4e9 — the step becomes one
Stage-2 sweep, halving total FLOPs vs ring AIDW.

Grid-aware ring (PR 5; :class:`SlabPartition` below + ``make_grid_ring_aidw``
in ``repro.core.distributed``): the serving session's ``layout='grid_ring'``
uses the SAME slab decomposition but rotates the slab CSR tables around the
ring instead of pre-partitioning queries, so it composes with the session's
query-sharded-over-all-axes layout.  Contracts:

* **Halo-width invariant** — slab ``s`` owns global grid rows
  ``[s*rps, (s+1)*rps)`` and its CSR table carries ``halo`` extra rows of
  boundary cells on each side (points REPLICATED from the neighbouring
  slabs).  With ``halo >= max_level`` (the search's level bound, the
  default), a query landing in slab ``s`` finds its ENTIRE expanding search
  window — every cell a certified level-``L <= halo`` expansion can touch —
  inside ``s``'s table, so the owner's result alone is the exact global
  answer for such queries and the candidate sequence is identical to the
  replicated layout's (bit-identical d2/r_obs/alpha).  Queries whose
  certified window exceeds the halo fall back to the cross-slab k-way
  merge, which is still exact: contributions are partitioned so every data
  point is counted exactly once (owner takes its rows plus in-halo-band
  halo rows; non-owners take only rows they own outside that band — see
  ``repro.core.knn._slab_query_knn``), and un-certified slab searches carry
  an ``excuse`` radius that keeps the merged overflow flag honest.
* **Memory model** — each device holds O(m/P) owned points + O(boundary)
  halo copies (``2 * halo`` rows of points) + the slab's CSR offsets
  ((rps + 2*halo) * n_cols + 1 int32), NEVER the O(m) dataset or the
  O(n_cells) global table.
* **Comms model** — one neighbour ``ppermute`` of the slab packet (points +
  CSR offsets, O(m/P + boundary) bytes) per ring step per stage; no
  all-gather, no per-query traffic.  Stage 2 rotates the same point blocks
  (the global Eq. (1) sum needs every block regardless of where kNN
  happened).
* **Hot-ring (LSM) ingest contract** — every slab carries a small
  fixed-capacity APPEND RING next to its CSR table (``ring_cap`` slots).
  An insert lands ONLY in its owning slab's ring (never halo-replicated:
  every rotating packet's ring is searched exhaustively by every query, so
  a ring point is globally visible the moment it is staged — no halo copy
  needed) and a CSR delete becomes an in-place TOMBSTONE
  (:func:`repro.core.grid.rebin_delta` ``tombstone=True``), so a delta
  changes O(Δ) ring slots + O(Δ) dead slots and the CSR arrays/offsets are
  otherwise untouched — the device staging cost drops from O(m) to
  O(Δ + touched-slab rows).  **Visibility**: a write is query-visible at
  the epoch whose update staged it (the next executed batch), exactly like
  a CSR write — Stage 1 k-way-merges the ring candidates with the CSR
  candidates with element-identical d2 arithmetic, so while a point sits
  in the ring the merged Stage-1 outputs equal a fresh build's within
  1 ulp (the ring scan is a separate XLA subgraph, so FMA contraction may
  round its d2 differently than the CSR gather's) and the GLOBAL Stage-2
  f32 summation order differs (values ~1 ulp); after :meth:`compact`
  every output is BITWISE a fresh build's again.  **Compaction**:
  :meth:`compact`
  (triggered when a ring cannot absorb an insert batch, when the tombstone
  fraction crosses ``tombstone_threshold``, or explicitly as a background
  FIFO-barrier epoch by the serving layer) folds every ring into the slab
  CSRs — halo replication happens HERE, via the standard insert routing —
  and purges tombstones, after which every table is element-identical to a
  fresh :meth:`build` of the same logical dataset.  Each point is counted
  exactly once across the move (ring ids are always strictly greater than
  every CSR member id, so the fold is a pure sorted append; a point is
  never in a ring and a CSR table at the same time): compaction changes
  WHERE a point is searched, never whether or how often it contributes.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import aidw as A
from . import grid as G
from . import knn as K
from .distributed import PAD_COORD, _ring_interp_step, auto_axes


def slab_plan(m_global: int, p: int, *, bounds=(0.0, 1.0, 0.0, 1.0),
              cell_factor: float = 4.0) -> tuple[G.GridSpec, int]:
    """(local GridSpec with 3*rps rows, rows-per-slab) for a P-way split.

    ``bounds`` = (min_x, max_x, min_y, max_y) must be known statically (the
    ingestion contract for tiled spatial data)."""
    min_x, max_x, min_y, max_y = bounds
    area = (max_x - min_x) * (max_y - min_y)
    cw = cell_factor * (1.0 / (2.0 * math.sqrt(m_global / area)))
    cols = max(int((max_x - min_x + cw) / cw), 1)
    rows_g = max(int((max_y - min_y + cw) / cw), 1)
    rps = -(-rows_g // p)                      # rows per slab (ceil)
    local = G.GridSpec(min_x, 0.0, cw, 3 * rps, cols)
    return local, rps


def partition_by_slab(points: np.ndarray, p: int, rps: int, cw: float,
                      min_y: float = 0.0):
    """Host-side: group rows into slabs, pad to equal size with sentinels.

    Returns (slabbed (p, cap, d), original_index (p, cap) with -1 padding).
    """
    rows = np.clip(((points[:, 1] - min_y) / cw).astype(np.int64), 0,
                   p * rps - 1)
    slab = np.minimum(rows // rps, p - 1)
    cap = int(np.bincount(slab, minlength=p).max())
    d = points.shape[1]
    out = np.full((p, cap, d), PAD_COORD, dtype=points.dtype)
    idx = np.full((p, cap), -1, dtype=np.int64)
    for s in range(p):
        sel = np.nonzero(slab == s)[0]
        out[s, : len(sel)] = points[sel]
        idx[s, : len(sel)] = sel
    return out, idx


def slab_rows(spec: G.GridSpec, p: int) -> int:
    """Rows per slab (ceil) for a P-way split of ``spec``'s rows."""
    return -(-spec.n_rows // p)


def member_delta(mem: np.ndarray, dels, m_kept: int, ins_idx):
    """Apply one (deletes, inserts) delta to a SORTED member-index array.

    The shared bookkeeping for every slab-style partition (the grid-ring
    layout's :class:`SlabPartition` tables and the serving fleet's
    per-shard membership — one implementation, so delete routing can never
    drift between them).  ``mem`` holds indices into the CURRENT dataset
    order; ``dels`` is the sorted unique global delete set (or None);
    ``m_kept`` the post-delete dataset size; ``ins_idx`` the positions of
    this member set's inserts within the global insert batch (or None).
    Returns ``(dels_local, new_mem)`` where ``dels_local`` are the deleted
    entries' positions WITHIN ``mem`` (what ``rebin_delta`` wants) and
    ``new_mem`` is remapped to the reconstructed kept-plus-appended order
    (still sorted: appends index past every kept entry).
    """
    dels_local = None
    if dels is not None and mem.size:
        pos = np.searchsorted(mem, dels)
        hit = pos < mem.size
        hit[hit] &= mem[pos[hit]] == dels[hit]
        dels_local = pos[hit]
        keep = np.ones(mem.size, bool)
        keep[dels_local] = False
        mem = mem[keep]
    if dels is not None:
        mem = mem - np.searchsorted(dels, mem)
    if ins_idx is not None and np.size(ins_idx):
        mem = np.concatenate([mem, m_kept + np.asarray(ins_idx)])
    return dels_local, mem


class DeltaReport:
    """What one :meth:`SlabPartition.apply_delta`/:meth:`compact` touched.

    The device-staging worklist: ``csr_rows`` are slabs whose CSR arrays
    changed wholesale (insert spill / compaction — restage those rows),
    ``dead`` maps a slab to the sorted-array slot positions tombstoned this
    delta (an O(Δ) scatter patch, the CSR arrays are otherwise byte-stable),
    ``ring_rows`` are slabs whose hot ring changed (restage one
    ``ring_cap``-slot row).  ``staged_bytes`` is filled in by the staging
    layer that consumes the report.
    """

    def __init__(self):
        self.csr_rows: set = set()
        self.dead: dict = {}
        self.ring_rows: set = set()
        self.compactions = 0
        self.n_inserts = 0
        self.n_deletes = 0
        self.spilled = False
        self.staged_bytes = 0


class SlabPartition:
    """Host-side slab decomposition of a dataset over a GLOBAL grid spec.

    The device-facing half of the grid-aware ring layout (module docstring,
    'Grid-aware ring'): slab ``s`` owns global rows ``[s*rps, (s+1)*rps)``
    and its CSR :class:`~repro.core.grid.CellTable` covers
    ``rps + 2*halo`` rows (its own plus ``halo`` boundary rows replicated
    from each neighbour).  All binning is done with ids derived from the
    GLOBAL spec (global id minus the slab's row offset), so per-row CSR
    content is bitwise what the replicated global table holds for the same
    rows — the root of the grid-ring layout's bit-identity story.

    Incremental updates: :meth:`apply_delta` is LSM-tiered (module
    docstring, 'Hot-ring (LSM) ingest contract').  Inserts append to the
    owning slab's fixed-capacity hot ring; CSR deletes tombstone dead slots
    in place; ring deletes compact the tiny ring host-side.  The CSR tables
    change only when a ring cannot absorb its insert batch or the tombstone
    fraction crosses ``tombstone_threshold`` — then :meth:`compact` folds
    every ring into the slab CSRs (halo replication happens at the fold)
    and purges tombstones, recovering a partition element-identical to a
    fresh :meth:`build` of the updated dataset.  Every call returns a
    :class:`DeltaReport` naming exactly which device rows/slots changed.

    ``members[s]`` holds each table's points as indices into the CURRENT
    dataset order (the session's kept-in-original-order-plus-appends
    order), always ascending — the delta router's join key.  Ring members
    (``ring_mem[s]``) are kept separately and are always strictly greater
    than every CSR member id (inserts take the top of the index space and
    CSR tables gain ids only at compaction, which empties the rings) — the
    invariant that makes the compaction fold a pure sorted append.
    """

    def __init__(self, spec: G.GridSpec, p: int, rps: int, halo: int,
                 tables: list, members: list, m: int, *,
                 ring_cap: int = 256):
        self.spec = spec
        self.p = p
        self.rps = rps
        self.halo = halo
        self.tables = tables          # per-slab CellTable of numpy arrays
        self.members = members        # per-slab sorted global indices
        self.m = m
        # per-slab Stage-2 ownership masks over the sorted table entries,
        # cached so a delta recomputes them for TOUCHED slabs only
        self._owned: list = [None] * p
        # hot append rings: freshly inserted points, owner slab only
        self.ring_cap = int(ring_cap)
        self.ring_pts = [np.zeros((0, 3), np.float32) for _ in range(p)]
        self.ring_ids = [np.zeros(0, np.int64) for _ in range(p)]
        self.ring_mem = [np.zeros(0, np.int64) for _ in range(p)]
        self.tombstone_threshold = 0.25
        self.compactions = 0

    @property
    def local_spec(self) -> G.GridSpec:
        """Static spec of one slab table: rps + 2*halo rows, global cols.
        (min_x/min_y are the GLOBAL origin — ids are always computed
        globally and offset, never re-derived from a shifted origin.)"""
        return G.GridSpec(self.spec.min_x, self.spec.min_y,
                          self.spec.cell_width,
                          self.rps + 2 * self.halo, self.spec.n_cols)

    @classmethod
    def build(cls, spec: G.GridSpec, points_xyz, p: int, halo: int,
              ring_cap: int = 256) -> "SlabPartition":
        pts = np.asarray(points_xyz)
        x, y, z = pts[:, 0], pts[:, 1], pts[:, 2]
        rps = slab_rows(spec, p)
        ids = G.cell_ids_host(spec, x, y)
        row = ids // spec.n_cols
        n_local = (rps + 2 * halo) * spec.n_cols
        tables, members = [], []
        for s in range(p):
            lo = s * rps
            mem = np.nonzero((row >= lo - halo)
                             & (row < lo + rps + halo))[0]
            lids = ids[mem] - (lo - halo) * spec.n_cols
            ordr = np.argsort(lids, kind="stable").astype(np.int32)
            cell_start = np.searchsorted(
                lids[ordr], np.arange(n_local + 1, dtype=np.int64),
                side="left").astype(np.int32)
            tables.append(G.CellTable(
                x[mem][ordr], y[mem][ordr], z[mem][ordr], cell_start, ordr))
            members.append(mem.astype(np.int64))
        return cls(spec, p, rps, halo, tables, members, pts.shape[0],
                   ring_cap=ring_cap)

    def apply_delta(self, inserts=None, deletes=None) -> DeltaReport:
        """LSM-tiered delta: rings absorb inserts, tombstones absorb deletes.

        ``deletes`` are indices into the CURRENT dataset order; ``inserts``
        append after compaction, exactly like
        :func:`repro.core.pipeline.plan_delta`'s dataset reconstruction —
        so ``compact()`` always recovers a partition element-identical to a
        fresh build of that reconstructed dataset (and queries see the
        same candidate multiset at every intermediate state).  Returns a
        :class:`DeltaReport` naming the touched device rows/slots.
        """
        spec = self.spec
        rep = DeltaReport()
        dels = np.unique(np.asarray(deletes, dtype=np.int64)) \
            if deletes is not None and np.size(deletes) else None
        if dels is not None and (dels[0] < 0 or dels[-1] >= self.m):
            raise IndexError(f"delete index out of range [0, {self.m})")
        ins = np.asarray(inserts) if inserts is not None \
            and np.size(inserts) else None
        m_kept = self.m - (0 if dels is None else dels.size)
        rep.n_deletes = 0 if dels is None else int(dels.size)
        rep.n_inserts = 0 if ins is None else int(ins.shape[0])
        lspec = self.local_spec

        # --- phase 1: hot-ring deletes (exact removal; rings stay tiny) ----
        if dels is not None:
            for s in range(self.p):
                rmem = self.ring_mem[s]
                if rmem.size:
                    hit = np.isin(rmem, dels)
                    if hit.any():
                        keep = ~hit
                        self.ring_pts[s] = self.ring_pts[s][keep]
                        self.ring_ids[s] = self.ring_ids[s][keep]
                        rmem = rmem[keep]
                        rep.ring_rows.add(s)
                self.ring_mem[s] = rmem - np.searchsorted(dels, rmem)

        # --- phase 2: CSR deletes -> tombstones (O(Δ) slots change) --------
        if dels is not None:
            for s in range(self.p):
                # membership always shifts: deletes ANYWHERE compact the
                # global order that members indexes into
                dels_local, self.members[s] = member_delta(
                    self.members[s], dels, m_kept, None)
                if dels_local is not None and dels_local.size:
                    old_order = np.asarray(self.tables[s].order)
                    t = G.rebin_delta(lspec, self.tables[s],
                                      deletes=dels_local, tombstone=True)
                    self.tables[s] = G.CellTable(
                        *(np.asarray(a) for a in t))
                    rep.dead[s] = np.nonzero(
                        (np.asarray(t.order) == -1) & (old_order != -1))[0]

        # --- phase 3: tombstone-threshold compaction -----------------------
        compacted = False
        if dels is not None \
                and self.tombstone_frac() > self.tombstone_threshold:
            self._compact_into(rep)
            compacted = True

        # --- phase 4: inserts -> hot rings (CSR spill only after a
        #     compaction has emptied every ring, preserving the id order
        #     invariant the fold depends on) ---------------------------------
        if ins is not None:
            ins_ids = G.cell_ids_host(spec, ins[:, 0], ins[:, 1])
            ins_row = ins_ids // spec.n_cols
            owner = np.minimum(ins_row // self.rps, self.p - 1)
            needed = np.bincount(owner, minlength=self.p)
            occ = np.array([self.ring_ids[s].size for s in range(self.p)])
            if not compacted and np.any(occ + needed > self.ring_cap):
                self._compact_into(rep)
                compacted = True
            if np.any(needed > self.ring_cap):
                rep.spilled = True
                for s in range(self.p):
                    lo = s * self.rps
                    mask = (ins_row >= lo - self.halo) \
                        & (ins_row < lo + self.rps + self.halo)
                    if not mask.any():
                        continue
                    base = (lo - self.halo) * spec.n_cols
                    t = G.rebin_delta(lspec, self.tables[s],
                                      inserts=ins[mask],
                                      insert_ids=ins_ids[mask] - base)
                    self.tables[s] = G.CellTable(
                        *(np.asarray(a) for a in t))
                    self.members[s] = np.concatenate(
                        [self.members[s], m_kept + np.nonzero(mask)[0]])
                    self._owned[s] = None
                    rep.csr_rows.add(s)
            else:
                for s in np.unique(owner):
                    s = int(s)
                    sel = owner == s
                    self.ring_pts[s] = np.concatenate(
                        [self.ring_pts[s], ins[sel]]) \
                        if self.ring_pts[s].size else np.array(ins[sel])
                    self.ring_ids[s] = np.concatenate(
                        [self.ring_ids[s], ins_ids[sel]])
                    self.ring_mem[s] = np.concatenate(
                        [self.ring_mem[s], m_kept + np.nonzero(sel)[0]])
                    rep.ring_rows.add(s)
        self.m = m_kept + rep.n_inserts
        return rep

    def compact(self) -> DeltaReport:
        """Fold every hot ring into its slab CSRs and purge tombstones.

        After this the partition is element-identical to a fresh
        :meth:`build` of the current logical dataset (module docstring
        contract).  Returns the staging worklist."""
        rep = DeltaReport()
        self._compact_into(rep)
        return rep

    def _compact_into(self, rep: DeltaReport) -> None:
        spec = self.spec
        lspec = self.local_spec
        all_mem = np.concatenate(self.ring_mem) if self.p else \
            np.zeros(0, np.int64)
        o = np.argsort(all_mem, kind="stable")
        all_mem = all_mem[o]
        all_ids = np.concatenate(self.ring_ids)[o]
        all_pts = np.concatenate(
            [p for p in self.ring_pts] or [np.zeros((0, 3), np.float32)],
            axis=0)[o]
        rows = all_ids // spec.n_cols
        for s in range(self.p):
            lo = s * self.rps
            purged = G.purge_tombstones(lspec, self.tables[s])
            changed = purged is not self.tables[s]
            mask = (rows >= lo - self.halo) \
                & (rows < lo + self.rps + self.halo)
            if mask.any():
                base = (lo - self.halo) * spec.n_cols
                purged = G.rebin_delta(lspec, purged, inserts=all_pts[mask],
                                       insert_ids=all_ids[mask] - base)
                self.members[s] = np.concatenate(
                    [self.members[s], all_mem[mask]])
                changed = True
            if changed:
                self.tables[s] = G.CellTable(
                    *(np.asarray(a) for a in purged))
                self._owned[s] = None
                rep.csr_rows.add(s)
                rep.dead.pop(s, None)   # the full-row restage covers it
            if self.ring_ids[s].size:
                rep.ring_rows.add(s)
        self.ring_pts = [np.zeros((0, 3), np.float32)
                         for _ in range(self.p)]
        self.ring_ids = [np.zeros(0, np.int64) for _ in range(self.p)]
        self.ring_mem = [np.zeros(0, np.int64) for _ in range(self.p)]
        self.compactions += 1
        rep.compactions += 1

    # -- ingest telemetry ----------------------------------------------------

    def tombstone_frac(self) -> float:
        """Max per-slab tombstone fraction (compaction trigger + stat)."""
        return max((G.tombstone_frac(t) for t in self.tables), default=0.0)

    def ring_occupancy(self) -> float:
        """Max per-slab hot-ring fill fraction."""
        if not self.p:
            return 0.0
        return max(self.ring_ids[s].size for s in range(self.p)) \
            / self.ring_cap

    def ring_size(self) -> int:
        """Total points currently resident in hot rings."""
        return int(sum(self.ring_ids[s].size for s in range(self.p)))

    # -- per-slab device staging helpers ------------------------------------

    def owned_mask(self, s: int) -> np.ndarray:
        """Stage-2 ownership mask over slab ``s``'s sorted table entries
        (cached; invalidated only when the slab's CSR layout changes —
        tombstones keep it valid since dead slots keep their position)."""
        if self._owned[s] is None:
            cs = np.asarray(self.tables[s].cell_start, np.int64)
            rows = np.repeat(np.arange(cs.size - 1, dtype=np.int64),
                             np.diff(cs)) // self.spec.n_cols
            self._owned[s] = (rows >= self.halo) \
                & (rows < self.halo + self.rps)
        return self._owned[s]

    def owned_positions(self, s: int, slots: np.ndarray) -> np.ndarray:
        """Owned-block (bx/by/bz) positions of the given sorted-array slots
        (only the owned ones; halo copies have no Stage-2 block slot)."""
        o = self.owned_mask(s)
        brank = np.cumsum(o) - 1
        owned = slots[o[slots]]
        return brank[owned]

    def slab_host_rows(self, s: int, cap: int, cap2: int) -> dict | None:
        """One slab's padded device rows (the delta-staging unit), or
        ``None`` if the slab no longer fits the given capacities."""
        t = self.tables[s]
        o = self.owned_mask(s)
        n_s = t.sx.shape[0]
        n_o = int(o.sum())
        if n_s > cap or n_o > cap2:
            return None
        dt, zt = t.sx.dtype, t.sz.dtype
        row = {"sx": np.full(cap, PAD_COORD, dt),
               "sy": np.full(cap, PAD_COORD, dt),
               "sz": np.zeros(cap, zt),
               "cell_start": np.asarray(t.cell_start, np.int32),
               "bx": np.full(cap2, PAD_COORD, dt),
               "by": np.full(cap2, PAD_COORD, dt),
               "bz": np.zeros(cap2, zt)}
        row["sx"][:n_s] = t.sx
        row["sy"][:n_s] = t.sy
        row["sz"][:n_s] = t.sz
        row["bx"][:n_o] = t.sx[o]
        row["by"][:n_o] = t.sy[o]
        row["bz"][:n_o] = t.sz[o]
        return row

    def ring_host_row(self, s: int) -> dict:
        """One slab's padded hot-ring device row (``ring_cap`` slots)."""
        dt = self.tables[s].sx.dtype if self.tables else np.float32
        zt = self.tables[s].sz.dtype if self.tables else np.float32
        row = {"rx": np.full(self.ring_cap, PAD_COORD, dt),
               "ry": np.full(self.ring_cap, PAD_COORD, dt),
               "rz": np.zeros(self.ring_cap, zt)}
        pts = self.ring_pts[s]
        r = pts.shape[0]
        if r:
            row["rx"][:r] = pts[:, 0]
            row["ry"][:r] = pts[:, 1]
            row["rz"][:r] = pts[:, 2]
        return row

    def device_tables(self, pad_multiple: int = 64, *, cap_floor: int = 0,
                      cap2_floor: int = 0) -> dict:
        """Stacked (P, ...) numpy arrays for the ring executor's rotating
        packets; point arrays padded to common caps (multiples of
        ``pad_multiple``, so balanced churn rarely changes array shapes
        and the compiled executables survive).  ``cap_floor``/``cap2_floor``
        let the staging layer keep caps sticky (grow-only) across deltas.

        Stage 1 rotates the halo'd slab tables (``sx``/``sy``/``sz``/
        ``cell_start``/``row_lo``; ``sz`` rides along for LOCAL Stage-2
        mode, whose in-scan gather gathers values by slab-sorted index)
        plus the hot-ring block (``rx``/``ry``/``rz``, ``ring_cap`` slots
        per slab — searched exhaustively, so padded slots with inf d2 are
        inert).  Stage 2 rotates SEPARATE owned-only blocks
        (``bx``/``by``/``bz``) — halo copies must not contribute to the
        global Eq. (1) sum twice, and carrying them as dead padded lanes
        would widen every Stage-2 tile by the boundary size, eating the
        Stage-1 win — and the ring block rides along (every ring point is
        owned by construction).  Padded slots hold ``PAD_COORD`` (Stage-2
        weight exactly 0) and are NEVER addressed by Stage 1
        (``cell_start[-1]`` stops short of them)."""
        def rounded(n):
            return max(pad_multiple, -(-n // pad_multiple) * pad_multiple)

        caps = [t.sx.shape[0] for t in self.tables]
        cap = max(rounded(max(caps + [1])), cap_floor)
        dt = self.tables[0].sx.dtype if self.tables else np.float32
        zt = self.tables[0].sz.dtype if self.tables else np.float32
        sx = np.full((self.p, cap), PAD_COORD, dt)
        sy = np.full((self.p, cap), PAD_COORD, dt)
        sz = np.zeros((self.p, cap), zt)
        cell_start = np.stack([np.asarray(t.cell_start, np.int32)
                               for t in self.tables])
        owned_sel = [self.owned_mask(s) for s in range(self.p)]
        for s, t in enumerate(self.tables):
            n_s = t.sx.shape[0]
            sx[s, :n_s] = t.sx
            sy[s, :n_s] = t.sy
            sz[s, :n_s] = t.sz
        cap2 = max(rounded(max([int(o.sum()) for o in owned_sel] + [1])),
                   cap2_floor)
        bx = np.full((self.p, cap2), PAD_COORD, dt)
        by = np.full((self.p, cap2), PAD_COORD, dt)
        bz = np.zeros((self.p, cap2), zt)
        for s, (t, o) in enumerate(zip(self.tables, owned_sel)):
            n_o = int(o.sum())
            bx[s, :n_o] = t.sx[o]
            by[s, :n_o] = t.sy[o]
            bz[s, :n_o] = t.sz[o]
        rx = np.full((self.p, self.ring_cap), PAD_COORD, dt)
        ry = np.full((self.p, self.ring_cap), PAD_COORD, dt)
        rz = np.zeros((self.p, self.ring_cap), zt)
        for s in range(self.p):
            pts = self.ring_pts[s]
            if pts.shape[0]:
                rx[s, :pts.shape[0]] = pts[:, 0]
                ry[s, :pts.shape[0]] = pts[:, 1]
                rz[s, :pts.shape[0]] = pts[:, 2]
        return {"sx": sx, "sy": sy, "sz": sz, "cell_start": cell_start,
                "row_lo": (np.arange(self.p) * self.rps).astype(np.int32),
                "bx": bx, "by": by, "bz": bz,
                "rx": rx, "ry": ry, "rz": rz}


def make_slab_aidw(
    mesh: Mesh,
    ring_axis: str,
    *,
    m_global: int,
    k: int = 15,
    cell_factor: float = 4.0,
    bounds=(0.0, 1.0, 0.0, 1.0),
    window: int = 256,
    q_block: int = 0,
    alphas=A.DEFAULT_ALPHAS,
    r_min: float = A.DEFAULT_R_MIN,
    r_max: float = A.DEFAULT_R_MAX,
):
    """fn(points (P*cap, 3), queries (P*qcap, 2), n_points, area) -> values.

    Inputs arrive slab-partitioned (see :func:`partition_by_slab`) and sharded
    over ``ring_axis``; sentinel-padded rows yield NaN outputs (dropped by the
    caller via the index map).
    """
    mesh = auto_axes(mesh)
    p_ring = mesh.shape[ring_axis]
    spec, rps = slab_plan(m_global, p_ring, bounds=bounds,
                          cell_factor=cell_factor)
    min_y = bounds[2]
    cw = spec.cell_width
    max_level = min(K.auto_max_level(spec, max(m_global // p_ring, 1), k) + 1,
                    rps)
    fwd = [(i, (i + 1) % p_ring) for i in range(p_ring)]
    bwd = [(i, (i - 1) % p_ring) for i in range(p_ring)]

    def local_fn(points, queries, n_points, area):
        s = jax.lax.axis_index(ring_axis)
        # --- halo exchange: whole neighbour slabs, one hop each way --------
        prev_blk = jax.lax.ppermute(points, ring_axis, fwd)   # from s-1
        next_blk = jax.lax.ppermute(points, ring_axis, bwd)   # from s+1
        pts = jnp.concatenate([prev_blk, points, next_blk], axis=0)

        # --- shift into the local 3*rps-row frame --------------------------
        y_base = min_y + (s.astype(jnp.float32) - 1.0) * (rps * cw)
        ys = pts[:, 1] - y_base
        # wraparound halos (slab 0's 'prev' etc.) land outside -> sentinel
        ok = (ys >= 0.0) & (ys < spec.n_rows * cw) & (pts[:, 0] < PAD_COORD / 2)
        xs = jnp.where(ok, pts[:, 0], PAD_COORD)
        ys = jnp.where(ok, ys, PAD_COORD)
        table = G.bin_points(spec, xs, ys, pts[:, 2])

        qy = queries[:, 1] - y_base
        q_ok = queries[:, 0] < PAD_COORD / 2
        q_local = jnp.stack(
            [jnp.where(q_ok, queries[:, 0], PAD_COORD),
             jnp.where(q_ok, qy, PAD_COORD)], axis=1)

        # --- paper Stage 1 on the local grid --------------------------------
        res = K.grid_knn(spec, table, q_local, k, max_level, window,
                         min(4096, queries.shape[0]), True)
        r_obs = K.mean_nn_distance(res.d2)
        alpha = A.adaptive_alpha(r_obs, n_points, area, alphas=alphas,
                                 r_min=r_min, r_max=r_max)

        # --- Stage 2: ring rotation (global Eq. 1 sum) ----------------------
        qx = queries[:, 0]
        qy_g = queries[:, 1]

        def interp_step(carry, _):
            acc, blk = carry
            acc, blk = _ring_interp_step(ring_axis, fwd, qx, qy_g, alpha,
                                         acc, blk, q_block)
            return (acc, blk), None

        acc0 = (jnp.zeros_like(qx), jnp.zeros_like(qx))
        ((swz, sw), _), _ = jax.lax.scan(interp_step, (acc0, points), None,
                                         length=p_ring)
        return swz / sw, res.overflow

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(ring_axis, None), P(ring_axis, None), P(), P()),
        out_specs=(P(ring_axis), P(ring_axis)),
    )
    return jax.jit(fn), spec, rps


def slab_aidw(mesh: Mesh, ring_axis: str, points_xyz, queries_xy, *,
              k: int = 15, cell_factor: float = 4.0,
              bounds=(0.0, 1.0, 0.0, 1.0), window: int = 256,
              q_block: int = 0):
    """Convenience wrapper: host-side slab partition, run, un-permute."""
    p = mesh.shape[ring_axis]
    pts = np.asarray(points_xyz)
    qs = np.asarray(queries_xy)
    m, n = len(pts), len(qs)
    fn, spec, rps = make_slab_aidw(
        mesh, ring_axis, m_global=m, k=k, cell_factor=cell_factor,
        bounds=bounds, window=window, q_block=q_block)
    cw = spec.cell_width
    pts_s, _ = partition_by_slab(pts, p, rps, cw, bounds[2])
    qs_s, q_idx = partition_by_slab(qs, p, rps, cw, bounds[2])
    area = (bounds[1] - bounds[0]) * (bounds[3] - bounds[2])
    vals, overflow = fn(
        jnp.asarray(pts_s.reshape(-1, 3)), jnp.asarray(qs_s.reshape(-1, 2)),
        jnp.float32(m), jnp.float32(area))
    vals = np.asarray(vals).reshape(p, -1)
    out = np.empty(n, np.float32)
    flat_idx = q_idx.reshape(-1)
    keep = flat_idx >= 0
    out[flat_idx[keep]] = vals.reshape(-1)[keep]
    return out, int(np.asarray(overflow).reshape(-1)[keep].sum())
