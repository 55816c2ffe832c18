"""End-to-end AIDW pipelines — the paper's Figure 1 as composable JAX.

Variants (all numerically equivalent modulo accumulation order):

* :func:`aidw_improved`  — grid-based fast kNN (Stage 1) + weighted
  interpolation (Stage 2).  ``stage2='naive'`` uses the blocked pure-jnp
  path; ``stage2='tiled'`` uses the Pallas VMEM-tiled kernel (the TPU
  analogue of the paper's shared-memory tiled version).
* :func:`aidw_original`  — the authors' previous algorithm (Mei et al. 2015):
  brute-force global kNN + the same Stage 2.  This is the paper's baseline.
* :func:`idw_standard`   — Shepard (1968) constant-alpha IDW.

Plan/execute contract (serving-scale API; see also ``repro.core.session``):

The paper splits the improved algorithm into a one-time grid build and a
per-query pass, but a naive ``aidw_improved`` call re-plans and re-bins on
every invocation.  For repeated queries over a mostly-static dataset the
pipeline is therefore factored into:

* :func:`plan` — HOST-side grid planning (static ``GridSpec``) plus the
  device-resident CSR cell table (:class:`repro.core.grid.CellTable`), the
  study-area constants for Eq. (2), and the pipeline config, bundled into an
  :class:`AidwPlan`.  Runs once per dataset (or per ``update``).  Because the
  grid spec determines downstream array SHAPES, ``plan`` must run eagerly;
  everything after it is shape-static and jit-safe.
* :func:`execute` — the per-query Stage-1 (grid kNN + mean NN distance) and
  Stage-2 (adaptive alpha + Eq. (1) weighting) over a prebuilt plan.  Pure in
  the plan arrays and queries: safe to wrap in ``jax.jit`` with the plan's
  static fields (``spec``, ``cfg``, ``n_points``, ``area``) as static args —
  :data:`_session_execute` below is exactly that jit, shared by every
  :class:`repro.core.session.InterpolationSession`.

Padding rules: callers may pad the query batch to a bucketed shape (power of
two) so repeated odd-sized batches reuse one compiled executable.  Padded
queries are ordinary coordinates (pad with an EDGE query, not zeros, so the
padded lanes stay in a dense, cheap-to-search cell); all per-query outputs
are independent, so slicing ``[:n]`` recovers results bit-identical to an
unpadded call.  Per-query reductions never cross the query axis, which is
what makes bucketed results match unbucketed ones bitwise.

Donation rules: the padded query buffer is created by the caller expressly
for one ``execute`` call, so sessions donate it (``donate_argnums``) on
backends that support buffer donation (not CPU); plan arrays are long-lived
and must NEVER be donated — they are reused by every subsequent query.

AOT / ladder rules (``InterpolationSession.precompile``; PR 10):

Because of the padding rules above, a session's entire steady-state compile
surface is finite and known at plan time: one executable per (query-bucket,
capacity-bucket) pair, where query buckets are the power-of-two ladder up to
``max_batch`` and the capacity bucket is fixed by the plan.  ``precompile``
walks that ladder through ``jax.jit(...).lower().compile()`` and installs
the resulting ``Compiled`` objects ahead of any traffic, so the first query
of every bucket size dispatches a prebuilt executable — zero traces, zero
backend compiles (the invariant tests/test_coldstart.py pins per layout).
The contract has three edges to know about:

* AOT covers the EXECUTE jit only.  The session's eager helper ops (query
  padding, result slicing, the warm-path reductions) still compile lazily
  per novel batch size; ``precompile(warm=True)`` — and the server prewarm,
  which submits one warm batch per bucket — flushes those for exact bucket
  sizes.  An odd-sized batch therefore pays a tiny one-off pad/sum compile
  on first sight even on a fully prewarmed server; the post-warmup compile
  counter treats any such hot-path compile as an anomaly worth flagging,
  not an error.
* The ladder survives delta updates by construction: ``plan_delta`` freezes
  the GridSpec and capacity bucket (incremental-binning rules below), so
  the AOT signature stays valid.  A full re-plan (fresh spec or capacity
  crossing) invalidates every installed executable; the session drops them
  and ``stats['aot_buckets']`` falls to 0 rather than serve a stale shape.
* Compiled-ladder entries are written through the persistent compilation
  cache when ``repro.runtime.compile_cache.enable`` ran first, so a
  restarted process — or a fleet host sharing ``AIDW_CACHE_DIR`` —
  deserializes the ladder instead of recompiling it.  Background prewarm
  additionally compiles under
  ``compile_cache.background_compile_options()`` (single-split CPU
  codegen) on a thread niced to the scheduler floor, keeping the
  seconds-long compile phase off the serving hot path; the server flips an
  internal event (``_prewarm_compiled``) at the compile→warm phase
  boundary so observers can tell expensive compilation apart from the
  ordinary queued warm batches that follow it.

Sharding rules (mesh-parallel serving; see :func:`shard_plan`):

The per-query pass is embarrassingly parallel, so one plan can serve a whole
mesh.  A :class:`ShardedAidwPlan` places the plan for a mesh in one of two
layouts:

* ``replicated`` (default) — the CSR :class:`~repro.core.grid.CellTable`,
  ``points_xy`` and ``values`` are REPLICATED on every device; queries are
  partitioned over ALL mesh axes and each device runs :func:`_execute_core`
  on its local shard inside ``shard_map``.  Because no per-query reduction
  crosses the query axis, each lane computes exactly what the single-device
  path computes for its queries: warm sharded results are BIT-IDENTICAL per
  query to the single-device session on the same plan.  The bucketed-padding
  and donation contracts above apply unchanged — the global bucket must be
  divisible by the query-axis device product (the session rounds per-device).
* ``ring`` — for datasets too large to replicate, data points are sharded
  into blocks along a ring axis and both stages rotate the blocks via
  collective-permute (:func:`repro.core.distributed.make_ring_aidw`).  The
  ring path does brute-force kNN over rotating blocks, so results match the
  grid path only to accumulation-order tolerance (~1e-5 f32), never bitwise
  — and Stage 1 costs O(m) candidate distances per query, the exact
  brute-force pattern the paper's grid search exists to beat.
* ``grid_ring`` — the grid-AWARE ring (PR 5; the default for
  ``layout='auto'`` at ring scale): the same O(m/P)-per-device data
  decomposition, but the even grid itself is partitioned into per-device
  row slabs (:class:`repro.core.slab.SlabPartition`: per-slab CSR
  ``CellTable`` + a halo ring of boundary cells) and the rotating block
  ships its slab's cell table, so Stage 1 evaluates only O(window)
  candidates per query from the expanding search window
  (:func:`repro.core.distributed.make_grid_ring_aidw`).  Per-slab top-k
  results k-way merge into the running neighbour heap; results carry the
  grid path's certification story: d2/r_obs/alpha BIT-IDENTICAL to the
  replicated layout for queries whose certified window closes inside one
  slab (incl. its halo), interpolated values within ~1e-5 f32 accumulation
  tolerance (Stage 2 sums slab partials in rotation order; the Stage-2
  tile shape follows the padded query bucket, so values may additionally
  vary ~1 ulp across batch compositions — Stage-1 outputs never do).

Stage-2 mode rules (``AidwConfig.stage2``; see ``repro.core.aidw``):

``'naive'``/``'tiled'`` (alias ``'global'``) evaluate Eq. (1) over ALL data
points — jnp-blocked or Pallas-tiled.  ``'local'`` truncates Eq. (1) to the
k merged Stage-1 neighbours: ``r_obs``/``alpha`` are bit-identical to global
mode by construction (Stage 1 is untouched), values differ by the truncated
far-field tail, and per-query work drops from O(m) to O(k)
(``fused=True`` weights the gathered neighbours in one Pallas kernel —
bit-identical to the unfused jnp top-k path eagerly and compiled on a TPU
v5e, within 1 ulp under jit on the CPU, where XLA contracts the jnp path's
mul+add).  In the ``grid_ring`` layout
local mode also drops the whole Stage-2 ring rotation — O(window + k) per
query end-to-end.

Incremental-binning rules (:func:`plan_delta` / ``session.update(deltas=...)``):

A delta update (inserts + deletes) reuses the existing ``GridSpec`` — cell
width, rows and cols are FROZEN so array shapes, the compiled executables and
Eq. (2)'s study area all survive — and patches the CSR table in
O(Δ log Δ + m memcpy + n_cells) via :func:`repro.core.grid.rebin_delta`
instead of the full O(m log m) re-sort.  A delta update falls back to a full
re-plan (fresh spec, full :func:`~repro.core.grid.bin_points`) when the
incremental result would be invalid or degraded: any insert landing outside
the planned grid's bounding box (it would be clamped to a border cell), or a
delta larger than ``max_delta_frac`` of the dataset (grid density drifts off
Eq. (2)).  ``n_points`` is TRACED in every layout, and :func:`plan` /
:func:`plan_delta` capacity-pad the plan arrays to
:data:`PLAN_PAD_MULTIPLE`-sized buckets (sentinel coordinates contribute
exactly zero weight), so dataset-resizing churn retraces NOTHING while the
point count stays inside one capacity bucket; crossing a bucket boundary
retraces once per new capacity, not once per new count.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import aidw as A
from . import grid as G
from . import knn as K
from ..obs import fence, span
from .distributed import auto_axes


@dataclass(frozen=True)
class AidwConfig:
    k: int = 15
    alphas: tuple = A.DEFAULT_ALPHAS
    r_min: float = A.DEFAULT_R_MIN
    r_max: float = A.DEFAULT_R_MAX
    cell_factor: float = 1.0       # scales Eq.(2) cell width (1.0 = paper)
    max_level: int | None = None   # None = auto from density (knn.auto_max_level)
    window: int = 256
    exact: bool = True             # certified 2-pass kNN (False = paper heuristic)
    knn_block: int = 4096
    interp_block: int = 1024
    interp_data_block: int = 0     # chunk Stage-2 data axis (0 = whole dataset)
    stage2: Literal["naive", "tiled", "local", "global"] = "naive"
    fused: bool = False            # tiled/local: alpha-in-kernel single launch
    tile_q: int = 256              # Pallas query-block
    tile_d: int = 512              # Pallas data-block
    interpret: bool | None = None  # None: compiled on TPU, interpreted elsewhere

    def __post_init__(self):
        # 'global' is the documented alias for the default all-points Eq. (1)
        # path; normalize it at construction so config-keyed executor caches
        # (and jit static args) see ONE canonical spelling.
        if self.stage2 == "global":
            object.__setattr__(self, "stage2", "naive")


@dataclass
class AidwResult:
    values: jax.Array              # (n,) predictions
    alpha: jax.Array               # (n,) adaptive power parameter
    r_obs: jax.Array               # (n,) observed mean NN distance
    overflow: int = 0              # queries whose candidate window overflowed
    timings: dict = field(default_factory=dict)   # stage -> seconds
    overflow_mask: jax.Array | None = None        # (n,) bool per-query flag
    # overflow_mask lets batch owners (the serving coalescer) attribute
    # overflowed queries to the request that contributed them; ``overflow``
    # stays the batch-level sum for one-shot callers.
    zero_weight_mask: jax.Array | None = None     # (n,) bool: sum(w) underflow
    # zero_weight_mask flags queries whose every f32 weight underflowed to
    # zero; their ``values`` entry is the 0.0 sentinel, never NaN (see
    # repro.core.aidw.guarded_values).


@dataclass(frozen=True)
class AidwPlan:
    """Reusable Stage-1 build: everything that depends only on the dataset.

    ``spec``/``cfg``/``area`` are static (hashable) and safe as jit static
    args; ``n_points`` is the TRUE point count and rides through the
    executors as a traced scalar (churn never retraces);
    ``table``/``points_xy``/``values`` are device-resident arrays reused —
    never donated — across queries, capacity-padded to
    :data:`PLAN_PAD_MULTIPLE` buckets by :func:`pad_plan` (rows beyond
    ``n_points`` hold sentinel coordinates whose Stage-2 weight is exactly
    zero and which no CSR cell range ever addresses).
    """

    spec: G.GridSpec
    table: G.CellTable | None      # None only for unbinned (ring-only) plans
    points_xy: jax.Array           # (cap, 2); rows [n_points:] are sentinels
    values: jax.Array              # (cap,)
    n_points: int
    area: float
    cfg: AidwConfig


# Plan arrays pad to this capacity multiple: small dataset churn keeps every
# array shape (and therefore every compiled executable) stable.  Matches the
# grid_ring slab packet's pad multiple (repro.core.slab.device_tables).
PLAN_PAD_MULTIPLE = 64


def pad_plan(pln: AidwPlan, multiple: int = PLAN_PAD_MULTIPLE) -> AidwPlan:
    """Capacity-pad a plan's point arrays to a ``multiple``-sized bucket.

    Padded point rows carry :data:`repro.core.aidw.PAD_SENTINEL` coordinates:
    their squared distance to any real query overflows f32 to inf, so their
    Eq. (1) weight is exactly 0.0 and no result bit changes.  Padded CSR tail
    slots sit beyond ``cell_start[-1]`` and are never addressed by a cell
    range.  ``n_points`` keeps the TRUE count (Eq. (2) and the kNN count
    floor read it, not the array shape).
    """
    m = pln.n_points
    cap = -(-max(m, 1) // multiple) * multiple
    pad = cap - pln.points_xy.shape[0]
    if pad == 0:
        return pln
    if pad < 0:
        raise ValueError(f"plan arrays ({pln.points_xy.shape[0]}) exceed "
                         f"capacity bucket {cap} for n_points={m}")
    big = jnp.float32(A.PAD_SENTINEL)
    points_xy = jnp.pad(pln.points_xy, ((0, pad), (0, 0)),
                        constant_values=big)
    values = jnp.pad(pln.values, (0, pad))
    table = pln.table
    if table is not None:
        tpad = cap - table.sx.shape[0]
        table = G.CellTable(
            sx=jnp.pad(table.sx, (0, tpad), constant_values=big),
            sy=jnp.pad(table.sy, (0, tpad), constant_values=big),
            sz=jnp.pad(table.sz, (0, tpad)),
            cell_start=table.cell_start,
            order=jnp.pad(table.order, (0, tpad)),
        )
    return AidwPlan(spec=pln.spec, table=table, points_xy=points_xy,
                    values=values, n_points=m, area=pln.area, cfg=pln.cfg)


def plan_host_points(pln: AidwPlan) -> np.ndarray:
    """The TRUE (n_points, 3) dataset from a (possibly capacity-padded) plan."""
    return np.concatenate(
        [np.asarray(pln.points_xy)[:pln.n_points],
         np.asarray(pln.values)[:pln.n_points, None]], axis=1)


@dataclass(frozen=True)
class ShardedAidwPlan:
    """An :class:`AidwPlan` placed on a mesh (module docstring, 'Sharding
    rules').  ``replicated``: plan arrays replicated, queries partitioned over
    all mesh axes, per-lane bit-identity with the single-device path.
    ``ring``: ``ring_points`` holds the (padded, (m_pad, 3)) dataset sharded
    along ``ring_axis``; execution rotates blocks via collective-permute.
    ``grid_ring``: ``slab_part`` holds the host-side
    :class:`repro.core.slab.SlabPartition` (per-slab CSR tables + delta
    bookkeeping) and ``slab_arrays`` its device placement (stacked packet
    sharded along ``ring_axis``, kept resident and delta-PATCHED by
    ``staging`` — a :class:`SlabStaging`); ``rps``/``halo``/``max_level``
    are the static slab geometry the executor is compiled against.
    """

    base: AidwPlan
    mesh: Mesh
    layout: Literal["replicated", "ring", "grid_ring"] = "replicated"
    ring_axis: str | None = None
    ring_points: jax.Array | None = None
    slab_part: object | None = None
    slab_arrays: dict | None = None
    rps: int | None = None
    halo: int | None = None
    max_level: int | None = None
    staging: object | None = None   # SlabStaging (grid_ring layout only)

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)


class SlabStaging:
    """Per-slab donation-aliased device staging for the grid_ring packet.

    The device-side half of the LSM ingest tier (``repro.core.slab`` module
    docstring): where the old path re-uploaded the whole stacked packet on
    every delta (O(m) memcpy + transfer), this keeps the packet resident
    and patches ONLY what a :class:`repro.core.slab.DeltaReport` names —

    * ``csr_rows``  -> one padded slab row per array
      (``lax.dynamic_update_slice`` at the slab index, old buffer DONATED
      so XLA aliases the update in place; O(touched-slab rows) bytes);
    * ``dead``      -> an O(Δ) element scatter of tombstone sentinels into
      the slab's sorted coordinates (and the matching owned-block slots) —
      the CSR offsets are byte-stable under tombstone deletes;
    * ``ring_rows`` -> one ``ring_cap``-slot hot-ring row per touched slab.

    Capacities are STICKY (grow-only): a delta that would overflow the
    current caps falls back to :meth:`full_stage` once, establishing new
    caps that every later delta patches against — so sustained churn
    converges to pure O(Δ + touched-slab) staging.  Scatter index vectors
    are bucketed to powers of two (duplicating the first index, which
    rewrites the same sentinel — a no-op) so the patch executables retrace
    per bucket, not per delta size.  Donation is disabled on CPU (no
    buffer aliasing there; XLA would warn on every patch).

    Telemetry (read by ``session.stats``): ``staged_bytes`` (host bytes
    shipped by the LAST stage call), ``staged_bytes_total``,
    ``slabs_touched`` (last call), ``full_restages``.
    """

    def __init__(self, mesh: Mesh, ring_axis: str):
        self.mesh = mesh
        self.ring_axis = ring_axis
        self.arrays: dict = {}
        self.cap = 0
        self.cap2 = 0
        self.staged_bytes = 0
        self.staged_bytes_total = 0
        self.slabs_touched = 0
        self.full_restages = 0
        self._donate = jax.default_backend() != "cpu"
        self._fns: dict = {}

    def _sharding(self, ndim: int) -> NamedSharding:
        spec = PartitionSpec(self.ring_axis) if ndim == 1 \
            else PartitionSpec(self.ring_axis, None)
        return NamedSharding(self.mesh, spec)

    def _row_fn(self, shape, dtype):
        """Jitted single-row patcher for a (P, width) packet array."""
        key = ("row", shape, dtype)
        fn = self._fns.get(key)
        if fn is None:
            def patch(dst, row, s):
                return jax.lax.dynamic_update_slice(
                    dst, row[None], (s, jnp.int32(0)))
            fn = jax.jit(patch, out_shardings=self._sharding(2),
                         donate_argnums=(0,) if self._donate else ())
            self._fns[key] = fn
        return fn

    def _scatter_fn(self, shape, dtype, n_idx):
        """Jitted element scatter into row ``s`` of a (P, width) array."""
        key = ("scatter", shape, dtype, n_idx)
        fn = self._fns.get(key)
        if fn is None:
            def patch(dst, s, idx, val):
                return dst.at[s, idx].set(val)
            fn = jax.jit(patch, out_shardings=self._sharding(2),
                         donate_argnums=(0,) if self._donate else ())
            self._fns[key] = fn
        return fn

    def _patch_row(self, name: str, s: int, row: np.ndarray) -> int:
        dst = self.arrays[name]
        fn = self._row_fn(dst.shape, dst.dtype)
        self.arrays[name] = fn(dst, jnp.asarray(row), jnp.int32(s))
        return row.nbytes

    def _patch_slots(self, name: str, s: int, idx: np.ndarray,
                     val: float) -> int:
        dst = self.arrays[name]
        # power-of-two index bucket: duplicates rewrite the same sentinel
        n = int(idx.size)
        bucket = 1 << max(n - 1, 0).bit_length()
        padded = np.empty(bucket, np.int32)
        padded[:n] = idx
        padded[n:] = idx[0]
        fn = self._scatter_fn(dst.shape, dst.dtype, bucket)
        self.arrays[name] = fn(dst, jnp.int32(s), jnp.asarray(padded),
                               jnp.asarray(val, dst.dtype))
        return padded.nbytes + np.dtype(dst.dtype).itemsize

    def full_stage(self, part) -> dict:
        """Upload the whole stacked packet (build / cap-overflow path)."""
        host = part.device_tables(PLAN_PAD_MULTIPLE, cap_floor=self.cap,
                                  cap2_floor=self.cap2)
        self.cap = host["sx"].shape[1]
        self.cap2 = host["bx"].shape[1]
        nbytes = 0
        out = {}
        for name, arr in host.items():
            out[name] = jax.device_put(jnp.asarray(arr),
                                       self._sharding(arr.ndim))
            nbytes += arr.nbytes
        self.arrays = out
        self.full_restages += 1
        self.staged_bytes = nbytes
        self.staged_bytes_total += nbytes
        self.slabs_touched = part.p
        return out

    def delta_stage(self, part, rep) -> dict:
        """Patch the resident packet per a DeltaReport (O(Δ + touched)).

        Falls back to one :meth:`full_stage` when a restaged slab no
        longer fits the sticky capacities.  Fills ``rep.staged_bytes``.
        """
        if not self.arrays:
            out = self.full_stage(part)
            rep.staged_bytes = self.staged_bytes
            return out
        rows = {}
        for s in sorted(rep.csr_rows):
            row = part.slab_host_rows(s, self.cap, self.cap2)
            if row is None:                      # sticky caps overflowed
                out = self.full_stage(part)
                rep.staged_bytes = self.staged_bytes
                return out
            rows[s] = row
        nbytes = 0
        for s, row in rows.items():
            for name in ("sx", "sy", "sz", "cell_start", "bx", "by", "bz"):
                nbytes += self._patch_row(name, s, row[name])
        tomb = np.float32(G.TOMBSTONE_COORD)
        for s, slots in rep.dead.items():
            if s in rows:
                continue                         # full-row restage covers it
            slots = np.asarray(slots, np.int32)
            if not slots.size:
                continue
            nbytes += self._patch_slots("sx", s, slots, tomb)
            nbytes += self._patch_slots("sy", s, slots, tomb)
            nbytes += self._patch_slots("sz", s, slots, np.float32(0.0))
            bpos = np.asarray(part.owned_positions(s, slots), np.int32)
            if bpos.size:
                nbytes += self._patch_slots("bx", s, bpos, tomb)
                nbytes += self._patch_slots("by", s, bpos, tomb)
                nbytes += self._patch_slots("bz", s, bpos, np.float32(0.0))
        for s in sorted(rep.ring_rows):
            row = part.ring_host_row(s)
            for name in ("rx", "ry", "rz"):
                nbytes += self._patch_row(name, s, row[name])
        self.staged_bytes = nbytes
        self.staged_bytes_total += nbytes
        self.slabs_touched = len(
            set(rep.csr_rows) | set(rep.ring_rows) | set(rep.dead))
        rep.staged_bytes = nbytes
        return dict(self.arrays)


def shard_plan(pln: AidwPlan, mesh: Mesh,
               layout: Literal["auto", "replicated", "ring",
                               "grid_ring"] = "auto",
               *, ring_axis: str | None = None,
               ring_threshold: int = 4_000_000,
               ring_cap: int = 256,
               host_points=None) -> ShardedAidwPlan:
    """Place a plan on ``mesh``: replicate the CSR table + point arrays, or
    slab-shard the points when ``m`` is large (``layout='auto'`` picks
    ``grid_ring`` at ``n_points >= ring_threshold`` — the grid-aware ring
    dominates the brute-force ``ring``, which is kept as the merge
    baseline).  ``host_points`` optionally supplies the (m, 3) dataset as a
    host array for the slab partitioner, avoiding a device pull."""
    mesh = auto_axes(mesh)
    if layout == "auto":
        layout = "grid_ring" if pln.n_points >= ring_threshold \
            else "replicated"
    if layout == "replicated":
        rep = NamedSharding(mesh, PartitionSpec())
        pln = AidwPlan(
            spec=pln.spec, table=jax.device_put(pln.table, rep),
            points_xy=jax.device_put(pln.points_xy, rep),
            values=jax.device_put(pln.values, rep),
            n_points=pln.n_points, area=pln.area, cfg=pln.cfg)
        return ShardedAidwPlan(base=pln, mesh=mesh, layout="replicated")
    ring_axis = ring_axis or mesh.axis_names[0]
    if layout == "grid_ring":
        from . import knn as K
        from .slab import SlabPartition

        cfg = pln.cfg
        max_level = cfg.max_level if cfg.max_level is not None \
            else K.auto_max_level(pln.spec, pln.n_points, cfg.k)
        if host_points is None:
            host_points = plan_host_points(pln)
        part = SlabPartition.build(pln.spec, host_points,
                                   int(mesh.shape[ring_axis]),
                                   halo=max_level, ring_cap=ring_cap)
        staging = SlabStaging(mesh, ring_axis)
        return ShardedAidwPlan(
            base=pln, mesh=mesh, layout="grid_ring", ring_axis=ring_axis,
            slab_part=part, slab_arrays=staging.full_stage(part),
            rps=part.rps, halo=part.halo, max_level=max_level,
            staging=staging)
    from .distributed import pad_to_multiple

    # pad to a CAPACITY bucket (64 rows per ring device), not just to the
    # device count: like the other layouts, churn that stays inside the
    # bucket keeps the ring executor's shapes (and its compiled trace) stable
    pts = pad_to_multiple(
        jnp.concatenate([pln.points_xy[:pln.n_points],
                         pln.values[:pln.n_points, None]], axis=1),
        PLAN_PAD_MULTIPLE * int(mesh.shape[ring_axis]))
    pts = jax.device_put(
        pts, NamedSharding(mesh, PartitionSpec(ring_axis, None)))
    return ShardedAidwPlan(base=pln, mesh=mesh, layout="ring",
                           ring_axis=ring_axis, ring_points=pts)


def grid_ring_plan_delta(splan: ShardedAidwPlan, new_base: AidwPlan,
                         inserts=None, deletes=None):
    """Incrementally re-place a ``grid_ring`` plan after a dataset delta.

    The shard-aware half of the session's incremental update: the delta is
    routed to the OWNING slabs' host state only
    (:meth:`repro.core.slab.SlabPartition.apply_delta` — LSM-tiered:
    inserts land in hot rings, CSR deletes tombstone in place; untouched
    slabs keep their host arrays and cached ownership masks), and the
    resident device packet is PATCHED per the returned
    :class:`~repro.core.slab.DeltaReport` by :class:`SlabStaging` —
    O(Δ + touched-slab) staged bytes instead of the former O(m) whole-
    packet re-upload.  The grid spec / slab geometry / compiled executor
    all survive.  ``new_base`` is the updated base plan from
    :func:`plan_delta` (same spec by construction).

    Returns ``(new_splan, delta_report)``; the report carries the ingest
    telemetry (``staged_bytes``, spill/compaction flags) the session
    surfaces through ``stats``.
    """
    if splan.layout != "grid_ring" or splan.slab_part is None:
        raise ValueError("grid_ring_plan_delta needs a grid_ring plan")
    if new_base.spec != splan.base.spec:
        raise ValueError("delta re-placement requires an unchanged GridSpec")
    rep = splan.slab_part.apply_delta(inserts=inserts, deletes=deletes)
    staging = splan.staging or SlabStaging(splan.mesh, splan.ring_axis)
    arrays = staging.delta_stage(splan.slab_part, rep)
    return ShardedAidwPlan(
        base=new_base, mesh=splan.mesh, layout="grid_ring",
        ring_axis=splan.ring_axis, slab_part=splan.slab_part,
        slab_arrays=arrays, rps=splan.rps, halo=splan.halo,
        max_level=splan.max_level, staging=staging), rep


def grid_ring_plan_compact(splan: ShardedAidwPlan):
    """Fold every hot ring into its slab CSRs (the background compaction
    epoch) and patch the device packet.  The logical dataset is unchanged
    (``base`` survives) — only WHERE points are searched moves, after
    which the partition is element-identical to a fresh build and warm
    queries are bitwise a fresh session's.  Returns
    ``(new_splan, delta_report)``."""
    if splan.layout != "grid_ring" or splan.slab_part is None:
        raise ValueError("grid_ring_plan_compact needs a grid_ring plan")
    rep = splan.slab_part.compact()
    staging = splan.staging or SlabStaging(splan.mesh, splan.ring_axis)
    arrays = staging.delta_stage(splan.slab_part, rep)
    return ShardedAidwPlan(
        base=splan.base, mesh=splan.mesh, layout="grid_ring",
        ring_axis=splan.ring_axis, slab_part=splan.slab_part,
        slab_arrays=arrays, rps=splan.rps, halo=splan.halo,
        max_level=splan.max_level, staging=staging), rep


def _study_area(spec: G.GridSpec) -> float:
    return (spec.n_cols * spec.cell_width) * (spec.n_rows * spec.cell_width)


# Python-invocation counter for the execute body: under jit this increments at
# TRACE time only, so a stable count across repeated calls proves the
# compilation cache was hit (see tests/test_session.py).
_EXECUTE_TRACES = [0]


def execute_traces() -> int:
    """How many times the execute body has been (re)traced or run eagerly."""
    return _EXECUTE_TRACES[0]


def plan(points_xyz, cfg: AidwConfig = AidwConfig(), *,
         query_domain=None, bin: bool = True,
         timings: dict | None = None) -> AidwPlan:
    """One-time Stage-1 build: grid planning + CSR binning for a dataset.

    ``query_domain`` optionally extends the grid's bounding box to cover
    queries that lie outside the data points' hull (pass the query array, or
    any (n, 2) sample of the expected query region).  Queries outside the
    planned grid are clamped to the border cells; their kNN is still correct
    whenever the expansion level covers the true neighbours, and the
    per-query ``overflow`` flag reports when it could not be certified.

    ``bin=False`` skips the CSR build (``table=None``) for consumers that
    only need the spec/area/point arrays — the ring layout's brute-force
    executor never reads the table, and for the dataset sizes ring targets
    the full sort is exactly the cost to avoid.

    ``timings`` (optional dict) receives ``bin_s`` — the fenced wall of the
    CSR build alone — so the session's ``plan`` span can attribute its
    ``bin`` sub-span honestly (the fence costs one device sync on a path
    that is already eager and host-dominated).
    """
    points_xyz = jnp.asarray(points_xyz)
    px, py, pz = points_xyz[:, 0], points_xyz[:, 1], points_xyz[:, 2]
    qd = None if query_domain is None else np.asarray(query_domain)
    spec = G.plan_grid(np.asarray(points_xyz[:, :2]), qd,
                       cell_factor=cfg.cell_factor)
    if bin:
        tb = time.perf_counter()
        table = G.bin_points(spec, px, py, pz)
        if timings is not None:
            jax.block_until_ready(table)
            timings["bin_s"] = time.perf_counter() - tb
    else:
        table = None
    return pad_plan(AidwPlan(
        spec=spec, table=table, points_xy=points_xyz[:, :2],
        values=pz, n_points=points_xyz.shape[0],
        area=_study_area(spec), cfg=cfg))


def _stage1(spec: G.GridSpec, cfg: AidwConfig, table: G.CellTable, queries_xy):
    block = min(cfg.knn_block, max(queries_xy.shape[0], 1))
    res = K.grid_knn(spec, table, queries_xy, cfg.k, cfg.max_level,
                     cfg.window, block, cfg.exact)
    return res, K.mean_nn_distance(res.d2)


def _stage2(queries_xy, points_xy, values, alpha, cfg: AidwConfig):
    """Global Eq. (1): returns ``(values, zero_weight_mask)``."""
    if cfg.stage2 == "tiled":
        from repro.kernels.aidw import ops as aidw_ops

        return aidw_ops.tiled_interpolate(
            queries_xy, points_xy, values, alpha,
            tile_q=cfg.tile_q, tile_d=cfg.tile_d, interpret=cfg.interpret,
        )
    swz, sw = A.weighted_partial_sums(queries_xy, points_xy, values, alpha,
                                      cfg.interp_block, cfg.interp_data_block)
    return A.guarded_values(swz, sw)


def _stage2_fused(queries_xy, points_xy, values, r_obs, n_points, area,
                  cfg: AidwConfig):
    """Alpha-in-kernel Stage 2: Eqs. (2)/(4)/(5)/(6) + Eq. (1) in ONE launch.

    Returns ``(values, zero_weight_mask)``; ``n_points``/``area`` ride
    through as traced scalars."""
    from repro.kernels.aidw import ops as aidw_ops

    return aidw_ops.fused_stage2(
        queries_xy, points_xy, values, r_obs,
        n_points=jnp.float32(n_points), area=jnp.float32(area),
        alphas=tuple(cfg.alphas), r_min=cfg.r_min, r_max=cfg.r_max,
        tile_q=cfg.tile_q, tile_d=cfg.tile_d, interpret=cfg.interpret,
    )


def _stage2_local(knn_res: K.KnnResult, values, r_obs, alpha, n_points, area,
                  cfg: AidwConfig):
    """Local (exact-k) Eq. (1) over the merged Stage-1 neighbours.

    ``fused=True`` launches the Pallas weighting kernel at the session's
    alpha over the gathered ``values[idx]`` (sequential weighting in ONE
    launch); otherwise the jnp top-k path runs
    :func:`repro.core.aidw.topk_weighted_partial_sums`.  Both return
    ``(values, zero_weight_mask)``; eagerly they are bit-identical
    (sequential k-axis accumulation — tests/test_kernels.py), and so they
    were compiled on a TPU v5e (1,024 queries at m = 1M, ``chip_smoke.py``);
    under jit on the CPU, XLA's FMA contraction on the jnp path can shift
    values by 1 ulp.
    The alpha-in-kernel variant
    (:func:`repro.kernels.aidw.ops.fused_local_stage2`) stays kernel-layer
    only: recomputing Eqs. (2)-(6) inside the interpreter and outside jit
    can differ from the compiled host chain by ~1 ulp, which would break
    the session's fused==unfused bitwise contract.
    """
    if cfg.fused:
        from repro.kernels.aidw import ops as aidw_ops

        return aidw_ops.local_interpolate(
            knn_res.d2, knn_res.idx, values, alpha,
            tile_q=cfg.tile_q, interpret=cfg.interpret,
        )
    z = values[knn_res.idx]
    swz, sw = A.topk_weighted_partial_sums(knn_res.d2, z, alpha)
    return A.guarded_values(swz, sw)


def _execute_core(spec: G.GridSpec, cfg: AidwConfig, area: float,
                  table: G.CellTable, points_xy, values, queries_xy,
                  n_points):
    """Stage 1 + Stage 2 over a prebuilt plan (jit-safe; spec/cfg/area
    static, ``n_points`` TRACED so churn never retraces).  Returns
    ``(values, alpha, r_obs, overflow_mask, zero_weight_mask)``."""
    _EXECUTE_TRACES[0] += 1
    n_points = jnp.float32(n_points)
    with jax.named_scope(A.STAGE1_SCOPE):
        res, r_obs = _stage1(spec, cfg, table, queries_xy)
    with jax.named_scope(A.STAGE2_SCOPE):
        alpha = A.adaptive_alpha(r_obs, n_points, area, alphas=cfg.alphas,
                                 r_min=cfg.r_min, r_max=cfg.r_max)
        if cfg.stage2 == "local":
            out, zero = _stage2_local(res, values, r_obs, alpha, n_points,
                                      area, cfg)
        elif cfg.fused and cfg.stage2 == "tiled":
            out, zero = _stage2_fused(queries_xy, points_xy, values, r_obs,
                                      n_points, area, cfg)
        else:
            out, zero = _stage2(queries_xy, points_xy, values, alpha, cfg)
    return out, alpha, r_obs, res.overflow, zero


# The session entry points: one compiled executable per (spec, cfg, area,
# array shapes) — n_points is traced (argnum 7), so dataset churn inside one
# capacity bucket reuses the executable.  Bucketed query padding makes the
# shape key coarse, so repeated odd-sized batches all hit the same
# executable.  The donating variant gives up the padded query buffer
# (argnums 6) — see the module docstring's donation rules.
_session_execute = jax.jit(_execute_core, static_argnums=(0, 1, 2))
_session_execute_donate = jax.jit(_execute_core, static_argnums=(0, 1, 2),
                                  donate_argnums=(6,))


# Mesh-parallel session entry points: one jitted shard_map wrapper per
# (mesh, donate).  Queries are partitioned over ALL mesh axes; the plan
# arrays are replicated (in_specs P()); every per-query output shards back
# over the same axes.  Per-lane the body IS _execute_core, so warm sharded
# queries are bit-identical per query to the single-device path (module
# docstring, 'Sharding rules').
_SHARDED_EXECUTE_CACHE: dict = {}


def sharded_session_execute(mesh: Mesh, donate: bool = False):
    """The ``shard_map``-wrapped :data:`_session_execute` for ``mesh``."""
    mesh = auto_axes(mesh)
    key = (mesh, bool(donate))
    fn = _SHARDED_EXECUTE_CACHE.get(key)
    if fn is None:
        axes = tuple(mesh.axis_names)

        def run(spec, cfg, area, table, points_xy, values, queries_xy,
                n_points):
            body = jax.shard_map(
                partial(_execute_core, spec, cfg, area),
                mesh=mesh,
                in_specs=(PartitionSpec(), PartitionSpec(), PartitionSpec(),
                          PartitionSpec(axes, None), PartitionSpec()),
                out_specs=PartitionSpec(axes),
            )
            return body(table, points_xy, values, queries_xy,
                        jnp.float32(n_points))

        fn = jax.jit(run, static_argnums=(0, 1, 2),
                     donate_argnums=(6,) if donate else ())
        _SHARDED_EXECUTE_CACHE[key] = fn
    return fn


_RING_EXECUTE_CACHE: dict = {}


def ring_session_execute(mesh: Mesh, ring_axis: str, cfg: AidwConfig):
    """The ring-rotation executor for a ``layout='ring'`` sharded plan.

    Returns ``fn(points_xyz_padded, queries_xy, n_points, area) ->
    (values, alpha, r_obs, zero_weight_mask)``; brute-force ring kNN, so
    ~1e-5 of the grid path, never bitwise (module docstring, 'Sharding
    rules').  ``cfg.stage2='local'`` skips the Stage-2 interpolation
    rotation and weights the k merged neighbours directly."""
    from .distributed import make_ring_aidw

    key = (mesh, ring_axis, cfg.k, tuple(cfg.alphas), cfg.r_min, cfg.r_max,
           cfg.stage2 == "local")
    fn = _RING_EXECUTE_CACHE.get(key)
    if fn is None:
        fn = make_ring_aidw(mesh, ring_axis, k=cfg.k, alphas=cfg.alphas,
                            r_min=cfg.r_min, r_max=cfg.r_max,
                            stage2_local=cfg.stage2 == "local",
                            return_stats=True)
        _RING_EXECUTE_CACHE[key] = fn
    return fn


_GRID_RING_EXECUTE_CACHE: dict = {}


def grid_ring_session_execute(mesh: Mesh, ring_axis: str, cfg: AidwConfig,
                              spec: G.GridSpec, rps: int, halo: int,
                              max_level: int):
    """The grid-aware ring executor for a ``layout='grid_ring'`` plan.

    Returns ``fn(sx, sy, sz, cell_start, row_lo, bx, by, bz, rx, ry, rz,
    queries, n_points, area) -> (values, alpha, r_obs, overflow,
    n_candidates, zero_weight_mask)`` — see
    :func:`repro.core.distributed.make_grid_ring_aidw`.  Cached per
    (mesh, ring_axis, cfg, slab geometry): a delta update that keeps the
    spec reuses the compiled executable, and because ``n_points`` is traced
    a delta that RESIZES the dataset reuses it too.
    ``cfg.stage2='local'`` drops the Stage-2 block rotation entirely —
    values come straight from the merged (d2, z) neighbour carry.
    """
    key = (mesh, ring_axis, cfg, spec, rps, halo, max_level)
    fn = _GRID_RING_EXECUTE_CACHE.get(key)
    if fn is None:
        from .distributed import make_grid_ring_aidw

        fn = make_grid_ring_aidw(
            mesh, ring_axis, spec=spec, rps=rps, halo=halo,
            max_level=max_level, k=cfg.k, window=cfg.window,
            knn_block=cfg.knn_block, alphas=cfg.alphas, r_min=cfg.r_min,
            r_max=cfg.r_max, stage2_local=cfg.stage2 == "local",
            return_stats=True)
        _GRID_RING_EXECUTE_CACHE[key] = fn
    return fn


# Fleet-partitioning shard executes (repro.serving.cluster.fleet): a shard
# host answers Stage 1 (its shard's kNN distances AND neighbour values — the
# per-shard top-k heap the client k-way merges) and Stage 2 partial sums (at
# the client-merged alpha) as two separate passes over ITS plan — never a
# full interpolation.  In local Stage-2 mode the merged (d2, z) heap alone
# finishes the query client-side and the partial-sum pass is skipped.


def _shard_knn_core(spec: G.GridSpec, cfg: AidwConfig, table: G.CellTable,
                    values, queries_xy):
    with jax.named_scope(A.STAGE1_SCOPE):
        res, _ = _stage1(spec, cfg, table, queries_xy)
        return res.d2, values[res.idx], res.overflow


def _shard_partial_core(cfg: AidwConfig, points_xy, values, queries_xy,
                        alpha):
    with jax.named_scope(A.STAGE2_SCOPE):
        return A.weighted_partial_sums(queries_xy, points_xy, values, alpha,
                                       cfg.interp_block,
                                       cfg.interp_data_block)


_shard_knn_execute = jax.jit(_shard_knn_core, static_argnums=(0, 1))
_shard_partial_execute = jax.jit(_shard_partial_core, static_argnums=(0,))


def plan_delta(pln: AidwPlan, inserts=None, deletes=None, *,
               max_delta_frac: float = 0.25, host_points=None,
               report: dict | None = None, clock=time.perf_counter):
    """Incrementally apply an (inserts, deletes) delta to a plan.

    Returns ``(new_plan, updated_points_xyz)``.  ``new_plan`` keeps the
    existing ``GridSpec`` and patches the CSR table via
    :func:`repro.core.grid.rebin_delta`; it is ``None`` when the delta must
    fall back to a full re-plan (out-of-bbox insert, or
    ``len(delta) > max_delta_frac * m`` — module docstring,
    'Incremental-binning rules'), in which case the caller re-plans from the
    returned updated dataset.

    ``host_points`` optionally supplies the current (m, 3) dataset as a host
    array (the session keeps one as a mirror), avoiding the full
    device-to-host pull of ``points_xy``/``values`` that the reconstruction
    otherwise costs on accelerator backends.

    Two profiler spans split the work: ``ingest.merge`` (the dataset
    reconstruction and the CSR merge, including the pull of the table from
    the device) and ``ingest.stage`` (padding and upload of the new plan,
    closed after a fence on its arrays, so the upload is paid here and not
    by the first query after the update).  ``report`` (optional dict)
    receives ``pulled_bytes`` (device-to-host), ``staged_bytes``
    (host-to-device; 0 on the fallback) and, when a plan was staged,
    ``stage`` = ``(start, end)`` of the staging on ``clock``.
    """
    ins = None if inserts is None else np.asarray(inserts)
    dels = None if deletes is None else np.asarray(deletes, dtype=np.int64)
    n_ins = 0 if ins is None else ins.shape[0]
    n_del = 0 if dels is None else dels.shape[0]
    if n_del and (dels.min() < 0 or dels.max() >= pln.n_points):
        # reject before any fancy indexing: negative indices would silently
        # wrap on the unbinned (ring) path that never reaches rebin_delta
        raise IndexError(f"delete index out of range [0, {pln.n_points})")
    report = {} if report is None else report
    report.update(pulled_bytes=0, staged_bytes=0)

    with span("ingest.merge"):
        # reconstruct the updated dataset in original order (kept + appended)
        if host_points is not None:
            old = np.asarray(host_points)
        else:
            old = plan_host_points(pln)
            report["pulled_bytes"] += _device_bytes(
                (pln.points_xy, pln.values))
        keep = np.ones(pln.n_points, bool)
        if n_del:
            keep[dels] = False
        parts = [old[keep]]
        if n_ins:
            parts.append(ins.astype(old.dtype, copy=False))
        new_pts = np.concatenate(parts, axis=0) if len(parts) > 1 \
            else parts[0]

        spec = pln.spec
        hi_x = spec.min_x + spec.n_cols * spec.cell_width
        hi_y = spec.min_y + spec.n_rows * spec.cell_width
        in_bbox = n_ins == 0 or bool(
            (ins[:, 0] >= spec.min_x).all() and
            (ins[:, 1] >= spec.min_y).all() and
            (ins[:, 0] <= hi_x).all() and (ins[:, 1] <= hi_y).all())
        if not in_bbox \
                or n_ins + n_del > max_delta_frac * max(pln.n_points, 1):
            return None, new_pts

        # unbinned (ring-layout) plans skip the CSR patch — nothing reads it;
        # rebin_delta pulls every table array to the host and merges there
        table = None
        if pln.table is not None:
            report["pulled_bytes"] += _device_bytes(pln.table)
            table = G.rebin_delta(spec, pln.table, inserts=ins, deletes=dels)
    with span("ingest.stage"):
        t0 = clock()
        new_plan = pad_plan(AidwPlan(
            spec=spec, table=table,
            points_xy=jnp.asarray(new_pts[:, :2]),
            values=jnp.asarray(new_pts[:, 2]),
            n_points=new_pts.shape[0], area=pln.area, cfg=pln.cfg))
        staged = (new_plan.points_xy, new_plan.values, new_plan.table)
        fence(staged)
        report["stage"] = (t0, clock())
    report["staged_bytes"] = _device_bytes(staged)
    return new_plan, new_pts


def _device_bytes(tree) -> int:
    """Bytes of the device arrays in ``tree`` (host arrays count 0)."""
    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree)
               if isinstance(a, jax.Array))


def execute(pln: AidwPlan, queries_xy, *, timings: bool = False) -> AidwResult:
    """Per-query pass over a prebuilt :class:`AidwPlan` (eager staging).

    For the jitted, shape-bucketed, donation-aware path use
    :class:`repro.core.session.InterpolationSession`.
    """
    queries_xy = jnp.asarray(queries_xy)
    cfg = pln.cfg
    n_points = jnp.float32(pln.n_points)  # same op chain as the traced path

    t0 = time.perf_counter()
    res, r_obs = _stage1(pln.spec, cfg, pln.table, queries_xy)
    if timings:
        r_obs.block_until_ready()
    t1 = time.perf_counter()

    alpha = A.adaptive_alpha(r_obs, n_points, pln.area, alphas=cfg.alphas,
                             r_min=cfg.r_min, r_max=cfg.r_max)
    if cfg.stage2 == "local":
        values, zero = _stage2_local(res, pln.values, r_obs, alpha,
                                     n_points, pln.area, cfg)
    elif cfg.fused and cfg.stage2 == "tiled":
        values, zero = _stage2_fused(queries_xy, pln.points_xy, pln.values,
                                     r_obs, n_points, pln.area, cfg)
    else:
        values, zero = _stage2(queries_xy, pln.points_xy, pln.values, alpha,
                               cfg)
    if timings:
        values.block_until_ready()
    t2 = time.perf_counter()

    return AidwResult(
        values=values, alpha=alpha, r_obs=r_obs,
        overflow=int(jnp.sum(res.overflow)),
        timings={"knn": t1 - t0, "interp": t2 - t1} if timings else {},
        overflow_mask=res.overflow,
        zero_weight_mask=zero,
    )


def aidw_improved(points_xyz, queries_xy, cfg: AidwConfig = AidwConfig(),
                  *, timings: bool = False) -> AidwResult:
    """The paper's improved algorithm: grid kNN -> adaptive alpha -> Eq. (1).

    One-shot convenience: plans (grid build + binning) on EVERY call.  For
    repeated queries over a static dataset build the plan once — see
    :func:`plan`/:func:`execute` and ``repro.core.session``.
    """
    t0 = time.perf_counter()
    pln = plan(points_xyz, cfg, query_domain=np.asarray(queries_xy))
    res = execute(pln, queries_xy, timings=timings)
    if timings:
        # keep the historical split: 'knn' covers plan+bin+Stage-1
        res.timings["plan"] = time.perf_counter() - t0 \
            - res.timings["knn"] - res.timings["interp"]
        res.timings["knn"] += res.timings["plan"]
    return res


def aidw_original(points_xyz, queries_xy, cfg: AidwConfig = AidwConfig(),
                  *, timings: bool = False) -> AidwResult:
    """The Mei et al. (2015) baseline: brute-force global kNN + same Stage 2."""
    points_xyz = jnp.asarray(points_xyz)
    queries_xy = jnp.asarray(queries_xy)

    t0 = time.perf_counter()
    d2, _ = K.brute_knn(points_xyz[:, :2], queries_xy, cfg.k, cfg.knn_block)
    r_obs = K.mean_nn_distance(d2)
    if timings:
        r_obs.block_until_ready()
    t1 = time.perf_counter()

    spec = G.plan_grid(np.asarray(points_xyz[:, :2]), np.asarray(queries_xy),
                       cell_factor=cfg.cell_factor)
    alpha = A.adaptive_alpha(r_obs, points_xyz.shape[0], _study_area(spec),
                             alphas=cfg.alphas, r_min=cfg.r_min, r_max=cfg.r_max)
    values, zero = _stage2(queries_xy, points_xyz[:, :2], points_xyz[:, 2],
                           alpha, cfg)
    if timings:
        values.block_until_ready()
    t2 = time.perf_counter()

    return AidwResult(
        values=values, alpha=alpha, r_obs=r_obs,
        timings={"knn": t1 - t0, "interp": t2 - t1} if timings else {},
        zero_weight_mask=zero,
    )


def idw_standard(points_xyz, queries_xy, alpha: float = 2.0,
                 cfg: AidwConfig = AidwConfig()) -> jax.Array:
    """Shepard (1968): constant user-specified power parameter."""
    points_xyz = jnp.asarray(points_xyz)
    queries_xy = jnp.asarray(queries_xy)
    return _stage2(queries_xy, points_xyz[:, :2], points_xyz[:, 2],
                   jnp.full((queries_xy.shape[0],), alpha,
                            points_xyz.dtype), cfg)[0]
