"""InterpolationSession — amortized AIDW serving over a static dataset.

The paper's improved algorithm already factors into a one-time grid build
(Stage 1 substrate) and a per-query kNN + weighting pass, but the one-shot
:func:`repro.core.pipeline.aidw_improved` pays the build on every call.  For
the serving workload (heavy repeated query traffic, mostly-static data) this
session keeps the build resident and makes the per-query path cheap:

* ``plan once``   — grid planning + CSR binning run at construction (and on
  :meth:`update`), never per query.  The plan's arrays stay device-resident.
* ``bucketed jit`` — query batches are padded to power-of-two buckets, so a
  stream of odd-sized batches compiles ONE executable per bucket instead of
  one per distinct size.  Padding uses the batch's last query (edge mode):
  per-query results are independent, so the slice ``[:n]`` is bit-identical
  to an unpadded call (pipeline module docstring, 'Padding rules').
* ``donation``    — the padded query buffer is donated to the executable on
  backends that support it (not CPU), saving one allocation per batch.
  Plan arrays are never donated ('Donation rules').
* ``fused Stage 2`` — with ``AidwConfig(stage2='tiled', fused=True)`` the
  adaptive-alpha determination runs inside the Pallas weighting kernel: one
  launch for the whole Stage 2.  ``stage2='local'`` instead truncates
  Eq. (1) to the k merged Stage-1 neighbours (O(k) per query, identical
  r_obs/alpha, values within the documented far-field-tail tolerance;
  ``fused=True`` weights the gathered neighbours in one Pallas
  launch).  Every layout supports it; ``grid_ring`` additionally
  drops its whole Stage-2 ring rotation.
* ``mesh``        — with ``mesh=``, one session serves queries across every
  device of the mesh ('Sharding rules'): the plan is placed once via
  :func:`repro.core.pipeline.shard_plan` (CSR table + points replicated;
  ``layout='ring'`` brute-force ring-shards the points when the dataset is
  too large to replicate; ``layout='grid_ring'`` ring-shards them behind
  per-slab CSR tables with a boundary-cell halo, keeping the paper's
  O(window) Stage-1 cost at O(m/P) memory) and each query batch is
  partitioned over all mesh axes.  Buckets are rounded per-device
  (power-of-two PER LANE times the device product), and replicated-layout
  results stay bit-identical per query to the single-device session on
  the same plan.
* ``AOT ladder``  — :meth:`precompile` lowers + compiles the whole
  power-of-two bucket ladder ahead of time via
  ``jax.jit(...).lower().compile()`` and stores the resulting ``Compiled``
  executables; :meth:`_run` dispatches to them directly, bypassing jit
  tracing AND the XLA compile layer entirely, so the first query of every
  precompiled bucket is a warm query.  ``warm=True`` additionally executes
  each ladder bucket once (exact bucket size) to warm the tiny eager
  helper ops around the executable (pad/slice/sum).  Stored executables
  carry a staleness signature (spec, cfg, shapes); a full re-plan clears
  them and falls back to the lazy jit path until the next
  :meth:`precompile`.
* ``delta update`` — ``update(inserts=..., deletes=...)`` (or
  ``deltas=(inserts, deletes)``) patches the resident CSR table in
  O(Δ log Δ + memcpy) via :func:`repro.core.grid.rebin_delta` instead of
  re-binning from scratch, keeping the grid spec and every compiled
  executable alive ('Incremental-binning rules'; falls back to a full
  re-plan on out-of-bbox inserts or oversized deltas).

``stats`` exposes the amortization counters the tests assert on:
``stage1_builds`` (full plan/update invocations), ``delta_updates``
(incremental updates that did NOT rebuild Stage 1), ``batches``/``queries``
served, ``bucket_hits``/``bucket_misses`` (compile-cache behaviour),
``devices`` (mesh width; 1 for a single-device session), and ``n_points``
(current dataset size — the serving scheduler keys its execute-time model
on it, and cluster telemetry reports it per host).

Observability (``repro.obs``): the session records its stage walls into a
:class:`repro.obs.Registry` (``session/plan_s`` with ``session/bin_s`` and
``session/staging_s`` sub-parts, ``session/compact_s``, and — when timing
or profiling a query — ``session/query_s`` / ``session/stage1_s`` /
``session/stage2_s``), and, when constructed with a ``tracer``, emits the
matching ``plan``/``bin``/``staging``/``compact``/``query``/``stage1``/
``stage2`` spans.  ``stats["last_plan_s"]`` and
``res.timings["query"]`` are kept as documented ALIASES of the newest
registry observation so pre-PR-8 consumers keep working.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from . import pipeline as P
from ..obs import Registry

__all__ = ["InterpolationSession", "bucket_size"]


def bucket_size(n: int, min_bucket: int = 64) -> int:
    """Smallest power-of-two >= n, floored at ``min_bucket``.

    ``min_bucket`` is rounded UP to a power of two first, so every returned
    bucket is a true power of two even for e.g. ``min_bucket=48`` (doubling
    from a non-power floor would yield 96, 192, ... and silently break the
    one-executable-per-bucket compile-cache story).
    """
    if n <= 0:
        raise ValueError(f"query batch must be non-empty, got n={n}")
    b = 1
    while b < min_bucket:
        b *= 2
    while b < n:
        b *= 2
    return b


class InterpolationSession:
    """Reusable AIDW query session over one (mostly static) dataset.

    >>> sess = InterpolationSession(points_xyz)
    >>> out = sess.query(queries_xy)          # jitted Stage-1 + Stage-2
    >>> out2 = sess.query(more_queries_xy)    # same bucket -> zero retrace
    >>> sess.update(new_points_xyz)           # re-bin once, keep executables
    >>> sess.update(inserts=new_rows, deletes=[3, 17])   # incremental re-bin

    With ``mesh=`` the same API serves the whole mesh: queries are sharded
    over every mesh axis and (replicated layout) results are bit-identical
    per query to the single-device session.
    """

    def __init__(self, points_xyz, cfg: P.AidwConfig = P.AidwConfig(), *,
                 query_domain=None, min_bucket: int = 64,
                 donate: bool | None = None, mesh=None,
                 layout: str = "replicated", ring_axis: str | None = None,
                 max_delta_frac: float = 0.25, ring_cap: int = 256,
                 tracer=None, registry: Registry | None = None):
        self.cfg = cfg
        # observability: the registry is always on (a record is a few dict
        # updates); spans only when a tracer is injected AND its sampler
        # admits the operation's trace
        self.tracer = tracer
        self.registry = registry if registry is not None else Registry()
        self.min_bucket = int(min_bucket)
        self._query_domain = query_domain
        self._mesh = mesh
        self._ring_cap = int(ring_cap)
        if mesh is not None and layout not in ("replicated", "ring",
                                               "grid_ring"):
            # no 'auto' here: the query path dispatches on the layout, so it
            # must be pinned before the first plan is placed
            raise ValueError(f"layout must be 'replicated', 'ring' or "
                             f"'grid_ring', got {layout!r}")
        self._layout = layout if mesh is not None else "single"
        self._ring_axis = ring_axis
        self._n_dev = int(mesh.devices.size) if mesh is not None else 1
        self.max_delta_frac = float(max_delta_frac)
        # CPU XLA cannot donate buffers; donating there only emits warnings.
        self._donate = (jax.default_backend() != "cpu") if donate is None \
            else bool(donate)
        self.stats = {"stage1_builds": 0, "delta_updates": 0, "batches": 0,
                      "queries": 0, "bucket_hits": 0, "bucket_misses": 0,
                      "last_plan_s": 0.0, "devices": self._n_dev,
                      "n_points": 0,
                      # ingest telemetry (flat int/float keys so the serving
                      # report's scalar filter forwards them; grid_ring
                      # fills them from SlabStaging/SlabPartition, other
                      # layouts report their honest full-restage bytes)
                      "staged_bytes": 0, "staged_bytes_total": 0,
                      "slabs_touched": 0, "full_restages": 0,
                      "ring_occupancy": 0.0, "ring_points": 0,
                      "tombstone_frac": 0.0, "compactions": 0,
                      "spilled_updates": 0,
                      # cold-start telemetry: distinct buckets with a live
                      # AOT executable (precompile) — 0 on lazy sessions
                      "aot_buckets": 0}
        self._seen_buckets: set[int] = set()
        # AOT bucket ladder: (bucket, donate) -> (Compiled, signature).
        # Entries whose signature no longer matches the resident plan are
        # ignored by _run (and cleared wholesale on full re-plans).
        self._aot: dict[tuple[int, bool], tuple] = {}
        self._plan: P.AidwPlan | None = None
        self._splan: P.ShardedAidwPlan | None = None
        # grid_ring only: per-query Stage-1 candidate counts of the LAST
        # batch (device array) — the measured O(window) evidence the ring
        # benchmark / analytic census read
        self.last_stage1_candidates = None
        # host-side (m, 3) mirror of the dataset: delta updates reconstruct
        # from it instead of pulling the plan arrays off the device
        self._host_pts = None
        self.update(points_xyz)

    # -- dataset lifecycle ---------------------------------------------------

    @property
    def plan(self) -> P.AidwPlan:
        return self._plan

    @property
    def sharded_plan(self) -> P.ShardedAidwPlan | None:
        return self._splan

    def _place(self) -> None:
        """(Re)place the current plan on the mesh (no-op single-device)."""
        if self._mesh is None:
            return
        self._splan = P.shard_plan(self._plan, self._mesh, self._layout,
                                   ring_axis=self._ring_axis,
                                   ring_cap=self._ring_cap,
                                   host_points=self._host_pts)
        if self._splan.layout == "replicated":
            self._plan = self._splan.base   # replicated arrays serve both
        self._refresh_ingest_stats()

    def _refresh_ingest_stats(self, rep=None) -> None:
        """Pull the ingest-path counters into the flat ``stats`` dict."""
        sp = self._splan
        if sp is None or sp.layout != "grid_ring" or sp.staging is None:
            return
        st, part = sp.staging, sp.slab_part
        self.stats["staged_bytes"] = int(st.staged_bytes)
        self.stats["staged_bytes_total"] = int(st.staged_bytes_total)
        self.stats["slabs_touched"] = int(st.slabs_touched)
        self.stats["full_restages"] = int(st.full_restages)
        self.stats["ring_occupancy"] = float(part.ring_occupancy())
        self.stats["ring_points"] = int(part.ring_size())
        self.stats["tombstone_frac"] = float(part.tombstone_frac())
        self.stats["compactions"] = int(part.compactions)
        if rep is not None and rep.spilled:
            self.stats["spilled_updates"] += 1
        # registry mirror (fleet merge modes match cluster/telemetry.py:
        # byte/point totals are additive across hosts, occupancy/tombstone
        # ratios are high-waters)
        reg = self.registry
        reg.set("ingest/staged_bytes", self.stats["staged_bytes"],
                merge="sum")
        reg.set("ingest/staged_bytes_total",
                self.stats["staged_bytes_total"], merge="sum")
        reg.set("ingest/ring_points", self.stats["ring_points"], merge="sum")
        reg.set("ingest/compactions", self.stats["compactions"], merge="sum")
        reg.set("ingest/ring_occupancy", self.stats["ring_occupancy"],
                merge="max")
        reg.set("ingest/tombstone_frac", self.stats["tombstone_frac"],
                merge="max")

    def compact(self) -> None:
        """Background compaction epoch: fold every hot ring into the slab
        CSRs and purge tombstones (``repro.core.slab`` LSM contract).  The
        logical dataset is unchanged; after this, warm grid_ring queries
        are bitwise a fresh session's.  No-op on other layouts (their
        updates restage eagerly — there is nothing to fold)."""
        if self._layout != "grid_ring" or self._splan is None:
            return
        clk = self.tracer.clock if self.tracer is not None \
            else time.perf_counter
        tid = self.tracer.new_trace() if self.tracer is not None else None
        t0 = clk()
        self._splan, rep = P.grid_ring_plan_compact(self._splan)
        # fence: the compaction wall covers the restage, not its dispatch
        jax.block_until_ready(self._splan.slab_arrays)
        # compaction may regrow slab capacities; stale AOT executables are
        # shape-specialized, so drop them (signature check would skip them
        # anyway — clearing keeps the compiled_buckets gauge honest)
        self._aot_invalidate()
        t1 = clk()
        self.registry.observe("session/compact_s", t1 - t0)
        if tid is not None:
            self.tracer.record("compact", t0, t1, trace_id=tid)
        self._refresh_ingest_stats(rep)

    def update(self, points_xyz=None, *, inserts=None, deletes=None,
               deltas=None) -> None:
        """Dataset refresh.

        Full (``points_xyz``): re-plan + re-bin once; compiled executables
        are keyed on (GridSpec, cfg, shapes) and survive whenever those
        match.  Incremental (``inserts``/``deletes``/``deltas``): patch the
        CSR table in place, keeping the grid spec and ALL executables; falls
        back to a full re-plan per the pipeline's incremental-binning rules.
        """
        if deltas is not None:
            inserts, deletes = deltas
        has_delta = inserts is not None or deletes is not None
        if points_xyz is not None and has_delta:
            raise ValueError(
                "pass either a full dataset or inserts/deletes, not both")
        if points_xyz is None and not has_delta:
            raise ValueError(
                "update() needs a full dataset or inserts/deletes")
        clk = self.tracer.clock if self.tracer is not None \
            else time.perf_counter
        tid = self.tracer.new_trace() if self.tracer is not None else None
        t0 = clk()
        bin_t: dict = {}        # pipeline fills 'bin_s' on full re-plans
        t_stage = None          # (start, end) of the device staging sub-span
        if points_xyz is None and self._plan is not None:
            new_plan, new_pts = P.plan_delta(
                self._plan, inserts, deletes,
                max_delta_frac=self.max_delta_frac,
                host_points=self._host_pts)
            self._host_pts = new_pts
            if new_plan is not None:
                self._plan = new_plan
                ts0 = clk()
                if self._layout == "grid_ring" and self._splan is not None:
                    # shard-aware LSM delta: inserts land in the owning
                    # slabs' hot rings, deletes tombstone CSR slots in
                    # place, and the resident device packet is PATCHED
                    # per the delta report (O(Δ + touched-slab) staged
                    # bytes) — spec, slab geometry and compiled executor
                    # all survive
                    self._splan, rep = P.grid_ring_plan_delta(
                        self._splan, new_plan, inserts, deletes)
                    # fence: the staging wall must cover the upload, not
                    # just its dispatch (obs clock/fencing contract)
                    jax.block_until_ready(self._splan.slab_arrays)
                    t_stage = (ts0, clk())
                    self._refresh_ingest_stats(rep)
                else:
                    self._place()
                    t_stage = (ts0, clk())
                    nb = int(new_plan.points_xy.nbytes
                             + new_plan.values.nbytes)
                    if new_plan.table is not None:
                        nb += sum(int(np.asarray(a).nbytes)
                                  for a in new_plan.table)
                    # honest O(m) restage accounting for non-LSM layouts
                    self.stats["staged_bytes"] = nb
                    self.stats["staged_bytes_total"] += nb
                self.stats["delta_updates"] += 1
                self.stats["n_points"] = int(new_plan.n_points)
                self._finish_update(t0, clk, tid, bin_t, t_stage)
                return
            points_xyz = new_pts        # fallback: full re-plan below
        elif points_xyz is None:
            raise ValueError("first update needs the full dataset")
        else:
            self._host_pts = np.asarray(points_xyz)
        # the ring executors never read the global CSR table; skip the full
        # sort (grid_ring builds PER-SLAB tables in shard_plan instead)
        self._plan = P.plan(points_xyz, self.cfg,
                            query_domain=self._query_domain,
                            bin=self._layout in ("single", "replicated"),
                            timings=bin_t)
        if self._mesh is not None:
            ts0 = clk()
            self._place()
            t_stage = (ts0, clk())
        else:
            self._place()
        self.stats["stage1_builds"] += 1
        self.stats["n_points"] = int(self._plan.n_points)
        # full re-plan: spec/area/capacity may all have moved — every AOT
        # executable is specialized on them, so the ladder must recompile
        self._aot_invalidate()
        self._finish_update(t0, clk, tid, bin_t, t_stage)

    def _finish_update(self, t0, clk, tid, bin_t, t_stage) -> None:
        """Close out one :meth:`update`: registry stage walls, the
        ``stats["last_plan_s"]`` alias, and (sampled) plan/bin/staging
        spans."""
        t1 = clk()
        dur = t1 - t0
        self.registry.observe("session/plan_s", dur)
        if bin_t.get("bin_s"):
            self.registry.observe("session/bin_s", bin_t["bin_s"])
        if t_stage is not None:
            self.registry.observe("session/staging_s",
                                  t_stage[1] - t_stage[0])
        # documented alias of the newest session/plan_s observation
        self.stats["last_plan_s"] = dur
        if tid is not None:
            root = self.tracer.record("plan", t0, t1, trace_id=tid)
            if bin_t.get("bin_s"):
                # the CSR build runs at the head of plan(); anchor it there
                self.tracer.record("bin", t0, t0 + bin_t["bin_s"],
                                   trace_id=tid, parent_id=root)
            if t_stage is not None:
                self.tracer.record("staging", t_stage[0], t_stage[1],
                                   trace_id=tid, parent_id=root)

    # -- query path ----------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        """Pure bucket math (no hit/miss accounting): the padded batch size
        a batch of ``n`` queries dispatches at under this session's mesh."""
        if self._n_dev == 1:
            return bucket_size(n, self.min_bucket)
        # power-of-two per lane, divisible by the device product globally
        per = -(-n // self._n_dev)
        return bucket_size(per, max(1, self.min_bucket // self._n_dev)) \
            * self._n_dev

    def _bucket(self, n: int) -> int:
        b = self._bucket_for(n)
        if b in self._seen_buckets:
            self.stats["bucket_hits"] += 1
        else:
            self._seen_buckets.add(b)
            self.stats["bucket_misses"] += 1
        return b

    # -- AOT bucket ladder ---------------------------------------------------

    def bucket_ladder(self, max_queries: int) -> list[int]:
        """Every bucket the session can dispatch for batches up to
        ``max_queries``: doubling powers of two (times the device product on
        a mesh) from the minimum bucket up to ``_bucket_for(max_queries)``."""
        top = self._bucket_for(int(max_queries))
        b = self._bucket_for(1)
        out = [b]
        while b < top:
            b *= 2
            out.append(b)
        return out

    def _aot_signature(self) -> tuple:
        """Staleness witness for stored ``Compiled`` executables: the static
        jit arguments plus the shapes/dtypes of every captured plan array.
        An AOT entry is only dispatched while its signature matches the
        resident plan — delta updates preserve it (n_points is traced),
        full re-plans and capacity-bucket moves change it."""
        pln = self._plan
        if self._layout == "grid_ring":
            sp = self._splan
            arr = sp.slab_arrays
            return ("grid_ring", pln.spec, pln.cfg, sp.rps, sp.halo,
                    sp.max_level,
                    tuple((k, arr[k].shape, str(arr[k].dtype))
                          for k in sorted(arr)))
        if self._layout == "ring":
            sp = self._splan
            return ("ring", pln.cfg, tuple(sp.ring_points.shape))
        table_sig = tuple((tuple(a.shape), str(a.dtype))
                          for a in jax.tree_util.tree_leaves(pln.table))
        return (self._layout, pln.spec, pln.cfg, pln.area, table_sig,
                tuple(pln.points_xy.shape), tuple(pln.values.shape))

    def _aot_invalidate(self) -> None:
        self._aot.clear()
        self.stats["aot_buckets"] = 0
        self.registry.set("compiled_buckets", 0, merge="max")

    def _lower(self, qp, donate: bool):
        """Lower the active layout's executor for one padded bucket; the
        caller ``.compile()``s the result.  Static arguments are baked into
        the lowering — the stored ``Compiled`` is called with the DYNAMIC
        arguments only (mirrors the jit call in :meth:`_run`)."""
        pln = self._plan
        if self._layout == "grid_ring":
            sp = self._splan
            fn = P.grid_ring_session_execute(
                sp.mesh, sp.ring_axis, pln.cfg, pln.spec, sp.rps, sp.halo,
                sp.max_level)
            arr = sp.slab_arrays
            return fn.lower(
                arr["sx"], arr["sy"], arr["sz"], arr["cell_start"],
                arr["row_lo"], arr["bx"], arr["by"], arr["bz"],
                arr["rx"], arr["ry"], arr["rz"], qp,
                jnp.float32(pln.n_points), jnp.float32(pln.area))
        if self._layout == "ring":
            sp = self._splan
            fn = P.ring_session_execute(sp.mesh, sp.ring_axis, pln.cfg)
            return fn.lower(sp.ring_points, qp, jnp.float32(pln.n_points),
                            jnp.float32(pln.area))
        if self._mesh is not None:
            fn = P.sharded_session_execute(self._mesh, donate)
        else:
            fn = P._session_execute_donate if donate else P._session_execute
        return fn.lower(pln.spec, pln.cfg, pln.area,
                        pln.table, pln.points_xy, pln.values, qp,
                        pln.n_points)

    def precompile(self, max_queries: int | None = None, buckets=None,
                   warm: bool = False,
                   compiler_options: dict | None = None) -> list[int]:
        """Ahead-of-time compile the bucket ladder for the ACTIVE layout.

        Lowers + compiles every (query-bucket × current-capacity-bucket)
        executable via ``jit(...).lower().compile()`` and stores the
        ``Compiled`` objects; subsequent :meth:`query` calls of those
        buckets dispatch straight to them — no trace, no XLA compile, warm
        from the first hit.  Pass ``max_queries=`` to cover the doubling
        ladder up to that batch size (:meth:`bucket_ladder`) or an explicit
        ``buckets=`` iterable (each entry is rounded to its bucket).  Donate
        variants are compiled alongside when the backend donates.

        ``warm=True`` additionally EXECUTES each bucket once on dummy
        queries (exact bucket size, results discarded) so the tiny eager
        helper ops around the executable — pad/slice/sum — are compiled
        too; leave it False when another thread owns device execution (the
        async server routes its warm batches through the worker instead).
        ``compiler_options`` pass through to ``Lowered.compile`` — the
        server's background prewarm uses
        :func:`repro.runtime.compile_cache.background_compile_options` to
        keep CPU codegen off the serving cores (options are part of the
        persistent-cache key; see that function's docstring).

        Each compile wall lands in the ``session/compile_s`` histogram; the
        ``compiled_buckets`` gauge and ``stats["aot_buckets"]`` track the
        distinct buckets with a live executable.  Returns the sorted bucket
        list covered by this call."""
        if buckets is None:
            if max_queries is None:
                raise ValueError(
                    "precompile() needs max_queries= or buckets=")
            buckets = self.bucket_ladder(max_queries)
        buckets = sorted({self._bucket_for(int(b)) for b in buckets})
        sig = self._aot_signature()
        donates = (False, True) \
            if (self._donate and self._layout in ("single", "replicated")) \
            else (False,)
        for b in buckets:
            qp = jnp.zeros((b, 2), jnp.float32)
            for dn in donates:
                ent = self._aot.get((b, dn))
                if ent is not None and ent[1] == sig:
                    continue
                t0 = time.perf_counter()
                self._aot[(b, dn)] = (
                    self._lower(qp, dn).compile(
                        compiler_options=compiler_options), sig)
                self.registry.observe("session/compile_s",
                                      time.perf_counter() - t0)
            # precompiled buckets are warm by construction, not misses
            self._seen_buckets.add(b)
        live = {b for (b, _d), (_c, s) in self._aot.items() if s == sig}
        self.stats["aot_buckets"] = len(live)
        self.registry.set("compiled_buckets", len(live), merge="max")
        if warm:
            for b in buckets:
                self.query(np.tile(np.asarray(self._host_pts[0, :2],
                                              dtype=np.float32), (b, 1)))
        return buckets

    def _run(self, qp, donate: bool):
        """Dispatch one padded bucket to the right executable.

        An AOT entry from :meth:`precompile` whose staleness signature still
        matches the resident plan wins (no trace, no compile layer); every
        other case falls back to the lazy jit path.  Every branch returns
        the same 5-tuple:
        ``(values, alpha, r_obs, overflow_mask, zero_weight_mask)``."""
        pln = self._plan
        dn = bool(donate) if self._layout in ("single", "replicated") \
            else False
        ent = self._aot.get((int(qp.shape[0]), dn))
        aot = ent[0] if ent is not None \
            and ent[1] == self._aot_signature() else None
        if self._layout == "grid_ring":
            sp = self._splan
            fn = aot if aot is not None else P.grid_ring_session_execute(
                sp.mesh, sp.ring_axis, pln.cfg, pln.spec, sp.rps, sp.halo,
                sp.max_level)
            arr = sp.slab_arrays
            values, alpha, r_obs, overflow, cand, zero = fn(
                arr["sx"], arr["sy"], arr["sz"], arr["cell_start"],
                arr["row_lo"], arr["bx"], arr["by"], arr["bz"],
                arr["rx"], arr["ry"], arr["rz"], qp,
                jnp.float32(pln.n_points), jnp.float32(pln.area))
            # Stage-1 candidate counts (device array; no sync here — the
            # benchmark census reads it after the batch materializes)
            self.last_stage1_candidates = cand
            return values, alpha, r_obs, overflow, zero
        if self._layout == "ring":
            sp = self._splan
            fn = aot if aot is not None \
                else P.ring_session_execute(sp.mesh, sp.ring_axis, pln.cfg)
            values, alpha, r_obs, zero = fn(
                sp.ring_points, qp, jnp.float32(pln.n_points),
                jnp.float32(pln.area))
            return values, alpha, r_obs, jnp.zeros(qp.shape[0], bool), zero
        if aot is not None:
            # statics (spec, cfg, area) were baked in at lower time
            return aot(pln.table, pln.points_xy, pln.values, qp,
                       pln.n_points)
        if self._mesh is not None:
            fn = P.sharded_session_execute(self._mesh, donate)
        else:
            fn = P._session_execute_donate if donate else P._session_execute
        return fn(pln.spec, pln.cfg, pln.area,
                  pln.table, pln.points_xy, pln.values, qp, pln.n_points)

    def knn(self, queries_xy):
        """Stage 1 only: (d2 (n, k) ascending, neighbour VALUES z (n, k),
        overflow mask) against THIS session's dataset — a shard host's
        local top-k heap for the serving fleet's client-side k-way merge
        (``repro.serving.cluster.fleet.ShardedAidwCluster``; local Stage-2
        mode finishes the query from the merged (d2, z) heap alone).
        Needs a binned plan (single-device or replicated layout)."""
        if self._plan.table is None:
            raise ValueError(
                "shard kNN needs a binned plan (single/replicated layout)")
        q = jnp.asarray(queries_xy)
        n = q.shape[0]
        b = self._bucket(n)
        qp = jnp.pad(q, ((0, b - n), (0, 0)), mode="edge") if b != n else q
        d2, z, ovf = P._shard_knn_execute(
            self._plan.spec, self._plan.cfg, self._plan.table,
            self._plan.values, qp)
        return d2[:n], z[:n], ovf[:n]

    def partial_interpolate(self, queries_xy, alpha):
        """Stage-2 partial sums (sum w*z, sum w) of Eq. (1) over THIS
        session's dataset at a caller-supplied per-query ``alpha`` — the
        fleet sums these across shards before the one global division."""
        q = jnp.asarray(queries_xy)
        a = jnp.asarray(alpha)
        n = q.shape[0]
        b = self._bucket(n)
        if b != n:
            q = jnp.pad(q, ((0, b - n), (0, 0)), mode="edge")
            a = jnp.pad(a, (0, b - n), mode="edge")
        swz, sw = P._shard_partial_execute(
            self._plan.cfg, self._plan.points_xy, self._plan.values, q, a)
        return swz[:n], sw[:n]

    def query(self, queries_xy, *, timings: bool = False,
              profile: bool = False) -> P.AidwResult:
        """Interpolate one query batch; (single-device and replicated-mesh
        layouts) results are bit-identical to a cold
        :func:`repro.core.pipeline.execute` on the same plan.

        ``timings=True`` fences the result and reports
        ``res.timings={"query": wall_s, "bucket": b}`` (the ``query`` key
        is the documented alias of the ``session/query_s`` registry
        histogram, which records the same wall).  ``profile=True`` instead
        runs Stage 1 and Stage 2 as two separately-jitted, individually
        FENCED launches and adds ``stage1``/``stage2`` walls to
        ``res.timings`` (recorded into ``session/stage1_s`` /
        ``session/stage2_s``) — honest per-stage attribution at the cost
        of losing cross-stage XLA fusion, so ``stage1 + stage2`` may
        exceed the fused path's ``query`` wall; needs a binned plan
        (single/replicated layout).
        """
        # a host batch is uploaded into a buffer this call owns
        owned = not isinstance(queries_xy, jax.Array)
        q = jnp.asarray(queries_xy)
        n = q.shape[0]
        b = self._bucket(n)
        clk = self.tracer.clock if self.tracer is not None \
            else time.perf_counter
        t0 = clk()
        qp = jnp.pad(q, ((0, b - n), (0, 0)), mode="edge") if b != n else q
        if profile:
            res = self._query_profiled(qp, n, b, clk, t0)
        else:
            # donate only a buffer this call created (the upload or the
            # padded copy) — never the caller's device array (donation
            # rules in the pipeline module docstring)
            values, alpha, r_obs, overflow, zero = self._run(
                qp, self._donate and (owned or qp is not q))
            res = P.AidwResult(
                values=values[:n], alpha=alpha[:n], r_obs=r_obs[:n],
                overflow=int(jnp.sum(overflow[:n])),
                overflow_mask=overflow[:n],
                zero_weight_mask=zero[:n],
            )
            if timings:
                res.values.block_until_ready()
                dur = clk() - t0
                self.registry.observe("session/query_s", dur)
                res.timings = {"query": dur, "bucket": b}
        self.stats["batches"] += 1
        self.stats["queries"] += n
        return res

    def _query_profiled(self, qp, n: int, b: int, clk, t0) -> P.AidwResult:
        """Stage-split query: two jitted launches, each fenced, so the
        per-stage walls are honest (obs fencing contract); emits
        stage1/stage2 spans under one sampled ``query`` root."""
        pln = self._plan
        if pln.table is None:
            raise ValueError(
                "profile=True needs a binned plan (single/replicated "
                "layout)")
        d2, idx, cand, ovf, r_obs = P._stage1_profile_execute(
            pln.spec, pln.cfg, pln.table, qp)
        jax.block_until_ready((d2, idx, cand, ovf, r_obs))
        t1 = clk()
        values, alpha, r_obs, overflow, zero = P._stage2_profile_execute(
            pln.cfg, pln.points_xy, pln.values, qp, d2, idx, cand, ovf,
            r_obs, jnp.float32(pln.n_points), jnp.float32(pln.area))
        jax.block_until_ready(values)
        t2 = clk()
        res = P.AidwResult(
            values=values[:n], alpha=alpha[:n], r_obs=r_obs[:n],
            overflow=int(jnp.sum(overflow[:n])),
            overflow_mask=overflow[:n],
            zero_weight_mask=zero[:n],
        )
        self.registry.observe("session/stage1_s", t1 - t0)
        self.registry.observe("session/stage2_s", t2 - t1)
        self.registry.observe("session/query_s", t2 - t0)
        res.timings = {"query": t2 - t0, "stage1": t1 - t0,
                       "stage2": t2 - t1, "bucket": b}
        if self.tracer is not None:
            tid = self.tracer.new_trace()
            if tid is not None:
                root = self.tracer.record("query", t0, t2, trace_id=tid)
                self.tracer.record("stage1", t0, t1, trace_id=tid,
                                   parent_id=root)
                self.tracer.record("stage2", t1, t2, trace_id=tid,
                                   parent_id=root)
        return res
