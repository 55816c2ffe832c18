"""AsyncAidwServer — the online drive mode of the AIDW serving subsystem.

Turns the session-backed engine into a real server: clients ``submit()``
query batches (optionally deadline-bound) from any thread; ONE background
worker thread drains the bounded :class:`repro.serving.queue.AdmissionQueue`,
forms deadline-aware microbatches with the shared
:class:`repro.serving.scheduler.DeadlineCoalescer`, and executes them on the
resident :class:`repro.core.session.InterpolationSession` — so all device
work stays single-threaded (JAX dispatch is not re-entered concurrently)
while admission and result pickup are fully concurrent.

Write-path integration: ``update_dataset(inserts=/deletes=)`` enqueues a
barrier op into the SAME admission queue the query requests flow through.
The worker applies it between batches, in FIFO order with the queries around
it — churn is serialized with query execution on one thread, so an
incremental CSR patch can never race a query batch that is mid-flight, and a
query submitted after the update observes the updated dataset.

Ring visibility + compaction epochs (``layout='grid_ring'`` sessions): a
delta's inserts tier into per-slab hot append rings and its deletes
tombstone in place (the O(Δ) staging contract in ``repro.core.slab``), and
the very next query batch searches ring + CSR exactly — ring-resident
results sit within 1 ulp of a fresh plan's.  ``submit_compaction()`` /
``compact()`` enqueue a COMPACTION epoch through the same FIFO: queries
admitted before it see the ring-resident state, queries after it see tables
bitwise-identical to a fresh build at the same GridSpec.  Standalone
servers also self-enqueue a compaction after any local-epoch delta that
leaves ring occupancy at/above ``compact_highwater``; cluster-epoch'd hosts
never self-compact — the coordinator broadcasts compaction epochs so a
single server replaying the epoch log replays them at the same points in
the total order.

Lifecycle: ``submit() -> result()`` per request; ``flush()`` waits for
everything admitted so far; ``close()`` stops the worker (context-manager
support included).  Telemetry (queue/execute/total latency histograms, QPS,
shed/overflow counters) accumulates on ``server.telemetry`` and snapshots
via ``server.report()``.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from . import scheduler as S
from ..obs import FlightRecorder, SloMonitor, Tracer, span
from ..runtime import compile_cache
from .engine import AidwEngine, InterpolationRequest
from .queue import AdmissionQueue, AdmissionQueueFull, validate_queries

__all__ = ["AsyncAidwServer"]


@dataclass
class _UpdateOp:
    """Dataset-update barrier flowing through the admission queue.

    Carries no ``queries_xy``, which is exactly how the coalescer recognizes
    it as a batch boundary (scheduler.next_batch stops the scan).

    ``epoch`` is the cluster-assigned epoch number for this update (see
    ``repro.serving.cluster.epochs``); ``None`` auto-increments the server's
    local epoch counter, so a standalone server replaying the same updates
    in the same order stamps the same epoch sequence as a cluster host.

    ``compact=True`` is the background COMPACTION epoch of the LSM ingest
    tier (``repro.core.slab`` hot-ring contract): it carries no data, but
    flows through the same FIFO — every query admitted before it is served
    against the ring-resident state, every query after it against the
    compacted (bitwise-fresh) tables — and bumps the epoch like any other
    update, so a single server replaying a cluster's epoch log replays
    its compactions at the same points in the order.

    ``trace_id``/``parent_span`` propagate the coordinator's trace context
    (``repro.obs``) through the barrier: the worker records the apply as an
    ``apply_epoch`` span under them, so a cluster-wide epoch broadcast
    renders as one connected trace across hosts.
    """

    points_xyz: object = None
    inserts: object = None
    deletes: object = None
    epoch: int | None = None         # explicit cluster epoch; None = +1
    compact: bool = False            # fold hot rings instead of a delta
    t_enqueue: float | None = None   # when the barrier entered the FIFO
    error: BaseException | None = None
    cancelled: bool = False          # timed-out caller withdrew the op
    skipped: bool = False            # worker honoured the withdrawal
    trace_id: str | None = None      # obs trace context (None = untraced)
    parent_span: str | None = None
    applied: threading.Event = field(default_factory=threading.Event)


@dataclass
class _ShardOp:
    """Shard-local compute op for the fleet's data-partitioned query path
    (``repro.serving.cluster.fleet.ShardedAidwCluster``).

    Like :class:`_UpdateOp` it carries no ``queries_xy``, so the coalescer
    treats it as a batch boundary and the worker executes it inline —
    which is exactly the consistency hook: a shard op is FIFO-ordered with
    epoch updates through the one admission queue, and is stamped with the
    epoch it executed under, so the fleet can detect (and retry) a query
    whose two phases straddled an update.

    ``kind``: ``"knn"`` (Stage 1 — this shard's top-k squared distances,
    the matching neighbour values, and the certification mask) or
    ``"partial"`` (Stage 2 — Eq. (1) partial sums at the client-merged
    per-query ``alpha``; skipped entirely in local Stage-2 mode).
    """

    kind: str
    queries: object
    alpha: object = None
    result: tuple | None = None
    epoch: int | None = None
    error: BaseException | None = None
    cancelled: bool = False          # timed-out caller withdrew the op
    applied: threading.Event = field(default_factory=threading.Event)


class AsyncAidwServer:
    """Admission queue + worker thread + deadline-aware coalescing over one
    :class:`repro.core.session.InterpolationSession`.

    Constructor arguments mirror :class:`repro.serving.engine.AidwEngine`
    (``mesh=`` serves every device of a mesh) plus the queueing knobs:
    ``max_depth`` bounds the admission queue (backpressure), ``slack_s`` pads
    the deadline-aware close test, ``linger_s`` optionally waits for more
    arrivals when a batch is still small (0.0 = dispatch as soon as the
    queue runs dry, which keeps pre-enqueued workloads byte-for-byte
    identical to the synchronous engine).  ``prewarm='background'``
    AOT-compiles the session's whole power-of-two bucket ladder off the
    worker thread at construction (serving starts immediately; compiled
    executables swap in as they land), then warms each bucket through the
    worker; ``'sync'`` blocks the constructor until warm.  :meth:`prewarm`
    is the same operation as a fleet control-plane call.
    """

    def __init__(self, points_xyz, cfg=None, *, max_batch: int = 8192,
                 max_depth: int = 1024, query_domain=None,
                 min_bucket: int = 64, mesh=None, layout: str = "replicated",
                 slack_s: float = 0.0, linger_s: float = 0.0,
                 pipeline_depth: int = 0, compact_highwater: float = 0.75,
                 ring_cap: int = 256, clock=time.monotonic, tracer=None,
                 trace_sample_rate: float | None = None, host_id="0",
                 wall=time.time, recorder=None, record_tail: bool = True,
                 recorder_opts: dict | None = None,
                 prewarm: str | None = None):
        # tracing is opt-in: pass a Tracer, or a trace_sample_rate to build
        # one on the SERVING clock (span timestamps must share the clock
        # domain of t_submit/t_dispatch/t_done — the obs clock contract)
        if tracer is None and trace_sample_rate is not None:
            tracer = Tracer(clock=clock, wall=wall,
                            sample_rate=trace_sample_rate, host=str(host_id))
        self.tracer = tracer
        self.host_id = str(host_id)
        # the flight recorder is ALWAYS-ON by default (tail-sampling —
        # head-sampled tracers never see the stragglers); record_tail=False
        # opts out for overhead A/B baselines, recorder_opts tunes
        # ring/top_percentile/min_window without constructing one by hand
        if recorder is None and record_tail:
            recorder = FlightRecorder(clock=clock, wall=wall,
                                      host=self.host_id,
                                      **(recorder_opts or {}))
        self.recorder = recorder
        # ONE construction path for the session/estimator/coalescer/
        # telemetry stack: the engine builds it, the server drives it from
        # a worker thread (and the sync facade stays usable via .engine)
        self.engine = AidwEngine(
            points_xyz, cfg, max_batch=max_batch, query_domain=query_domain,
            min_bucket=min_bucket, mesh=mesh, layout=layout, slack_s=slack_s,
            ring_cap=ring_cap, clock=clock, tracer=tracer, wall=wall)
        self.registry = self.engine.registry
        self.session = self.engine.session
        self.clock = clock
        self.estimator = self.engine.estimator
        self.coalescer = self.engine.coalescer
        self.telemetry = self.engine.telemetry
        self.queue = AdmissionQueue(max_depth, clock=clock)
        self._max_depth = int(max_depth)
        # SLO monitor: cold-path only — sampled/evaluated on report()/
        # debugz() pulls, never on the request path.  The ring-occupancy
        # threshold is the compaction highwater: occupancy pinned at/above
        # it means compactions are not keeping up with churn.
        self.slo = SloMonitor(
            clock=clock, recorder=self.recorder,
            targets={"ring_occupancy": compact_highwater
                     if compact_highwater > 0 else None})
        self.linger_s = float(linger_s)
        # pipeline_depth > 0: launch up to that many batches ahead of the
        # host-side scatter (jax async dispatch overlap — measured
        # experiment, see scheduler.launch_batch; 0 = classic dispatch,
        # byte-for-byte the synchronous engine's batch composition)
        self.pipeline_depth = int(pipeline_depth)
        self._pipeline: deque = deque()     # worker-local (group, res, t0)
        # dataset epoch: 0 for the construction-time dataset, bumped by every
        # applied update (or pinned to the update's explicit cluster epoch);
        # requests are stamped with the epoch they were SERVED under.
        # _epoch_gap records a withdrawn explicit-epoch barrier — the host
        # is missing that delta, and refuses further deltas until a full
        # update re-syncs it
        self.epoch = 0
        self._epoch_gap: int | None = None
        # LSM hot-ring high-water: after a LOCAL-epoch delta leaves ring
        # occupancy at/above this fraction, the worker self-enqueues a
        # background compaction epoch (standalone mode only — cluster-
        # epoch'd hosts compact when the coordinator says so, or the
        # replay-equivalence of the epoch log would break).  <= 0 disables.
        self.compact_highwater = float(compact_highwater)
        self._uid = itertools.count()
        self._reqs: dict[int, InterpolationRequest] = {}
        self._cv = threading.Condition()
        self._inflight = 0              # admitted, not yet done/shed
        self._worker_error: BaseException | None = None
        self._worker = threading.Thread(
            target=self._work, name="aidw-serving-worker", daemon=True)
        self._worker.start()
        # cold-start kill: AOT-compile the session's whole bucket ladder.
        # 'background' compiles OFF the worker thread (serving starts
        # immediately on the lazy jit path; compiled executables swap in
        # per bucket as they land) and then routes one warm batch per
        # bucket THROUGH the worker — AOT lower/compile is pure host work,
        # so it never violates the single-threaded-device-work invariant.
        # 'sync' blocks the constructor until the ladder is warm.
        if prewarm not in (None, "background", "sync"):
            raise ValueError(f"prewarm must be None, 'background' or "
                             f"'sync', got {prewarm!r}")
        compile_cache.install_listeners()
        self.prewarm_mode = prewarm
        self._prewarmed = threading.Event()
        self._prewarm_compiled = threading.Event()
        self._prewarm_stop = threading.Event()
        self._prewarm_error: BaseException | None = None
        self._prewarm_thread: threading.Thread | None = None
        if prewarm == "sync":
            self._do_prewarm()
        elif prewarm == "background":
            self._prewarm_thread = threading.Thread(
                target=self._do_prewarm, name="aidw-prewarm", daemon=True)
            self._prewarm_thread.start()

    # -- client API ----------------------------------------------------------

    def submit(self, queries_xy, *, deadline_s: float | None = None,
               uid: int | None = None, block: bool = True,
               timeout: float | None = None, trace_id: str | None = None,
               parent_span: str | None = None) -> InterpolationRequest:
        """Admit one request; returns its :class:`InterpolationRequest`.

        ``deadline_s`` is RELATIVE seconds from now (converted to an absolute
        deadline on the server clock).  A request already expired on arrival
        is shed immediately (``status == "shed"``, never enqueued).  A full
        queue blocks (backpressure) unless ``block=False``/``timeout``, in
        which case :class:`repro.serving.queue.AdmissionQueueFull` escapes to
        the caller.

        ``trace_id``/``parent_span`` join an EXISTING trace (a fleet router
        propagating its context); when absent and the server has a tracer,
        the sampler decides once here at the root — a ``None`` outcome makes
        every downstream span call a no-op for this request.
        """
        self._raise_worker_error()
        # validate at the boundary: a malformed array admitted here would
        # crash the WORKER and take down serving for every other client
        q = validate_queries(queries_xy)
        now = self.clock()
        if uid is None:
            uid = next(self._uid)
            with self._cv:                   # never collide with caller uids
                while uid in self._reqs:
                    uid = next(self._uid)
        req = InterpolationRequest(
            uid=uid, queries_xy=q,
            deadline=None if deadline_s is None else now + deadline_s)
        req.t_submit = now
        req.status = "queued"
        if trace_id is not None:
            req.trace_id = trace_id
            req.parent_span = parent_span
        elif self.tracer is not None:
            req.trace_id = self.tracer.new_trace()   # sampling at the root
        # count in-flight BEFORE admission: the worker may pop + dispatch +
        # decrement the instant put() releases the queue lock, and a late
        # increment here would strand _inflight at 1 (flush would hang)
        with self._cv:
            if req.uid in self._reqs:
                raise ValueError(f"duplicate request uid {req.uid}")
            self._reqs[req.uid] = req
            self._inflight += 1
        self.telemetry.record_submit(req)
        try:
            admitted = self.queue.put(req, block=block, timeout=timeout)
        except Exception as e:
            with self._cv:
                self._reqs.pop(req.uid, None)
                self._inflight -= 1
                self._cv.notify_all()
            if isinstance(e, AdmissionQueueFull):
                # only genuine backpressure counts as a rejection — a closed
                # queue (shutdown/crash) would misread as capacity pressure
                self.telemetry.record_rejected()
            raise
        if not admitted:                      # expired on arrival: shed
            S.shed_request(req, self.clock())
            self.telemetry.record_shed(req)
            if self.recorder is not None:
                self.recorder.observe_shed(req)
            with self._cv:
                self._inflight -= 1
                self._cv.notify_all()
        return req

    def result(self, req: InterpolationRequest | int,
               timeout: float | None = None) -> InterpolationRequest:
        """Block until the request reaches a terminal state and return it
        (``status`` is ``"done"`` or ``"shed"``); raises TimeoutError."""
        if isinstance(req, int):
            with self._cv:
                if req not in self._reqs:
                    raise KeyError(f"unknown request uid {req}")
                req = self._reqs[req]
        with self._cv:
            if not self._cv.wait_for(lambda: req.done or
                                     self._worker_error is not None,
                                     timeout=timeout):
                raise TimeoutError(f"request {req.uid} not done "
                                   f"after {timeout}s")
        if req.done:          # completed before any worker crash: still good
            return req
        self._raise_worker_error()
        return req

    def flush(self, timeout: float | None = None) -> None:
        """Wait until every request admitted so far is done or shed, then
        reap the terminal uid registry (callers hold their own request
        objects; without this a long-running submit/flush loop would grow
        host memory without bound).  ``result(uid)`` lookups for flushed
        requests therefore need the request OBJECT, not the bare uid."""
        with self._cv:
            if not self._cv.wait_for(lambda: self._inflight == 0 or
                                     self._worker_error is not None,
                                     timeout=timeout):
                raise TimeoutError(
                    f"{self._inflight} requests still in flight "
                    f"after {timeout}s")
        self._raise_worker_error()
        self.reap()

    def reap(self) -> int:
        """Drop terminal requests from the uid registry (long-running
        servers call this after collecting results; returns how many)."""
        with self._cv:
            done = [u for u, r in self._reqs.items() if r.done]
            for u in done:
                del self._reqs[u]
            return len(done)

    # -- cold-start prewarm --------------------------------------------------

    def _do_prewarm(self) -> None:
        """Compile the session's full bucket ladder, then warm each bucket
        with one dummy batch routed THROUGH the worker (the eager helper
        ops around the executable compile there, on the thread that owns
        device execution).  Runs on the caller's thread ('sync'/explicit
        prewarm()) or the dedicated prewarm thread ('background') — AOT
        lower/compile is host-only work either way."""
        try:
            t0 = self.clock()
            sess = self.session
            ladder = sess.bucket_ladder(self.engine.max_batch)
            if threading.current_thread() is self._prewarm_thread:
                # background mode: serving has the cores, prewarm takes
                # the leftovers.  Per-thread nice (Linux: PRIO_PROCESS
                # with a TID targets one thread) plus single-split CPU
                # codegen (below) keeps compile work on THIS thread —
                # the off-path p99 gate in benchmarks/coldstart_bench.py
                # holds the line at 1.1x steady state.
                try:
                    os.setpriority(os.PRIO_PROCESS,
                                   threading.get_native_id(), 19)
                except (AttributeError, OSError):
                    pass
            # lowering is GIL-bound Python tracing: at the default 5ms
            # switch interval a foreground dispatch can stall a full
            # quantum behind it.  A short interval preempts the tracing
            # thread often enough that dispatch latency stays flat
            # (restored below).
            switch0 = sys.getswitchinterval()
            sys.setswitchinterval(min(switch0, 0.0005))
            try:
                opts = compile_cache.background_compile_options()
                for b in ladder:
                    if self._prewarm_stop.is_set():
                        return
                    sess.precompile(buckets=[b], compiler_options=opts)
            finally:
                sys.setswitchinterval(switch0)
            # phase boundary: the EXPENSIVE part (seconds of XLA compiles,
            # off the worker thread) is done; what follows are ordinary
            # worker-queue batches (milliseconds).  The cold-start bench's
            # off-path gate measures contention against this event — a
            # foreground request queueing behind a warm batch is FIFO
            # head-of-line blocking, not compile leakage.
            self._prewarm_compiled.set()
            anchor = np.asarray(sess._host_pts[0, :2], dtype=np.float32)
            for b in ladder:
                if self._prewarm_stop.is_set():
                    return
                # dummy warm batch: exact bucket size (no pad), in-domain
                # coordinates, results discarded.  Submitted one at a time
                # (awaited before the next) so the coalescer cannot merge
                # them — each bucket must dispatch STANDALONE to warm its
                # own helper-op shapes.  Counted by telemetry like any
                # request — prewarming servers see len(ladder) extra
                # completed batches.
                self.result(self.submit(np.tile(anchor, (b, 1))),
                            timeout=600.0)
            self._prewarmed.set()
            self.registry.set("serving/prewarmed", 1, merge="max")
            self.registry.observe("serving/prewarm_s", self.clock() - t0)
            compile_cache.sync_registry(self.registry)
            if self.recorder is not None:
                self.recorder.event(
                    "prewarm_done", severity="info",
                    data={"buckets": ladder,
                          "wall_s": self.clock() - t0})
        except BaseException as e:
            self._prewarm_error = e

    def prewarm(self, wait: bool = True,
                timeout: float | None = None) -> dict:
        """AOT-compile + warm this server's whole bucket ladder (the fleet
        control-plane op: a joining or restarted host calls this before
        entering rotation).  No-op when already prewarmed; with a
        'background' thread in flight, ``wait=True`` blocks until it
        lands.  Returns a status dict (prewarmed flag, live AOT bucket
        count, persistent-cache stats)."""
        if self._prewarm_thread is None and not self._prewarmed.is_set():
            self.prewarm_mode = self.prewarm_mode or "sync"
            self._do_prewarm()
        if wait:
            deadline = None if timeout is None \
                else time.monotonic() + timeout
            while not self._prewarmed.is_set():
                if self._prewarm_error is not None:
                    break
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError(
                        f"prewarm not finished after {timeout}s")
                self._prewarmed.wait(timeout=0.05)
        if self._prewarm_error is not None:
            raise RuntimeError("prewarm failed") from self._prewarm_error
        return {"prewarmed": self._prewarmed.is_set(),
                "mode": self.prewarm_mode,
                "aot_buckets": int(
                    self.session.stats.get("aot_buckets", 0)),
                "compile_cache": compile_cache.cache_stats()}

    def submit_update(self, points_xyz=None, *, inserts=None, deletes=None,
                      deltas=None, epoch: int | None = None,
                      timeout: float | None = None,
                      trace_id: str | None = None,
                      parent_span: str | None = None) -> _UpdateOp:
        """Enqueue a dataset update WITHOUT waiting for it to apply.

        The op is a FIFO barrier in the admission queue: every request
        admitted before it is served against the old dataset, every request
        after against the new one.  This non-blocking half is the cluster
        hook — a coordinator broadcasts one epoch-tagged op per host and
        only then waits, so hosts apply the update concurrently while their
        per-host FIFO order against queries is already pinned.  ``timeout``
        bounds admission only (a full queue exerting backpressure raises
        :class:`~repro.serving.queue.AdmissionQueueFull` at the bound).
        Returns the op handle for :meth:`wait_update`.
        """
        self._raise_worker_error()
        if deltas is not None:
            inserts, deletes = deltas
        if trace_id is None and self.tracer is not None:
            # standalone traced server: sample an update root locally (a
            # fleet host's rate-0 tracer declines here, keeping sampling
            # at the coordinator — the propagated trace_id branch above)
            trace_id = self.tracer.new_trace()
        op = _UpdateOp(points_xyz=points_xyz, inserts=inserts,
                       deletes=deletes, epoch=epoch, trace_id=trace_id,
                       parent_span=parent_span, t_enqueue=self.clock())
        self.queue.put(op, timeout=timeout)
        return op

    def wait_update(self, op: _UpdateOp,
                    timeout: float | None = None) -> None:
        """Block until a :meth:`submit_update` op is applied; raises the
        op's error (poisoned update) or TimeoutError (op withdrawn)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        # poll in short slices so a worker that dies AFTER admission (its
        # crash handler resolves queued ops, but belt-and-braces) can never
        # strand this wait
        while not op.applied.wait(timeout=0.05):
            self._raise_worker_error()
            if deadline is not None and time.monotonic() > deadline:
                # withdraw the op (best effort: the worker skips cancelled
                # barriers it has not started) so a timed-out update cannot
                # silently apply later and double-apply on the caller's retry
                op.cancelled = True
                raise TimeoutError(
                    f"dataset update not applied after {timeout}s "
                    f"(op withdrawn; safe to retry)")
        if op.error is not None:
            raise op.error
        if op.skipped:
            # applied-event set by the SKIP path of a withdrawn op: a retry
            # of this wait must not read as success — nothing was applied.
            # (cancelled-but-applied-anyway — the worker was already mid-
            # apply when the caller withdrew — correctly reads as success)
            raise TimeoutError(
                "dataset update was withdrawn after an earlier timeout; "
                "it never applied")

    def shard_knn(self, queries_xy, *, timeout: float | None = None):
        """Stage-1-only pass over THIS server's dataset: returns
        ``(d2 (n, k), z (n, k), overflow (n,), epoch)`` — this shard's
        top-k heap of squared distances AND neighbour values.  The fleet's
        data-partitioned query path fans this out to every shard host and
        k-way merges (d2, z) client-side; in local Stage-2 mode the merged
        heap alone finishes the query (no partial-sum phase).
        FIFO-serialized with dataset updates through the admission queue
        (the returned epoch is the witness)."""
        return self._run_shard_op(_ShardOp(
            kind="knn", queries=validate_queries(queries_xy)), timeout)

    def shard_partial(self, queries_xy, alpha, *,
                      timeout: float | None = None):
        """Stage-2 partial sums over THIS server's dataset at a
        caller-supplied per-query ``alpha``: returns
        ``(sum_wz (n,), sum_w (n,), epoch)``."""
        q = validate_queries(queries_xy)
        a = np.asarray(alpha)
        if a.shape != (q.shape[0],):
            raise ValueError(f"alpha must be shape ({q.shape[0]},), "
                             f"got {a.shape}")
        return self._run_shard_op(
            _ShardOp(kind="partial", queries=q, alpha=a), timeout)

    def _run_shard_op(self, op: _ShardOp, timeout: float | None):
        self._raise_worker_error()
        deadline = None if timeout is None else time.monotonic() + timeout
        self.queue.put(op, timeout=timeout)
        # short-slice poll like wait_update: a worker that dies mid-op must
        # surface, never strand the fleet coordinator
        while not op.applied.wait(timeout=0.05):
            self._raise_worker_error()
            if deadline is not None and time.monotonic() > deadline:
                # withdraw (best effort): the fleet retries the whole
                # batch, so an orphaned op still in the FIFO must not burn
                # a full kNN/partial pass for a result nobody reads
                op.cancelled = True
                raise TimeoutError(
                    f"shard {op.kind} not executed after {timeout}s "
                    f"(op withdrawn)")
        if op.error is not None:
            raise op.error
        return op.result + (op.epoch,)

    def submit_compaction(self, *, epoch: int | None = None,
                          timeout: float | None = None,
                          trace_id: str | None = None,
                          parent_span: str | None = None) -> _UpdateOp:
        """Enqueue a background COMPACTION epoch without waiting (the LSM
        hot-ring fold — ``repro.core.session.InterpolationSession.compact``).
        A FIFO barrier like any update: queries admitted after it observe
        the compacted (bitwise-fresh) tables.  Returns the op handle for
        :meth:`wait_update`."""
        self._raise_worker_error()
        if trace_id is None and self.tracer is not None:
            trace_id = self.tracer.new_trace()   # standalone sampling, as
        op = _UpdateOp(compact=True, epoch=epoch,  # in submit_update
                       trace_id=trace_id, parent_span=parent_span,
                       t_enqueue=self.clock())
        self.queue.put(op, timeout=timeout)
        return op

    def compact(self, *, epoch: int | None = None,
                timeout: float | None = None) -> None:
        """Fold the session's hot rings through the admission queue and
        block until applied (no-op on layouts without an LSM tier)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        op = self.submit_compaction(epoch=epoch, timeout=timeout)
        self.wait_update(
            op, timeout=None if deadline is None
            else max(deadline - time.monotonic(), 0.0))

    def update_dataset(self, points_xyz=None, *, inserts=None, deletes=None,
                       deltas=None, epoch: int | None = None,
                       timeout: float | None = None) -> None:
        """Refresh the served dataset THROUGH the admission queue.

        Blocks until the worker applied the update (it never races a query
        batch — both run on the worker thread).  ``timeout`` bounds the
        whole call: admission past it raises
        :class:`~repro.serving.queue.AdmissionQueueFull`, application past
        it raises TimeoutError.
        """
        # the timeout bounds the WHOLE call: admission plus the applied-wait,
        # which reuses the same deadline
        deadline = None if timeout is None else time.monotonic() + timeout
        op = self.submit_update(points_xyz, inserts=inserts, deletes=deletes,
                                deltas=deltas, epoch=epoch, timeout=timeout)
        self.wait_update(
            op, timeout=None if deadline is None
            else max(deadline - time.monotonic(), 0.0))

    @property
    def alive(self) -> bool:
        """Worker-thread health (cluster liveness probes read this: a host
        whose admission queue still answers but whose worker died must
        probe as DEAD, not idle)."""
        return self._worker.is_alive() and self._worker_error is None

    def report(self) -> dict:
        """Telemetry snapshot + queue/session counters (JSON-serializable).

        ``merge`` carries the full REGISTRY state (counters, gauges with
        merge modes, full histogram bins — a superset of the old telemetry
        state) so a cluster coordinator can aggregate fleet percentiles
        exactly (:func:`repro.serving.cluster.telemetry.merge_reports`);
        ``stages`` is the human-facing registry snapshot — per-stage walls
        (``session/plan_s`` .. ``serving/scatter_s``) alongside the
        request-level latency histograms.
        """
        rep = self.telemetry.report()
        rep["epoch"] = self.epoch
        rep["admission"] = dict(self.queue.counters)
        rep["queue_depth"] = len(self.queue)
        rep["session"] = {k: v for k, v in self.session.stats.items()
                          if isinstance(v, (int, float))}
        rep["merge"] = self.telemetry.state()
        rep["stages"] = self.registry.snapshot()
        rep["registry"] = self.registry.state()
        rep["compile"] = self._compile_report()
        rep["slo"] = self._slo_eval()
        if self.recorder is not None:
            rep["recorder"] = self.recorder.snapshot()
        return rep

    def _compile_report(self) -> dict:
        """Cold-start observability block: prewarm state, live AOT bucket
        count, persistent-compilation-cache hit/miss totals (synced into
        the registry as additive counters first, so fleet merges stay
        correct), and any post-warmup hot-path compiles."""
        compile_cache.sync_registry(self.registry)
        return {
            "prewarm": self.prewarm_mode,
            "prewarmed": self._prewarmed.is_set(),
            "aot_buckets": int(self.session.stats.get("aot_buckets", 0)),
            "post_warmup_compiles":
                self.registry.counter("serving/post_warmup_compiles").value,
            "cache": compile_cache.cache_stats(),
        }

    def _slo_eval(self) -> dict:
        """Sample the current cumulative counters/gauges into the SLO
        monitor and evaluate burn rates (cold path: report()/debugz()
        pulls only)."""
        c = self.telemetry.counters
        anomalies = self.recorder.anomalies if self.recorder is not None \
            else {}
        counters = {"requests": c["completed"] + c["shed"],
                    "deadline_miss": anomalies.get("deadline_miss", 0),
                    "shed": c["shed"]}
        gauges = {"queue_depth_frac":
                  len(self.queue) / max(self._max_depth, 1),
                  # a compile reaching the hot path AFTER the ladder was
                  # prewarmed is an anomaly (target 1.0 in the monitor:
                  # any nonzero count breaches)
                  "post_warmup_compiles": float(
                      self.registry.counter(
                          "serving/post_warmup_compiles").value)}
        occ = self.session.stats.get("ring_occupancy")
        if occ is not None:
            gauges["ring_occupancy"] = float(occ)
        self.slo.sample(counters, gauges)
        return self.slo.evaluate()

    def debugz(self) -> dict:
        """One JSON-serializable diagnostics bundle for this server: queue
        and epoch position, session/ring state, full registry state, the
        SLO evaluation, and the flight recorder's retained anomaly traces.
        Non-draining — a debugz pull never changes what the next pull (or
        the running SLO windows) sees."""
        bundle = {
            "host_id": self.host_id,
            "alive": self.alive,
            "epoch": self.epoch,
            "queue_depth": len(self.queue),
            "admission": dict(self.queue.counters),
            "session": {k: v for k, v in self.session.stats.items()
                        if isinstance(v, (int, float))},
            "stages": self.registry.snapshot(),
            "registry": self.registry.state(),
            "compile": self._compile_report(),
            "slo": self._slo_eval(),
            "recorder": self.recorder.state()
            if self.recorder is not None else None,
        }
        return bundle

    # -- observability endpoints (served over rpc by the cluster host) -------

    def metrics_text(self, prefix: str = "aidw") -> str:
        """Prometheus text exposition of the engine's whole registry."""
        return self.registry.prometheus_text(prefix)

    def metrics_snapshot(self) -> dict:
        """JSON snapshot of the registry (scalars + histogram quantiles)."""
        return self.registry.snapshot()

    def spans(self, drain: bool = True) -> list[dict]:
        """Finished span dicts from the server's tracer ([] when tracing is
        off).  ``drain=True`` (default) empties the buffer, so a cluster
        coordinator polling per-host spans never double-collects."""
        if self.tracer is None:
            return []
        return self.tracer.drain() if drain else self.tracer.spans()

    def close(self, timeout: float | None = 30.0) -> None:
        """Stop admitting, let the worker drain, and join it.  Raises
        TimeoutError if the worker is still running after ``timeout``, and
        surfaces a worker crash — a silent return would leave requests
        unresolved behind the caller's back."""
        # stop a background prewarm first: it checks the flag between
        # bucket compiles, so the join below is bounded by one compile
        self._prewarm_stop.set()
        if self._prewarm_thread is not None:
            self._prewarm_thread.join(timeout=timeout)
        self.queue.close()
        self._worker.join(timeout=timeout)
        with self._cv:
            self._cv.notify_all()
        if self._worker.is_alive():
            raise TimeoutError(
                f"serving worker still draining after {timeout}s "
                f"(queue_depth={len(self.queue)})")
        self._raise_worker_error()

    def __enter__(self) -> "AsyncAidwServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- worker --------------------------------------------------------------

    def _raise_worker_error(self) -> None:
        if self._worker_error is not None:
            raise RuntimeError("serving worker died") from self._worker_error

    def _apply_update(self, op: _UpdateOp) -> None:
        if op.cancelled:                    # withdrawn by a timed-out caller
            op.skipped = True
            if op.epoch is not None:
                # an explicit-epoch (cluster) barrier that was withdrawn
                # leaves a GAP in this host's update order: remember it, so
                # later epochs fail loudly instead of silently serving a
                # dataset that is missing epoch k's delta
                self._epoch_gap = op.epoch
            op.applied.set()
            return
        try:
            if op.epoch is not None and self._epoch_gap is not None \
                    and op.points_xyz is None:
                # a delta cannot apply over a hole; a FULL update below re-
                # syncs the host and heals the gap
                raise RuntimeError(
                    f"host missed epoch {self._epoch_gap} (withdrawn after "
                    f"timeout); refusing delta epoch {op.epoch} — re-sync "
                    f"with a full dataset update first")
            if op.epoch is not None and op.epoch <= self.epoch:
                # an out-of-order cluster epoch reaching the worker means the
                # host-side EpochApplier was bypassed — refuse loudly rather
                # than silently diverging from the fleet's update order
                raise RuntimeError(
                    f"epoch {op.epoch} <= current {self.epoch}: updates "
                    f"must apply in increasing epoch order")
            t_apply = self.clock()
            if op.compact:
                self.session.compact()
            else:
                self.engine.update_dataset(op.points_xyz, inserts=op.inserts,
                                           deletes=op.deletes)
            self.epoch = op.epoch if op.epoch is not None else self.epoch + 1
            t_end = self.clock()
            # the FIFO-barrier hold, first-class: from the moment the op
            # entered the admission queue (every query admitted behind it
            # is pinned) to applied — NOT just the device fold wall the
            # session records as session/compact_s.  This is the number
            # that shows up as queue_wait in the victims' breakdowns; the
            # attribution report's stall block names it as the culprit.
            self.registry.observe(
                "session/compact_stall_s" if op.compact
                else "serving/epoch_barrier_s",
                t_end - (op.t_enqueue if op.t_enqueue is not None
                         else t_apply),
                exemplar=op.trace_id)
            if self.tracer is not None and op.trace_id is not None:
                # the session fences its own plan/compact internals, so the
                # wall here is honest device-inclusive apply time
                self.tracer.record(
                    "apply_epoch", t_apply, t_end,
                    trace_id=op.trace_id, parent_id=op.parent_span,
                    args={"epoch": self.epoch, "compact": op.compact})
            if op.points_xyz is not None:
                self._epoch_gap = None      # full refresh healed the hole
            if not op.compact and op.epoch is None \
                    and self.compact_highwater > 0 \
                    and self.session.stats.get("ring_occupancy", 0.0) \
                    >= self.compact_highwater:
                # standalone auto-epoch mode: self-enqueue the background
                # fold BEHIND whatever queries are already admitted (best
                # effort — a full queue skips; the next delta re-triggers)
                try:
                    self.queue.put(_UpdateOp(compact=True,
                                             t_enqueue=self.clock()),
                                   block=False)
                except AdmissionQueueFull:
                    pass
        except BaseException as e:          # surface to the waiting client
            op.error = e
        finally:
            op.applied.set()

    def _run_shard(self, op: _ShardOp) -> None:
        if op.cancelled:                # withdrawn by a timed-out caller
            op.applied.set()
            return
        try:
            if op.kind == "knn":
                d2, z, ovf = self.session.knn(op.queries)
                op.result = (np.asarray(d2), np.asarray(z), np.asarray(ovf))
            elif op.kind == "partial":
                swz, sw = self.session.partial_interpolate(op.queries,
                                                           op.alpha)
                op.result = (np.asarray(swz), np.asarray(sw))
            else:
                raise ValueError(f"unknown shard op kind {op.kind!r}")
            op.epoch = self.epoch
        except BaseException as e:          # surface to the waiting client
            op.error = e
        finally:
            op.applied.set()

    def _step(self, pending: deque) -> None:
        """One worker step over the front of ``pending``: apply an update
        barrier, run a shard op, or form + dispatch one coalesced batch
        (shared by the live loop and the drain-on-close loop)."""
        head = pending[0]
        if not hasattr(head, "queries_xy"):    # update barrier / shard op
            pending.popleft()
            if isinstance(head, _ShardOp):
                self._run_shard(head)
            else:
                with span("serving.apply_update"):
                    self._apply_update(head)
            with self._cv:
                self._cv.notify_all()
            return
        with span("serving.coalesce"):
            group, shed = self.coalescer.next_batch(pending)
        for r in shed:
            self.telemetry.record_shed(r)
            if self.recorder is not None:
                self.recorder.observe_shed(r)
        if group:
            # stamp the dataset epoch the batch executes under: updates only
            # apply between batches on this same thread, so one stamp covers
            # the whole group (the cluster's consistency-contract witness)
            for r in group:
                r.epoch = self.epoch
            c0 = compile_cache.backend_compiles()
            if self.pipeline_depth:
                res, t0 = S.launch_batch(self.session, group,
                                         clock=self.clock)
                self._pipeline.append((group, res, t0))
                while len(self._pipeline) > self.pipeline_depth:
                    self._scatter_oldest()
                group = []                  # in flight: resolve at scatter
            else:
                S.dispatch_batch(self.session, group,
                                 estimator=self.estimator,
                                 telemetry=self.telemetry, clock=self.clock,
                                 tracer=self.tracer, recorder=self.recorder)
            self._note_hot_compiles(c0)
        if group or shed:
            with self._cv:
                self._inflight -= len(group) + len(shed)
                self._cv.notify_all()

    def _note_hot_compiles(self, c0: int) -> None:
        """Post-warmup hot-path compile detection: once the ladder is
        prewarmed, a dispatch that reaches the XLA compile layer is an
        anomaly — count it and retain a critical flight-recorder event.
        (Before/without prewarm, lazy compiles are expected and ignored.)"""
        if not self._prewarmed.is_set():
            return
        dc = compile_cache.backend_compiles() - c0
        if dc <= 0:
            return
        self.registry.inc("serving/post_warmup_compiles", dc)
        if self.recorder is not None:
            self.recorder.event("hot_path_compile", severity="critical",
                                data={"compiles": dc})

    def _scatter_oldest(self) -> None:
        group, res, t0 = self._pipeline.popleft()
        S.scatter_batch(group, res, t0, estimator=self.estimator,
                        telemetry=self.telemetry, clock=self.clock,
                        tracer=self.tracer, recorder=self.recorder)
        with self._cv:
            self._inflight -= len(group)
            self._cv.notify_all()

    def _drain_pipeline(self) -> None:
        while self._pipeline:
            self._scatter_oldest()

    def _work(self) -> None:
        """Worker loop: drain admissions, apply barriers, dispatch batches.

        ``pending`` is the worker-local FIFO; the admission queue is drained
        into it so batch formation never holds the queue lock.  When
        ``pending`` still has queries, the queue is only polled (non-
        blocking); when idle, the worker blocks on the queue.
        """
        pending: deque = deque()
        try:
            while True:
                if not pending:
                    # idle: materialize pipelined batches before blocking
                    # (flush waits on in-flight hitting zero)
                    self._drain_pipeline()
                    with span("serving.wait_work"):
                        item = self.queue.get(timeout=0.1)
                    if item is None:
                        if self.queue.closed:
                            break
                        continue
                    pending.append(item)
                pending.extend(self.queue.drain())
                if self.linger_s and len(pending) >= 1 \
                        and hasattr(pending[0], "queries_xy"):
                    # optional linger: give near-simultaneous arrivals a
                    # window to coalesce; deadline pressure still closes
                    # early because next_batch re-reads the clock.  The
                    # window itself is bounded in REAL time — a test-injected
                    # frozen clock must not spin this loop forever
                    end = time.monotonic() + self.linger_s
                    while time.monotonic() < end:
                        more = self.queue.drain()
                        if more:
                            pending.extend(more)
                            break
                        time.sleep(min(self.linger_s / 10, 1e-3))
                self._step(pending)
            # drain-on-close: anything admitted before close() still resolves
            pending.extend(self.queue.drain())
            while pending:
                self._step(pending)
            self._drain_pipeline()
        except BaseException as e:
            self._worker_error = e
            # a dead worker must not strand anyone: wake blocked putters,
            # refuse new work, and resolve every queued update barrier so
            # update_dataset callers see the crash instead of hanging
            self.queue.close()
            pending.extend(self.queue.drain())
            for item in pending:
                if not hasattr(item, "queries_xy") \
                        and hasattr(item, "applied"):
                    item.error = item.error or e
                    item.applied.set()
            with self._cv:
                self._cv.notify_all()
