"""Deadline-aware microbatch coalescing — the batch former IS a perf subsystem.

Mei & Tian's data-layout study (arXiv:1402.4986) shows batch composition
dominates GPU IDW throughput, so the scheduler that forms microbatches is on
the critical path of the paper's 1017x story, not plumbing around it.  This
module is the ONE coalescing implementation behind both drive modes:
:class:`repro.serving.engine.AidwEngine` (synchronous: caller hands it a
request list) and :class:`repro.serving.server.AsyncAidwServer` (a worker
thread pulls from the admission queue).

Coalescing contract:

* **FIFO, never reordering** — requests join a batch in arrival order; a
  batch closes when adding the next request would exceed ``max_batch``
  queries (a request larger than ``max_batch`` forms its own batch).  With no
  deadlines anywhere this reproduces the classic greedy coalescing
  byte-for-byte: identical groups, identical concatenated batches, identical
  (bitwise) results through the session's bucketed executables.
* **deadline-aware early close** — each group tracks the earliest deadline of
  its members; the coalescer refuses to grow the batch past the point where
  ``now + estimate(execute_time(next_size)) + slack`` overshoots that
  deadline.  ``estimate`` is MEASURED, not assumed: an EWMA per compiled
  bucket size (:class:`ExecuteTimeModel`), reusing the session's
  power-of-two bucketing so the estimate keys on the executable that would
  actually run — growing a batch within one bucket costs nothing, crossing a
  bucket boundary is what changes the execute time.
* **dispatch-time shedding** — a request whose deadline has already passed
  when the coalescer reaches it is shed (status ``"shed"``) instead of served
  late.  Predicted-late-but-not-expired requests are NOT shed (the estimate
  is a forecast): they dispatch best-effort at the front of their own batch.

``clock`` is injectable everywhere for deterministic deadline tests.
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from repro.core.session import bucket_size
from repro.obs import span

__all__ = ["DeadlineCoalescer", "ExecuteTimeModel", "dispatch_batch",
           "launch_batch", "scatter_batch", "shed_request",
           "STATUS_PENDING", "STATUS_QUEUED", "STATUS_DONE", "STATUS_SHED"]

STATUS_PENDING = "pending"   # created, not yet admitted
STATUS_QUEUED = "queued"     # admitted, waiting for a batch
STATUS_DONE = "done"         # served; .values/.overflow populated
STATUS_SHED = "shed"         # deadline expired before dispatch; never served


class ExecuteTimeModel:
    """EWMA execute-time estimate keyed on (query bucket, dataset bucket).

    ``record(n, seconds)`` folds a measured batch execute time into the EWMA
    for ``(bucket_size(n, min_bucket), dataset bucket)``, where the dataset
    bucket is the power-of-two bucket of the CURRENT ``n_points`` (the engine
    refreshes :attr:`n_points` on every ``update_dataset``).  Execute time
    depends on the dataset size through the kNN candidate windows, so after a
    large delta update the per-query-bucket EWMA primed at the OLD size would
    mis-calibrate the deadline early-close until the EWMA relearned; keying
    on both keeps per-size estimates live across churn.

    ``estimate(n)`` reads the estimate back: exact key first, then the
    nearest query bucket measured AT the current dataset size (scaled
    linearly in n — bucket executables are ~linear in batch size), then the
    nearest dataset bucket (dataset-size dependence is measured, not
    modeled).  0.0 before ANY measurement — optimistic, so the scheduler
    never closes batches early on a cold model.
    """

    def __init__(self, min_bucket: int = 64, alpha: float = 0.3,
                 n_points: int | None = None):
        self.min_bucket = int(min_bucket)
        self.alpha = float(alpha)
        self.n_points = n_points        # engine-maintained; None = unkeyed
        self._ewma: dict[tuple[int, int], float] = {}

    def bucket(self, n: int) -> int:
        return bucket_size(n, self.min_bucket)

    def _dataset_bucket(self) -> int:
        return 0 if self.n_points is None \
            else bucket_size(int(self.n_points), 1)

    def record(self, n: int, seconds: float) -> None:
        key = (self.bucket(n), self._dataset_bucket())
        prev = self._ewma.get(key)
        self._ewma[key] = float(seconds) if prev is None else \
            self.alpha * float(seconds) + (1.0 - self.alpha) * prev

    def estimate(self, n: int) -> float:
        if not self._ewma:
            return 0.0
        nb, mb = self.bucket(n), self._dataset_bucket()
        hit = self._ewma.get((nb, mb))
        if hit is not None:
            return hit
        same_m = [k for k in self._ewma if k[1] == mb]
        if same_m:
            k = min(same_m, key=lambda k: abs(k[0] - nb))
        else:
            # nothing measured at this dataset size yet (right after a
            # resizing update): nearest dataset bucket, still scaled in n
            k = min(self._ewma, key=lambda k: (abs(k[1] - mb),
                                               abs(k[0] - nb)))
        return self._ewma[k] * (nb / k[0])


def shed_request(req, now: float) -> None:
    """Mark ``req`` shed (deadline expired before dispatch): terminal, never
    served, distinct status so clients can tell shed from served."""
    req.status = STATUS_SHED
    req.done = True
    req.t_done = now


class DeadlineCoalescer:
    """FIFO coalescer with deadline-aware early batch close (module
    docstring).  Stateless across calls except for the shared
    :class:`ExecuteTimeModel`."""

    def __init__(self, max_batch: int, estimator: ExecuteTimeModel | None
                 = None, *, clock=time.monotonic, slack_s: float = 0.0):
        self.max_batch = int(max_batch)
        self.estimator = estimator or ExecuteTimeModel()
        self.clock = clock
        self.slack_s = float(slack_s)

    # -- deadline predicates -------------------------------------------------

    @staticmethod
    def _expired(req, now: float) -> bool:
        return req.deadline is not None and now >= req.deadline

    def _would_miss(self, earliest_deadline: float | None, n: int,
                    now: float) -> bool:
        if earliest_deadline is None:
            return False
        return now + self.estimator.estimate(n) + self.slack_s \
            > earliest_deadline

    # -- batch formation -----------------------------------------------------

    def next_batch(self, pending: deque, now: float | None = None):
        """Pop ONE coalesced group off the front of ``pending``.

        Returns ``(group, shed)``: ``group`` is [] only when ``pending`` ran
        dry (after shedding).  Items without a ``queries_xy`` attribute
        (e.g. dataset-update barriers) stop the scan — the caller handles
        them between batches, preserving FIFO order with queries.
        """
        now = self.clock() if now is None else now
        shed: list = []
        while pending and hasattr(pending[0], "queries_xy") \
                and self._expired(pending[0], now):
            r = pending.popleft()
            shed_request(r, now)
            shed.append(r)
        if not pending or not hasattr(pending[0], "queries_xy"):
            return [], shed
        first = pending.popleft()
        group = [first]
        size = first.queries_xy.shape[0]
        earliest = first.deadline
        while pending:
            r = pending[0]
            if not hasattr(r, "queries_xy"):
                break                        # update barrier: close here
            if self._expired(r, now):
                pending.popleft()
                shed_request(r, now)
                shed.append(r)
                continue
            n_next = size + r.queries_xy.shape[0]
            if n_next > self.max_batch:
                break
            cand = earliest if r.deadline is None else (
                r.deadline if earliest is None else min(earliest, r.deadline))
            if self._would_miss(cand, n_next, now):
                break                        # deadline-aware early close
            pending.popleft()
            group.append(r)
            size = n_next
            earliest = cand
        return group, shed

    def coalesce(self, requests, now: float | None = None):
        """Partition a whole request list into dispatch groups (the
        synchronous drive mode).  Returns ``(groups, shed)``.

        Accepts QUERY requests only — barrier items (no ``queries_xy``)
        belong to the streaming drive mode, where the caller owns the deque
        and handles them between ``next_batch`` calls; here they would
        never be popped, so they are rejected loudly instead of hanging.
        """
        now = self.clock() if now is None else now
        pending = deque(requests)
        groups: list[list] = []
        shed: list = []
        while pending:
            group, s = self.next_batch(pending, now)
            shed.extend(s)
            if group:
                groups.append(group)
            elif pending:
                raise ValueError(
                    f"coalesce() takes query requests only, got "
                    f"{type(pending[0]).__name__} (drive barriers through "
                    f"next_batch)")
        return groups, shed


def launch_batch(session, group, *, clock=time.monotonic):
    """Dispatch one coalesced group on ``session`` WITHOUT materializing
    results.  JAX dispatch is asynchronous — ``session.query`` returns
    device arrays before the computation finishes — so a worker can form
    and launch batch N+1 while batch N's results transfer, hiding the
    host-side scatter latency (the pipelined drive mode:
    ``AsyncAidwServer(pipeline_depth=...)``).  Returns ``(res, t0)`` for a
    later :func:`scatter_batch`.
    """
    t0 = clock()
    for r in group:
        r.t_dispatch = t0
    with span("serving.coalesce"):
        queries = np.concatenate([r.queries_xy for r in group], axis=0)
    with span("serving.launch"):
        res = session.query(queries)
    return res, t0


def scatter_batch(group, res, t0, *, estimator: ExecuteTimeModel | None
                  = None, telemetry=None, clock=time.monotonic,
                  tracer=None, recorder=None):
    """Materialize a launched batch and scatter results to their requests.

    Slices values AND the per-query overflow mask back to each owning
    request (so a client can tell ITS bucket overflowed, not just that
    some query in some batch did), stamps timestamps/status, and feeds the
    measured execute time into the scheduler's estimate.  Under pipelined
    dispatch the measured span includes the overlap window, so the
    estimator's deadline forecasts become conservative — acceptable for a
    measured experiment, one reason pipelining is off by default.

    Observability: the batch's coalesce hold (dispatch minus the LAST
    member's arrival) and the host-side scatter wall go into the
    telemetry's registry (``serving/coalesce_s``/``serving/scatter_s``),
    and each TRACED request (``trace_id`` set) gets retroactive
    queue_wait/coalesce/execute/scatter spans from the timestamps already
    stamped — tracing adds no work between them.  The ``np.asarray``
    materialization above IS the execute fence (host sync), so the
    execute span honours the obs fencing contract.  The always-on
    ``recorder`` (:class:`repro.obs.recorder.FlightRecorder`) observes
    every request from the SAME fence points — retention decisions need
    the per-request zero-weight/overflow slices, which is why the mask
    slicing below is per-request to begin with.
    """
    with span("serving.materialize"):
        vals = np.asarray(res.values)    # host sync: results materialized
        mask = None if res.overflow_mask is None \
            else np.asarray(res.overflow_mask)
        zmask = getattr(res, "zero_weight_mask", None)
        if zmask is not None:
            zmask = np.asarray(zmask)
    t1 = clock()
    # scatter: per-request slices, then the batch's bookkeeping
    with span("serving.scatter"):
        off = 0
        for r in group:
            n = r.queries_xy.shape[0]
            r.values = vals[off:off + n]
            r.overflow = 0 if mask is None else int(mask[off:off + n].sum())
            if zmask is not None:
                r.zero_weight = int(zmask[off:off + n].sum())
            r.status = STATUS_DONE
            r.done = True
            r.t_done = t1
            off += n
        t2 = clock()
        last_submit = max((r.t_submit for r in group
                           if r.t_submit is not None), default=t0)
        if estimator is not None:
            estimator.record(off, t1 - t0)
        if telemetry is not None:
            telemetry.record_batch(group, t1 - t0)
            reg = getattr(telemetry, "registry", None)
            if reg is not None:
                reg.observe("serving/coalesce_s", max(t0 - last_submit, 0.0))
                reg.observe("serving/scatter_s", t2 - t1)
        if tracer is not None:
            for r in group:
                tid = getattr(r, "trace_id", None)
                if tid is None:
                    continue
                parent = getattr(r, "parent_span", None)
                if r.t_submit is not None:
                    tracer.record("queue_wait", r.t_submit, r.t_dispatch,
                                  trace_id=tid, parent_id=parent)
                    tracer.record("coalesce", min(last_submit, r.t_dispatch),
                                  t0, trace_id=tid, parent_id=parent)
                tracer.record("execute", t0, t1, trace_id=tid,
                              parent_id=parent, args={"batch_queries": off})
                tracer.record("scatter", t1, t2, trace_id=tid,
                              parent_id=parent)
        if recorder is not None:
            for r in group:
                recorder.observe_request(r, t0=t0, t1=t1, t2=t2,
                                         last_submit=last_submit)
    return res


def dispatch_batch(session, group, *, estimator: ExecuteTimeModel | None
                   = None, telemetry=None, clock=time.monotonic,
                   tracer=None, recorder=None):
    """Execute one coalesced group and scatter results back (launch +
    scatter, back to back — the default, non-pipelined drive mode).
    Returns the batch-level :class:`repro.core.pipeline.AidwResult`.
    """
    res, t0 = launch_batch(session, group, clock=clock)
    return scatter_batch(group, res, t0, estimator=estimator,
                         telemetry=telemetry, clock=clock, tracer=tracer,
                         recorder=recorder)
