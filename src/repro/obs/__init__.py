"""Observability spine: end-to-end tracing + one metrics registry.

This package is the single instrumentation surface for the whole stack —
core session, async server, and the multi-host fleet all report through
it, so BENCH_*.json rows, ``server.report()``, the Prometheus endpoint,
and Chrome traces are different views of the SAME measurements.

Span taxonomy (names are stable API; tests and the benchmark's metric
readers key on them):

* **session** — ``plan`` (one-time Stage-1 build or delta update) with
  sub-spans ``bin`` (CSR binning) and ``staging`` (device upload of the
  new plan or of delta row patches); ``compact`` (LSM ring fold-back).
  ``query`` wraps a whole session query timed with ``timings=True``.
* **serving** (one per request, parented on the request's root span) —
  ``queue_wait`` (submit -> dispatch), ``coalesce`` (batch hold beyond
  the last member's arrival), ``execute`` (dispatch -> results
  materialized on host), ``scatter`` (slice results back per request).
* **fleet** — ``route`` (host pick + submit; a drain resubmission records
  a child ``resubmit`` span under the SAME trace), ``fanout`` (parallel
  shard rpc), ``phase1`` (shard kNN + k-way merge input), ``merge``
  (client-side k-way merge + alpha), ``phase2`` (partial-sum fan-out),
  and ``epoch_update``/``apply_epoch`` for the update barrier path.

Profiler spans (:func:`~repro.obs.trace.span`: a
``jax.profiler.TraceAnnotation`` only, on the serving worker's thread,
never sampled; they exist in a trace only while a JAX profiler session
runs):

* **serving worker** — ``serving.wait_work`` (blocked on the admission
  queue, nothing pending), ``serving.coalesce`` (batch formation and the
  query concatenation), ``serving.launch`` (``session.query``: upload,
  pad, dispatch), ``serving.materialize`` (the host waiting for the
  batch's results), ``serving.scatter`` (slicing results back per
  request), ``serving.apply_update`` (a whole dataset update barrier).
* **ingest** (inside ``serving.apply_update``) — ``ingest.merge`` (host
  mirror rebuild plus the CSR merge, including its pull of the table
  from the device) and ``ingest.stage`` (plan padding and upload, closed
  after a fence; on ``grid_ring`` a second one patches the slab packet).

Device scopes (``jax.named_scope``, in the JAX name stack of every op of
the executables that serve): ``aidw.stage1`` (grid kNN and the mean NN
distance) and ``aidw.stage2`` (alpha and Eq. (1), whichever Stage-2 path
runs).  Pallas kernels carry names of their own (``aidw_tiled``,
``aidw_tiled_fused``, ``aidw_local``, ``aidw_local_fused``).

Counters at the same boundaries (registry): ``session/queries`` and
``session/bucket_slots`` (real and padded rows dispatched),
``ingest/pulled_bytes_total`` (device-to-host bytes a delta update pulls)
and the ``ingest/staged_bytes_total`` gauge (host-to-device bytes staged,
running total).

Clock / fencing contract:

* Every :class:`~repro.obs.trace.Tracer` takes an **explicit clock** — the
  same clock its component stamps request timestamps with — so spans and
  latency histograms share an epoch, and fake-clock tests are exact.  A
  wall-clock anchor captured once at construction aligns exports across
  processes (pass ``wall=None`` under fake clocks).
* Spans that bracket device work close only after
  :func:`~repro.obs.trace.fence` (``jax.block_until_ready``) on the
  stage's outputs — stage walls stay honest on async dispatch backends.
* **Overhead budget**: with sampling off (``sample_rate=0``) the entire
  subsystem costs one ``None``-check per call site, plus one profiler
  annotation object per code-bracketing span (a no-op with no profiler
  session) — enforced <2% on serving p99 by the
  ``serving/trace_overhead_p99_ratio`` load_gen gate.

Trace propagation: a sampled request carries ``trace_id``/``parent_span``
on ``InterpolationRequest``, across the JSON/TCP rpc control plane,
through ``EpochUpdate`` barriers and router drain-resubmission, so one
fleet query yields ONE connected cross-host trace.
:func:`~repro.obs.trace.chrome_trace` renders collected span dicts as
Chrome ``trace_event`` JSON (loads in ``chrome://tracing``/Perfetto).

Registry -> Prometheus naming: see :mod:`repro.obs.metrics` — internal
slash-namespaced names (``session/plan_s``) export as
``aidw_session_plan_s`` (counters ``_total``-suffixed, histograms
summary-style with ``quantile`` labels, ``# HELP``/``# TYPE`` per
family).  Histograms carry per-bin **exemplars**
(``observe(..., exemplar=trace_id)``): a p99 bucket links straight to a
flight-recorder trace.

Always-on vs sampled — the two tiers of the tail story:

* The **Tracer** is HEAD-sampled (root decides at submit); production
  runs it at ``sample_rate=0``, so it explains requests you chose in
  advance, never the stragglers.
* The :class:`~repro.obs.recorder.FlightRecorder` is ALWAYS-ON and
  TAIL-sampled: every request pays a fixed-size coarse breakdown
  (queue_wait/coalesce/execute/scatter floats off the existing fence
  points), and the full span tree is retained in a bounded ring only
  when the request is anomalous.  Anomaly classes (stable API):
  ``deadline_miss``, ``shed``, ``overflow``, ``zero_weight``, and
  ``slow`` (total at/above the recorder's own running
  ``top_percentile``, armed after ``min_window`` observations).
  Retention is deterministic under fake clocks; evictions are counted in
  ``dropped``.  Because the recorder is always-on it lives INSIDE the
  <2% p99 budget — the load_gen overhead gate re-verifies p99 <=1.02x
  with the recorder enabled.
* The :class:`~repro.obs.slo.SloMonitor` evaluates burn-rate windows
  over cumulative counters (deadline-miss rate, shed rate) plus gauge
  thresholds (queue depth, ring occupancy vs ``compact_highwater``) on
  the COLD path only (``report()``/``debugz()`` pulls); breaches emit
  edge-triggered events into the recorder's event ring.  Fleet epoch
  staleness is derived at the ``AidwCluster.debugz()`` merge point.
* :func:`~repro.obs.attribution.tail_attribution` decomposes the
  p99−p50 gap into per-stage contributions from the retained outliers
  (proportional to each additive stage's tail excess over its p50), with
  a stall block for ``session/compact_stall_s`` and
  ``serving/epoch_barrier_s`` — rendered as JSON and text
  (:func:`~repro.obs.attribution.render_attribution`).
"""

from .attribution import render_attribution, tail_attribution
from .metrics import Counter, Gauge, Histogram, Registry
from .recorder import FlightRecorder
from .slo import SloMonitor, fleet_epoch_events
from .trace import Span, Tracer, chrome_trace, fence, new_span_id, span

__all__ = ["Counter", "FlightRecorder", "Gauge", "Histogram", "Registry",
           "SloMonitor", "Span", "Tracer", "chrome_trace", "fence",
           "fleet_epoch_events", "new_span_id", "render_attribution",
           "span", "tail_attribution"]
