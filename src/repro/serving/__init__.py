"""Batched serving: LM continuous batching + session-backed AIDW serving.

AIDW serving has two drive modes over ONE deadline-aware coalescer
(``scheduler``): the synchronous :class:`AidwEngine` facade (caller hands it
request lists) and the online :class:`AsyncAidwServer` (admission-queue
worker thread with backpressure, deadline shedding, serialized dataset
updates, and telemetry).

Scale-out lives in the ``repro.serving.cluster`` subpackage: a fleet of
host processes, each one ``AsyncAidwServer`` over a full dataset replica,
kept consistent by an **epoch-numbered update protocol** — every
``update_dataset`` gets a monotonically increasing epoch from one
coordinator and is broadcast into every host's FIFO admission stream, so
all hosts apply the same deltas in the same order between the same batches
(the same barrier the single-process worker provides, reconstructed fleet-
wide).  The contract: a query served by ANY host sees the dataset state a
single server would reach after applying epochs ``1..k`` in order, for the
``k`` stamped on the request — so cluster results are bit-identical to a
single server replaying the same epoch log.  Queries are spread by a
routing layer (round-robin / queue-depth-aware, heartbeat-drained via
``repro.runtime.fault_tolerance``), and per-host latency histograms merge
bin-exactly into fleet p50/p95/p99 (``cluster.telemetry``).  Import from
``repro.serving.cluster`` (kept out of this namespace: the subpackage
imports this one).

Observability (PR 8) rides on :mod:`repro.obs` end to end.  Every layer
reports into ONE :class:`repro.obs.Registry` per engine — the session's
stage walls (``session/plan_s`` .. ``session/query_s``), the serving
histograms ``Telemetry`` registers (``serving/queue_wait_s`` /
``execute_s`` / ``total_s`` / ``shed_s``), and the coalescer's
``serving/coalesce_s`` / ``serving/scatter_s`` — so
``AsyncAidwServer.report()`` (the ``stages`` block),
``metrics_snapshot()``, and the Prometheus text exposition
(``metrics_text()``, names like ``aidw_serving_queue_wait_s``) are views
of the same bins, and the fleet rollup merges them bin-exactly.  Tracing
is opt-in per server (``trace_sample_rate=``; sampling decided once at
the root): a sampled request carries ``trace_id``/``parent_span`` on
:class:`InterpolationRequest` through admission, coalescing, and the rpc
control plane, yielding ``queue_wait``/``coalesce``/``execute``/
``scatter`` spans per request and ``apply_epoch`` spans per update
barrier — one connected cross-host trace per fleet query, exported as
Chrome ``trace_event`` JSON via ``spans()`` +
:func:`repro.obs.chrome_trace`.  Fleet QPS is anchored on the UNION of
per-host wall-clock windows (``Telemetry.state()['window']``), never on
summed per-host rates.
"""

from .engine import AidwEngine, InterpolationRequest, Request, ServingEngine
from .queue import AdmissionQueue, AdmissionQueueClosed, AdmissionQueueFull
from .scheduler import DeadlineCoalescer, ExecuteTimeModel
from .server import AsyncAidwServer
from .telemetry import LatencyHistogram, Telemetry

__all__ = [
    "AidwEngine", "InterpolationRequest", "Request", "ServingEngine",
    "AdmissionQueue", "AdmissionQueueClosed", "AdmissionQueueFull",
    "DeadlineCoalescer", "ExecuteTimeModel",
    "AsyncAidwServer", "LatencyHistogram", "Telemetry",
]
