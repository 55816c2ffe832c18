"""Low-overhead spans with explicit clocks, sampling, and Chrome export.

A :class:`Tracer` records :class:`Span` objects — named intervals tied to a
``trace_id`` (one request / one fleet query / one epoch update) and nested
via ``parent_id``.  The design rules that keep it honest and cheap:

* **Explicit clocks.**  Every tracer is constructed with the clock its
  component already uses (``time.monotonic`` for serving, ``perf_counter``
  for the session) so span timestamps share an epoch with the component's
  own latency bookkeeping, and tests can inject fake clocks.  A wall-clock
  anchor (``wall=time.time``, captured once at construction) shifts
  exported timestamps into a cross-process-comparable timebase so spans
  from different hosts line up in one Chrome trace; pass ``wall=None``
  under fake clocks to keep exports deterministic.
* **Retroactive recording.**  Hot paths that already stamp timestamps
  (``t_submit``/``t_dispatch``/``t_done`` on requests) call
  :meth:`Tracer.record` after the fact instead of holding a context
  manager open — tracing then adds zero work between the timestamps it
  reports.  :meth:`Tracer.span` is the context-manager form for
  code-bracketing spans (plan/compact/fleet phases).
* **Sampling decides at the root, once.**  :meth:`Tracer.new_trace`
  returns a fresh ``trace_id`` with probability ``sample_rate`` and
  ``None`` otherwise; every child call is a no-op when its ``trace_id`` is
  ``None``, so a disabled tracer (rate 0) costs one ``if`` per call site.
* **Device fencing.**  Spans that bracket device work must close only
  after the work is done: call :func:`fence` (``jax.block_until_ready``)
  on the stage's outputs before closing the span, otherwise async dispatch
  attributes a stage's cost to whoever synchronizes later.
* **One clock with the device trace.**  Every code-bracketing span
  (:meth:`Tracer.span`, and :func:`span` for components that hold no
  Tracer) also opens a ``jax.profiler.TraceAnnotation`` of its name, so
  under a JAX profiler session it lands in the ``.xplane.pb`` beside the
  device ops, on the profiler's clock.  With no profiler session active
  the annotation is one object whose enter/exit do nothing.  The profiler
  stamps host events on ``CLOCK_REALTIME`` (``time.time``) and stores them
  relative to the ``profile_start_time`` stat of its ``Task Environment``
  plane; a Tracer anchored with ``wall=time.time`` (the default) exports
  the same span within microseconds of that start.  Retroactive
  :meth:`Tracer.record` spans cannot enter a profiler trace after the fact.

Export formats: :meth:`Tracer.chrome_trace` emits Chrome ``trace_event``
JSON (complete ``"ph": "X"`` events, microsecond timestamps — loads in
``chrome://tracing`` and Perfetto); :meth:`Tracer.export_jsonl` writes one
span dict per line.  :func:`chrome_trace` converts span dicts collected
from many hosts into a single connected trace.
"""

from __future__ import annotations

import contextlib
import functools
import json
import random
import threading
import time
import uuid

__all__ = ["Span", "Tracer", "chrome_trace", "fence", "new_span_id", "span"]


def fence(tree):
    """Block until every array in ``tree`` is computed; returns ``tree``.

    The stage-boundary fencing contract: a span that times device work
    closes after ``fence(outputs)`` so the wall covers the actual compute,
    not just dispatch.  Falls back to per-leaf ``block_until_ready`` when
    JAX is unavailable (the tracer itself never imports JAX at load time).
    """
    try:
        import jax
        return jax.block_until_ready(tree)
    except ImportError:                                   # pragma: no cover
        if hasattr(tree, "block_until_ready"):
            tree.block_until_ready()
        return tree


@functools.cache
def _annotation_class():
    """``jax.profiler.TraceAnnotation``, imported on the first span (the
    tracer never imports JAX at load time); a no-op where JAX is absent."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:                                   # pragma: no cover
        return contextlib.nullcontext
    return TraceAnnotation


def span(name: str) -> "_OpenSpan":
    """Context manager marking a code span in the JAX profiler's trace only
    (no Tracer, no sampling): for components that hold no Tracer, such as
    the serving worker's phases and the ingest path."""
    return _OpenSpan(None, name, None, None, None)


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    """A fresh span id, for callers that must hand a parent id to children
    BEFORE retroactively recording the parent itself (pass it back to
    :meth:`Tracer.record` via ``span_id=``) — e.g. the fleet router, whose
    root ``route`` span only closes after the host already holds the
    request."""
    return _new_id()


class Span:
    """One finished span: a named ``[t0, t0+dur]`` interval on a trace.

    ``t0`` is in the exporting tracer's (wall-anchored) clock, seconds;
    ``dur`` is seconds.  ``host`` labels the recording process (maps to
    the Chrome ``pid`` lane).
    """

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "t0", "dur",
                 "host", "args")

    def __init__(self, name, trace_id, span_id, parent_id, t0, dur,
                 host="0", args=None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = t0
        self.dur = dur
        self.host = host
        self.args = args

    def to_dict(self) -> dict:
        d = {"name": self.name, "trace_id": self.trace_id,
             "span_id": self.span_id, "parent_id": self.parent_id,
             "t0": self.t0, "dur": self.dur, "host": self.host}
        if self.args:
            d["args"] = self.args
        return d


class Tracer:
    """Thread-safe span recorder for ONE process/component.

    Parameters
    ----------
    clock: the component's monotonic clock (injectable for tests); all
        ``t0``/``t1`` arguments to :meth:`record` must be in this clock.
    wall: wall clock used ONCE at construction to anchor exports in a
        cross-process timebase (``None`` => no anchoring; exports stay in
        ``clock``'s epoch — use under fake clocks).
    sample_rate: probability that :meth:`new_trace` starts a sampled trace.
    host: process label for the Chrome ``pid`` lane (host id in a fleet).
    max_spans: retention cap; beyond it new spans are counted in
        ``dropped`` instead of stored (the trace log is a diagnostic ring,
        not an unbounded buffer).
    """

    def __init__(self, clock=time.monotonic, wall=time.time,
                 sample_rate: float = 1.0, host: str = "0",
                 max_spans: int = 100_000, seed=None):
        self.clock = clock
        self.sample_rate = float(sample_rate)
        self.host = str(host)
        self.max_spans = int(max_spans)
        self.dropped = 0
        self._offset = (wall() - clock()) if wall is not None else 0.0
        self._spans: list[Span] = []
        self._lock = threading.Lock()
        self._rng = random.Random(seed)

    # -- trace/span creation -------------------------------------------------

    def new_trace(self) -> str | None:
        """Sampling decision + root id: a fresh ``trace_id`` with
        probability ``sample_rate``, else ``None`` (the whole trace is
        then skipped at every layer for one ``if`` per call)."""
        if self.sample_rate <= 0.0:
            return None
        if self.sample_rate < 1.0 and self._rng.random() >= self.sample_rate:
            return None
        return _new_id()

    def record(self, name: str, t0: float, t1: float, *, trace_id,
               parent_id=None, span_id=None, args=None) -> str | None:
        """Retroactively record a finished span from ``clock``-domain
        timestamps.  No-op (returns ``None``) when ``trace_id`` is None —
        call sites need no sampling branch of their own."""
        if trace_id is None:
            return None
        sid = span_id or _new_id()
        span = Span(name, trace_id, sid, parent_id,
                    t0 + self._offset, max(t1 - t0, 0.0),
                    host=self.host, args=args)
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
            else:
                self._spans.append(span)
        return sid

    def span(self, name: str, *, trace_id, parent_id=None, args=None):
        """Context manager bracketing a code span; yields a handle with
        ``trace_id``/``span_id`` for parenting children.  Device work
        inside must be fenced (:func:`fence`) before the block closes.
        The span always opens a profiler annotation of ``name``; it is
        recorded here only when ``trace_id`` is sampled."""
        return _OpenSpan(self, name, trace_id, parent_id, args)

    # -- collection / export -------------------------------------------------

    def spans(self) -> list[dict]:
        """Copy of the recorded span dicts (oldest first)."""
        with self._lock:
            return [s.to_dict() for s in self._spans]

    def drain(self) -> list[dict]:
        """Return and clear the recorded spans (the rpc collection hook)."""
        with self._lock:
            out = [s.to_dict() for s in self._spans]
            self._spans.clear()
            return out

    def chrome_trace(self) -> dict:
        """This tracer's spans as a Chrome ``trace_event`` JSON object."""
        return chrome_trace(self.spans())

    def export_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)

    def export_jsonl(self, path: str) -> None:
        with open(path, "w") as f:
            for d in self.spans():
                f.write(json.dumps(d) + "\n")


class _OpenSpan:
    """The object returned by :meth:`Tracer.span` and :func:`span`.

    Usable as a context manager.  It always enters a profiler annotation of
    its name; when ``trace_id`` is None nothing is recorded in a Tracer and
    ``span_id`` stays None.
    """

    __slots__ = ("_tracer", "_name", "_parent", "_t0", "_ann", "trace_id",
                 "span_id", "args")

    def __init__(self, tracer, name, trace_id, parent_id, args):
        self._tracer = tracer
        self._name = name
        self._parent = parent_id
        self._t0 = None
        self._ann = _annotation_class()(name)
        self.trace_id = trace_id
        self.span_id = _new_id() if trace_id is not None else None
        self.args = dict(args) if args else None

    def __enter__(self):
        self._ann.__enter__()
        if self.trace_id is not None:
            self._t0 = self._tracer.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.trace_id is not None:
            self._tracer.record(
                self._name, self._t0, self._tracer.clock(),
                trace_id=self.trace_id, parent_id=self._parent,
                span_id=self.span_id, args=self.args)
        self._ann.__exit__(exc_type, exc, tb)
        return False


def chrome_trace(span_dicts) -> dict:
    """Convert span dicts (possibly gathered from many hosts) into one
    Chrome ``trace_event`` JSON object.

    Each span becomes a complete (``"ph": "X"``) event with microsecond
    ``ts``/``dur``; the recording host maps to ``pid`` so a fleet trace
    shows one lane per host, and trace/span/parent ids ride in ``args``
    for programmatic checks.  The result loads in ``chrome://tracing`` and
    Perfetto.
    """
    events = []
    for d in span_dicts:
        args = {"trace_id": d["trace_id"], "span_id": d["span_id"],
                "parent_span": d.get("parent_id")}
        if d.get("args"):
            args.update(d["args"])
        events.append({
            "name": d["name"], "cat": "aidw", "ph": "X",
            "ts": d["t0"] * 1e6, "dur": max(d["dur"], 0.0) * 1e6,
            "pid": f"host-{d.get('host', '0')}",
            "tid": f"host-{d.get('host', '0')}",
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
