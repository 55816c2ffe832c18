"""Open-loop load generator for the async AIDW serving subsystem.

Drives :class:`repro.serving.AsyncAidwServer` with OPEN-LOOP Poisson
arrivals — requests are submitted at exponentially-spaced instants from a
pre-drawn trace, regardless of completions, so queueing delay under
overload is measured instead of hidden (a closed-loop generator would
self-throttle and report flattering latencies).

The trace mixes deadline classes (``--deadline-frac`` of requests carry a
deadline drawn from ``--deadline-ms``; the rest are best-effort) and
odd-sized request bodies, which exercises the deadline-aware coalescer and
the session's power-of-two bucketing together.

Output: CSV rows via :func:`load_rows` (wired into ``benchmarks/run.py``)
or a JSON latency report with ``--json`` (the CI serving-suite job uploads
it as the latency-trajectory artifact next to the session benchmark):

    {"config": {...}, "report": {submitted, completed, shed, queries_per_s,
                                 latency: {queue, execute, total:
                                           {p50_s, p95_s, p99_s, ...}}},
     "lost": 0, "duplicated": 0}

``--mesh`` serves the load over every visible device (run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to simulate a pod
slice on CPU).  ``--cluster N`` replays the SAME open-loop trace against an
N-host :class:`repro.serving.cluster.AidwCluster` fleet — queries routed
across hosts, updates broadcast as epoch-ordered barriers — and reports the
MERGED fleet telemetry (per-host histograms merged bin-exactly into fleet
p50/p95/p99, QPS and shed counters summed; per-host reports attached).
``--cluster-procs`` backs every host but the coordinator's with a real
subprocess over the socket control plane.

Tracing (PR 8): ``--trace-sample-rate P`` turns on end-to-end spans —
single-server mode builds the server's tracer at rate ``P``; cluster mode
samples at the ROUTER (rate ``P``) and runs every host's tracer at rate 0
so propagated contexts are recorded but no fleet-invisible roots start.
``--trace-out PATH`` writes the collected spans as Chrome ``trace_event``
JSON (loads in ``chrome://tracing``/Perfetto; the CI cluster-suite uploads
it as the sample-trace artifact).  ``--trace-overhead-gate`` runs the
observability overhead acceptance check instead of a plain load run: two
identical loads, one with no tracer and no flight recorder, one with a
sample-rate-0 tracer plus the always-on recorder (the production
configuration), and RAISES when the instrumented p99 exceeds
``TRACE_OVERHEAD_LIMIT`` (2%) over baseline — best of 3 attempts, since
open-loop p99 on a shared CPU box is noisy and the gate exists to catch
hot-path instrumentation cost, not scheduler jitter.  The same flag then
runs the tail-sampling retention gate (``recorder_retention_rows``): a
deadline-heavy trace where >= 95% of missed-deadline requests must retain
full span trees, zero in-SLO requests may be retained, and the tail
attribution must decompose the p99-p50 gap within 15%.  ``--debugz-out
PATH`` (PR 9) writes the diagnostics bundle — fleet-merged under
``--cluster`` — as the CI debugz artifact.  Standalone:

    PYTHONPATH=src python benchmarks/load_gen.py [--json] [--mesh]
        [--requests N] [--rate QPS] [--updates K]
        [--cluster N [--cluster-procs]] [--policy least_loaded]
        [--trace-sample-rate P] [--trace-out trace.json]
        [--trace-overhead-gate] [--debugz-out debugz.json]
        [--no-warmup] [--prewarm sync|background]
        [--compilation-cache-dir DIR]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from repro.data.pipeline import spatial_points, spatial_queries
from repro.serving import AsyncAidwServer


def make_trace(n_requests: int, rate_rps: float, req_queries: int,
               deadline_frac: float, deadline_ms: tuple, seed: int = 0):
    """Pre-draw the open-loop arrival trace.

    Returns a list of ``(t_arrival_s, n_queries, deadline_s_or_None)``:
    exponential inter-arrivals at ``rate_rps`` requests/s, odd-ish request
    sizes around ``req_queries``, and a ``deadline_frac`` mix of
    deadline-bound requests with deadlines drawn uniformly from
    ``deadline_ms`` (milliseconds, relative to arrival).
    """
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_rps, n_requests)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(n_requests):
        n = max(1, req_queries - int(rng.integers(0, max(req_queries // 3,
                                                         2))))
        deadline = None
        if rng.random() < deadline_frac:
            deadline = float(rng.uniform(*deadline_ms)) / 1e3
        trace.append((float(arrivals[i]), n, deadline))
    return trace


def run_load(server, trace, *, updates: int = 0,
             points: int = 0, seed: int = 0,
             write_rate_rps: float = 0.0, write_batch: int = 32,
             write_bbox=None) -> dict:
    """Replay ``trace`` against ``server`` (open loop), optionally weaving
    ``updates`` incremental dataset deltas through the admission stream at
    even intervals.  Returns the JSON report body.

    ``write_rate_rps > 0`` turns on the MIXED read/write mode: writer
    arrivals are drawn from their own open-loop Poisson process over the
    trace horizon, and each due write submits one balanced
    ``write_batch``-point delta NON-BLOCKING (``submit_update``) — a FIFO
    barrier in the admission stream, never a stop-the-world wait — with all
    write handles awaited at flush time.  ``write_bbox`` (lo, hi) clips
    insert coordinates into the frozen grid bbox so the O(Δ) delta path is
    what gets measured, not the out-of-bbox full-re-plan fallback.
    Single-server mode only (the fleet's epoch-ordered writes go through
    ``update_dataset``/``compact``).

    ``server`` is anything with the submit/update_dataset/flush/report
    surface: an :class:`AsyncAidwServer` or a multi-host
    :class:`repro.serving.cluster.AidwCluster` (whose ``report()`` nests
    the merged fleet view — ``drive_cluster`` flattens it)."""
    rng = np.random.default_rng(seed + 1)
    update_every = len(trace) // (updates + 1) if updates else None
    write_arrivals = []
    if write_rate_rps > 0:
        wr = np.random.default_rng(seed + 7)
        t = wr.exponential(1.0 / write_rate_rps)
        while t < trace[-1][0]:
            write_arrivals.append(t)
            t += wr.exponential(1.0 / write_rate_rps)
    reqs, write_ops, wi = [], [], 0
    t0 = time.monotonic()
    for i, (t_arrival, n, deadline_s) in enumerate(trace):
        if update_every and i and i % update_every == 0 \
                and len(reqs) // update_every <= updates:
            d = max(points // 100, 1)
            server.update_dataset(
                inserts=spatial_points(d, seed=seed + 50 + i),
                deletes=rng.choice(max(points - d, 1), d, replace=False))
        while wi < len(write_arrivals) and write_arrivals[wi] <= t_arrival:
            ins = spatial_points(write_batch, seed=seed + 5000 + wi)
            if write_bbox is not None:
                ins[:, :2] = np.clip(ins[:, :2], *write_bbox)
            write_ops.append(server.submit_update(
                inserts=ins,
                deletes=rng.choice(max(points, 1), write_batch,
                                   replace=False),
                timeout=60))
            wi += 1
        now = time.monotonic() - t0
        if t_arrival > now:                  # open loop: wait for the slot,
            time.sleep(t_arrival - now)      # never for completions
            now = t_arrival
        # deadlines are anchored at the TRACE arrival, not at submit: when
        # submission falls behind (update barrier blocking, backpressure),
        # a delayed request must NOT gain deadline budget — that is exactly
        # the overload signal an open-loop harness exists to report
        reqs.append(server.submit(
            spatial_queries(n, seed=seed + 1000 + i),
            deadline_s=None if deadline_s is None
            else t_arrival + deadline_s - now))
    wall_submit = time.monotonic() - t0
    for op in write_ops:
        server.wait_update(op, timeout=600)
    server.flush(timeout=600)
    wall_total = time.monotonic() - t0

    terminal = [r for r in reqs if r.status in ("done", "shed")]
    report = server.report()
    return {
        "report": report,
        "offered_rps": len(trace) / max(wall_submit, 1e-9),
        "wall_s": wall_total,
        "writes": len(write_ops),
        "lost": len(reqs) - len(terminal),
        "duplicated": len(reqs) - len({r.uid for r in reqs}),
        # the request objects themselves (NOT JSON: the CLI pops this
        # before serializing) — the recorder retention gate needs per-
        # request terminal state to cross-check against retained traces
        "_reqs": reqs,
    }


def drive(points: int, trace, *, max_batch: int = 4096, mesh=None,
          updates: int = 3, req_queries: int = 96, seed: int = 0,
          pipeline_depth: int = 0, layout: str = "replicated",
          ring_cap: int = 1024, write_rate_rps: float = 0.0,
          write_batch: int = 32,
          trace_sample_rate: float | None = None,
          record_tail: bool = True, recorder_opts: dict | None = None,
          debugz: bool = False, warmup: bool = True,
          prewarm: str | None = None) -> dict:
    """Build a server, warm it, and replay ``trace`` (shared by the CSV rows
    and the JSON CLI so both measure the same configuration).

    Warmup primes the executables + the scheduler's execute-time model,
    then telemetry is RESET so the reported window reflects steady state,
    not first-bucket compiles.  ``warmup=False`` (``--no-warmup``) skips
    both, so the replay measures the COLD trajectory — first-bucket
    compiles land inside the reported latencies (the cold-start rows; pair
    with a persistent compilation cache to measure the restart path).
    ``prewarm`` passes through to :class:`AsyncAidwServer` (AOT-compile
    the whole bucket ladder at construction).  ``pipeline_depth`` turns on the worker's
    launch-ahead pipelining (``--pipeline``; a measured experiment — see
    ROADMAP's post-PR-5 re-triage for the CPU result).  ``write_rate_rps``
    turns on the mixed read/write open-loop mode (:func:`run_load`);
    ``layout='grid_ring'`` (+ ``mesh``) serves writes through the O(Δ)
    per-slab delta staging instead of a full re-stage per delta.
    ``trace_sample_rate`` builds the server's tracer at that rate (``None``
    = no tracer at all — the overhead-gate baseline); collected spans ride
    out under ``"spans"``.  ``record_tail=False`` drops the always-on
    flight recorder too (the PR-9 overhead-gate baseline: no observability
    objects at all on the hot path); ``recorder_opts`` pass through to
    :class:`repro.obs.FlightRecorder` (the retention gate pins
    ``top_percentile=None`` so retention is a pure function of the trace);
    ``debugz=True`` attaches the server's diagnostics bundle under
    ``"debugz"``.
    """
    pts = spatial_points(points, seed=seed)
    with AsyncAidwServer(pts, max_batch=max_batch, mesh=mesh, layout=layout,
                         ring_cap=ring_cap, pipeline_depth=pipeline_depth,
                         trace_sample_rate=trace_sample_rate,
                         record_tail=record_tail, recorder_opts=recorder_opts,
                         prewarm=prewarm,
                         query_domain=spatial_queries(1024, seed=1)) as srv:
        if warmup:
            for _ in range(3):
                srv.submit(spatial_queries(req_queries, seed=2))
            srv.flush(timeout=600)
            srv.telemetry.reset()
            srv.spans()                 # drop warmup spans ([] if no tracer)
            for k in srv.queue.counters:
                srv.queue.counters[k] = 0
        out = run_load(srv, trace, updates=updates, points=points,
                       seed=seed, write_rate_rps=write_rate_rps,
                       write_batch=write_batch,
                       write_bbox=(pts[:, :2].min(axis=0),
                                   pts[:, :2].max(axis=0)))
        if trace_sample_rate:
            out["spans"] = srv.spans()
        if debugz:
            out["debugz"] = srv.debugz()
        return out


def drive_cluster(points: int, trace, *, n_hosts: int, procs: bool = False,
                  max_batch: int = 4096, updates: int = 3,
                  req_queries: int = 96, seed: int = 0,
                  policy: str = "round_robin", mesh=None,
                  trace_sample_rate: float | None = None,
                  debugz: bool = False, warmup: bool = True) -> dict:
    """Replay ``trace`` against an ``n_hosts`` fleet; returns the merged
    fleet report (flattened: ``report`` = fleet view, ``hosts``/``routing``
    attached).

    ``procs=True`` runs every host except host 0 as a REAL subprocess
    behind the socket control plane (``repro.serving.cluster.rpc``) — the
    multi-host deployment shape, minus the machines.  ``mesh`` applies to
    IN-PROCESS hosts only (they share this process's devices); subprocess
    hosts build their own local mesh from their own visible devices.
    ``trace_sample_rate`` samples at the ROUTER; hosts (subprocess ones
    included) run their tracers at rate 0 so they record propagated
    contexts without starting fleet-invisible roots; spans collected from
    every live host ride out under ``"spans"``.  ``debugz=True`` attaches
    the MERGED fleet diagnostics bundle (per-host debugz + fleet-level
    SLO events + tail-latency attribution) under ``"debugz"``.
    """
    import os

    from repro.serving.cluster import AidwCluster, HostServer, RemoteHost
    from repro.serving.cluster.rpc import free_port_base, spawn_worker

    pts = spatial_points(points, seed=seed)
    qd = spatial_queries(1024, seed=1)
    workers, hosts = [], None
    host_rate = 0.0 if trace_sample_rate is not None else None
    if procs and n_hosts > 1:
        # host 0 runs in this process: start its backend before spawning,
        # so spawn_worker refuses on a TPU instead of letting a child take
        # the chip this process needs (one process per chip)
        import jax

        jax.devices()
        base = free_port_base(n_hosts)
        env = dict(os.environ)
        env.setdefault("PYTHONPATH", "src")
        workers = [spawn_worker(i, n_hosts, points=points, seed=seed,
                                control_port=base, max_batch=max_batch,
                                trace_sample_rate=host_rate, env=env)
                   for i in range(1, n_hosts)]
        hosts = [HostServer(0, pts, max_batch=max_batch, query_domain=qd,
                            trace_sample_rate=host_rate)] \
            + [RemoteHost(i, ("127.0.0.1", base + i), connect_timeout_s=300)
               for i in range(1, n_hosts)]
    try:
        with AidwCluster(None if hosts else pts, n_hosts=n_hosts,
                         hosts=hosts, policy=policy,
                         trace_sample_rate=trace_sample_rate,
                         **({} if hosts else
                            {"max_batch": max_batch,
                             "query_domain": qd, "mesh": mesh})) as cl:
            if warmup:
                # parallel warmup: every host compiles its executables
                # CONCURRENTLY under one fleet deadline (cold-start used to
                # be per-host sequential and dominated the 2-host CPU bench
                # rows)
                cl.warmup(spatial_queries(req_queries, seed=2),
                          batches_per_host=3, timeout=600)
                cl.reset_telemetry()
            out = run_load(cl, trace, updates=updates, points=points,
                           seed=seed)
            rep = out["report"]              # AidwCluster.report(): nested
            out["report"] = rep["fleet"]
            out["hosts"] = rep["hosts"]
            out["routing"] = rep["routing"]
            out["epoch"] = rep["epoch"]
            if trace_sample_rate:
                out["spans"] = cl.collect_spans()
            if debugz:
                out["debugz"] = cl.debugz()
    finally:
        for w in workers:
            try:
                w.wait(timeout=60)
            except Exception:
                w.kill()
    return out


def load_rows(n_requests: int = 96, rate_rps: float = 400.0,
              req_queries: int = 96, points: int = 16384,
              deadline_frac: float = 0.25,
              deadline_ms: tuple = (20.0, 200.0), updates: int = 3,
              seed: int = 0, mesh=None) -> list[tuple]:
    """CSV rows for benchmarks/run.py (schema name,us_per_call,derived)."""
    trace = make_trace(n_requests, rate_rps, req_queries, deadline_frac,
                       deadline_ms, seed=seed)
    out = drive(points, trace, mesh=mesh, updates=updates,
                req_queries=req_queries, seed=seed)
    rep = out["report"]
    lat = rep["latency"]
    if out["lost"] or out["duplicated"]:
        raise RuntimeError(f"load run lost/duplicated requests: "
                           f"{out['lost']}/{out['duplicated']}")
    tag = f"{points}x{req_queries}@{rate_rps:.0f}rps"
    return [
        (f"serving/load_total_p50/{tag}", lat["total"]["p50_s"] * 1e6,
         f"{rep['queries_per_s']:.0f} q/s served, "
         f"{out['offered_rps']:.0f} req/s offered"),
        (f"serving/load_total_p99/{tag}", lat["total"]["p99_s"] * 1e6,
         f"queue p99 {lat['queue']['p99_s'] * 1e3:.1f}ms, "
         f"execute p99 {lat['execute']['p99_s'] * 1e3:.1f}ms"),
        (f"serving/load_shed/{tag}", 0.0,
         f"{rep['shed']} shed / {rep['completed']} completed "
         f"({updates} delta updates interleaved)"),
    ]


def mixed_rows(n_requests: int = 96, rate_rps: float = 400.0,
               req_queries: int = 96, points: int = 16384,
               write_rate_rps: float = 25.0, write_batch: int = 32,
               seed: int = 0, p99_ratio_limit: float = 1.5) -> list[tuple]:
    """Sustained-churn rows: read-only vs mixed read/write p99 at the SAME
    offered read load, served from a ``grid_ring`` session whose writes ride
    the O(Δ) per-slab delta staging + hot append rings.

    The acceptance gate RAISES when the mixed-workload p99 exceeds
    ``p99_ratio_limit`` x the read-only p99 (best of two attempts — open-
    loop p99 on a shared CPU CI box is noisy, and the gate exists to catch
    systematic write-path stalls, not scheduler jitter), or when any
    request is lost/duplicated under churn (the mixed-workload invariant).

    The offered load is CALIBRATED to the box before the comparison: at
    oversaturation an open-loop p99 measures queue depth, which grows with
    ANY extra work — the ratio would trip on healthy write paths on slow
    machines and hide real stalls on fast ones.  A short saturating burst
    measures read capacity; both runs then offer ~40% of it (capped at
    ``rate_rps``), with the writer rate capped at a 1:4 write:read ratio."""
    import jax

    mesh = jax.make_mesh((len(jax.devices()),), ("q",))
    kw = dict(mesh=mesh, layout="grid_ring", updates=0,
              req_queries=req_queries, seed=seed)
    cal = drive(points, make_trace(12, 1000.0, req_queries, 0.0,
                                   (0.0, 0.0), seed=seed), **kw)
    cap_rps = cal["report"]["queries_per_s"] / req_queries
    rate_rps = max(min(rate_rps, 0.4 * cap_rps), 2.0)
    write_rate_rps = max(min(write_rate_rps, rate_rps / 4), 1.0)
    # deadline-free trace: a shed tail would censor exactly the p99 this
    # row compares across the two runs
    trace = make_trace(n_requests, rate_rps, req_queries,
                       deadline_frac=0.0, deadline_ms=(0.0, 0.0), seed=seed)
    for attempt in (1, 2):
        ro = drive(points, trace, **kw)
        mixed = drive(points, trace, write_rate_rps=write_rate_rps,
                      write_batch=write_batch, **kw)
        for out in (ro, mixed):
            if out["lost"] or out["duplicated"]:
                raise RuntimeError(
                    f"mixed-workload run lost/duplicated requests: "
                    f"{out['lost']}/{out['duplicated']}")
        ro_p99 = ro["report"]["latency"]["total"]["p99_s"]
        mx_p99 = mixed["report"]["latency"]["total"]["p99_s"]
        ratio = mx_p99 / max(ro_p99, 1e-9)
        if ratio <= p99_ratio_limit:
            break
    if ratio > p99_ratio_limit:
        raise RuntimeError(
            f"mixed-workload acceptance gate: p99 ratio {ratio:.2f}x > "
            f"{p99_ratio_limit}x at {write_rate_rps:.0f} writes/s "
            f"(read-only {ro_p99 * 1e3:.1f}ms, mixed {mx_p99 * 1e3:.1f}ms)")
    sess = mixed["report"]["session"]
    tag = f"{points}x{req_queries}@{rate_rps:.0f}r+{write_rate_rps:.0f}w"
    return [
        (f"serving/churn_read_p99/{tag}", ro_p99 * 1e6,
         f"read-only baseline, {ro['report']['queries_per_s']:.0f} q/s"),
        (f"serving/churn_mixed_p99/{tag}", mx_p99 * 1e6,
         f"{ratio:.2f}x read-only p99 (limit {p99_ratio_limit}x), "
         f"{mixed['writes']} writes of {write_batch} pts applied"),
        (f"serving/churn_staged_bytes/{tag}",
         sess.get("staged_bytes", 0),
         f"last delta staged {sess.get('staged_bytes', 0)} B, ring "
         f"{sess.get('ring_occupancy', 0.0):.0%} full, "
         f"{sess.get('compactions', 0)} compactions, "
         f"{sess.get('spilled_updates', 0)} spills"),
    ]


TRACE_OVERHEAD_LIMIT = 1.02     # traced/baseline p99 ceiling (the <2% story)


def trace_overhead_rows(n_requests: int = 64, rate_rps: float = 200.0,
                        req_queries: int = 96, points: int = 16384,
                        seed: int = 0, attempts: int = 3) -> list[tuple]:
    """The always-on observability overhead acceptance gate.

    Replays one open-loop trace twice — baseline with NO observability
    objects anywhere on the hot path (``trace_sample_rate=None`` +
    ``record_tail=False``: the pre-PR-8 configuration) vs the full
    production configuration (``trace_sample_rate=0.0``: tracer built,
    sampler never admits; flight recorder ON, classifying and recording
    every request) — and RAISES when the instrumented p99 exceeds
    ``TRACE_OVERHEAD_LIMIT`` x baseline on the best of ``attempts`` runs.
    Deadline-free trace (a shed tail would censor the very p99 under
    comparison) at a sub-saturation rate (at oversaturation p99 measures
    queue depth, which amplifies any jitter into false trips)."""
    trace = make_trace(n_requests, rate_rps, req_queries,
                       deadline_frac=0.0, deadline_ms=(0.0, 0.0), seed=seed)
    kw = dict(updates=0, req_queries=req_queries, seed=seed)
    best = float("inf")
    for _ in range(attempts):
        base = drive(points, trace, trace_sample_rate=None,
                     record_tail=False, **kw)
        traced = drive(points, trace, trace_sample_rate=0.0,
                       record_tail=True, **kw)
        for out in (base, traced):
            if out["lost"] or out["duplicated"]:
                raise RuntimeError(
                    f"trace-overhead run lost/duplicated requests: "
                    f"{out['lost']}/{out['duplicated']}")
        b99 = base["report"]["latency"]["total"]["p99_s"]
        t99 = traced["report"]["latency"]["total"]["p99_s"]
        ratio = t99 / max(b99, 1e-12)
        best = min(best, ratio)
        if best <= TRACE_OVERHEAD_LIMIT:
            break
    if best > TRACE_OVERHEAD_LIMIT:
        raise RuntimeError(
            f"trace overhead gate: rate-0 tracing + flight recorder p99 is "
            f"{best:.3f}x baseline (> {TRACE_OVERHEAD_LIMIT}x) over "
            f"{attempts} attempts "
            f"(baseline {b99 * 1e3:.2f}ms, instrumented {t99 * 1e3:.2f}ms)")
    tag = f"{points}x{req_queries}@{rate_rps:.0f}rps"
    return [
        (f"serving/trace_overhead_p99_ratio/{tag}", 0.0,
         f"rate-0 tracing + recorder p99 {best:.3f}x baseline "
         f"(limit {TRACE_OVERHEAD_LIMIT}x, best of {attempts})"),
    ]


def recorder_retention_rows(n_requests: int = 48, rate_rps: float = 300.0,
                            req_queries: int = 96, points: int = 16384,
                            seed: int = 0) -> list[tuple]:
    """The tail-sampling retention acceptance gate.

    Replays a deadline-heavy open-loop trace (half the requests carry
    deadlines drawn from 0.5–10ms — tight enough that some MUST miss under
    real dispatch latency) against a recorder with the noise classes off
    (``top_percentile=None``: no 'slow' class, so retention is a pure
    function of each request's own outcome) and a ring large enough that
    nothing evicts.  Asserts the ISSUE-9 acceptance bars:

    - >= 95% of requests that MISSED their deadline (shed at admission/
      dispatch, or served past it) have a full span tree retained;
    - ZERO in-SLO requests (served in time, no overflow, no zero-weight
      neighborhoods) retained — tail sampling, not head sampling;
    - the tail-latency attribution built from the recorder's state
      decomposes the p99-p50 gap into per-stage contributions whose sum
      lands within 15% of the gap (exact by construction when any additive
      stage shows positive excess — the row records the residual).
    """
    from repro.obs import tail_attribution

    trace = make_trace(n_requests, rate_rps, req_queries,
                       deadline_frac=0.5, deadline_ms=(0.5, 10.0), seed=seed)
    out = drive(points, trace, updates=0, req_queries=req_queries, seed=seed,
                trace_sample_rate=0.0, record_tail=True,
                recorder_opts={"top_percentile": None,
                               "ring": 4 * n_requests},
                debugz=True)
    if out["lost"] or out["duplicated"]:
        raise RuntimeError(f"retention run lost/duplicated requests: "
                           f"{out['lost']}/{out['duplicated']}")
    reqs = out["_reqs"]
    rec = out["debugz"]["recorder"]
    retained = {t["id"] for t in rec["traces"]}

    def rec_id(r):
        return getattr(r, "trace_id", None) or f"req-{r.uid}"

    missed = [r for r in reqs
              if r.status == "shed"
              or (r.deadline is not None and r.status == "done"
                  and r.t_done is not None and r.t_done > r.deadline)]
    in_slo = [r for r in reqs
              if r.status == "done" and not r.overflow
              and not getattr(r, "zero_weight", 0)
              and (r.deadline is None
                   or (r.t_done is not None and r.t_done <= r.deadline))]
    miss_kept = sum(rec_id(r) in retained for r in missed)
    slo_kept = [rec_id(r) for r in in_slo if rec_id(r) in retained]
    if missed and miss_kept < 0.95 * len(missed):
        raise RuntimeError(
            f"retention gate: only {miss_kept}/{len(missed)} missed-deadline "
            f"requests have retained span trees (need >= 95%; recorder "
            f"dropped={rec['dropped']})")
    if slo_kept:
        raise RuntimeError(
            f"retention gate: {len(slo_kept)} in-SLO requests retained "
            f"(tail sampling must retain zero): {slo_kept[:5]}")

    attr = tail_attribution([rec],
                            registry_state=out["debugz"].get("registry"))
    gap, attributed = attr["gap_s"], attr["attributed_s"]
    residual = abs(attributed - gap) / max(gap, 1e-12)
    if gap > 0 and any(s["tail_mean_s"] > 0
                       for s in attr["stages"].values()
                       if s.get("additive")) and residual > 0.15:
        raise RuntimeError(
            f"attribution identity: per-stage contributions sum to "
            f"{attributed * 1e3:.2f}ms vs p99-p50 gap {gap * 1e3:.2f}ms "
            f"({residual:.0%} residual > 15%)")
    tag = f"{points}x{req_queries}@{rate_rps:.0f}rps"
    return [
        (f"serving/recorder_retention/{tag}", 0.0,
         f"{miss_kept}/{len(missed)} missed-deadline requests retained, "
         f"0/{len(in_slo)} in-SLO retained, "
         f"attribution residual {residual:.1%} of "
         f"{gap * 1e3:.2f}ms gap"),
    ]


def cluster_rows(n_requests: int = 64, rate_rps: float = 300.0,
                 req_queries: int = 96, points: int = 16384,
                 updates: int = 2, seed: int = 0,
                 policy: str = "round_robin") -> list[tuple]:
    """1-host vs 2-host fleet at the SAME offered load: the scale-out
    trajectory rows for benchmarks/run.py (QPS + p99 per width, plus the
    2-host scale-out efficiency = qps2 / (2 * qps1))."""
    trace = make_trace(n_requests, rate_rps, req_queries,
                       deadline_frac=0.25, deadline_ms=(20.0, 200.0),
                       seed=seed)
    rows = []
    qps = {}
    for n_hosts in (1, 2):
        out = drive_cluster(points, trace, n_hosts=n_hosts, updates=updates,
                            req_queries=req_queries, seed=seed,
                            policy=policy)
        rep = out["report"]
        if out["lost"] or out["duplicated"]:
            # explicit raise, not assert: python -O must not turn a lost/
            # duplicated request into a silently wrong scale-out row
            raise RuntimeError(f"cluster load run lost/duplicated requests: "
                               f"{out['lost']}/{out['duplicated']}")
        qps[n_hosts] = rep["queries_per_s"]
        tag = f"{points}x{req_queries}@{rate_rps:.0f}rps/{n_hosts}host"
        rows.append(
            (f"cluster/load_total_p99/{tag}",
             rep["latency"]["total"]["p99_s"] * 1e6,
             f"{rep['queries_per_s']:.0f} q/s fleet, {rep['shed']} shed, "
             f"epochs {rep['epoch_min']}..{rep['epoch_max']}"))
    rows.append(
        (f"cluster/scaleout_eff/{points}x{req_queries}@{rate_rps:.0f}rps",
         0.0,
         f"2-host efficiency {qps[2] / max(2 * qps[1], 1e-9):.2f} "
         f"({qps[1]:.0f} -> {qps[2]:.0f} q/s)"))
    return rows


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--points", type=int, default=16384)
    p.add_argument("--requests", type=int, default=96)
    p.add_argument("--rate", type=float, default=400.0,
                   help="offered load, requests/s (open loop)")
    p.add_argument("--req-queries", type=int, default=96)
    p.add_argument("--max-batch", type=int, default=4096)
    p.add_argument("--deadline-frac", type=float, default=0.25,
                   help="fraction of requests carrying a deadline")
    p.add_argument("--deadline-ms", type=float, nargs=2,
                   default=(20.0, 200.0))
    p.add_argument("--updates", type=int, default=3,
                   help="incremental dataset updates woven into the stream")
    p.add_argument("--write-rate", type=float, default=0.0, metavar="WPS",
                   help="mixed read/write mode: open-loop Poisson writer "
                        "arrivals/s, each a balanced --write-batch delta "
                        "submitted non-blocking (single-server mode only)")
    p.add_argument("--write-batch", type=int, default=32)
    p.add_argument("--layout", default="replicated",
                   choices=("replicated", "ring", "grid_ring"),
                   help="session layout (grid_ring = O(Delta) ingest path; "
                        "needs --mesh)")
    p.add_argument("--pipeline", type=int, default=0, metavar="DEPTH",
                   help="worker launch-ahead pipelining depth (0 = off; "
                        "single-server mode only)")
    p.add_argument("--mesh", action="store_true",
                   help="serve across every visible device")
    p.add_argument("--cluster", type=int, default=0, metavar="N",
                   help="serve from an N-host fleet and report MERGED "
                        "fleet telemetry")
    p.add_argument("--cluster-procs", action="store_true",
                   help="back fleet hosts 1..N-1 with real subprocesses "
                        "(socket control plane)")
    p.add_argument("--policy", default="round_robin",
                   choices=("round_robin", "least_loaded"))
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--no-warmup", action="store_true",
                   help="skip the warmup batches + telemetry reset, so the "
                        "replay measures the COLD trajectory (first-bucket "
                        "compiles land inside the reported latencies)")
    p.add_argument("--prewarm", choices=("background", "sync"), default=None,
                   help="AOT-compile + warm the whole bucket ladder at "
                        "server construction (single-server mode; 'sync' "
                        "blocks, 'background' compiles off the worker "
                        "thread)")
    p.add_argument("--compilation-cache-dir", metavar="DIR", default=None,
                   help="persistent XLA compilation cache directory "
                        "(JAX_COMPILATION_CACHE_DIR env wins; default: "
                        "AIDW_CACHE_DIR env, else the checkout's "
                        ".jax_cache; a restart with the same directory "
                        "deserializes instead of recompiling)")
    p.add_argument("--trace-sample-rate", type=float, default=None,
                   metavar="P",
                   help="end-to-end tracing: root sample rate (cluster "
                        "mode samples at the router; hosts record at rate "
                        "0). 0.0 = tracer on, sampler off (the overhead-"
                        "gate configuration)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write collected spans as Chrome trace_event JSON "
                        "(needs --trace-sample-rate > 0; CI uploads it as "
                        "the sample-trace artifact)")
    p.add_argument("--trace-overhead-gate", action="store_true",
                   help="run the observability overhead acceptance gate "
                        "(<2% p99 with rate-0 tracing + flight recorder ON "
                        "over a bare baseline, best of 3) plus the tail-"
                        "sampling retention gate instead of a plain load "
                        "run; raises on failure")
    p.add_argument("--debugz-out", default=None, metavar="PATH",
                   help="write the diagnostics bundle (queue/epoch state, "
                        "SLO events, flight-recorder traces, tail-latency "
                        "attribution; fleet-merged in --cluster mode) as "
                        "JSON to PATH after the run")
    p.add_argument("--json", action="store_true",
                   help="emit the full JSON latency report (CI artifact)")
    args = p.parse_args()

    # before any compile (directory rules: compile_cache.enable)
    from repro.runtime import compile_cache
    compile_cache.enable(args.compilation_cache_dir)

    if args.trace_overhead_gate:
        rows = trace_overhead_rows(n_requests=args.requests,
                                   req_queries=args.req_queries,
                                   points=args.points, seed=args.seed)
        rows += recorder_retention_rows(req_queries=args.req_queries,
                                        points=args.points, seed=args.seed)
        print("name,us_per_call,derived")
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        return

    mesh = None
    if args.mesh and not (args.cluster and args.cluster_procs):
        # in-process fleets can share this process's mesh; subprocess
        # hosts build their own from their own visible devices
        import jax

        mesh = jax.make_mesh((len(jax.devices()),), ("q",))

    trace = make_trace(args.requests, args.rate, args.req_queries,
                       args.deadline_frac, tuple(args.deadline_ms),
                       seed=args.seed)
    if args.cluster:
        out = drive_cluster(args.points, trace, n_hosts=args.cluster,
                            procs=args.cluster_procs,
                            max_batch=args.max_batch, updates=args.updates,
                            req_queries=args.req_queries, seed=args.seed,
                            policy=args.policy, mesh=mesh,
                            trace_sample_rate=args.trace_sample_rate,
                            debugz=bool(args.debugz_out),
                            warmup=not args.no_warmup)
    else:
        out = drive(args.points, trace, max_batch=args.max_batch, mesh=mesh,
                    updates=args.updates, req_queries=args.req_queries,
                    seed=args.seed, pipeline_depth=args.pipeline,
                    layout=args.layout, write_rate_rps=args.write_rate,
                    write_batch=args.write_batch,
                    trace_sample_rate=args.trace_sample_rate,
                    debugz=bool(args.debugz_out),
                    warmup=not args.no_warmup, prewarm=args.prewarm)

    out.pop("_reqs", None)               # request objects are not JSON
    spans = out.pop("spans", [])
    if args.debugz_out:
        with open(args.debugz_out, "w") as f:
            json.dump(out.pop("debugz"), f, indent=1)
        print(f"# wrote debugz bundle to {args.debugz_out}",
              file=sys.stderr)
    if args.trace_out:
        from repro.obs import chrome_trace

        with open(args.trace_out, "w") as f:
            json.dump(chrome_trace(spans), f)
        out["trace_events"] = len(spans)
        print(f"# wrote {len(spans)} spans to {args.trace_out}",
              file=sys.stderr)

    if out["lost"] or out["duplicated"]:
        # CLI invariant gate (CI churn step): a lost or duplicated request
        # under mixed read/write load must fail the job, json mode included
        raise SystemExit(f"load run lost/duplicated requests: "
                         f"{out['lost']}/{out['duplicated']}")
    if args.json:
        out["config"] = {k: (list(v) if isinstance(v, tuple) else v)
                         for k, v in vars(args).items()}
        print(json.dumps(out, indent=2))
        return
    rep = out["report"]
    lat = rep["latency"]
    print(f"offered {out['offered_rps']:.0f} req/s | served "
          f"{rep['queries_per_s']:.0f} q/s | completed {rep['completed']} "
          f"shed {rep['shed']} lost {out['lost']} dup {out['duplicated']}")
    for axis in ("queue", "execute", "total", "shed"):
        s = lat[axis]
        print(f"  {axis:8s} p50 {s['p50_s'] * 1e3:8.2f}ms  "
              f"p95 {s['p95_s'] * 1e3:8.2f}ms  p99 {s['p99_s'] * 1e3:8.2f}ms"
              f"  max {s['max_s'] * 1e3:8.2f}ms  (n={s['count']})")


if __name__ == "__main__":
    main()
