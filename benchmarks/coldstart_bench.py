"""Cold-start benchmark: first-query latency across the warmup spectrum.

Four points on the cold-start trajectory, measured as ``coldstart/*`` rows
(wired into benchmarks/run.py):

* **cold** — a FRESH subprocess with an EMPTY compilation cache builds a
  session and serves its first query; the wall includes construction,
  tracing, and every XLA compile on the path.  Subprocess, not in-process: jax's
  in-memory jit cache would hide the cost from any second measurement in
  the same interpreter.
* **restart** — the same subprocess workload with a PERSISTENT compilation
  cache directory a prior process already populated: compiles deserialize
  instead of running.  RAISING GATE: the restart first-query wall must be
  <= ``1/RESTART_SPEEDUP_FLOOR`` of cold (i.e. the cache must buy >= 2x),
  and the child must report actual persistent-cache hits (a silently
  disabled cache would otherwise pass on noise).
* **warm / AOT-prewarmed** — in-process: the steady-state query wall, and
  the first query after ``InterpolationSession.precompile(warm=True)``
  (the AOT bucket-ladder path a prewarmed serving host takes).  RAISING
  GATE: after ``precompile(warm=True)``, serving one exact-bucket-sized
  batch of EVERY ladder bucket triggers ZERO new backend compiles
  (``coldstart/postwarm_compiles`` == 0).
* **prewarm-offpath** — an ``AsyncAidwServer(prewarm='background')``
  serves a warmed bucket WHILE the background thread compiles the rest of
  the ladder.  RAISING GATE: p99 during prewarm must stay <=
  ``OFFPATH_P99_LIMIT`` (1.1x) of the same server's post-prewarm
  steady-state p99, best of ``attempts`` (shared CPU boxes are noisy; the
  gate exists to catch prewarm work leaking onto the worker thread, not
  scheduler jitter).

Exact-bucket measurement semantics: the zero-compile gates query at
power-of-two ladder sizes.  Odd-sized batches additionally pay tiny
one-off pad/sum helper compiles on first sight of each new size — inherent
to eager-op shape specialization, documented in ``core/pipeline.py``, and
deliberately out of scope for the gates.

Standalone: ``PYTHONPATH=src python -m benchmarks.coldstart_bench``
(``--child`` is the subprocess entry the parent spawns; not for direct
use).  Run alone, the parent starts every child BEFORE it touches JAX
itself (the subprocess rows come first), so on a TPU each child gets the
chip; inside ``benchmarks/run.py``, whose earlier phases already hold the
chip, the children are refused (one process per chip).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

RESTART_SPEEDUP_FLOOR = 2.0   # restart first query must be >= 2x faster
OFFPATH_P99_LIMIT = 1.1       # serving p99 during background prewarm
# distinct dataset sizes (distinct 64-multiple capacity buckets) so every
# in-process phase compiles fresh shapes instead of reusing the jit cache
_COLD_POINTS = 8192
_WARM_POINTS = 2903
_OFFPATH_POINTS = (2963, 3023, 3089)


def _bench_cache(name: str, empty: bool) -> str:
    """A fixed cache directory of this bench inside the checkout's cache
    (never a temporary name: the path is part of every cache key);
    ``empty=True`` clears it first, for a cold start."""
    from repro.runtime import compile_cache

    d = os.path.join(compile_cache.DEFAULT_CACHE_DIR, "coldstart-bench", name)
    if empty:
        shutil.rmtree(d, ignore_errors=True)
    return d


def _run_child(points: int, queries: int, cache_dir: str) -> dict:
    """One cold-start sample in a FRESH interpreter; returns its JSON."""
    from repro.runtime import refuse_child_if_tpu_held

    refuse_child_if_tpu_held("the cold-start child")
    cmd = [sys.executable, "-m", "benchmarks.coldstart_bench", "--child",
           "--points", str(points), "--queries", str(queries),
           "--cache-dir", cache_dir]
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", "src")
    # the bench places the child's cache itself: a cache placed from
    # outside would win over --cache-dir (compile_cache.enable) and warm
    # the cold measurement
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=900, check=False)
    if out.returncode != 0:
        raise RuntimeError(f"coldstart child failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.splitlines()[-1])


def _child(args) -> None:
    """Subprocess body: enable the cache, build a session, time the first
    query (construction included — that IS the cold path a restarted host
    pays), report compile-cache stats as JSON on stdout."""
    from repro.runtime import compile_cache

    compile_cache.enable(args.cache_dir)

    import numpy as np

    from repro.core import AidwConfig, InterpolationSession
    from repro.data.pipeline import spatial_points, spatial_queries

    pts = spatial_points(args.points, seed=0)
    qs = spatial_queries(args.queries, seed=2)
    t0 = time.perf_counter()
    sess = InterpolationSession(pts, AidwConfig(),
                                query_domain=spatial_queries(1024, seed=1))
    np.asarray(sess.query(qs).values)
    first_s = time.perf_counter() - t0
    print(json.dumps({"first_query_s": first_s,
                      "backend_compiles": compile_cache.backend_compiles(),
                      "cache": compile_cache.cache_stats()}))


def subprocess_rows(points: int = _COLD_POINTS, queries: int = 256,
                    attempts: int = 2) -> list[tuple]:
    """``coldstart/cold_first_query`` + ``coldstart/restart_first_query``
    and the raising restart-speedup gate (best of ``attempts`` — each
    attempt is 3 fresh interpreters, and a loaded CI box can smear any
    single cold/restart pair)."""
    best = None
    for _ in range(attempts):
        cold = _run_child(points, queries, _bench_cache("cold", empty=True))
        d = _bench_cache("restart", empty=True)
        _run_child(points, queries, d)                 # populate the cache
        restart = _run_child(points, queries, d)
        hits = restart["cache"]["persistent_cache_hits"]
        if hits <= 0:
            raise RuntimeError(
                "coldstart gate: restart child reported zero persistent-"
                f"cache hits — the compilation cache is not engaged "
                f"({restart})")
        speedup = cold["first_query_s"] / max(restart["first_query_s"],
                                              1e-9)
        if best is None or speedup > best[0]:
            best = (speedup, cold, restart, hits)
        if speedup >= RESTART_SPEEDUP_FLOOR:
            break
    speedup, cold, restart, hits = best
    if speedup < RESTART_SPEEDUP_FLOOR:
        raise RuntimeError(
            f"coldstart gate: restart first query "
            f"{restart['first_query_s']:.2f}s is only {speedup:.2f}x faster "
            f"than cold {cold['first_query_s']:.2f}s "
            f"(floor {RESTART_SPEEDUP_FLOOR}x over {attempts} attempts; "
            f"{hits} cache hits)")
    tag = f"{points}x{queries}"
    return [
        (f"coldstart/cold_first_query/{tag}",
         cold["first_query_s"] * 1e6,
         f"fresh process, no cache: {cold['backend_compiles']} backend "
         f"compiles inside the wall", True),
        (f"coldstart/restart_first_query/{tag}",
         restart["first_query_s"] * 1e6,
         f"{speedup:.2f}x faster than cold (gate >= "
         f"{RESTART_SPEEDUP_FLOOR}x), {hits} persistent-cache hits", True),
    ]


def inprocess_rows(points: int = _WARM_POINTS,
                   queries: int = 256) -> list[tuple]:
    """``coldstart/warm_query`` + ``coldstart/aot_prewarmed_first_query``
    + the raising ``coldstart/postwarm_compiles`` == 0 gate."""
    import numpy as np

    from repro.core import AidwConfig, InterpolationSession
    from repro.data.pipeline import spatial_points, spatial_queries
    from repro.runtime import compile_cache

    compile_cache.install_listeners()
    pts = spatial_points(points, seed=0)
    sess = InterpolationSession(pts, AidwConfig(),
                                query_domain=spatial_queries(1024, seed=1))
    buckets = sess.precompile(max_queries=queries, warm=True)
    # first post-prewarm query of EVERY ladder bucket: zero new compiles
    anchor = np.asarray(pts[0, :2], dtype=np.float32)
    c0 = compile_cache.backend_compiles()
    t0 = time.perf_counter()
    np.asarray(sess.query(np.tile(anchor, (buckets[-1], 1))).values)
    aot_first_s = time.perf_counter() - t0
    for b in buckets:
        np.asarray(sess.query(np.tile(anchor, (b, 1))).values)
    dc = compile_cache.backend_compiles() - c0
    if dc != 0:
        raise RuntimeError(
            f"coldstart gate: {dc} backend compiles after "
            f"precompile(warm=True) across ladder {buckets} (gate == 0)")
    qs = np.tile(anchor, (queries, 1))
    walls = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(sess.query(qs).values)
        walls.append(time.perf_counter() - t0)
    warm_s = float(np.mean(walls))
    tag = f"{points}x{queries}"
    return [
        (f"coldstart/warm_query/{tag}", warm_s * 1e6,
         f"steady-state exact-bucket query, mean of {len(walls)}", False),
        (f"coldstart/aot_prewarmed_first_query/{tag}", aot_first_s * 1e6,
         f"first query after precompile(warm=True) over ladder "
         f"{buckets}", False),
        (f"coldstart/postwarm_compiles/{tag}", 0.0,
         f"{dc} backend compiles serving every ladder bucket post-prewarm "
         f"(gate == 0)", False),
    ]


def prewarm_offpath_rows(queries: int = 64,
                         attempts: int = len(_OFFPATH_POINTS)) -> list[tuple]:
    """The prewarm-off-hot-path acceptance gate: serving p99 during
    background prewarm <= ``OFFPATH_P99_LIMIT`` x steady-state p99."""
    import numpy as np

    from repro.data.pipeline import spatial_points, spatial_queries
    from repro.serving import AsyncAidwServer

    best, best_stats = float("inf"), None
    for attempt in range(attempts):
        # fresh dataset size per attempt: fresh capacity-bucket shapes, so
        # the background thread has REAL compiles to do
        points = _OFFPATH_POINTS[attempt % len(_OFFPATH_POINTS)]
        pts = spatial_points(points, seed=0)
        qs = spatial_queries(queries, seed=2)
        with AsyncAidwServer(pts, max_batch=1024, prewarm="background",
                             query_domain=spatial_queries(1024,
                                                          seed=1)) as srv:
            during, steady = [], []
            # closed loop against the worker while the prewarm thread
            # COMPILES (the seconds-long phase the gate is about; past
            # _prewarm_compiled the remaining warm batches are ordinary
            # worker-queue items and a foreground request queueing behind
            # one is FIFO head-of-line blocking, not compile leakage).
            # The first samples carry this bucket's own lazy compile and
            # are dropped below.
            while not srv._prewarm_compiled.is_set() and len(during) < 200:
                t0 = time.perf_counter()
                srv.result(srv.submit(qs), timeout=600)
                during.append(time.perf_counter() - t0)
            srv.prewarm(wait=True, timeout=600)
            for _ in range(max(len(during), 20)):
                t0 = time.perf_counter()
                srv.result(srv.submit(qs), timeout=600)
                steady.append(time.perf_counter() - t0)
        during = during[5:]             # drop the lazy-compile head
        if len(during) < 8:
            continue                    # prewarm outran the sampler
        d99 = float(np.percentile(during, 99))
        s99 = float(np.percentile(steady, 99))
        ratio = d99 / max(s99, 1e-12)
        if ratio < best:
            best, best_stats = ratio, (d99, s99, len(during), points)
        if best <= OFFPATH_P99_LIMIT:
            break
    if best_stats is None:
        # prewarm completed before enough contended samples existed on
        # every attempt — nothing measurable leaked onto the hot path
        return [("coldstart/prewarm_offpath_p99/uncontended", 0.0,
                 f"background prewarm finished before {8} post-head "
                 f"samples on all {attempts} attempts (no contention "
                 f"window to measure)", False)]
    d99, s99, n, points = best_stats
    if best > OFFPATH_P99_LIMIT:
        raise RuntimeError(
            f"coldstart gate: p99 during background prewarm "
            f"{d99 * 1e3:.1f}ms is {best:.2f}x steady-state "
            f"{s99 * 1e3:.1f}ms (> {OFFPATH_P99_LIMIT}x over {attempts} "
            f"attempts) — prewarm work is leaking onto the worker thread")
    return [
        (f"coldstart/prewarm_offpath_p99/{points}x{queries}", d99 * 1e6,
         f"{best:.2f}x steady-state p99 {s99 * 1e3:.1f}ms "
         f"(gate <= {OFFPATH_P99_LIMIT}x, n={n} contended samples)",
         False),
    ]


def coldstart_rows() -> list[tuple]:
    """All ``coldstart/*`` rows (wired into benchmarks/run.py)."""
    return subprocess_rows() + inprocess_rows() + prewarm_offpath_rows()


def main() -> None:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--child", action="store_true",
                   help="subprocess entry: one cold-start sample as JSON")
    p.add_argument("--points", type=int, default=_COLD_POINTS)
    p.add_argument("--queries", type=int, default=256)
    p.add_argument("--cache-dir", default=None)
    p.add_argument("--json", action="store_true")
    args = p.parse_args()
    if args.child:
        _child(args)
        return
    rows = coldstart_rows()
    if args.json:
        print(json.dumps([{"name": r[0], "us_per_call": r[1],
                           "derived": r[2],
                           "includes_compile": bool(r[3])}
                          for r in rows], indent=1))
        return
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r[0]},{r[1]:.1f},{r[2]}")


if __name__ == "__main__":
    main()
