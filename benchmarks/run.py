"""Benchmark harness — one function per paper table/figure + roofline readers.

``PYTHONPATH=src python -m benchmarks.run [--full] [--skip-paper]
[--skip-roofline] [--skip-session] [--skip-ring] [--skip-ingest]
[--skip-load] [--skip-churn] [--skip-cluster] [--skip-coldstart]
[--json [PATH]]``

Prints ``name,us_per_call,derived`` CSV rows.  The ``session/*`` rows compare
cold one-shot ``aidw_improved`` against warm ``InterpolationSession.query``
throughput (Stage-1 rebuild excluded), verify the fused Stage-2 path, report
warm SHARDED-session throughput on a mesh over every visible device
(bit-identity checked), and time incremental ``update(deltas=...)`` against
the full re-plan it replaces.  The ``ring/*`` rows measure brute-force ring
Stage 1 against the grid-aware ring (slab CSR + halo) at >= 100k points —
the paper's grid-vs-brute headline re-measured for the sharded layouts,
with the measured per-query candidate count checked against the analytic
census.  The ``serving/*`` rows put the ASYNC serving subsystem under
open-loop Poisson load (deadline mix + interleaved delta updates) and
report end-to-end p50/p99 latency and shed counts — the whole speedup
story, traffic included, in one command.  The ``cluster/*`` rows replay the
same offered load against 1-host and 2-host serving fleets
(``repro.serving.cluster``) so the trajectory starts capturing scale-out
efficiency alongside single-host latency.  The ``ingest/*`` rows measure
the O(Δ) per-slab donation-aliased delta staging against the full-packet
re-stage (>= 10x fewer staged bytes required at 1% churn), and the
``serving/churn_*`` rows put a grid_ring server under a sustained mixed
read/write open-loop load (mixed p99 must stay within 1.5x of read-only at
the same offered load).  The ``coldstart/*`` rows
(benchmarks/coldstart_bench.py) measure first-query latency cold (fresh
subprocess, no cache), after a persistent-compilation-cache restart
(RAISING gate: >= 2x faster than cold), warm, and AOT-prewarmed — with the
zero-compile gate (no backend compile serving any ladder bucket after
``precompile(warm=True)``) and the prewarm-off-hot-path p99 gate (serving
p99 during background prewarm <= 1.1x steady state).

``--json`` additionally writes the rows (plus environment metadata) to a
repo-root perf-trajectory artifact.  The artifact name is derived per PR —
``BENCH_<tag>.json`` where ``<tag>`` comes from ``--artifact-tag`` or the
``BENCH_ARTIFACT_TAG`` env var (so CI never re-overwrites an earlier PR's
trajectory file the way a hardcoded name would) — and the CI mesh-suite job
regenerates and uploads it per PR.  An explicit ``--json PATH`` still wins.
"""

from __future__ import annotations

import argparse
import os
import sys

DEFAULT_TAG = os.environ.get("BENCH_ARTIFACT_TAG", "PR10")


def default_artifact(tag: str = DEFAULT_TAG) -> str:
    return f"BENCH_{tag}.json"


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true",
                   help="add the 64K size; serving shape becomes 1Mx64K")
    p.add_argument("--skip-paper", action="store_true")
    p.add_argument("--skip-roofline", action="store_true")
    p.add_argument("--skip-session", action="store_true")
    p.add_argument("--skip-ring", action="store_true",
                   help="skip the brute-vs-grid-aware ring Stage-1 rows")
    p.add_argument("--skip-load", action="store_true",
                   help="skip the async-serving load-generator rows")
    p.add_argument("--skip-cluster", action="store_true",
                   help="skip the 1-host-vs-2-host fleet scale-out rows")
    p.add_argument("--skip-ingest", action="store_true",
                   help="skip the O(Delta) delta-staging ingest rows")
    p.add_argument("--skip-churn", action="store_true",
                   help="skip the sustained-churn mixed read/write rows")
    p.add_argument("--skip-coldstart", action="store_true",
                   help="skip the cold-start rows + gates (restart-speedup "
                        "floor, postwarm zero-compile, prewarm-offpath p99)")
    p.add_argument("--artifact-tag", default=DEFAULT_TAG, metavar="TAG",
                   help="perf-trajectory artifact tag: --json with no PATH "
                        "writes BENCH_<TAG>.json (env BENCH_ARTIFACT_TAG "
                        f"overrides the default, currently {DEFAULT_TAG})")
    p.add_argument("--json", nargs="?", const="", default=None,
                   metavar="PATH",
                   help="also write the rows as a JSON perf-trajectory "
                        "artifact at the repo root (default "
                        "BENCH_<artifact-tag>.json)")
    args = p.parse_args()

    rows: list[tuple] = []

    if not args.skip_paper:
        from . import paper_tables as T

        sizes = T.FULL_SIZES if args.full else T.SIZES
        rows += T.table1_exec_time(sizes)
        rows += T.table2_stage_split(sizes)
        rows += T.table3_knn_compare(sizes)
        rows += T.accuracy_check()

    if not args.skip_session:
        from . import session_bench as S

        sizes = S.FULL_SIZES if args.full else S.SIZES
        rows += S.session_rows(sizes)
        rows += S.fused_rows()
        rows += S.sharded_rows(sizes)   # mesh over every visible device
        rows += S.delta_rows()          # incremental vs full dataset refresh

    if not args.skip_ring:
        from . import session_bench as S

        rows += S.ring_rows()           # brute vs grid-aware ring Stage 1

    if not args.skip_ingest:
        from . import session_bench as S

        rows += S.ingest_rows()         # O(Delta) per-slab delta staging

    if not args.skip_load:
        from . import load_gen as L

        rows += L.load_rows()           # async server under Poisson load
        rows += L.trace_overhead_rows()  # rate-0 tracing <2% p99 gate

    if not args.skip_churn:
        from . import load_gen as L

        rows += L.mixed_rows()          # sustained-churn mixed read/write

    if not args.skip_cluster:
        from . import load_gen as L

        rows += L.cluster_rows()        # 1-host vs 2-host fleet scale-out

    if not args.skip_coldstart:
        from . import coldstart_bench as C

        rows += C.coldstart_rows()      # cold/restart/AOT-prewarmed + gates

    if not args.skip_roofline:
        from . import roofline as R

        rows += R.rows_csv(R.full_table())

    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row[0]},{row[1]:.1f},{row[2]}")

    if args.json is not None:
        import json
        import platform
        from pathlib import Path

        import jax

        out = Path(args.json or default_artifact(args.artifact_tag))
        if not out.is_absolute():
            out = Path(__file__).resolve().parents[1] / out
        out.write_text(json.dumps({
            "env": {"devices": len(jax.devices()),
                    "backend": jax.default_backend(),
                    "jax": jax.__version__,
                    "python": platform.python_version(),
                    "argv": sys.argv[1:]},
            "rows": [{"name": r[0], "us_per_call": r[1], "derived": r[2],
                      "includes_compile": bool(r[3]) if len(r) > 3
                      else False}
                     for r in rows],
        }, indent=1) + "\n")
        print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
