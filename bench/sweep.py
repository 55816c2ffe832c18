"""Find the knee of an open-loop cell: the highest read rate at which the
backlog does not grow over a window.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates <r> ... [--out <file.json>]

One server is built as a run's set-up builds it, then driven with the
cell's mix at each rate in turn (reads a second; updates keep their share).
For each rate it reports the latency percentiles, the median latency of
the last third of the reads over that of the first third (near 1 when the
queue is steady, growing with the window when it is not) and how long the
last answer came after the last read was due.  The rate a cell offers is
fixed in its traffic file from one such sweep; benchmark runs never
search for it.
"""

import os
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        Path(__file__).resolve().parents[1] / ".jax_cache")

import argparse  # noqa: E402
import json  # noqa: E402

import numpy as np  # noqa: E402

from bench import data, drive, generate  # noqa: E402
from bench import run as R  # noqa: E402


def summarize(rate: float, win: drive.Window) -> dict:
    lat = np.asarray([(r["t_done"] - r["due"]) * 1e3 for r in win.reads])
    third = max(1, len(lat) // 3)
    due_last = max(r["due"] for r in win.reads + win.updates)
    return {"rate_per_s": rate, "reads": len(lat),
            "updates": len(win.updates),
            "failed": sum(not r["ok"] for r in win.reads + win.updates),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "growth": float(np.median(lat[-third:]) / np.median(lat[:third])),
            "tail_s": win.t_close - due_last,
            "lateness_p99_ms": float(np.percentile(win.lateness_s, 99)) * 1e3}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--out")
    args = p.parse_args(argv)

    spec = R.load_spec()
    cell = R.find(spec["workloads"], args.workload)
    config = R.load_config(cell["config"])
    mix = generate.load_mix(R.BENCH, cell["traffic"])
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    from repro.core import AidwConfig
    from repro.runtime import compile_cache

    compile_cache.enable()
    jax.config.update("jax_compilation_cache_max_size", -1)
    points, inserts = data.data_set(config["data"])
    pts = points(config["points"], data.rng(args.seed, 0))
    cfg = AidwConfig(k=config["k"], stage2=config["stage2"],
                     exact=config["exact"])
    srv, _ = R.serve_setup(pts, cfg, mix, mix.get("update_share", 0) > 0,
                           data.rng(args.seed, 2), inserts)
    rows = []
    try:
        for i, rate in enumerate(args.rates):
            ops = generate.open_loop(dict(mix, rate_per_s=rate),
                                     args.seconds, len(pts),
                                     data.rng(args.seed, 100 + i), inserts)
            rows.append(summarize(rate, drive.served(srv, ops,
                                                     first_epoch=srv.epoch)))
            print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    finally:
        srv.close(timeout=600)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
