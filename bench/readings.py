"""Readings that a cell's limits are set from, in one process.

    python bench/readings.py --workload <cell> --seconds <s> \
        --seeds <n> ... --control-seeds <n> ... [--out <file.json>]

For each of ``--seeds`` it makes one run of the cell, as ``bench/run.py``
does, and records the numbers its comparison gives (the lower readings:
sound runs of the program).  For each of ``--control-seeds`` it puts the
reference itself, computed in bfloat16, in the program's place for the same
sampled answers, and records what the comparison gives (the upper
readings: the control has to fail).  A short window is enough: the
comparison samples a fixed number of queries.  Benchmark runs never run
this; it needs the chip like a run does.
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
from types import SimpleNamespace  # noqa: E402

from bench import check, data, drive, generate, reference  # noqa: E402
from bench import run as R  # noqa: E402


def control_record(config: dict, mix: dict, seed: int, seconds: float,
                   calls: int):
    """The data set and a window record holding the inputs that a run of
    ``seed`` would send, with every answer due (none come from the
    program)."""
    points, inserts = data.data_set(config["data"])
    pts = points(config["points"], data.rng(seed, 0))
    win = drive.Window()
    setup_updates = []
    if mix["loop"] == "closed":
        queries = data.query_stream(mix["queries"], data.rng(seed, 1))
        win.calls = [{"queries": queries(mix["call_queries"])}
                     for _ in range(calls)]
    else:
        ops = generate.open_loop(mix, seconds, len(pts), data.rng(seed, 1),
                                 inserts)
        _, setup_updates = R.warm_plan(
            mix, len(pts), any(op.kind == "update" for op in ops),
            data.rng(seed, 2), inserts)
        epoch = len(setup_updates)
        for op in ops:
            if op.kind == "read":
                win.reads.append({"ok": True, "queries": op.queries,
                                  "epoch": epoch, "served_epoch": epoch})
            else:
                epoch += 1
                win.updates.append({"ok": True, "inserts": op.inserts,
                                    "deletes": op.deletes})
    return pts, SimpleNamespace(win=win, setup_updates=setup_updates)


def control(config: dict, mix: dict, seed: int, seconds: float,
            calls: int = 8) -> dict:
    """The control's numbers for ``seed``: the bfloat16 reference compared
    with the float32 reference as a run's answers are."""
    import jax.numpy as jnp

    pts, rec = control_record(config, mix, seed, seconds, calls)
    area = reference.study_area(pts[:, :2], config["grid_pad"])

    def answer(points, queries):
        return check.reference_for(points, queries, config, area,
                                   dtype=jnp.bfloat16)

    return R.compare(rec, pts, config, mix, area, seed, answer=answer)[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--out")
    args = p.parse_args(argv)

    spec = R.load_spec()
    cell = R.find(spec["workloads"], args.workload)
    config = R.load_config(cell["config"])
    mix = generate.load_mix(R.BENCH, cell["traffic"])
    import jax

    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    from repro.runtime import compile_cache

    compile_cache.enable()
    jax.config.update("jax_compilation_cache_max_size", -1)
    out = {"cell": cell["name"], "program": {}, "control": {}}
    for seed in args.seeds:
        res = R.execute(spec, cell, config, mix, seed=seed,
                        seconds=args.seconds, trace=False)
        out["program"][seed] = {k: v["value"] for k, v in
                                res["checks"].items()}
        out["program"][seed]["correct"] = res["correct"]
        print(json.dumps({"seed": seed, **out["program"][seed]}),
              file=sys.stderr, flush=True)
    for seed in args.control_seeds:
        table = control(config, mix, seed, args.seconds)
        out["control"][seed] = {k: v["value"] for k, v in table.items()}
        print(json.dumps({"control_seed": seed, **out["control"][seed]}),
              file=sys.stderr, flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
