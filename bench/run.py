"""Run one cell of ``BENCHMARK.json`` once, on the chips it asks for.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by its name, with no list of cells,
configurations, data sets, query patterns or metrics here: the cell's entry
in ``BENCHMARK.json`` names its configuration (``bench/configs/<config>.json``)
and its traffic mix (``bench/traffic/<mix>.json``, read by
``bench/generate.py``); the configuration names its data set and the mix its
query pattern, each a file of its own (below); every metric is a reader of
its own (``bench/metrics/<metric>.py``, ``read(run)``).  Chip peaks are in
``bench/peaks.json``, keyed by the device kind JAX reports.

A data set is ``bench/datasets/<name>.py``, named by the configuration's
``"data": {"name": <name>, ...}``; the object's other keys are parameters
of both of its functions:

- ``points(n, gen, **params)``: the (n, 3) float32 data points (x, y, z),
  every one inside the unit square, with its two corners (0, 0) and (1, 1)
  among them, so that every seed has the same bounding box and so the same
  compiled grid (checked when the run makes them);
- ``inserts(n, gen, **params)``: (n, 3) float32 new points for updates,
  inside ``[0.01, 0.99]^2``.

A query pattern is ``bench/queries/<name>.py``, named by the mix's
``"queries": {"name": <name>, ...}``; it gives ``stream(gen, **params)``,
which returns ``next(n) -> (n, 2) float32`` queries inside
``[0.01, 0.99]^2``, so a pattern may carry state from one call to the next.
Both are deterministic from ``gen``.  Each purpose draws from a generator
of its own: ``data.rng(seed, 0)`` for the data set, ``(seed, 1)`` for the
window's traffic, ``(seed, 2)`` for the set-up's warm-up, ``(seed, 3)`` for
the sample the comparison checks.  A new data set or pattern is a new file
and nothing else.

The run makes its data and traffic from ``--seed``, builds the system,
warms every shape the window will use (set-up), drives the window for
``--seconds``, reads the device's memory peak, frees the system, and then
compares a sample of the window's answers with the plain reference
(``bench/check.py``).  ``--trace 1`` runs the same window under the JAX
profiler and reports the per-layer metrics and the ``breakdown`` in place
of the end-to-end ones.  The last line of standard output is the result;
everything else goes to standard error, the compared numbers last.  Off a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the persistent compilation cache: a fixed path inside the checkout, set
# before JAX starts so the program's own cache setting takes this one
CACHE_DIR = ROOT / ".jax_cache"
if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import check, data, drive, generate, measures, reference  # noqa: E402
from bench import trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LABELS = ("bench.sleep", "bench.submit", "bench.update", "bench.drain",
          "bench.call")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# -- what the benchmark is made of, found by name ------------------------------

def validate(spec: dict) -> None:
    """Refuse a ``BENCHMARK.json`` whose names or units leave the allowed
    characters, or whose cells name a configuration or metric twice."""
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    names += [w[k] for w in spec["workloads"] for k in ("config", "traffic")]
    metrics = spec["end_to_end"] + spec["per_layer"]
    names += [m["name"] for m in metrics]
    names += [k for c in spec["configs"] for k in c["reduced"]]
    for n in names:
        if not NAME.match(n):
            raise ValueError(f"BENCHMARK.json: bad name {n!r}")
    for m in metrics:
        if not UNIT.match(m["unit"]):
            raise ValueError(f"BENCHMARK.json: bad unit {m['unit']!r}")
    for group in ("configs", "workloads"):
        seen = [e["name"] for e in spec[group]]
        if len(seen) != len(set(seen)):
            raise ValueError(f"BENCHMARK.json: duplicate {group} name")
    if len({m["name"] for m in metrics}) != len(metrics):
        raise ValueError("BENCHMARK.json: duplicate metric name")


def load_spec(root: Path = ROOT) -> dict:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    validate(spec)
    return spec


def find(entries: list[dict], name: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no entry named {name!r}")


def load_config(name: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "configs" / f"{name}.json").read_text())


def load_reader(name: str, bench: Path = BENCH):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    return data.load("metrics", name, bench).read


def metrics_for(spec: dict, cell: str, per_layer: bool) -> list[dict]:
    """The metrics a cell reports: those that list it, or list no cells."""
    group = spec["per_layer" if per_layer else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def peak_of(kind: str, bench: Path = BENCH) -> dict:
    """The chip's published peaks; a device kind missing from the table is
    an error, not a default."""
    table = json.loads((bench / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# -- one run ----------------------------------------------------------------

def warm_plan(mix: dict, n_points: int, updates: bool, warm, inserts):
    """What the set-up of an open-loop cell sends before the window: a read
    of every batch size the mix can form (the program compiles small
    helpers, pad and slices, per size, which must not happen in the
    window), then, when the mix has updates, one update of the mix's size,
    so that the ingest path is compiled too.  Queries come from the mix's
    pattern, update points from ``inserts(n, gen)``, both drawing from
    ``warm``."""
    queries = data.query_stream(mix["queries"], warm)
    reads = [queries(n) for n in generate.batch_sizes(mix)]
    setup_updates = []
    if updates:
        u = mix["update"]
        setup_updates.append({
            "inserts": inserts(u["inserts"], warm),
            "deletes": warm.choice(n_points, u["deletes"], replace=False)})
    return reads, setup_updates


def serve_setup(pts, cfg, mix: dict, updates: bool, warm, inserts):
    """The server of an open-loop cell, built, compiled and warmed for every
    shape its window can use (:func:`warm_plan`).  Returns it with the
    set-up's updates."""
    from repro.serving import AsyncAidwServer

    srv = AsyncAidwServer(pts, cfg, max_batch=mix["server"]["max_batch"],
                          min_bucket=mix["server"]["min_bucket"],
                          prewarm="sync")
    reads, setup_updates = warm_plan(mix, len(pts), updates, warm, inserts)
    for q in reads:
        srv.result(srv.submit(q), timeout=600)
    for u in setup_updates:
        srv.update_dataset(**u, timeout=600)
    srv.flush(timeout=600)
    return srv, setup_updates


def _served(pts, inserts, cfg, mix, seed, seconds, trace_dir):
    """Set-up and window of an open-loop cell; returns its record.  Its
    updates' new points come from ``inserts(n, gen)``."""
    from repro.runtime import compile_cache

    ops = generate.open_loop(mix, seconds, len(pts), data.rng(seed, 1),
                             inserts)
    srv, setup_updates = serve_setup(
        pts, cfg, mix, any(op.kind == "update" for op in ops),
        data.rng(seed, 2), inserts)
    try:
        rec = SimpleNamespace(registry=[srv.registry.state()],
                              compiles=compile_cache.backend_compiles(),
                              setup_s=time.monotonic() - T_START,
                              setup_updates=setup_updates)
        with drive.profiled(trace_dir):
            rec.win = drive.served(srv, ops, first_epoch=srv.epoch)
        rec.compiles = compile_cache.backend_compiles() - rec.compiles
        rec.registry.append(srv.registry.state())
        rec.memory_peak = _memory_peak()
        for r in rec.win.reads:
            h = r.pop("handle")
            r.update(values=h.values, served_epoch=h.epoch,
                     t_dispatch=h.t_dispatch, t_submit_server=h.t_submit)
    finally:
        srv.close(timeout=600)
    return rec


def _closed(pts, inserts, cfg, mix, seed, seconds, trace_dir):
    """Set-up and window of a closed-loop cell; returns its record.  It
    sends no updates, so ``inserts`` goes unused."""
    from repro.core import InterpolationSession
    from repro.runtime import compile_cache

    sess = InterpolationSession(pts, cfg)
    sess.precompile(buckets=[mix["call_queries"]], warm=True)
    rec = SimpleNamespace(registry=[sess.registry.state()],
                          compiles=compile_cache.backend_compiles(),
                          setup_s=time.monotonic() - T_START,
                          setup_updates=[])
    with drive.profiled(trace_dir):
        rec.win = drive.closed(
            sess, mix["call_queries"], seconds,
            data.query_stream(mix["queries"], data.rng(seed, 1)))
    rec.compiles = compile_cache.backend_compiles() - rec.compiles
    rec.registry.append(sess.registry.state())
    rec.memory_peak = _memory_peak()
    return rec


def _memory_peak() -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def compare(rec, pts, config, mix, area, seed, answer=None):
    """The window's answers against the reference on a sample drawn from
    the seed, plus exact counts (answers missing, reads served at another
    epoch than their place in the stream gives).  Returns ``(correct,
    {name: {"value", "limit"}})``.

    ``answer(points, queries)``, when given, stands in the program's place
    for the sampled answers: the control is the reference itself, one
    precision lower."""
    gen = data.rng(seed, 3)
    win = rec.win
    want = mix["check_queries"]
    numbers, groups = {"unanswered": 0}, []   # (data set, queries, answers)
    if win.calls:
        n = mix["call_queries"]
        rows = np.sort(gen.choice(len(win.calls) * n,
                                  min(want, len(win.calls) * n),
                                  replace=False))
        q = np.concatenate([c["queries"] for c in win.calls])[rows]
        groups.append((pts, q, {
            k: None if answer else np.concatenate([c[k] for c in
                                                   win.calls])[rows]
            for k in ("values", "alpha", "r_obs")}))
    else:
        ok = [r for r in win.reads if r["ok"]]
        numbers["unanswered"] = len(win.reads) - len(ok) + sum(
            not u["ok"] for u in win.updates)
        numbers["epoch_mismatch"] = sum(r["served_epoch"] != r["epoch"]
                                        for r in ok)
        chosen, total = [], 0
        if ok:      # the largest read, then reads in an order from the seed
            order = [max(range(len(ok)), key=lambda i: len(ok[i]["queries"]))]
            order += [int(i) for i in gen.permutation(len(ok))
                      if i != order[0]]
            for i in order:
                if total >= want:
                    break
                chosen.append(ok[i])
                total += len(ok[i]["queries"])
        sets = check.epochs(pts, rec.setup_updates + win.updates)
        for epoch in sorted({r["epoch"] for r in chosen}):
            group = [r for r in chosen if r["epoch"] == epoch]
            groups.append((sets[epoch],
                           np.concatenate([r["queries"] for r in group]),
                           {"values": np.concatenate([r["values"]
                                                      for r in group])
                            if answer is None else None}))
    samples = []
    for points, q, got in groups:
        if answer is not None:
            got = {k: v for k, v in answer(points, q).items() if k in got}
        samples.append(check.gaps(got, check.reference_for(points, q, config,
                                                           area)))
    numbers.update(check.worst(samples or [{"values_abs": float("inf")}]))
    limits = dict(config["limits"], unanswered=0, epoch_mismatch=0)
    return check.verdict(numbers, limits)


def _breakdown(tr, lo, hi, rec) -> dict:
    """Top device ops and the longest idle gaps, each gap labelled with the
    benchmark's activity then and the reads in flight at it."""
    win = rec.win
    shift = lo / 1e12 - win.t_open          # trace clock - monotonic clock
    gaps = []
    for label, s, e in trace_reduce.idle_gaps(tr, lo, hi, LABELS):
        a, b = s / 1e12 - shift, e / 1e12 - shift
        if win.reads:
            flying = sum(r["t_submit"] <= a and r["t_done"] >= b
                         for r in win.reads)
            label = f"{label}, {flying} reads in flight"
        gaps.append([label, (e - s) / 1e12])
    return {"device_ops": trace_reduce.top_ops(tr, lo, hi), "idle_gaps": gaps}


def execute(spec: dict, cell: dict, config: dict, mix: dict, *, seed: int,
            seconds: float, trace: bool) -> dict:
    """One run of ``cell``; returns the result line (without looking for a
    chip: ``main`` does that)."""
    from repro.core import AidwConfig
    from repro.runtime import compile_cache

    compile_cache.install_listeners()
    points, inserts = data.data_set(config["data"])
    pts = points(config["points"], data.rng(seed, 0))
    area = reference.study_area(pts[:, :2], config["grid_pad"])
    cfg = AidwConfig(k=config["k"], stage2=config["stage2"],
                     exact=config["exact"])
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace \
        else None
    try:
        drive_cell = _closed if mix["loop"] == "closed" else _served
        rec = drive_cell(pts, inserts, cfg, mix, seed, seconds, trace_dir)
        win = rec.win
        late = np.asarray(win.lateness_s or [0.0]) * 1e3
        log(f"set-up {rec.setup_s:.3f} s; window {win.t_close - win.t_open:.3f}"
            f" s; compiles in the window {rec.compiles}; generator lateness "
            f"ms p50 {np.percentile(late, 50):.3f} p99 "
            f"{np.percentile(late, 99):.3f} max {late.max():.3f}")
        run = SimpleNamespace(
            setup_s=rec.setup_s, reads=win.reads, updates=win.updates,
            calls=win.calls, registry=rec.registry, trace=None,
            trace_window=None,
            queries=sum(len(r["queries"]) for r in win.reads if r["ok"])
            + sum(len(c["queries"]) for c in win.calls))
        device = _device(rec.memory_peak)
        result = {}
        if trace:
            files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                              recursive=True)
            run.trace = trace_reduce.read(files[0])
            lo, hi = trace_reduce.window_of(run.trace, "bench.window")
            run.trace_window = (lo, hi)
            device["busy_s"] = trace_reduce.busy_ps(run.trace, lo, hi) / 1e12
            device["window_s"] = (hi - lo) / 1e12
            result["breakdown"] = _breakdown(run.trace, lo, hi, rec)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in metrics_for(spec, cell["name"], per_layer=trace):
        value = measures.finite(load_reader(m["name"])(run))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct, table = compare(rec, pts, config, mix, area, seed)
    attempted = len(win.reads) + len(win.updates) + len(win.calls)
    failed = sum(not r["ok"] for r in win.reads + win.updates)
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, **result, "checks": table}


def _device(memory_peak: int) -> dict:
    import jax

    d = jax.local_devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d), "memory_peak_bytes": memory_peak}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    spec = load_spec()
    cell = find(spec["workloads"], args.workload)
    config = load_config(cell["config"])
    mix = generate.load_mix(BENCH, cell["traffic"])

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX "
            f"found {len(devices)} x {devices[0].platform}: no result")
        return 2
    peak = peak_of(devices[0].device_kind)
    from repro.runtime import compile_cache

    log(f"compile cache {compile_cache.enable()}")
    # no eviction: an eviction scan that meets an entry another thread is
    # still writing fails every later write of the process
    jax.config.update("jax_compilation_cache_max_size", -1)
    result = execute(spec, cell, config, mix, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace))
    log(f"memory peak {result['device']['memory_peak_bytes']} B = "
        f"{100.0 * result['device']['memory_peak_bytes'] / peak['hbm_bytes']:.3f}"
        f"% of the chip's {peak['hbm_bytes']:.0f} B")
    for name, t in result["checks"].items():
        log(f"check {name} {t['value']!r} limit {t['limit']!r}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
