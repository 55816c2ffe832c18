"""Smoke run of the AIDW serving path on TPU chips (a smoke run, not a benchmark).

    python chip_smoke.py [--seed N]     # one chip
    python chip_smoke.py --chips 4      # the four-chip mesh phase only

One chip: the paper's largest test group (1000K random points in the unit
square) behind an ``AsyncAidwServer`` with global, local and fused-tiled
Stage 2 — two waves of requests around a 1% delta update — then the
1000K x 1000K batch interpolation with local Stage 2.  Four chips: a
1000K-point ``InterpolationSession`` over a 4-device mesh, replicated and
grid_ring, against a one-device session, before and after a 1% delta.

Every phase checks its results (reference: ``aidw_original``, float32,
highest precision, on 1,024 queries).  A failed check or a crash is
printed and the remaining phases still run, so one run reports every
fault; the exit code is then 1.  The last line of standard output is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed on a TPU.  Timings are host-clock walls of this one run, set-up
(server build, AOT compiles, prewarm) apart from serving: smoke-run
figures, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

M = 1_000_000          # data points (the paper's 1000K test group)
REF_N = 1024           # queries compared with the reference per check
LADDER = (512, 1024, 2048, 4096)   # request sizes = the served bucket ladder
WAVE = 7               # random-size requests per wave (plus the reference)
DELTA = M // 100       # 1% update: this many inserts and as many deletes

# Tolerances against the float32 reference, each with its reason.
R_OBS_REL = 2e-6
# ... r_obs is the mean of the same 15 f32 square roots in both programs;
# they may add them in another order (at most ~15 ulp apart).
ALPHA_ABS = 5e-5
# ... Eqs. (5)-(6) scale a relative change of R = r_obs / r_exp by at most
# 5 * pi / 4 ~ 3.9, and r_exp is rounded once more on each side.
GLOBAL_ABS = 5e-5
# ... the same Eq. (1) code over 1e6 f32 terms: differences come only from
# alpha's last bits and the order of the reduction (grid_ring: the order of
# the slab partials).
TILED_ABS = 1e-4
# ... the Pallas kernel takes exp(-a/2 log d2) in place of XLA's pow and adds
# 1,954 tile partials in sequence: ~n_tiles * eps relative in the worst case.
LOCAL_SLACK = 1e-4
# ... local Stage 2 drops the far-field tail: |Z_local - Z_global| <=
# (tail weight / total weight) * spread(z), taken from the reference's own
# f32 sums; the slack is the one tests/test_local_stage2.py uses.
FUSED_LOCAL_ABS = 1e-5
# ... fused (Pallas) and unfused local Stage 2 run the same op chain, but
# Mosaic and XLA lower pow separately and XLA may contract mul+add.

FAILURES: list[str] = []


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    """Record a failed check and carry on; ``main`` fails the run."""
    if not cond:
        FAILURES.append(msg)
        log(f"FAIL {msg}")


def inner(xy: np.ndarray) -> np.ndarray:
    """Map unit-square coordinates into [0.01, 0.99]: queries and inserts
    stay inside the data's bounding box, so the planned grid (and Eq. 2's
    study area) is the data's in the system and in the reference alike."""
    return (0.01 + 0.98 * xy).astype(np.float32)


def reference(pts: np.ndarray, q: np.ndarray) -> dict:
    """``aidw_original`` (brute-force kNN + global Eq. 1) in float32 at
    highest precision, plus the truncated-tail bound of local Stage 2."""
    import jax
    import jax.numpy as jnp

    from repro.core import AidwConfig, aidw_original, brute_knn
    from repro.core import aidw as A

    # 128-query brute-force blocks: a (128, 1e6) f32 distance tile is 512 MB
    cfg = AidwConfig(knn_block=128)
    with jax.default_matmul_precision("highest"):
        res = aidw_original(pts, q, cfg)
        xy, z = jnp.asarray(pts[:, :2]), jnp.asarray(pts[:, 2])
        d2, idx = brute_knn(xy, jnp.asarray(q), cfg.k, cfg.knn_block)
        _, sw_g = A.weighted_partial_sums(jnp.asarray(q), xy, z, res.alpha)
        _, sw_l = A.topk_weighted_partial_sums(d2, z[idx], res.alpha)
    spread = float(pts[:, 2].max() - pts[:, 2].min())
    tail = np.asarray((sw_g - sw_l) / sw_g)
    return {"values": np.asarray(res.values), "alpha": np.asarray(res.alpha),
            "r_obs": np.asarray(res.r_obs),
            "local_bound": np.maximum(tail, 0.0) * spread}


class Dataset:
    """Points, the 1% delta, the reference queries and both references."""

    def __init__(self, seed: int):
        from repro.data.pipeline import (spatial_points, spatial_queries,
                                         spatial_surface)

        rng = np.random.default_rng(seed)
        self.pts = spatial_points(M, seed=seed)
        self.dels = rng.choice(M, DELTA, replace=False)
        ixy = inner(rng.random((DELTA, 2)))
        self.ins = np.concatenate(
            [ixy, spatial_surface(ixy[:, 0], ixy[:, 1])[:, None]],
            axis=1).astype(np.float32)
        keep = np.ones(M, bool)
        keep[self.dels] = False
        self.pts_after = np.concatenate([self.pts[keep], self.ins])
        self.ref_q = inner(spatial_queries(REF_N, seed=seed + 1))
        t0 = time.perf_counter()
        self.ref = [reference(p, self.ref_q)
                    for p in (self.pts, self.pts_after)]
        log(f"reference aidw_original, before and after the delta "
            f"({REF_N} queries x {M} points): {time.perf_counter() - t0:.3f}"
            f" s, compiles included")


def stats(res) -> dict:
    return {"values": np.asarray(res.values), "alpha": np.asarray(res.alpha),
            "r_obs": np.asarray(res.r_obs)}


def compare(tag: str, got: dict, ref: dict, mode: str) -> None:
    """Check one result set against the reference; ``mode`` picks the
    value tolerance (global, tiled or local)."""
    r_rel = np.abs(got["r_obs"] - ref["r_obs"]) / ref["r_obs"]
    a_abs = np.abs(got["alpha"] - ref["alpha"])
    v_abs = np.abs(got["values"] - ref["values"])
    same = int((got["r_obs"] == ref["r_obs"]).sum())
    log(f"{tag}: r_obs bitwise {same}/{len(r_rel)}, max rel "
        f"{r_rel.max():.3e}; alpha max abs {a_abs.max():.3e}; "
        f"values max abs {v_abs.max():.3e}")
    check(r_rel.max() <= R_OBS_REL, f"{tag}: r_obs vs reference")
    check(a_abs.max() <= ALPHA_ABS, f"{tag}: alpha vs reference")
    if mode == "local":
        excess = v_abs - (ref["local_bound"] + LOCAL_SLACK)
        log(f"{tag}: local tail bound max {ref['local_bound'].max():.3e}, "
            f"worst margin {-excess.max():.3e}")
        check((excess <= 0).all(), f"{tag}: values vs truncated-tail bound")
    else:
        tol = TILED_ABS if mode == "tiled" else GLOBAL_ABS
        check(v_abs.max() <= tol, f"{tag}: values vs reference ({tol})")


class _Counted:
    """Counts the calls of one AOT executable of the session's ladder."""

    def __init__(self, fn):
        self.fn, self.calls = fn, 0

    def __call__(self, *args):
        self.calls += 1
        return self.fn(*args)


def served_phase(name: str, cfg, data: Dataset, seed: int) -> list[dict]:
    """Two waves of requests through an ``AsyncAidwServer`` around one 1%
    delta update; checks every request and compares the reference queries
    of each wave.  Returns the reference-query results of both waves."""
    import jax.numpy as jnp

    from repro.kernels import resolve_interpret
    from repro.runtime import compile_cache
    from repro.serving import AsyncAidwServer

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    srv = AsyncAidwServer(data.pts, cfg, max_batch=LADDER[-1],
                          min_bucket=LADDER[0], prewarm="sync")
    setup_s = time.perf_counter() - t0
    try:
        sess = srv.session
        check(sorted(b for (b, dn) in sess._aot if dn) == list(LADDER),
              f"{name}: prewarm compiled the donating ladder")
        donated = []
        for key, (comp, sig) in list(sess._aot.items()):
            if key[1]:
                donated.append(_Counted(comp))
                sess._aot[key] = (donated[-1], sig)
        if cfg.stage2 == "tiled" or cfg.fused:
            text = sess._lower(jnp.zeros((LADDER[0], 2), jnp.float32),
                               False).as_text()
            check(resolve_interpret(cfg.interpret) is False,
                  f"{name}: interpret resolves False on the TPU")
            check("tpu_custom_call" in text,
                  f"{name}: the lowered program holds a compiled kernel")

        reqs, refs, compiles, served_calls, wall_s = [], [], [], 0, 0.0
        for wave in range(2):
            if wave:
                tu = time.perf_counter()
                srv.update_dataset(inserts=data.ins, deletes=data.dels,
                                   timeout=600)
                log(f"{name}: 1% delta update ({DELTA} inserts + {DELTA} "
                    f"deletes) {time.perf_counter() - tu:.3f} s")
            batch = [inner(rng.random((int(n), 2)))
                     for n in rng.choice(LADDER, WAVE)] + [data.ref_q]
            c0 = compile_cache.backend_compiles()
            n0 = sum(c.calls for c in donated)
            t1 = time.perf_counter()
            # closed loop: each request is its own exact-bucket batch
            wave_reqs = [srv.result(srv.submit(q), timeout=600)
                         for q in batch]
            srv.flush(timeout=600)
            wall_s += time.perf_counter() - t1
            compiles.append(compile_cache.backend_compiles() - c0)
            served_calls += sum(c.calls for c in donated) - n0
            reqs += wave_reqs
            # the requests carry values only: read alpha/r_obs of the
            # reference queries from the (idle) server's session
            res = stats(sess.query(data.ref_q))
            check(np.array_equal(res["values"], wave_reqs[-1].values),
                  f"{name} wave {wave + 1}: served == direct query")
            refs.append(res)

        check(all(r.status == "done" for r in reqs), f"{name}: all done")
        check(len({r.uid for r in reqs}) == len(reqs),
              f"{name}: no request lost or duplicated")
        check(all(np.isfinite(r.values).all() for r in reqs),
              f"{name}: finite values")
        check(sum(r.overflow for r in reqs) == 0, f"{name}: overflow == 0")
        st = sess.stats
        check(st["delta_updates"] == 1 and st["stage1_builds"] == 1,
              f"{name}: delta_updates {st['delta_updates']}, stage1_builds "
              f"{st['stage1_builds']}")
        check(served_calls == len(reqs),
              f"{name}: donated executables ran {served_calls} of "
              f"{len(reqs)} batches")
        check(compiles == [0, 0],
              f"{name}: backend compiles while serving {compiles}")
        queries = sum(r.queries_xy.shape[0] for r in reqs)
        log(f"{name}: setup (build + AOT ladder + prewarm) {setup_s:.3f} s; "
            f"served {len(reqs)} requests / {queries} queries in "
            f"{wall_s:.3f} s wall; donated calls {served_calls}; backend "
            f"compiles while serving {compiles}")

        mode = {"naive": "global"}.get(cfg.stage2, cfg.stage2)
        for i, got in enumerate(refs):
            compare(f"{name} wave {i + 1}", got, data.ref[i], mode)
        if cfg.stage2 == "local" and not cfg.fused:
            fused_local_check(name, sess, data.ref_q, refs[1])
        return refs
    finally:
        srv.close(timeout=600)


def fused_local_check(name: str, sess, q: np.ndarray, unfused: dict) -> None:
    """The Pallas local kernel (``stage2='local', fused=True``) against the
    unfused jnp path on the same plan and queries, compiled on the chip."""
    import dataclasses

    import jax.numpy as jnp

    from repro.core import pipeline as P

    pln = sess.plan
    cfg = dataclasses.replace(pln.cfg, fused=True)
    args = (pln.spec, cfg, pln.area, pln.table, pln.points_xy, pln.values,
            jnp.asarray(q), pln.n_points)
    check("tpu_custom_call" in P._session_execute.lower(*args).as_text(),
          f"{name}: fused local kernel compiled")
    values = np.asarray(P._session_execute(*args)[0])
    diff = np.abs(values - unfused["values"])
    ulps = diff / np.spacing(np.abs(unfused["values"]))
    log(f"{name}: fused vs unfused local: bitwise "
        f"{int((diff == 0).sum())}/{len(diff)}, max abs {diff.max():.3e}, "
        f"max {ulps.max():.1f} ulp")
    check(diff.max() <= FUSED_LOCAL_ABS, f"{name}: fused vs unfused local")


def batch_phase(data: Dataset, seed: int) -> None:
    """``aidw_improved`` at m = n = 1000K with local Stage 2 (O(k) work
    per query); the first REF_N queries are the reference queries."""
    import jax

    from repro.core import AidwConfig, aidw_improved
    from repro.data.pipeline import spatial_queries

    q = np.concatenate([data.ref_q,
                        inner(spatial_queries(M - REF_N, seed=seed + 2))])
    walls = []
    for _ in range(2):          # cold (with compiles), then warm
        t0 = time.perf_counter()
        res = aidw_improved(data.pts, q, AidwConfig(stage2="local"))
        jax.block_until_ready(res.values)
        walls.append(time.perf_counter() - t0)
    got = stats(res)
    check(got["values"].shape == (M,) and np.isfinite(got["values"]).all(),
          "batch: finite values of shape (n,)")
    check(res.overflow == 0, f"batch: overflow {res.overflow}")
    log(f"batch {M} x {M} local: cold {walls[0]:.3f} s (compiles "
        f"included), warm {walls[1]:.3f} s wall")
    compare("batch", {k: v[:REF_N] for k, v in got.items()}, data.ref[0],
            "local")


def one_chip(seed: int) -> None:
    from repro.core import AidwConfig

    data = Dataset(seed)
    phases = (("served global", AidwConfig()),
              ("served local", AidwConfig(stage2="local")),
              ("served fused-tiled", AidwConfig(stage2="tiled", fused=True)))
    refs = {}
    for i, (name, cfg) in enumerate(phases):
        refs[name] = run_phase(name, served_phase, name, cfg, data,
                               seed + 10 + i)
    # Stage 1 is shared: r_obs/alpha are bitwise across Stage-2 modes
    base = refs["served global"]
    for name in ("served local", "served fused-tiled"):
        if base is None or refs[name] is None:
            continue
        for w in range(2):
            for key in ("r_obs", "alpha"):
                check(np.array_equal(refs[name][w][key], base[w][key]),
                      f"{name} wave {w + 1}: {key} bitwise vs served global")
    run_phase("batch", batch_phase, data, seed)


def four_chips(seed: int) -> None:
    """Replicated and grid_ring sessions on a 4-device mesh vs a session on
    device 0, before and after a 1% delta update."""
    import jax

    check(len(jax.devices()) >= 4, "four devices")
    data = Dataset(seed)
    q = inner(np.random.default_rng(seed + 20).random((4096, 2)))
    # a bare jax.make_mesh: Explicit axes, as users build it
    mesh = jax.make_mesh((4,), ("q",), devices=jax.devices()[:4])
    for layout in ("replicated", "grid_ring"):
        run_phase(layout, mesh_phase, layout, mesh, data, q)


def mesh_phase(layout: str, mesh, data: Dataset, q: np.ndarray) -> None:
    import jax

    from repro.core import InterpolationSession

    single = InterpolationSession(data.pts)
    t0 = time.perf_counter()
    sess = InterpolationSession(data.pts, mesh=mesh, layout=layout)
    got = sess.query(q)
    jax.block_until_ready(got.values)
    log(f"{layout} on 4 chips: build + first query {time.perf_counter() - t0:.3f}"
        f" s (compiles included)")
    one = single.query(q)
    for step in ("before", "after"):
        if step == "after":
            t0 = time.perf_counter()
            sess.update(inserts=data.ins, deletes=data.dels)
            jax.block_until_ready(sess.query(q).values)
            log(f"{layout}: 1% delta update + query "
                f"{time.perf_counter() - t0:.3f} s")
            single.update(inserts=data.ins, deletes=data.dels)
            one, got = single.query(q), sess.query(q)
        cert = ~np.asarray(one.overflow_mask) & ~np.asarray(got.overflow_mask)
        for key in ("r_obs", "alpha"):
            check(np.array_equal(np.asarray(getattr(got, key))[cert],
                                 np.asarray(getattr(one, key))[cert]),
                  f"{layout} {step} delta: {key} bitwise, certified queries")
        dv = np.abs(np.asarray(got.values) - np.asarray(one.values))
        log(f"{layout} {step} delta: certified {int(cert.sum())}/{len(cert)}"
            f", values max abs vs one device {dv.max():.3e}")
        # replicated runs the one-device program per lane: bitwise
        tol = 0.0 if layout == "replicated" else GLOBAL_ABS
        check(dv.max() <= tol, f"{layout} {step} delta: values ({tol})")
    st = sess.stats
    check(st["delta_updates"] == 1 and st["stage1_builds"] == 1,
          f"{layout}: delta_updates {st['delta_updates']}, stage1_builds "
          f"{st['stage1_builds']}")
    compare(f"{layout} after delta", stats(sess.query(data.ref_q)),
            data.ref[1], "global")


def run_phase(name: str, fn, *args):
    """Run one phase; a crash is printed and recorded, not hidden."""
    t0 = time.perf_counter()
    try:
        out = fn(*args)
    except Exception:
        traceback.print_exc()
        check(False, f"{name}: crashed")
        return None
    log(f"phase {name}: {time.perf_counter() - t0:.3f} s")
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the four-chip mesh phase")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2

    from repro.runtime import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} check(s) failed:\n  "
              + "\n  ".join(FAILURES), file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
