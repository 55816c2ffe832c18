import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This proves the distribution config is coherent on the production meshes
without hardware: parameters/optimizer state/caches/batches are
ShapeDtypeStructs (zero allocation), ``jit(...).lower(...).compile()`` runs
the full SPMD partitioner, and the compiled artifact yields

* ``memory_analysis()``  — per-device bytes (proves it fits),
* ``cost_analysis()``    — per-device HLO FLOPs/bytes for the roofline,
* the optimized HLO text — parsed for collective wire bytes (§Roofline).

Artifacts land in ``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` and are
resumable (existing cells are skipped unless --force).

NOTE: the XLA_FLAGS line above MUST precede any jax import — device count is
locked at first backend init.  Tests and benchmarks do NOT import this
module's environment (they see 1 device).
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

if os.environ.get("REPRO_SHARDY", "0") == "1":
    # newer XLA partitioner: avoids GSPMD's involuntary full-rematerialization
    # path on FSDP x TP transitions (§Perf iteration 5)
    jax.config.update("jax_use_shardy_partitioner", True)

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh, make_ring_mesh
from repro.models import api, sharding
from repro.models.config import ModelConfig
from repro.nn.param import abstract_params, make_shardings, count_params
from repro.optim import adamw
from repro.training import trainer

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# TPU v5e hardware constants (see DESIGN.md §5)
PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # bytes/s per chip
LINK_BW = 50e9             # bytes/s per ICI link

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
# ring-algorithm wire-cost factors (x result bytes, per chip)
_WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16|c\d+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes of every tensor literal in an HLO type string (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-op-type {count, result_bytes, wire_bytes} from optimized HLO."""
    stats = {c: {"count": 0, "result_bytes": 0, "wire_bytes": 0.0}
             for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+ = (.+?) (all-reduce|all-gather|reduce-scatter|"
                     r"all-to-all|collective-permute)(-start|-done)?\(", line)
        if not m:
            continue
        if m.group(3) == "-done":
            continue  # counted at -start
        op = m.group(2)
        b = _shape_bytes(m.group(1))
        stats[op]["count"] += 1
        stats[op]["result_bytes"] += b
        stats[op]["wire_bytes"] += b * _WIRE_FACTOR[op]
    return stats


def roofline_terms(flops: float, hbm_bytes: float, wire_bytes: float) -> dict:
    """All quantities are PER-CHIP (post-SPMD local module)."""
    compute_s = flops / PEAK_FLOPS
    memory_s = hbm_bytes / HBM_BW
    coll_s = wire_bytes / LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom.replace("_s", "")
    terms["step_time_lower_bound_s"] = max(compute_s, memory_s, coll_s)
    return terms


def _mesh_for(name: str):
    if name == "pod":
        return make_production_mesh(multi_pod=False)
    if name == "multipod":
        return make_production_mesh(multi_pod=True)
    if name == "ring512":
        return make_ring_mesh(512)
    raise ValueError(name)


# --- §Perf hillclimb variants: cfg overrides + trainer knobs ------------
# baseline  : naive pjit sharding (paper of record for the iteration log)
# rs        : gradients constrained to param shardings -> reduce-scatter
# rs_sp     : + Megatron-style sequence-sharded residual stream
# rs_sp_lc  : + chunked CE loss (logits one chunk at a time)
# ep        : + expert-parallel dispatch-buffer constraint (MoE archs)


def variant_overrides(name: str, mesh) -> tuple[dict, dict]:
    """-> (cfg overrides, trainer kwargs)"""
    b_axes = tuple(a for a in mesh.axis_names if a != "model")
    bs = {"act_spec": (b_axes, None, None)}    # batch-shard residual stream
    seq = {"act_spec": (b_axes, "model", None)}  # + sequence sharding (SP)
    lc = {"loss_chunk": 512}
    ep = {"moe_spec": ("model", None, None)}
    epsm = {"moe_impl": "ep"}
    rs = {"constrain_grads": True}
    g16 = {"constrain_grads": True, "grad_dtype": "bf16"}
    nm = {"constrain_grads": True, "grad_dtype": "bf16", "master_weights": False}
    table = {
        "baseline": ({}, {}),
        "rs": ({}, rs),
        "bs": ({**bs}, rs),
        "bs_lc": ({**bs, **lc}, rs),
        "sp": ({**seq}, rs),
        "sp_lc": ({**seq, **lc}, rs),
        "sp_lc_g16": ({**seq, **lc}, g16),
        "sp_lc_nm": ({**seq, **lc}, nm),
        "bs_lc_epsm": ({**bs, **lc, **epsm}, g16),
        "sp_lc_epsm": ({**seq, **lc, **epsm}, g16),
        "sp_lc_ep": ({**seq, **lc, **ep}, rs),
        "sp_lc_g16_ep": ({**seq, **lc, **ep}, g16),
        "bs_lc_ep": ({**bs, **lc, **ep}, rs),
        "ep": ({**ep}, rs),
    }
    return table[name]


def lower_cell(cfg: ModelConfig, shape: api.ShapeSpec, mesh, *,
               constrain_grads: bool = False, grad_dtype=None,
               master_weights: bool = True):
    """Build (jitted_fn, arg_structs, in_shardings) for one cell."""
    defs = api.param_defs(cfg)
    params_abs = abstract_params(defs)
    param_sh = make_shardings(defs, mesh, sharding.param_rules(mesh))

    batch_abs = api.input_specs(cfg, shape)
    batch_sh = sharding.shard_batch(
        mesh, sharding.data_specs(mesh, cfg, batch_abs))

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig(master_weights=master_weights)
        opt_abs = jax.eval_shape(lambda p: trainer.init_opt_state(opt_cfg, p),
                                 params_abs)
        opt_sh = trainer.opt_state_specs(opt_cfg, param_sh)
        from jax.sharding import NamedSharding, PartitionSpec as P
        opt_sh["step"] = NamedSharding(mesh, P())
        step = trainer.make_train_step(
            cfg, opt_cfg, grad_shardings=param_sh if constrain_grads else None,
            grad_dtype=jnp.bfloat16 if grad_dtype == "bf16" else None)
        jitted = jax.jit(step, in_shardings=(param_sh, opt_sh, batch_sh),
                         donate_argnums=(0, 1))
        return jitted, (params_abs, opt_abs, batch_abs)

    if shape.kind == "prefill":
        fn = api.prefill_fn(cfg)
        jitted = jax.jit(fn, in_shardings=(param_sh, batch_sh))
        return jitted, (params_abs, batch_abs)

    # decode
    cache_defs_ = api.cache_defs(cfg, shape.global_batch, shape.seq_len)
    cache_abs = abstract_params(cache_defs_)
    cache_sh = make_shardings(
        cache_defs_, mesh, sharding.cache_rules(mesh, cfg, shape.global_batch))
    fn = api.decode_fn(cfg)
    jitted = jax.jit(fn, in_shardings=(param_sh, cache_sh, batch_sh),
                     donate_argnums=(1,))
    return jitted, (params_abs, cache_abs, batch_abs)


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             force: bool = False, variant: str = "baseline") -> dict:
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    suffix = "" if variant == "baseline" else f"__{variant}"
    if os.environ.get("REPRO_SHARDY", "0") == "1":
        suffix += "__shardy"
    out_path = ARTIFACTS / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    shape = api.SHAPES[shape_name]
    ok, reason = api.applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "params": cfg.param_count(), "active_params": cfg.active_param_count()}
    if not ok:
        rec.update(status="skipped", reason=reason)
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    mesh = _mesh_for(mesh_name)
    overrides, tkw = variant_overrides(variant, mesh)
    cfg = cfg.with_(**overrides)
    rec["variant"] = variant
    n_chips = mesh.devices.size
    t0 = time.time()
    try:
        jitted, args = lower_cell(cfg, shape, mesh, **tkw)
        with jax.set_mesh(mesh):
            lowered = jitted.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        colls = collective_stats(hlo)
        flops = float(cost.get("flops", 0.0))
        hbm_bytes = float(cost.get("bytes accessed", 0.0))
        wire = sum(c["wire_bytes"] for c in colls.values())
        rec.update(
            status="ok",
            n_chips=n_chips,
            lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
                "peak_bytes_per_device": (
                    (getattr(mem, "argument_size_in_bytes", 0) or 0)
                    + (getattr(mem, "temp_size_in_bytes", 0) or 0)
                    + (getattr(mem, "output_size_in_bytes", 0) or 0)
                    - (getattr(mem, "alias_size_in_bytes", 0) or 0)),
            },
            per_chip={"flops": flops, "hbm_bytes": hbm_bytes,
                      "collective_wire_bytes": wire},
            collectives=colls,
            roofline=roofline_terms(flops, hbm_bytes, wire),
        )
        # useful-compute ratio: MODEL_FLOPS / (HLO flops * chips)
        mf = model_flops(cfg, shape)
        rec["model_flops"] = mf
        hlo_total = flops * n_chips
        rec["useful_compute_ratio"] = (mf / hlo_total) if hlo_total else None
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def model_flops(cfg: ModelConfig, shape: api.ShapeSpec) -> float:
    """MODEL_FLOPS: 6*N*D for train, 2*N*D forward-only (N = active params)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


# ---------------------------------------------------------------------------
# probe mode: corrected per-layer metrics via 1-vs-2-layer UNROLLED compiles
# ---------------------------------------------------------------------------
#
# XLA's HLO cost analysis counts a while-loop body ONCE, so the scanned-layer
# production lowering under-reports FLOPs/bytes/collectives by ~the trip
# count.  The probe compiles the same cell at depth-1 and depth-2 with the
# layer scan fully unrolled and attention query-chunking disabled (both
# while-free), takes the exact marginal per-depth-unit cost under the real
# SPMD partitioning, and extrapolates:  total = f(1) + (units-1) * (f(2)-f(1)).
# Validated against analytic 6*N*D in tests/test_dryrun_probe.py.

PROBE_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "probe"


def _depth_units(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers


def _probe_cfg(cfg: ModelConfig, shape: api.ShapeSpec, units: int) -> ModelConfig:
    kw: dict = {"unroll_layers": True}
    if shape.kind != "decode":
        kw["q_chunk"] = shape.seq_len  # no q-chunk while loop
    if cfg.family == "hybrid":
        kw["n_layers"] = units * cfg.attn_every
    elif cfg.enc_dec:
        kw.update(n_layers=units, n_enc_layers=units)
    else:
        kw["n_layers"] = units
    return cfg.with_(**kw)


def _probe_metrics(cfg: ModelConfig, shape, mesh, **tkw) -> dict:
    jitted, args = lower_cell(cfg, shape, mesh, **tkw)
    with jax.set_mesh(mesh):
        compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis() or {}
    colls = collective_stats(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "hbm_bytes": float(cost.get("bytes accessed", 0.0)),
        "wire_bytes": sum(c["wire_bytes"] for c in colls.values()),
        "collectives": colls,
    }


def probe_cell(arch: str, shape_name: str, mesh_name: str = "pod", *,
               force: bool = False, variant: str = "baseline") -> dict:
    PROBE_DIR.mkdir(parents=True, exist_ok=True)
    suffix = "" if variant == "baseline" else f"__{variant}"
    if os.environ.get("REPRO_SHARDY", "0") == "1":
        suffix += "__shardy"
    out_path = PROBE_DIR / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    shape = api.SHAPES[shape_name]
    ok, reason = api.applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        rec.update(status="skipped", reason=reason)
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    mesh = _mesh_for(mesh_name)
    overrides, tkw = variant_overrides(variant, mesh)
    cfg = cfg.with_(**overrides)
    rec["variant"] = variant
    rec["shardy"] = os.environ.get("REPRO_SHARDY", "0") == "1"
    t0 = time.time()
    try:
        m1 = _probe_metrics(_probe_cfg(cfg, shape, 1), shape, mesh, **tkw)
        m2 = _probe_metrics(_probe_cfg(cfg, shape, 2), shape, mesh, **tkw)
        units = _depth_units(cfg)
        corr = {}
        for key in ("flops", "hbm_bytes", "wire_bytes"):
            delta = max(m2[key] - m1[key], 0.0)
            corr[key] = m1[key] + (units - 1) * delta
        colls = {}
        for op in _COLLECTIVES:
            c1, c2 = m1["collectives"][op], m2["collectives"][op]
            colls[op] = {
                k: c1[k] + (units - 1) * max(c2[k] - c1[k], 0)
                for k in ("count", "result_bytes", "wire_bytes")
            }
        mf = model_flops(cfg, shape)
        n_chips = mesh.devices.size
        rec.update(
            status="ok", units=units, probe_s=round(time.time() - t0, 1),
            probe_1=m1, probe_2=m2,
            per_chip=corr, collectives=colls,
            roofline=roofline_terms(corr["flops"], corr["hbm_bytes"],
                                    corr["wire_bytes"]),
            model_flops=mf,
            useful_compute_ratio=(mf / (corr["flops"] * n_chips)
                                  if corr["flops"] else None),
        )
    except Exception as e:  # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="all", help="arch id or 'all'")
    p.add_argument("--shape", default="all", help="shape name or 'all'")
    p.add_argument("--mesh", default="all",
                   choices=["pod", "multipod", "ring512", "all"])
    p.add_argument("--probe", action="store_true",
                   help="corrected per-layer metrics (single-pod, see above)")
    p.add_argument("--variant", default="baseline")
    p.add_argument("--force", action="store_true")
    args = p.parse_args()

    if args.probe:
        archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
        shapes = list(api.SHAPES) if args.shape == "all" else [args.shape]
        for arch in archs:
            for shape_name in shapes:
                rec = probe_cell(arch, shape_name, force=args.force,
                                 variant=args.variant)
                r = rec.get("roofline", {})
                print(f"{rec['status']:8s} {arch:24s} {shape_name:12s} "
                      f"dom={r.get('dominant','-'):10s} "
                      f"useful={rec.get('useful_compute_ratio') or 0:.3f} "
                      f"err={rec.get('error','')[:80]}", flush=True)
        return

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(api.SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "all" else [args.mesh]

    results = []
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                rec = run_cell(arch, shape_name, mesh_name, force=args.force,
                               variant=args.variant)
                r = rec.get("roofline", {})
                print(f"{rec['status']:8s} {arch:24s} {shape_name:12s} "
                      f"{mesh_name:9s} dom={r.get('dominant','-'):10s} "
                      f"compile={rec.get('compile_s','-')}s "
                      f"err={rec.get('error','')[:80]}", flush=True)
                results.append(rec)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_err} errors")


if __name__ == "__main__":
    main()
