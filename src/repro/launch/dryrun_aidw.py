import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""AIDW production dry-run — the paper's technique on the 512-chip mesh.

Workload: the billion-point regime the paper's citations gesture at (Guan &
Wu 2010 process ~1e9 LiDAR points): m = n = 2^30 points/queries in the unit
square, k = 15.  Cells:

* ``paper``      — the paper's own scheme scaled up: queries sharded over all
                   512 chips, data points + grid REPLICATED per chip (this is
                   exactly the single-GPU algorithm, fanned out).  Fits only
                   because 2^30 x 12 B = 12.9 GB/chip — at 2^31 it is DEAD.
* ``ring``       — beyond-paper domain decomposition: data sharded into 512
                   ring blocks (25 MB/chip), both stages rotate blocks via
                   collective-permute.  NAIVE version materializes the
                   (n_loc, m_loc) distance tile.
* ``ring_blocked`` — + query chunking inside each ring step (the §Perf
                   iteration that makes the tile HBM-resident).
* ``slab``       — final iteration: Stage-1 keeps the paper's GRID search,
                   domain-decomposed into row slabs with halo exchange
                   (core/slab.py); only Stage 2 rings.  Halves step FLOPs.

Since both stages sit inside a length-512 lax.scan (HLO cost analysis counts
the body once), FLOPs/wire are reported analytically (exact — the body is
three dense einsums) alongside the compiled memory_analysis, which is the
quantity the scan does NOT distort.
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aidw as A
from repro.core import grid as G
from repro.core import knn as K
from repro.core.distributed import make_ring_aidw
from repro.launch.dryrun import (HBM_BW, LINK_BW, PEAK_FLOPS, collective_stats,
                                 roofline_terms)
from repro.launch.mesh import make_ring_mesh

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun_aidw"

M = N = 2 ** 30        # data points / queries (paper protocol: equal counts)
K_NN = 15
CELL_FACTOR = 4.0      # Eq.(2) width * 4: 1B-point grid table must fit HBM
                       # (cf=1 -> 4.3e9 cells x 4 B = 17 GB replicated: OOM)


def _unit_square_spec(m: int, cell_factor: float) -> G.GridSpec:
    """Static GridSpec for the synthetic unit-square workload (bounds known)."""
    cw = cell_factor * G.expected_nn_distance(m, 1.0)
    n = int((1.0 + cw) / cw)
    return G.GridSpec(0.0, 0.0, cw, n, n)


def paper_step_fn(spec: G.GridSpec, n_chips: int):
    """The paper's scheme at scale: replicated data+grid, sharded queries."""

    def step(px, py, pz, queries):
        table = G.bin_points(spec, px, py, pz)
        res = K.grid_knn(spec, table, queries, K_NN, None, 256, 4096, True)
        r_obs = K.mean_nn_distance(res.d2)
        alpha = A.adaptive_alpha(r_obs, M, 1.0)
        # double blocking: (512 x 2^19) tiles + accumulators (1B-point scale)
        return A.weighted_interpolate(queries, jnp.stack([px, py], 1), pz,
                                      alpha, 512, 2 ** 19)

    return step


def analytic_aidw(kind: str, n_chips: int, q_block: int) -> dict:
    """Exact FLOPs/wire for the scan-hidden parts (8 FLOPs per q-p pair:
    2 sub, 2 mul, 1 add for d2; ~3 for weight+accumulate)."""
    n_loc = N // n_chips
    m_loc = M // n_chips
    pair_flops = 8.0
    stage2 = n_loc * float(M) * pair_flops
    if kind == "paper":
        # grid kNN ~ window(256) candidates/query + stage2 over ALL m
        knn = n_loc * 256 * pair_flops
        wire = 0.0
    elif kind == "slab":
        knn = n_loc * 256 * pair_flops               # local grid search
        wire = (2.0 * m_loc * 12.0                   # halo (both neighbours)
                + n_chips * (m_loc * 12.0))          # stage-2 rotations
    elif kind == "grid_ring":
        # grid-aware ring (PR 5): rotating slab CSR tables; per query the
        # candidate count comes from the census, the wire adds the slab's
        # CSR offset array to every rotation
        from repro.launch.analytic import aidw_ring_stage1_census

        census = aidw_ring_stage1_census(M, n_chips, K_NN,
                                         cell_factor=CELL_FACTOR)
        knn = n_loc * census.grid_candidates * pair_flops
        cells_loc = 4.0 * (M / n_chips)              # ~n_cells/P offsets x 4B
        wire = 2.0 * n_chips * (m_loc * 12.0 + cells_loc)
    else:
        knn = n_loc * float(M) * pair_flops          # ring brute kNN
        wire = 2.0 * n_chips * (m_loc * 12.0)        # 2 stages x 512 rotations
    return {"flops": knn + stage2, "wire_bytes": wire}


def run_cell(kind: str, *, force: bool = False, q_block: int = 512) -> dict:
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    out_path = ARTIFACTS / f"aidw_1b__{kind}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    mesh = make_ring_mesh(512)
    n_chips = 512
    rec = {"cell": f"aidw_1b_{kind}", "m": M, "n": N, "k": K_NN,
           "n_chips": n_chips}
    t0 = time.time()
    try:
        from jax.sharding import NamedSharding, PartitionSpec as P

        if kind == "slab":
            from repro.core.slab import make_slab_aidw

            fn, spec, rps = make_slab_aidw(
                mesh, "ring", m_global=M, k=K_NN, cell_factor=CELL_FACTOR,
                q_block=q_block)
            rec["grid"] = {"rows_local": spec.n_rows, "cols": spec.n_cols,
                           "rows_per_slab": rps}
            args = (jax.ShapeDtypeStruct((M, 3), jnp.float32),
                    jax.ShapeDtypeStruct((N, 2), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.float32))
            with jax.set_mesh(mesh):
                compiled = fn.lower(*args).compile()
        elif kind == "paper":
            spec = _unit_square_spec(M, CELL_FACTOR)
            rec["grid"] = {"rows": spec.n_rows, "cols": spec.n_cols,
                           "cell_width": spec.cell_width}
            fn = paper_step_fn(spec, n_chips)
            rep = NamedSharding(mesh, P())
            shq = NamedSharding(mesh, P(("ring",), None))
            jitted = jax.jit(fn, in_shardings=(rep, rep, rep, shq))
            args = (jax.ShapeDtypeStruct((M,), jnp.float32),) * 3 + (
                jax.ShapeDtypeStruct((N, 2), jnp.float32),)
        elif kind == "grid_ring":
            from repro.core.distributed import make_grid_ring_aidw
            from repro.core.slab import slab_rows

            spec = _unit_square_spec(M, CELL_FACTOR)
            rps = slab_rows(spec, n_chips)
            max_level = K.auto_max_level(spec, M // n_chips, K_NN)
            halo = max_level
            # cap: owned points + 2*halo rows of boundary copies
            per_row = M / max(spec.n_rows, 1)
            cap = int(M // n_chips + 2 * halo * per_row + 64)
            n_local = (rps + 2 * halo) * spec.n_cols
            rec["grid"] = {"rows": spec.n_rows, "cols": spec.n_cols,
                           "rps": rps, "halo": halo, "cap": cap}
            cap2 = int(M // n_chips + 64)
            fn = make_grid_ring_aidw(mesh, "ring", spec=spec, rps=rps,
                                     halo=halo, max_level=max_level,
                                     k=K_NN, q_block=q_block)
            ring_cap = 256
            args = ((jax.ShapeDtypeStruct((n_chips, cap), jnp.float32),) * 3
                    + (jax.ShapeDtypeStruct((n_chips, n_local + 1),
                                            jnp.int32),
                       jax.ShapeDtypeStruct((n_chips,), jnp.int32))
                    + (jax.ShapeDtypeStruct((n_chips, cap2),
                                            jnp.float32),) * 3
                    + (jax.ShapeDtypeStruct((n_chips, ring_cap),
                                            jnp.float32),) * 3
                    + (jax.ShapeDtypeStruct((N, 2), jnp.float32),
                       jax.ShapeDtypeStruct((), jnp.float32),
                       jax.ShapeDtypeStruct((), jnp.float32)))
        else:
            qb = 0 if kind == "ring" else q_block
            fn = make_ring_aidw(mesh, "ring", k=K_NN, q_block=qb)
            args = (jax.ShapeDtypeStruct((M, 3), jnp.float32),
                    jax.ShapeDtypeStruct((N, 2), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.float32))

        if kind != "slab":
            with jax.set_mesh(mesh):
                lowered = jitted.lower(*args) if kind == "paper" else \
                    jax.jit(fn).lower(*args)
                compiled = lowered.compile()
        mem = compiled.memory_analysis()
        peak = ((getattr(mem, "argument_size_in_bytes", 0) or 0)
                + (getattr(mem, "temp_size_in_bytes", 0) or 0)
                + (getattr(mem, "output_size_in_bytes", 0) or 0)
                - (getattr(mem, "alias_size_in_bytes", 0) or 0))
        an = analytic_aidw(kind, n_chips, q_block)
        flops_chip = an["flops"]
        wire_chip = an["wire_bytes"] / n_chips
        # HBM traffic: stage tiles r/w once per rotation (ring) or one sweep
        if kind == "paper":
            hbm = M * 12.0 * 2  # data sweep x2 stages (+ grid table reads)
        elif kind == "slab":
            hbm = 3 * (M // n_chips) * 12.0 + (M // n_chips) * 12.0 * n_chips
        else:
            hbm = (M // n_chips) * 12.0 * 2 * n_chips  # rotations sweep
        rec.update(
            status="ok", compile_s=round(time.time() - t0, 1),
            memory={"peak_bytes_per_device": peak,
                    "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                    "temp_bytes": getattr(mem, "temp_size_in_bytes", None)},
            per_chip={"flops": flops_chip, "hbm_bytes": hbm,
                      "collective_wire_bytes": wire_chip},
            analytic=an,
            roofline=roofline_terms(flops_chip, hbm, wire_chip),
            fits_hbm=bool(peak <= 16e9),
        )
    except Exception as e:  # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-3000:])
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--cell", default="all",
                   choices=["paper", "ring", "ring_blocked", "grid_ring",
                            "slab", "all"])
    p.add_argument("--force", action="store_true")
    args = p.parse_args()
    cells = (["paper", "ring", "ring_blocked", "grid_ring", "slab"]
             if args.cell == "all" else [args.cell])
    for c in cells:
        rec = run_cell(c, force=args.force)
        r = rec.get("roofline", {})
        print(f"{rec['status']:8s} aidw_1b_{c:13s} "
              f"peak={rec.get('memory', {}).get('peak_bytes_per_device', 0) / 1e9:8.1f}GB "
              f"fits={rec.get('fits_hbm')} dom={r.get('dominant', '-')} "
              f"err={rec.get('error', '')[:60]}", flush=True)


if __name__ == "__main__":
    main()
