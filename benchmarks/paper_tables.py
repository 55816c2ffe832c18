"""Paper-table benchmarks (Mei, Xu & Xu 2016) — one function per table/figure.

The paper's protocol: n data points == n interpolated points, random in a
square; five sizes 10K..1000K on a GT730M GPU.  This container is CPU-only,
so sizes scale down (default 1K/4K/16K; --full adds 64K) and absolute times
are CPU times — the REPORTED quantities are the paper's own derived ratios
(stage splits, improved-vs-original speedups), which are hardware-relative.

CSV schema: name,us_per_call,derived
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import AidwConfig, aidw_improved, aidw_original, idw_standard
from repro.data.pipeline import spatial_points, spatial_queries
from repro.kernels import resolve_interpret

from .serial_ref import serial_aidw

SIZES = (1024, 4096, 16384)
FULL_SIZES = SIZES + (65536,)
K = 15


def _data(n, seed=0):
    return spatial_points(n, seed=seed), spatial_queries(n, seed=seed + 1)


def _time(fn, *args, reps=3, **kw):
    fn(*args, **kw)  # warmup / compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args, **kw)
        ts.append(time.perf_counter() - t0)
    return min(ts) * 1e6  # us


def table1_exec_time(sizes=SIZES, serial_cap: int = 8192) -> list[tuple]:
    """Table 1: execution time of serial / original / improved algorithms.

    'tiled' = the same Stage-2 math through the Pallas kernel at the
    SMALLEST size only: compiled on a TPU, in interpret mode elsewhere
    (interpret mode is a correctness vehicle, not a performance one).
    """
    rows = []
    for n in sizes:
        pts, qs = _data(n)
        cfg = AidwConfig(k=K)
        if n <= serial_cap:
            t_serial = _time(serial_aidw, pts, qs, k=K, reps=1)
            rows.append((f"table1/serial/{n}", t_serial, ""))
        t_orig = _time(lambda: aidw_original(pts, qs, cfg).values.block_until_ready())
        rows.append((f"table1/original_naive/{n}", t_orig, ""))
        t_impr = _time(lambda: aidw_improved(pts, qs, cfg).values.block_until_ready())
        rows.append((f"table1/improved_naive/{n}", t_impr, ""))
        if n <= serial_cap:
            rows.append((f"table1/speedup_improved_vs_serial/{n}", 0.0,
                         f"{t_serial / t_impr:.1f}x"))
        rows.append((f"table1/speedup_improved_vs_original/{n}", 0.0,
                     f"{t_orig / t_impr:.2f}x"))
    # tiled (Pallas) at smallest size: structural + numerical check
    n = sizes[0]
    pts, qs = _data(n)
    cfg_t = AidwConfig(k=K, stage2="tiled")
    t_tiled = _time(lambda: aidw_improved(pts, qs, cfg_t).values.block_until_ready(),
                    reps=1)
    mode = "interpret (correctness mode)" if resolve_interpret(None) \
        else "compiled"
    rows.append((f"table1/improved_tiled/{n}", t_tiled, f"pallas-{mode}"))
    return rows


def table2_stage_split(sizes=SIZES) -> list[tuple]:
    """Table 2 / Fig 7: kNN-search vs weighted-interpolation stage split."""
    rows = []
    for n in sizes:
        pts, qs = _data(n)
        res = aidw_improved(pts, qs, AidwConfig(k=K), timings=True)
        res = aidw_improved(pts, qs, AidwConfig(k=K), timings=True)  # warm
        knn_us = res.timings["knn"] * 1e6
        int_us = res.timings["interp"] * 1e6
        share = knn_us / (knn_us + int_us) * 100
        rows.append((f"table2/knn_stage/{n}", knn_us, f"{share:.1f}% of total"))
        rows.append((f"table2/interp_stage/{n}", int_us,
                     f"{100 - share:.1f}% of total"))
    return rows


def table3_knn_compare(sizes=SIZES) -> list[tuple]:
    """Table 3 / Fig 9: kNN stage, improved (grid) vs original (brute)."""
    rows = []
    for n in sizes:
        pts, qs = _data(n)
        t_impr = aidw_improved(pts, qs, AidwConfig(k=K), timings=True)
        t_impr = aidw_improved(pts, qs, AidwConfig(k=K), timings=True)
        t_orig = aidw_original(pts, qs, AidwConfig(k=K), timings=True)
        t_orig = aidw_original(pts, qs, AidwConfig(k=K), timings=True)
        g = t_impr.timings["knn"] * 1e6
        b = t_orig.timings["knn"] * 1e6
        rows.append((f"table3/grid_knn/{n}", g, ""))
        rows.append((f"table3/brute_knn/{n}", b, ""))
        rows.append((f"table3/knn_pct_of_original/{n}", 0.0,
                     f"{g / b * 100:.1f}%"))
    return rows


def accuracy_check(n: int = 4096) -> list[tuple]:
    """Beyond-paper: AIDW vs standard IDW prediction error on an analytic
    surface (the paper's own accuracy motivation, Lu & Wong 2008)."""
    from repro.data.pipeline import spatial_surface

    pts, qs = _data(n)
    truth = spatial_surface(qs[:, 0], qs[:, 1])
    aidw = np.asarray(aidw_improved(pts, qs, AidwConfig(k=K)).values)
    idw2 = np.asarray(idw_standard(pts, qs, alpha=2.0))
    serial = serial_aidw(pts, qs, k=K)
    rows = [
        ("accuracy/aidw_rmse", 0.0, f"{np.sqrt(np.mean((aidw - truth) ** 2)):.5f}"),
        ("accuracy/idw2_rmse", 0.0, f"{np.sqrt(np.mean((idw2 - truth) ** 2)):.5f}"),
        ("accuracy/aidw_vs_serial_maxerr", 0.0,
         f"{np.abs(aidw - serial).max():.2e}"),
    ]
    return rows
