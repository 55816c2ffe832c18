"""Pallas TPU kernel: tiled AIDW Stage-2 weighted interpolation.

TPU analogue of the paper's shared-memory "tiled version" (§3.3/§4.2.2):

* CUDA shared-memory tile of data-point coordinates  ->  a ``(1, TILE_D)``
  VMEM block per grid step along the data axis (BlockSpec-managed).
* per-thread register accumulators (sum of partial weights / weighted values)
  ->  ``(TILE_Q, 1)`` float32 VMEM scratch accumulators that persist across
  the ``arbitrary`` data-axis grid dimension.
* one thread per interpolated point  ->  one (8,128)-vectorized lane row per
  query inside a ``(TILE_Q, TILE_D)`` distance/weight tile (MXU/VPU shaped).

The kernel optionally FUSES the adaptive-alpha determination (Eqs. 2/4/5/6)
with the weighting pass: it takes the Stage-1 mean NN distance ``r_obs`` and
computes alpha in-kernel on the first data step — one kernel launch for the
whole Stage 2 instead of the paper's two (beyond-paper optimization,
DESIGN.md §2).

Layouts are SoA exactly as the paper prescribes (§4.2.1): queries arrive as
``(n, 1)`` column vectors (sublane-major), data points as ``(1, m)`` row
vectors (lane-major), so the broadcasted difference is a native outer
product on the VPU.

Padding contract: data sentinels at +1e30 make ``d2 = inf`` in f32, hence
``w = exp(-inf) = 0`` exactly — padded data points contribute nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import aidw as A
from repro.kernels import resolve_interpret

DEFAULT_TILE_Q = 256
DEFAULT_TILE_D = 512


def _alpha_from_r_obs(r_obs, n_points, area, alphas, r_min, r_max):
    """Eqs. (2)->(4)->(5)->(6) — delegates to the canonical jnp chain so the
    in-kernel alpha is bit-identical to the two-launch path's
    :func:`repro.core.aidw.adaptive_alpha` (jnp only, safe inside a kernel)."""
    return A.adaptive_alpha(r_obs, n_points, area, alphas=alphas,
                            r_min=r_min, r_max=r_max)


def _interp_kernel(
    qx_ref, qy_ref, aux_ref,            # queries: (TQ, 1); aux = alpha or r_obs
    stats_ref,                          # SMEM (1, 2): (n_points, area), traced
    px_ref, py_ref, pz_ref,             # data:    (1, TD)
    out_ref, sumw_ref,                  # outputs: (TQ, 1) values / weight sums
    sum_w, sum_wz, alpha_s,             # scratch: (TQ, 1) f32
    *, n_dblocks: int, fused: bool,
    alphas, r_min: float, r_max: float,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        sum_w[...] = jnp.zeros_like(sum_w)
        sum_wz[...] = jnp.zeros_like(sum_wz)
        aux = aux_ref[...].astype(jnp.float32)
        if fused:
            alpha_s[...] = _alpha_from_r_obs(
                aux, stats_ref[0, 0], stats_ref[0, 1], alphas, r_min, r_max)
        else:
            alpha_s[...] = aux

    qx = qx_ref[...].astype(jnp.float32)          # (TQ, 1)
    qy = qy_ref[...].astype(jnp.float32)
    px = px_ref[...].astype(jnp.float32)          # (1, TD)
    py = py_ref[...].astype(jnp.float32)
    pz = pz_ref[...].astype(jnp.float32)
    alpha = alpha_s[...]                          # (TQ, 1)

    d2 = (qx - px) ** 2 + (qy - py) ** 2          # (TQ, TD) outer broadcast
    # w = d2 ** (-alpha/2), squared distances throughout (paper: sqrt deferred);
    # exp/log form feeds the VPU transcendental unit once each.
    w = jnp.exp(-0.5 * alpha * jnp.log(jnp.maximum(d2, A.EPS_D2)))
    sum_w[...] += w.sum(axis=1, keepdims=True)
    sum_wz[...] += (w * pz).sum(axis=1, keepdims=True)

    @pl.when(j == n_dblocks - 1)
    def _finish():
        # zero-weight guard: a query whose every f32 weight underflowed gets
        # the 0.0 sentinel (sum_wz is then also 0), never NaN; the caller
        # derives the zero_weight_mask from the sumw output.
        denom = jnp.maximum(sum_w[...], jnp.float32(1e-30))
        out_ref[...] = (sum_wz[...] / denom).astype(out_ref.dtype)
        sumw_ref[...] = sum_w[...].astype(sumw_ref.dtype)


def tiled_interpolate_kernel(
    qx, qy, aux, stats, px, py, pz,
    *, tile_q: int = DEFAULT_TILE_Q, tile_d: int = DEFAULT_TILE_D,
    fused: bool = False,
    alphas=A.DEFAULT_ALPHAS, r_min: float = A.DEFAULT_R_MIN,
    r_max: float = A.DEFAULT_R_MAX, interpret: bool | None = None,
):
    """Raw pallas_call wrapper.  Shapes: qx/qy/aux (n,1); stats (1,2) f32
    (n_points, area — TRACED, so dataset churn never retraces); px/py/pz (1,m).

    Returns ``(values (n,1), sum_w (n,1))``.  n % tile_q == 0 and
    m % tile_d == 0 (ops.py pads).
    """
    n, m = qx.shape[0], px.shape[1]
    assert n % tile_q == 0 and m % tile_d == 0, (n, tile_q, m, tile_d)
    grid = (n // tile_q, m // tile_d)

    kernel = functools.partial(
        _interp_kernel, n_dblocks=grid[1], fused=fused,
        alphas=tuple(alphas), r_min=r_min, r_max=r_max,
    )
    q_spec = pl.BlockSpec((tile_q, 1), lambda i, j: (i, 0))
    d_spec = pl.BlockSpec((1, tile_d), lambda i, j: (0, j))
    s_spec = pl.BlockSpec((1, 2), lambda i, j: (0, 0),
                          memory_space=pltpu.SMEM)
    o_spec = pl.BlockSpec((tile_q, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, q_spec, q_spec, s_spec, d_spec, d_spec, d_spec],
        out_specs=(o_spec, o_spec),
        out_shape=(jax.ShapeDtypeStruct((n, 1), qx.dtype),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
        name="aidw_tiled_fused" if fused else "aidw_tiled",
    )(qx, qy, aux, stats, px, py, pz)


def _local_kernel(
    d2_ref, z_ref,                      # (TQ, K): merged Stage-1 neighbours
    aux_ref,                            # (TQ, 1): alpha, or r_obs when fused
    stats_ref,                          # SMEM (1, 2): (n_points, area), traced
    out_ref, sumw_ref,                  # outputs: (TQ, 1)
    *, fused: bool, alphas, r_min: float, r_max: float,
):
    aux = aux_ref[...].astype(jnp.float32)
    if fused:
        alpha = _alpha_from_r_obs(
            aux, stats_ref[0, 0], stats_ref[0, 1], alphas, r_min, r_max)
    else:
        alpha = aux                                   # (TQ, 1)

    d2 = d2_ref[...].astype(jnp.float32)              # (TQ, K)
    z = z_ref[...].astype(jnp.float32)                # neighbour values
    w = A.idw_weights_sq(d2, alpha)                   # same op chain as jnp path
    # the select (a no-op: w = 0 exactly where d2 = inf) keeps the
    # interpreter's XLA from contracting w * z into the running sum below,
    # an FMA the eager jnp path does not take
    wz = jnp.where(d2 < jnp.inf, w * z, 0.0)
    # sequential k-axis accumulation — the SAME pinned order as
    # A.topk_weighted_partial_sums, so fused == unfused bitwise
    swz, sw = wz[:, 0:1], w[:, 0:1]
    for i in range(1, d2.shape[1]):
        swz = swz + wz[:, i:i + 1]
        sw = sw + w[:, i:i + 1]
    zero = sw <= 0.0
    vals = jnp.where(zero, jnp.float32(A.ZERO_WEIGHT_SENTINEL),
                     swz / jnp.where(zero, 1.0, sw))
    out_ref[...] = vals.astype(out_ref.dtype)
    sumw_ref[...] = sw.astype(sumw_ref.dtype)


def local_interpolate_kernel(
    d2, z, aux, stats,
    *, tile_q: int = DEFAULT_TILE_Q, fused: bool = False,
    alphas=A.DEFAULT_ALPHAS, r_min: float = A.DEFAULT_R_MIN,
    r_max: float = A.DEFAULT_R_MAX, interpret: bool | None = None,
):
    """Raw pallas_call wrapper for the local (exact-k) Stage-2 kernel.

    Shapes: d2/z (n, k) — the k merged Stage-1 neighbours per query and
    their data values (gathered by the caller); aux (n, 1) alpha (or r_obs
    when ``fused``); stats (1, 2) f32 traced (n_points, area).

    One grid dimension over query tiles — each query touches only its k
    neighbours, O(k) work instead of the global kernel's O(m) data axis.
    Returns ``(values (n,1), sum_w (n,1))``.
    """
    n, k = d2.shape
    assert n % tile_q == 0, (n, tile_q)
    grid = (n // tile_q,)

    kernel = functools.partial(
        _local_kernel, fused=fused, alphas=tuple(alphas),
        r_min=r_min, r_max=r_max,
    )
    k_spec = pl.BlockSpec((tile_q, k), lambda i: (i, 0))
    q_spec = pl.BlockSpec((tile_q, 1), lambda i: (i, 0))
    s_spec = pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[k_spec, k_spec, q_spec, s_spec],
        out_specs=(q_spec, q_spec),
        out_shape=(jax.ShapeDtypeStruct((n, 1), aux.dtype),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=resolve_interpret(interpret),
        name="aidw_local_fused" if fused else "aidw_local",
    )(d2, z, aux, stats)
