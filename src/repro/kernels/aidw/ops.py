"""jit'd public wrappers for the AIDW Stage-2 Pallas kernels.

Every wrapper returns ``(values, zero_weight_mask)``: the per-query mask is
True where the f32 weight sum underflowed to zero and the value is the 0.0
sentinel instead of NaN (see ``repro.core.aidw.guarded_values``).

``n_points``/``area`` ride through as TRACED scalars (an SMEM (1, 2) stats
block), so dataset churn never retraces the fused kernels.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import aidw as A

from .aidw_kernel import (DEFAULT_TILE_D, DEFAULT_TILE_Q,
                          local_interpolate_kernel, tiled_interpolate_kernel)

PAD_COORD = 1e30  # padded data points -> d2 = inf (f32) -> weight exactly 0


def _pad1(a, mult, value=0.0):
    pad = (-a.shape[0]) % mult
    return jnp.pad(a, (0, pad), constant_values=value) if pad else a


def _stats(n_points, area):
    """The traced (1, 2) f32 (n_points, area) SMEM block."""
    return jnp.stack([jnp.asarray(n_points, jnp.float32).reshape(()),
                      jnp.asarray(area, jnp.float32).reshape(())]).reshape(1, 2)


@partial(jax.jit, static_argnames=("tile_q", "tile_d", "interpret"))
def tiled_interpolate(
    queries_xy: jax.Array,   # (n, 2)
    points_xy: jax.Array,    # (m, 2)
    values: jax.Array,       # (m,)
    alpha: jax.Array,        # (n,) or scalar
    *, tile_q: int = DEFAULT_TILE_Q, tile_d: int = DEFAULT_TILE_D,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Eq. (1) weighted average over all data points, per-query alpha.

    The TPU 'tiled version': drop-in replacement for
    ``repro.core.aidw.weighted_interpolate``.  Returns
    ``(values, zero_weight_mask)``.
    """
    n = queries_xy.shape[0]
    alpha = jnp.broadcast_to(jnp.asarray(alpha, queries_xy.dtype), (n,))
    qx = _pad1(queries_xy[:, 0], tile_q)[:, None]
    qy = _pad1(queries_xy[:, 1], tile_q)[:, None]
    aux = _pad1(alpha, tile_q, value=1.0)[:, None]
    px = _pad1(points_xy[:, 0], tile_d, PAD_COORD)[None, :]
    py = _pad1(points_xy[:, 1], tile_d, PAD_COORD)[None, :]
    pz = _pad1(values, tile_d)[None, :]
    out, sumw = tiled_interpolate_kernel(
        qx, qy, aux, _stats(1.0, 1.0), px, py, pz,
        tile_q=tile_q, tile_d=tile_d, fused=False, interpret=interpret,
    )
    return out[:n, 0], sumw[:n, 0] <= 0.0


@partial(jax.jit, static_argnames=(
    "tile_q", "tile_d", "interpret", "alphas", "r_min", "r_max"))
def fused_stage2(
    queries_xy: jax.Array,   # (n, 2)
    points_xy: jax.Array,    # (m, 2)
    values: jax.Array,       # (m,)
    r_obs: jax.Array,        # (n,) Stage-1 mean NN distance
    *, n_points, area,       # TRACED scalars (dataset churn never retraces)
    alphas: tuple = A.DEFAULT_ALPHAS,
    r_min: float = A.DEFAULT_R_MIN, r_max: float = A.DEFAULT_R_MAX,
    tile_q: int = DEFAULT_TILE_Q, tile_d: int = DEFAULT_TILE_D,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Beyond-paper fusion: alpha determination (Eqs. 2/4/5/6) + Eq. (1)
    weighting in ONE kernel launch (the paper launches two).  Returns
    ``(values, zero_weight_mask)``."""
    n = queries_xy.shape[0]
    qx = _pad1(queries_xy[:, 0], tile_q)[:, None]
    qy = _pad1(queries_xy[:, 1], tile_q)[:, None]
    aux = _pad1(jnp.asarray(r_obs, queries_xy.dtype), tile_q, value=1.0)[:, None]
    px = _pad1(points_xy[:, 0], tile_d, PAD_COORD)[None, :]
    py = _pad1(points_xy[:, 1], tile_d, PAD_COORD)[None, :]
    pz = _pad1(values, tile_d)[None, :]
    out, sumw = tiled_interpolate_kernel(
        qx, qy, aux, _stats(n_points, area), px, py, pz,
        tile_q=tile_q, tile_d=tile_d, fused=True, alphas=tuple(alphas),
        r_min=r_min, r_max=r_max, interpret=interpret,
    )
    return out[:n, 0], sumw[:n, 0] <= 0.0


def _local_call(d2, idx, aux, stats, values, *, tile_q, fused, alphas,
                r_min, r_max, interpret):
    """Shared gather + padding + launch for the local (exact-k) kernel.

    The neighbour values are gathered here, ``values[idx]`` exactly as the
    unfused jnp path gathers them, so the kernel sees an (n, k) block and
    never holds the m-long value row in VMEM.  The k axis is not padded: a
    block spanning the whole axis needs no 128-lane multiple."""
    n = d2.shape[0]
    z = values[idx]
    qpad = (-n) % tile_q
    if qpad:
        d2 = jnp.pad(d2, ((0, qpad), (0, 0)), constant_values=jnp.inf)
        z = jnp.pad(z, ((0, qpad), (0, 0)))
        aux = jnp.pad(aux, (0, qpad), constant_values=1.0)
    out, sumw = local_interpolate_kernel(
        d2, z, aux[:, None], stats,
        tile_q=tile_q, fused=fused, alphas=tuple(alphas),
        r_min=r_min, r_max=r_max, interpret=interpret,
    )
    return out[:n, 0], sumw[:n, 0] <= 0.0


@partial(jax.jit, static_argnames=("tile_q", "interpret"))
def local_interpolate(
    d2: jax.Array,           # (n, k) merged Stage-1 neighbour distances^2
    idx: jax.Array,          # (n, k) neighbour indices into ``values``
    values: jax.Array,       # (m,) data values (gathered by idx)
    alpha: jax.Array,        # (n,) or scalar
    *, tile_q: int = DEFAULT_TILE_Q, interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Local (exact-k) Eq. (1): neighbour weighting in one kernel launch.

    Bit-identical to ``repro.core.aidw.topk_weighted_partial_sums`` +
    ``guarded_values`` on the same (d2, values[idx], alpha) inputs.  Returns
    ``(values, zero_weight_mask)``.
    """
    n = d2.shape[0]
    alpha = jnp.broadcast_to(jnp.asarray(alpha, values.dtype), (n,))
    return _local_call(d2, idx, alpha, _stats(1.0, 1.0), values,
                       tile_q=tile_q, fused=False, alphas=A.DEFAULT_ALPHAS,
                       r_min=A.DEFAULT_R_MIN, r_max=A.DEFAULT_R_MAX,
                       interpret=interpret)


@partial(jax.jit, static_argnames=(
    "tile_q", "interpret", "alphas", "r_min", "r_max"))
def fused_local_stage2(
    d2: jax.Array,           # (n, k) merged Stage-1 neighbour distances^2
    idx: jax.Array,          # (n, k) neighbour indices into ``values``
    values: jax.Array,       # (m,) data values (gathered by idx)
    r_obs: jax.Array,        # (n,) Stage-1 mean NN distance
    *, n_points, area,       # TRACED scalars
    alphas: tuple = A.DEFAULT_ALPHAS,
    r_min: float = A.DEFAULT_R_MIN, r_max: float = A.DEFAULT_R_MAX,
    tile_q: int = DEFAULT_TILE_Q, interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Adaptive alpha (Eqs. 2/4/5/6) + local Eq. (1) weighting over the
    gathered neighbour values, one launch, O(k) per query.  Returns
    ``(values, zero_weight_mask)``."""
    aux = jnp.asarray(r_obs, values.dtype)
    return _local_call(d2, idx, aux, _stats(n_points, area), values,
                       tile_q=tile_q, fused=True, alphas=tuple(alphas),
                       r_min=r_min, r_max=r_max, interpret=interpret)
