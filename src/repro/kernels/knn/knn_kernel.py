"""Pallas TPU kernel: blocked brute-force kNN (squared distances).

TPU adaptation of the 'original' algorithm's hot loop (Mei et al. 2015 /
paper §3.1) and of the final filter step of the improved grid search:

* CUDA: one thread per query walks all m data points, maintaining a length-k
  insertion-sorted buffer in registers — per-lane insertion sort does not
  vectorize on a TPU.
* Here: a ``(TILE_Q, TILE_D)`` distance tile is computed per grid step (outer
  broadcast, VPU-shaped); the per-query running top-k lives in a
  ``(TILE_Q, k)`` VMEM scratch carried across the ``arbitrary`` data-block
  dimension, and the merge is a **k-pass masked-min selection** over the
  concatenated ``(TILE_Q, k + TILE_D)`` tile: each pass extracts the row
  minimum and masks its first occurrence (duplicate-safe).  k passes of
  vectorized reductions replace m insertion-sort steps.

Squared distances throughout (sqrt deferred — paper §4.1.4).  Padding
contract: data sentinels at +1e30 give d2 = inf and never enter the top-k.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_TILE_Q = 256
DEFAULT_TILE_D = 512


def _kpass_topk(cat: jax.Array, k: int) -> jax.Array:
    """k smallest per row of ``cat`` (ascending) by masked-min extraction."""
    col = jax.lax.broadcasted_iota(jnp.int32, cat.shape, 1)
    outs = []
    for _ in range(k):
        v = jnp.min(cat, axis=1, keepdims=True)            # (TQ, 1)
        # first column holding the minimum: masks exactly one duplicate
        first = jnp.min(jnp.where(cat == v, col, cat.shape[1]), axis=1,
                        keepdims=True)
        cat = jnp.where(col == first, jnp.inf, cat)
        outs.append(v)
    return jnp.concatenate(outs, axis=1)                   # (TQ, k)


def _knn_kernel(
    qx_ref, qy_ref,          # queries: (TQ, 1)
    px_ref, py_ref,          # data:    (1, TD)
    out_ref,                 # output:  (TQ, k) squared distances ascending
    topk_s,                  # scratch: (TQ, k) f32
    *, k: int, n_dblocks: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        topk_s[...] = jnp.full_like(topk_s, jnp.inf)

    qx = qx_ref[...].astype(jnp.float32)
    qy = qy_ref[...].astype(jnp.float32)
    px = px_ref[...].astype(jnp.float32)
    py = py_ref[...].astype(jnp.float32)

    d2 = (qx - px) ** 2 + (qy - py) ** 2                   # (TQ, TD)
    cat = jnp.concatenate([topk_s[...], d2], axis=1)       # (TQ, k + TD)
    topk_s[...] = _kpass_topk(cat, k)

    @pl.when(j == n_dblocks - 1)
    def _finish():
        out_ref[...] = topk_s[...].astype(out_ref.dtype)


def knn_kernel(
    qx, qy, px, py, *, k: int,
    tile_q: int = DEFAULT_TILE_Q, tile_d: int = DEFAULT_TILE_D,
    interpret: bool | None = None,
):
    """Raw pallas_call wrapper.  qx/qy (n,1); px/py (1,m); returns (n,k) d2."""
    n, m = qx.shape[0], px.shape[1]
    assert n % tile_q == 0 and m % tile_d == 0, (n, tile_q, m, tile_d)
    grid = (n // tile_q, m // tile_d)

    kernel = functools.partial(_knn_kernel, k=k, n_dblocks=grid[1])
    q_spec = pl.BlockSpec((tile_q, 1), lambda i, j: (i, 0))
    d_spec = pl.BlockSpec((1, tile_d), lambda i, j: (0, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, q_spec, d_spec, d_spec],
        out_specs=pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), qx.dtype),
        scratch_shapes=[pltpu.VMEM((tile_q, k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(qx, qy, px, py)
