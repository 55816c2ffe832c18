"""jit'd public wrappers for the blocked brute-force kNN Pallas kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .knn_kernel import DEFAULT_TILE_D, DEFAULT_TILE_Q, knn_kernel

PAD_COORD = 1e30


def _pad1(a, mult, value=0.0):
    pad = (-a.shape[0]) % mult
    return jnp.pad(a, (0, pad), constant_values=value) if pad else a


@partial(jax.jit, static_argnames=("k", "tile_q", "tile_d", "interpret"))
def knn_d2(
    points_xy: jax.Array,    # (m, 2)
    queries_xy: jax.Array,   # (n, 2)
    *, k: int = 15,
    tile_q: int = DEFAULT_TILE_Q, tile_d: int = DEFAULT_TILE_D,
    interpret: bool | None = None,
) -> jax.Array:
    """Squared distances (n, k), ascending, of each query's k nearest points."""
    n = queries_xy.shape[0]
    qx = _pad1(queries_xy[:, 0], tile_q)[:, None]
    qy = _pad1(queries_xy[:, 1], tile_q)[:, None]
    px = _pad1(points_xy[:, 0], tile_d, PAD_COORD)[None, :]
    py = _pad1(points_xy[:, 1], tile_d, PAD_COORD)[None, :]
    out = knn_kernel(qx, qy, px, py, k=k, tile_q=tile_q, tile_d=tile_d,
                     interpret=interpret)
    return out[:n]


@partial(jax.jit, static_argnames=("k", "tile_q", "tile_d", "interpret"))
def knn_d2_with_ring(
    points_xy: jax.Array,    # (m, 2)   CSR-resident (compacted) points
    ring_xy: jax.Array,      # (r, 2)   hot append ring; dead slots PAD_COORD
    queries_xy: jax.Array,   # (n, 2)
    *, k: int = 15,
    tile_q: int = DEFAULT_TILE_Q, tile_d: int = DEFAULT_TILE_D,
    interpret: bool | None = None,
) -> jax.Array:
    """:func:`knn_d2` over the compacted table PLUS the LSM hot append ring
    (``repro.core.slab`` module docstring): ring points join the brute-force
    candidate set directly, so freshly staged inserts are query-visible with
    no re-sort.  Empty/dead ring slots must carry ``PAD_COORD`` — their
    squared distance overflows f32 to inf and is never selected, exactly the
    tombstone convention of the grid path."""
    return knn_d2(jnp.concatenate([points_xy, ring_xy], axis=0), queries_xy,
                  k=k, tile_q=tile_q, tile_d=tile_d, interpret=interpret)


def mean_nn_distance(d2: jax.Array) -> jax.Array:
    """Eq. (3) r_obs from the kernel's squared distances (sqrt deferred here)."""
    return jnp.sqrt(jnp.maximum(d2, 0.0)).mean(axis=-1)
