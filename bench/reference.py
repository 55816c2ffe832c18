"""Plain AIDW reference: Mei, Xu & Xu (2016), Eqs. (1)-(6), brute-force kNN.

Written from the paper's equations, as ``benchmarks/serial_ref.py`` is, and
importing nothing of the program under test.  Elementwise float32 under
``jax.default_matmul_precision("highest")``, one jitted block of queries at a
time against the whole data set, so that it fits on one chip at m = 1,000,000
once the program's own state is freed.

``dtype=jnp.bfloat16`` computes the same arithmetic one precision lower: the
benchmark's control, which its limits have to refuse.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ALPHAS = (0.5, 1.0, 2.0, 3.0, 4.0)   # alpha_1..alpha_5 of Eq. (6)
R_MIN, R_MAX = 0.0, 2.0              # Eq. (5)'s normalisation bounds
EPS_D2 = 1e-12                       # a query on a data point keeps a finite weight
CHUNK = 8192                         # data points per first-pass top-k


def study_area(points_xy: np.ndarray, pad: float) -> float:
    """Eq. (2)'s study area A: the paper's even grid over the data's bounding
    box, widened by ``pad`` on each side.  The cell width is the expected
    nearest-neighbour distance of the box (Eq. 2) and
    ``nCol = (maxX - minX + cellWidth) / cellWidth`` (paper, section 4.1.1),
    so A is the grid's extent, not the box's."""
    p = np.asarray(points_xy, np.float64)
    min_x, max_x = p[:, 0].min() - pad, p[:, 0].max() + pad
    min_y, max_y = p[:, 1].min() - pad, p[:, 1].max() + pad
    w, h = max_x - min_x, max_y - min_y
    cell = 1.0 / (2.0 * math.sqrt(p.shape[0] / (w * h)))
    n_cols = int((w + cell) / cell)
    n_rows = int((h + cell) / cell)
    return (n_cols * cell) * (n_rows * cell)


def _alpha(r_obs, n_points, area):
    """Eqs. (2), (4), (5), (6): r_obs -> R -> mu_R -> alpha."""
    r_exp = 1.0 / (2.0 * jnp.sqrt(n_points / area))
    r = r_obs / r_exp
    mu = 0.5 - 0.5 * jnp.cos(jnp.pi / R_MAX * (r - R_MIN))
    mu = jnp.where(r <= R_MIN, 0.0, jnp.where(r >= R_MAX, 1.0, mu))
    a1, a2, a3, a4, a5 = ALPHAS
    t = 5.0 * mu
    return jnp.select(
        [mu <= 0.1, mu <= 0.3, mu <= 0.5, mu <= 0.7, mu <= 0.9],
        [jnp.full_like(mu, a1),
         a1 * (1.0 - (t - 0.5)) + a2 * (t - 0.5),
         a2 * (1.0 - (t - 1.5)) + a3 * (t - 1.5),
         a3 * (1.0 - (t - 2.5)) + a4 * (t - 2.5),
         a4 * (1.0 - (t - 3.5)) + a5 * (t - 3.5)],
        jnp.full_like(mu, a5)).astype(mu.dtype)


@partial(jax.jit, static_argnames=("k", "local"))
def _block(q, px, py, pz, n_points, area, *, k: int, local: bool):
    """One block of queries against every data point.  ``px``/``py``/``pz``
    are padded to a multiple of :data:`CHUNK` with far-away points."""
    d2 = (q[:, 0:1] - px[None, :]) ** 2 + (q[:, 1:2] - py[None, :]) ** 2
    b, m = d2.shape
    # exact top-k in two passes: the k nearest of each chunk, then of those
    neg, idx = jax.lax.top_k(-d2.reshape(b, m // CHUNK, CHUNK), k)
    idx = idx + (jnp.arange(m // CHUNK) * CHUNK)[None, :, None]
    neg, pos = jax.lax.top_k(neg.reshape(b, -1), k)
    knn_d2 = -neg
    knn_idx = jnp.take_along_axis(idx.reshape(b, -1), pos, axis=1)
    r_obs = jnp.mean(jnp.sqrt(knn_d2), axis=1)                   # Eq. (3)
    alpha = _alpha(r_obs, n_points, area)
    if local:        # Eq. (1) over the k nearest points only
        d2, z = knn_d2, pz[knn_idx]
    else:            # Eq. (1) over every data point
        z = pz[None, :]
    w = jnp.maximum(d2, EPS_D2) ** (-0.5 * alpha[:, None])
    return jnp.sum(w * z, axis=1) / jnp.sum(w, axis=1), alpha, r_obs


def aidw(points_xyz: np.ndarray, queries_xy: np.ndarray, *, area: float,
         k: int = 15, local: bool = False, dtype=jnp.float32,
         block: int = 128) -> dict:
    """AIDW values, alpha and r_obs for every query, as numpy arrays.

    ``local=False``: Eq. (1) over all m data points (the paper's semantics);
    ``local=True``: Eq. (1) over exactly the k nearest points.  ``area`` is
    Eq. (2)'s study area (:func:`study_area` of the data set the grid was
    planned on); ``n_points`` in Eq. (2) is the current number of points."""
    pts = np.asarray(points_xyz, np.float32)
    m = pts.shape[0]
    pad = (-m) % CHUNK
    far = np.float32(1e20)       # its squared distance overflows to inf: weight 0
    cols = [np.concatenate([pts[:, i], np.full(pad, fill, np.float32)])
            for i, fill in ((0, far), (1, far), (2, 0.0))]
    px, py, pz = (jnp.asarray(c, dtype) for c in cols)
    n_points, a = jnp.asarray(m, dtype), jnp.asarray(area, dtype)
    q = np.asarray(queries_xy, np.float32)
    n = q.shape[0]
    qpad = np.concatenate([q, np.repeat(q[-1:], (-n) % block, axis=0)])
    out = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, qpad.shape[0], block):
            out.append(_block(jnp.asarray(qpad[s:s + block], dtype),
                              px, py, pz, n_points, a, k=k, local=local))
    values, alpha, r_obs = (
        np.concatenate([np.asarray(o[i], np.float32) for o in out])[:n]
        for i in range(3))
    return {"values": values, "alpha": alpha, "r_obs": r_obs}
