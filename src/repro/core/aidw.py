"""AIDW mathematics — Eqs. (1)-(6) of Mei, Xu & Xu (2016) / Lu & Wong (2008).

Stage 2 of the improved algorithm: given the observed mean nearest-neighbour
distance ``r_obs`` per interpolated point (from Stage 1 / kNN), adaptively
determine the distance-decay parameter ``alpha`` and take the inverse-distance
weighted average over data points (Eq. 1).

Stage-2 mode contract (``AidwConfig.stage2``):

* **global** (``'naive'``/``'tiled'``) — Eq. (1) exactly as written: the
  weighted average runs over ALL m data points.
* **local** — Eq. (1) truncated to the k merged nearest neighbours that
  Stage 1 already produced (:func:`topk_weighted_partial_sums`).  Because
  Stage 1 is untouched, ``r_obs`` and therefore ``alpha`` are **bit-identical**
  to global mode by construction; only the predicted values differ, and they
  differ exactly by the truncated far-field tail
  ``sum_{i>k} w_i (z_i - Z_local) / sum_{i<=k} w_i`` — a relative error that
  shrinks like the tail weight mass ``O(k^(1-alpha/2))`` for alpha > 2 and
  vanishes as k -> n.  Because the tail mass is set by the alpha that
  Eq. (6) itself picks, the regimes split the opposite way from naive
  intuition: UNIFORM patterns (R-statistic near 1) get alpha >= 2 — fast
  decay, tight bound — while CLUSTERED patterns get alpha ~ 0.5 near the
  clusters, whose heavy far-field tail makes local mode loosest exactly
  there; ``tests/test_local_stage2.py`` pins both regimes against the
  analytic f64 tail bound.

Zero-weight contract: every division by ``sum_i w_i`` in this module is
guarded (:func:`guarded_values`).  A query so far from all data that every
f32 weight underflows to zero yields the sentinel value 0.0 and a raised bit
in the per-query ``zero_weight_mask`` — never NaN.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

# Five distance-decay levels alpha_1..alpha_5 (Eq. 6).  The paper inherits the
# triangular-membership levels from Lu & Wong (2008); these are configurable.
DEFAULT_ALPHAS = (0.5, 1.0, 2.0, 3.0, 4.0)
DEFAULT_R_MIN = 0.0
DEFAULT_R_MAX = 2.0
EPS_D2 = 1e-12
PAD_SENTINEL = 1e30  # padded points -> d2 = inf (f32) -> weight exactly 0

# JAX name-stack scopes of the two stages in every executable that serves
# (single-device, sharded, grid_ring, fleet shard ops): a device trace finds
# each stage's ops by these names, whatever jits sit inside them.
STAGE1_SCOPE = "aidw.stage1"   # grid kNN + mean NN distance
STAGE2_SCOPE = "aidw.stage2"   # alpha + Eq. (1), whichever Stage-2 path


def expected_nn_distance(n_points, area):
    """Eq. (2): r_exp = 1 / (2 sqrt(n / A)) for a random point pattern."""
    return 1.0 / (2.0 * jnp.sqrt(n_points / area))


def nn_statistic(r_obs, r_exp):
    """Eq. (4): R(S0) = r_obs / r_exp."""
    return r_obs / r_exp


def fuzzy_membership(r_stat, r_min: float = DEFAULT_R_MIN, r_max: float = DEFAULT_R_MAX):
    """Eq. (5): normalize R(S0) to mu_R in [0, 1] by a cosine fuzzy membership."""
    mu = 0.5 - 0.5 * jnp.cos(jnp.pi / r_max * (r_stat - r_min))
    return jnp.where(r_stat <= r_min, 0.0, jnp.where(r_stat >= r_max, 1.0, mu))


def alpha_from_membership(mu, alphas=DEFAULT_ALPHAS):
    """Eq. (6): map mu_R to a distance-decay alpha by triangular membership.

    Piecewise-linear interpolation through the five levels: constant a1 on
    [0, .1], linear a1->a2 on [.1, .3], a2->a3 on [.3, .5], a3->a4 on [.5, .7],
    a4->a5 on [.7, .9], constant a5 on [.9, 1].
    """
    a1, a2, a3, a4, a5 = [jnp.asarray(a, dtype=jnp.result_type(mu, 1.0)) for a in alphas]
    mu = jnp.asarray(mu)
    out = jnp.where(mu <= 0.1, a1, 0.0)
    segs = ((0.1, a1, a2), (0.3, a2, a3), (0.5, a3, a4), (0.7, a4, a5))
    for lo, alo, ahi in segs:
        t = 5.0 * (mu - lo)
        out = jnp.where((mu > lo) & (mu <= lo + 0.2), alo * (1.0 - t) + ahi * t, out)
    return jnp.where(mu > 0.9, a5, out)


def adaptive_alpha(r_obs, n_points, area, *, alphas=DEFAULT_ALPHAS,
                   r_min: float = DEFAULT_R_MIN, r_max: float = DEFAULT_R_MAX):
    """Full Stage-2 alpha determination: Eqs. (2) -> (4) -> (5) -> (6)."""
    r_exp = expected_nn_distance(n_points, area)
    return alpha_from_membership(
        fuzzy_membership(nn_statistic(r_obs, r_exp), r_min, r_max), alphas
    )


def idw_weights_sq(d2, alpha):
    """w_i = 1/d^alpha computed from SQUARED distances: (d^2)^(-alpha/2).

    The paper defers sqrt everywhere; a zero distance (query == data point)
    is clamped so the weight saturates and the prediction converges to the
    exact data value.
    """
    return jnp.power(jnp.maximum(d2, EPS_D2), -0.5 * alpha)


@partial(jax.jit, static_argnums=(4, 5))
def weighted_partial_sums(queries_xy, points_xy, values, alpha,
                          block: int = 1024, data_block: int = 0):
    """Eq. (1) numerator/denominator: (sum_i w_i z_i, sum_i w_i) per query.

    The reusable heart of :func:`weighted_interpolate` — exposed separately
    because a data-partitioned deployment (the serving fleet's shard hosts,
    ``repro.serving.cluster.fleet``) sums these partials ACROSS shards
    before the one global division.  Blocking as in
    :func:`weighted_interpolate`.
    """
    n = queries_xy.shape[0]
    m = points_xy.shape[0]
    alpha = jnp.broadcast_to(jnp.asarray(alpha, values.dtype), (n,))
    px, py = points_xy[:, 0], points_xy[:, 1]

    def tile(qb, ab, dx, dy, dz):
        d2 = (qb[:, 0:1] - dx[None, :]) ** 2 + (qb[:, 1:2] - dy[None, :]) ** 2
        w = idw_weights_sq(d2, ab[:, None])
        return (w * dz[None, :]).sum(-1), w.sum(-1)

    if data_block and data_block < m:
        dpad = (-m) % data_block
        big = jnp.float32(PAD_SENTINEL)
        dxc = jnp.pad(px, (0, dpad), constant_values=big)
        dyc = jnp.pad(py, (0, dpad), constant_values=big)
        dzc = jnp.pad(values, (0, dpad))
        nd = (m + dpad) // data_block
        chunks = (dxc.reshape(nd, data_block), dyc.reshape(nd, data_block),
                  dzc.reshape(nd, data_block))

        def one_block(args):
            qb, ab = args

            def dstep(acc, dchunk):
                wz, wsum = tile(qb, ab, *dchunk)
                return (acc[0] + wz, acc[1] + wsum), None

            zero = jnp.zeros((qb.shape[0],), jnp.float32)
            (swz, sw), _ = jax.lax.scan(dstep, (zero, zero), chunks)
            return swz, sw
    else:
        def one_block(args):
            qb, ab = args
            return tile(qb, ab, px, py, values)

    pad = (-n) % block
    qp = jnp.pad(queries_xy, ((0, pad), (0, 0)))
    ap = jnp.pad(alpha, (0, pad))
    nb = (n + pad) // block
    swz, sw = jax.lax.map(one_block,
                          (qp.reshape(nb, block, 2), ap.reshape(nb, block)))
    return swz.reshape(-1)[:n], sw.reshape(-1)[:n]


ZERO_WEIGHT_SENTINEL = 0.0  # value reported where sum(w) underflowed to zero


def guarded_values(swz, sw):
    """Eq. (1) final division with the zero-denominator guard.

    Returns ``(values, zero_weight_mask)``.  Where the f32 weight sum
    underflowed to exactly zero (query far from all data with large alpha),
    the value is the explicit sentinel ``ZERO_WEIGHT_SENTINEL`` (0.0) and the
    mask bit is set — the NaN that plain ``swz / sw`` would emit never
    escapes.  Everywhere else the division is performed verbatim, keeping
    guarded results bit-identical to the unguarded ones.
    """
    zero = sw <= 0.0
    vals = jnp.where(zero, ZERO_WEIGHT_SENTINEL,
                     swz / jnp.where(zero, 1.0, sw))
    return vals, zero


def topk_weighted_partial_sums(d2, z, alpha):
    """Local-mode Eq. (1) partials over the k merged Stage-1 neighbours.

    ``d2``: (n, k) squared distances to the k nearest neighbours,
    ``z``: (n, k) the neighbours' data values (gathered via the kNN indices),
    ``alpha``: per-query (n,) or scalar decay.  Padded / missing neighbour
    slots carry ``d2 = inf``, whose weight is exactly 0.0 for every
    alpha > 0 — padding the k axis never perturbs the sums bitwise.

    Accumulation over the k axis is SEQUENTIAL (pinned left-to-right order)
    rather than ``jnp.sum``'s shape-dependent reduction tree: appending
    zero-weight slots then changes nothing bitwise, and the Pallas local
    kernel, which accumulates in the same order, reproduces this path
    bit-for-bit.
    """
    alpha = jnp.asarray(alpha, z.dtype)
    if alpha.ndim == 1:
        alpha = alpha[:, None]
    w = idw_weights_sq(d2, alpha)
    wz = w * z
    swz, sw = wz[..., 0], w[..., 0]
    for i in range(1, d2.shape[-1]):
        swz = swz + wz[..., i]
        sw = sw + w[..., i]
    return swz, sw


@partial(jax.jit, static_argnums=(4, 5))
def weighted_interpolate(queries_xy, points_xy, values, alpha,
                         block: int = 1024, data_block: int = 0):
    """Eq. (1): Z(x) = sum_i w_i z_i / sum_i w_i over ALL data points.

    ``alpha`` is per-query (AIDW) or scalar (standard IDW).  Blocked over
    queries; ``data_block`` additionally chunks the data axis with running
    (sum w*z, sum w) accumulators, bounding the tile at
    (block x data_block) for billion-point datasets — the pure-jnp analogue
    of the Pallas kernel's accumulate-over-data-blocks grid dimension.

    The division is guarded: zero-weight queries produce the 0.0 sentinel,
    never NaN (see :func:`guarded_values`; callers needing the mask use
    ``guarded_values(*weighted_partial_sums(...))`` directly).
    """
    swz, sw = weighted_partial_sums(queries_xy, points_xy, values, alpha,
                                    block, data_block)
    return guarded_values(swz, sw)[0]
