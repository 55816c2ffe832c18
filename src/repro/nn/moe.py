"""Mixture-of-Experts layer: top-k routing with sort-based capacity dispatch.

TPU-friendly formulation (no per-token control flow, no ragged GEMMs):

1. router top-k -> (token, expert, weight) assignments,
2. stable argsort of assignments by expert id groups each expert's tokens,
3. position-within-group (rank - group start) + static capacity C gives every
   assignment a slot in an (E, C, D) buffer; overflow assignments are dropped
   (classic capacity-factor dropping — the dispatch one-hot einsum used by
   small-E models would be O(T*E*C) memory and is hopeless at E=128),
4. batched expert SwiGLU via (E, ...) einsums on the stacked expert weights,
5. combine: gather each assignment's output slot, scale by router weight,
   segment-sum back over tokens.

Expert weights are sharded expert-major ("expert" -> model axis) so step 4 is
expert-parallel; the scatter/gather in 3/5 lowers to collective dispatch under
pjit (measured in the roofline; a shard_map all-to-all variant is a §Perf
iteration).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


from .layers import dense


def moe_capacity(n_tokens: int, n_experts: int, top_k: int,
                 capacity_factor: float = 1.25, multiple: int = 8) -> int:
    c = int(n_tokens * top_k * capacity_factor / n_experts) + 1
    return max(multiple, -(-c // multiple) * multiple)


def _constrain(t, spec):
    if spec is None:
        return t
    from jax.sharding import PartitionSpec as P
    try:
        return jax.lax.with_sharding_constraint(t, P(*spec))
    except (ValueError, RuntimeError):
        return t


def moe_apply(x: jax.Array, w_router: jax.Array, w_gate: jax.Array,
              w_up: jax.Array, w_down: jax.Array, *, top_k: int,
              capacity_factor: float = 1.25, buf_spec=None) -> jax.Array:
    """x (B, S, D); router (D, E); experts (E, D, F)/(E, F, D).  Returns (B, S, D)."""
    import jax as _jax  # noqa: F811
    B, S, D = x.shape
    E = w_router.shape[1]
    T = B * S
    xt = x.reshape(T, D)

    # 1. routing (f32 for numerics)
    logits = dense(xt, w_router).astype(jnp.float32)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    rw, eidx = jax.lax.top_k(probs, top_k)                    # (T, k)
    rw = rw / jnp.maximum(rw.sum(-1, keepdims=True), 1e-9)

    # 2. sort assignments by expert id (stable: ties keep token order)
    flat_e = eidx.reshape(-1)                                 # (T*k,)
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    sorted_e = flat_e[order]
    tok = (order // top_k).astype(jnp.int32)                  # token per assignment

    # 3. slot assignment with static capacity
    C = moe_capacity(T, E, top_k, capacity_factor)
    group_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(T * top_k, dtype=jnp.int32) - group_start.astype(jnp.int32)
    keep = pos < C
    slot = jnp.where(keep, sorted_e * C + pos, E * C)         # E*C = drop slot

    buf = jnp.zeros((E * C, D), x.dtype)
    buf = buf.at[slot].set(xt[tok], mode="drop")              # (E*C, D)
    buf = _constrain(buf.reshape(E, C, D), buf_spec)          # EP placement

    # 4. batched expert SwiGLU
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down.astype(x.dtype),
                         preferred_element_type=jnp.float32).astype(x.dtype)
    out_buf = _constrain(out_buf, buf_spec).reshape(E * C, D)

    # 5. combine: gather slots, weight, segment-sum over tokens
    w_sorted = rw.reshape(-1)[order].astype(x.dtype)          # (T*k,)
    contrib = out_buf[jnp.minimum(slot, E * C - 1)] * (w_sorted * keep)[:, None]
    out = jnp.zeros((T, D), x.dtype).at[tok].add(contrib)
    return out.reshape(B, S, D)


# ---------------------------------------------------------------------------
# expert-parallel dispatch (shard_map over the tensor axis)
# ---------------------------------------------------------------------------
#
# §Perf iteration (qwen3 cell): under plain pjit the capacity scatter
# materializes the FULL (E*C, D) buffer per chip and all-reduces it
# (~2 x 43 GB/layer on qwen3 train_4k).  Here each model-rank owns E/tp
# experts and dispatches ONLY the assignments routed to its local experts —
# tokens are replicated across the tensor axis (they are sharded over
# data/pod), so no all-to-all is needed; partial outputs are combined with
# one (T_local, D) psum.  Wire: ~2 x 0.27 GB/layer — a ~160x reduction.


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(1,))
def _psum_identity_grad(x, axis):
    return jax.lax.psum(x, axis)


def _psum_ig_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _psum_ig_bwd(axis, _, g):
    # cotangent is replicated across ``axis``; mark it varying to match the
    # primal input's manual-axes type (identity is the true psum backward).
    return (jax.lax.pcast(g, axis, to="varying"),)


_psum_identity_grad.defvjp(_psum_ig_fwd, _psum_ig_bwd)


def moe_apply_ep(x, w_router, w_gate, w_up, w_down, *, top_k: int,
                 capacity_factor: float = 1.25, axis: str = "model"):
    """Expert-parallel MoE via FULLY-manual shard_map (all mesh axes).

    Tokens stay sharded over the batch axes (local sort/scatter — no
    distributed sort, which GSPMD lowers via copy-reduction all-reduces that
    crash XLA-CPU); experts are sharded over ``axis``; each rank dispatches
    only assignments routed to its local experts and partial outputs combine
    with ONE f32 psum over ``axis``.  Routing runs on every ``axis`` rank
    redundantly (router is tiny).  Call inside a mesh context.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or axis not in mesh.axis_names:
        # no mesh context (single-device unit tests): plain dispatch
        return moe_apply(x, w_router, w_gate, w_up, w_down, top_k=top_k,
                         capacity_factor=capacity_factor)
    E = w_router.shape[1]
    b_axes = tuple(a for a in mesh.axis_names if a != axis)
    # expert-parallel degree from the EXPLICIT mesh: jax.lax.axis_size is
    # newer than 0.4.37, and e_loc must be static anyway (it shapes the
    # local dispatch buffer)
    tp = int(mesh.shape[axis])
    e_loc = E // tp

    def local_fn(x, w_router, w_gate, w_up, w_down):
        rank = jax.lax.axis_index(axis)
        lo = rank * e_loc

        B, S, D = x.shape                                     # local shard
        T = B * S
        xt = x.reshape(T, D)
        logits = dense(xt, w_router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        rw, eidx = jax.lax.top_k(probs, top_k)
        rw = rw / jnp.maximum(rw.sum(-1, keepdims=True), 1e-9)

        # keep only assignments routed to OUR experts; foreign -> drop bucket
        flat_e = eidx.reshape(-1) - lo                        # local ids
        mine = (flat_e >= 0) & (flat_e < e_loc)
        flat_e = jnp.where(mine, flat_e, e_loc)
        order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
        sorted_e = flat_e[order]
        tok = (order // top_k).astype(jnp.int32)

        C = moe_capacity(T, E, top_k, capacity_factor)
        group_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
        pos = jnp.arange(T * top_k, dtype=jnp.int32) - group_start.astype(jnp.int32)
        keep = (pos < C) & (sorted_e < e_loc)
        slot = jnp.where(keep, sorted_e * C + pos, e_loc * C)

        buf = jnp.zeros((e_loc * C, D), x.dtype)
        buf = buf.at[slot].set(xt[tok], mode="drop").reshape(e_loc, C, D)
        g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
        u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
        h = jax.nn.silu(g) * u
        out_buf = jnp.einsum("ecf,efd->ecd", h, w_down.astype(x.dtype),
                             preferred_element_type=jnp.float32
                             ).astype(x.dtype).reshape(e_loc * C, D)

        w_sorted = rw.reshape(-1)[order].astype(x.dtype)
        contrib = out_buf[jnp.minimum(slot, e_loc * C - 1)] \
            * (w_sorted * keep)[:, None]
        partial = jnp.zeros((T, D), x.dtype).at[tok].add(contrib)
        out = _psum_identity_grad(partial.astype(jnp.float32), axis)
        return out.astype(x.dtype).reshape(B, S, D)

    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(b_axes, None, None), P(None, None),
                  P(axis, None, None), P(axis, None, None),
                  P(axis, None, None)),
        out_specs=P(b_axes, None, None),
    )(x, w_router, w_gate, w_up, w_down)
