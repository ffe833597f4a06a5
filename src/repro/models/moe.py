"""Mixture-of-Experts FFN (top-k routing, capacity-based dispatch).

Classic TPU-style MoE: router -> top-k -> one-hot dispatch/combine einsums.
The expert dimension E is sharded on the "model" mesh axis (expert
parallelism); GSPMD turns the dispatch/combine einsums into all-to-alls.
Capacity factor bounds per-expert work so the computation is static-shaped
(dropped tokens fall through the residual connection).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig


class MoEParams(NamedTuple):
    router: jax.Array    # (d_model, E)
    w_gate: jax.Array    # (E, d_model, d_ff)
    w_up: jax.Array      # (E, d_model, d_ff)
    w_down: jax.Array    # (E, d_ff, d_model)


def init_moe(cfg: ArchConfig, key, dtype) -> MoEParams:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    k0, k1, k2, k3 = jax.random.split(key, 4)
    s = d ** -0.5
    return MoEParams(
        router=(jax.random.normal(k0, (d, e)) * s).astype(dtype),
        w_gate=(jax.random.normal(k1, (e, d, f)) * s).astype(dtype),
        w_up=(jax.random.normal(k2, (e, d, f)) * s).astype(dtype),
        w_down=(jax.random.normal(k3, (e, f, d)) * (f ** -0.5)).astype(dtype),
    )


def _maybe_constrain(x: jax.Array, spec) -> jax.Array:
    """Sharding constraint under a mesh (``jax.set_mesh``); a no-op
    outside one."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def moe_forward(cfg: ArchConfig, p: MoEParams, x: jax.Array,
                capacity_factor: float = None) -> jax.Array:
    """Default MoE forward: shard-local scatter dispatch.

    Tokens are viewed as (D, t_local, d) where D = ``cfg.moe_data_shards``
    (the data-axis width used for the dry-run; 1 on a single host — the
    algorithm is pure reshape semantics either way).  Routing, capacity
    positions and the dispatch scatter are all *local to a data shard*;
    only the expert computation is expert-sharded ("model" axis), so the
    per-layer communication is O(activations), not O(t*e*c) like the
    one-hot einsum dispatch (kept as :func:`moe_forward_einsum`) that made
    the arctic baseline collective-bound (§Perf log).
    """
    if getattr(cfg, "moe_impl", "scatter") == "einsum":
        return moe_forward_einsum(cfg, p, x, capacity_factor)
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    D = max(1, getattr(cfg, "moe_data_shards", 1))
    if t % D:
        D = 1
    tl = t // D
    xt = x.reshape(D, tl, d)
    xt = _maybe_constrain(xt, ("data", None, None)) if D > 1 else xt

    logits = jnp.einsum("Dtd,de->Dte", xt, p.router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, experts = jax.lax.top_k(probs, k)             # (D, tl, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    cap = max(1, int(math.ceil(capacity_factor * k * tl / e)))

    onehot = jax.nn.one_hot(experts, e, dtype=jnp.int32)     # (D, tl, k, e)
    flat = onehot.reshape(D, tl * k, e)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(D, tl, k, e)
    pos = (pos * onehot).sum(-1)                             # (D, tl, k)
    keep = pos < cap
    slot = experts * cap + jnp.minimum(pos, cap - 1)
    slot = jnp.where(keep, slot, e * cap).reshape(D, tl * k)

    src = jnp.broadcast_to(xt[:, :, None, :],
                           (D, tl, k, d)).reshape(D, tl * k, d)
    buf = jnp.zeros((D, e * cap + 1, d), dtype=x.dtype)
    if D > 1:
        # keep the scatter shard-local: src, indices and buffer all live on
        # the data axis
        buf = _maybe_constrain(buf, ("data", None, None))
        src = _maybe_constrain(src, ("data", None, None))
    buf = buf.at[jnp.arange(D)[:, None], slot].set(src)
    if D > 1:
        buf = _maybe_constrain(buf, ("data", None, None))
    xe = buf[:, :e * cap].reshape(D, e, cap, d)
    if D > 1:
        xe = _maybe_constrain(xe, ("data", "model", None, None))

    g = jnp.einsum("Decd,edf->Decf", xe, p.w_gate)
    u = jnp.einsum("Decd,edf->Decf", xe, p.w_up)
    ye = jnp.einsum("Decf,efd->Decd", jax.nn.silu(g) * u, p.w_down,
                    preferred_element_type=x.dtype)
    if D > 1:
        ye = _maybe_constrain(ye, ("data", "model", None, None))

    ye_flat = jnp.concatenate(
        [ye.reshape(D, e * cap, d),
         jnp.zeros((D, 1, d), dtype=ye.dtype)], axis=1)
    y_tok = ye_flat[jnp.arange(D)[:, None], slot].reshape(D, tl, k, d)
    w = (gate_vals * keep).astype(y_tok.dtype)
    yt = jnp.einsum("Dtkd,Dtk->Dtd", y_tok, w)
    return yt.reshape(b, s, d)


def moe_forward_einsum(cfg: ArchConfig, p: MoEParams, x: jax.Array,
                       capacity_factor: float = None) -> jax.Array:
    """Classic one-hot dispatch/combine einsum MoE (baseline)."""
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt, p.router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, experts = jax.lax.top_k(probs, k)             # (t, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    capacity = max(1, int(math.ceil(capacity_factor * k * t / e)))

    # position of each (token, choice) within its expert's capacity buffer
    onehot = jax.nn.one_hot(experts, e, dtype=jnp.int32)     # (t, k, e)
    flat = onehot.reshape(t * k, e)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(t, k, e)
    pos = (pos_in_expert * onehot).sum(-1)                   # (t, k)
    keep = pos < capacity
    slot = experts * capacity + jnp.minimum(pos, capacity - 1)  # (t, k)
    slot = jnp.where(keep, slot, e * capacity)               # drop -> pad row

    # scatter tokens into (e*c, d) expert buffers (pad row absorbs drops)
    buf = jnp.zeros((e * capacity + 1, d), dtype=x.dtype)
    src = jnp.broadcast_to(xt[:, None, :], (t, k, d)).reshape(t * k, d)
    buf = buf.at[slot.reshape(t * k)].set(src)
    xe = buf[:e * capacity].reshape(e, capacity, d)

    g = jnp.einsum("ecd,edf->ecf", xe, p.w_gate)
    u = jnp.einsum("ecd,edf->ecf", xe, p.w_up)
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p.w_down)

    # gather back + combine with gate weights
    ye_flat = jnp.concatenate(
        [ye.reshape(e * capacity, d),
         jnp.zeros((1, d), dtype=ye.dtype)], axis=0)
    y_tok = ye_flat[slot.reshape(t * k)].reshape(t, k, d)
    w = (gate_vals * keep).astype(y_tok.dtype)               # (t, k)
    yt = jnp.einsum("tkd,tk->td", y_tok, w)
    return yt.reshape(b, s, d)


def moe_forward_einsum(cfg: ArchConfig, p: MoEParams, x: jax.Array,
                       capacity_factor: float = None) -> jax.Array:
    """Classic one-hot dispatch/combine einsum MoE (baseline)."""
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt, p.router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, experts = jax.lax.top_k(probs, k)             # (t, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    capacity = max(1, int(math.ceil(capacity_factor * k * t / e)))

    # position of each (token, choice) within its expert's buffer
    onehot = jax.nn.one_hot(experts, e, dtype=jnp.int32)     # (t, k, e)
    flat = onehot.reshape(t * k, e)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(t, k, e)
    pos = (pos_in_expert * onehot).sum(-1)                   # (t, k)
    keep = pos < capacity

    # dispatch tensor: (t, k, e, c) one-hot -> combine weights
    dispatch = (jax.nn.one_hot(experts, e, dtype=x.dtype)[..., None] *
                jax.nn.one_hot(pos, capacity, dtype=x.dtype)[..., None, :])
    dispatch = dispatch * keep[..., None, None].astype(x.dtype)
    combine = dispatch * gate_vals[..., None, None].astype(x.dtype)
    dispatch = dispatch.sum(axis=1)                          # (t, e, c)
    combine = combine.sum(axis=1)                            # (t, e, c)

    xe = jnp.einsum("td,tec->ecd", xt, dispatch)             # (e, c, d)
    g = jnp.einsum("ecd,edf->ecf", xe, p.w_gate)
    u = jnp.einsum("ecd,edf->ecf", xe, p.w_up)
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p.w_down)
    yt = jnp.einsum("ecd,tec->td", ye, combine)
    return yt.reshape(b, s, d)


def aux_load_balance_loss(logits: jax.Array, experts: jax.Array,
                          e: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    density = jax.nn.one_hot(experts[..., 0], e).mean(axis=0)
    density_proxy = probs.mean(axis=0)
    return e * jnp.sum(density * density_proxy)
