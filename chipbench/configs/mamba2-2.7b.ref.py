"""Plain reference of the ``mamba2-2.7b`` configuration.

The model's forward pass over whole sequences in ``jax.numpy``: embedding,
then per layer an RMS norm, the input projection into z, x, B, C and dt,
the selective state-space recurrence stepped one position at a time,
the gated RMS norm and the output projection added to the residual; a
final RMS norm and the tied output head.  No kernels, cache or batching
of the program's; it imports nothing of the program.  The block has no
depthwise convolution, as the program's block has none (``d_conv`` 0 in
the configuration, published 4).

It also makes the weights from the seed, in one jitted call on the
device, in the pytree the program's ``init_params`` describes (the
shapes are given, not the values).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

#: served logits against the reference's, max over served tokens of
#: max |served - ref| / max |ref| in the token's row.  Readings it was set
#: from (PERF.md, "Limits"): the program's, up to 4.40e-4, and the
#: control's, the reference at matmul precision ``high`` (three bfloat16
#: passes), from 1.21e-3.
LOGIT_LIMIT = 8e-4
#: which of ``kinds/serve.logit_numbers`` is ``logit_err``
LOGIT_NUMBER = "row_max"
#: the widest gap, in logits, by which a served token's reference logit
#: lies below the reference's best at its position.  Readings it was set
#: from (PERF.md, "Limits"): the program's, and a served token altered
#: where it is produced.
GAP_LIMIT = 0.05
EPS = 1e-6


def _leaf_name(path) -> str:
    last = path[-1]
    for attr in ("name", "key", "idx"):
        if hasattr(last, attr):
            return str(getattr(last, attr))
    return str(last)


def _init(name: str, shape, key, c: Mapping[str, Any]) -> jax.Array:
    """One layer's (or the model's) weight ``name`` from ``key``."""
    f32 = jnp.float32
    if name in ("embed", "in_proj"):
        return jax.random.normal(key, shape, f32) * c["d_model"] ** -0.5
    if name == "out_proj":
        return jax.random.normal(key, shape, f32) * shape[0] ** -0.5
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, np.log(1e-3),
                                        np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
    if name == "d_skip":
        return jnp.ones(shape, f32)
    if name in ("ln1", "norm_g", "final_norm"):
        return jnp.zeros(shape, f32)
    raise KeyError(f"no initializer for weight {name!r}")


def make_params(c: Mapping[str, Any], shapes, key: int):
    """Weights for the pytree ``shapes`` (``jax.ShapeDtypeStruct``
    leaves), made on the device in one jitted call.  Leaves under
    ``blocks`` are stacked over layers and made one layer at a time, so
    the device never holds more than the weights and one layer."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(k):
        leaves = []
        for i, (path, sd) in enumerate(flat):
            name = _leaf_name(path)
            ki = jax.random.fold_in(k, i)
            if any(getattr(p, "key", None) == "blocks" for p in path):
                keys = jax.random.split(ki, sd.shape[0])
                _, leaf = jax.lax.scan(
                    lambda _, kk, n=name, s=sd.shape[1:]:
                    (None, _init(n, s, kk, c)), None, keys)
            else:
                leaf = _init(name, sd.shape, ki, c)
            leaves.append(leaf.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(jax.random.PRNGKey(key))


def named(params) -> Dict[str, jax.Array]:
    """The weights by name (the last key of each leaf's path)."""
    return {_leaf_name(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}


def _round_bf16(x):
    """float32 ``x`` rounded to the nearest bfloat16, kept in float32, by
    integer arithmetic on the bits (a float32 -> bfloat16 -> float32
    convert pair may be folded away by the compiler)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _dot(eq: str, a, b, precision: str):
    """``jnp.einsum(eq, a, b)`` at a matrix-unit precision, made explicit
    so that it means the same on every platform: ``highest`` is float32;
    ``high`` is three bfloat16 products (of the high and low bfloat16
    parts of each operand, all but low times low) summed in float32."""
    exact = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=exact)
    if precision != "high":
        raise ValueError(f"matmul precision {precision!r}")
    a_hi, b_hi = _round_bf16(a), _round_bf16(b)
    a_lo, b_lo = _round_bf16(a - a_hi), _round_bf16(b - b_hi)
    return (jnp.einsum(eq, a_hi, b_hi, precision=exact)
            + jnp.einsum(eq, a_hi, b_lo, precision=exact)
            + jnp.einsum(eq, a_lo, b_hi, precision=exact))


def _rmsnorm(x, scale):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
    return x * (1.0 + scale)


def _layer(c, x, w, precision: str):
    """One block over whole sequences x: (B, L, d_model)."""
    b, length, _ = x.shape
    h, p, n, g = c["ssm_heads"], c["ssm_head_dim"], c["ssm_state"], \
        c["ssm_groups"]
    di = c["ssm_expand"] * c["d_model"]
    proj = _dot("bld,dw->blw", _rmsnorm(x, w["ln1"]), w["in_proj"],
                precision)
    z = proj[..., :di]
    xs = proj[..., di:2 * di].reshape(b, length, h, p)
    bmat = proj[..., 2 * di:2 * di + g * n].reshape(b, length, g, n)
    cmat = proj[..., 2 * di + g * n:2 * di + 2 * g * n].reshape(
        b, length, g, n)
    dt = jax.nn.softplus(proj[..., 2 * di + 2 * g * n:] + w["dt_bias"])
    a = -jnp.exp(w["a_log"])
    bmat = jnp.repeat(bmat, h // g, axis=2)            # (B, L, H, N)
    cmat = jnp.repeat(cmat, h // g, axis=2)

    def step(state, t):                                # state (B, H, P, N)
        x_t, b_t, c_t, dt_t = t
        state = state * jnp.exp(a * dt_t)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, _dot("bhpn,bhn->bhp", state, c_t, precision)

    seq = (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(bmat, 1, 0),
           jnp.moveaxis(cmat, 1, 0), jnp.moveaxis(dt, 1, 0))
    _, ys = jax.lax.scan(step, jnp.zeros((b, h, p, n), jnp.float32), seq)
    y = jnp.moveaxis(ys, 0, 1) + w["d_skip"][:, None] * xs
    y = y.reshape(b, length, di) * jax.nn.silu(z)
    return x + _dot("bli,id->bld", _rmsnorm(y, w["norm_g"]),
                    w["out_proj"], precision)


def forward(c: Mapping[str, Any], params, tokens, precision: str):
    """Logits (B, L, vocab) of ``tokens`` (B, L), every matrix product at
    matmul precision ``precision`` (see ``_dot``)."""
    w = named(params)
    layers = {k: w[k] for k in ("ln1", "in_proj", "a_log", "d_skip",
                                "dt_bias", "norm_g", "out_proj")}

    @jax.jit
    def run(layers, embed, final_norm, tokens):
        x = embed[tokens]
        x, _ = jax.lax.scan(lambda x, lw: (_layer(c, x, lw, precision),
                                           None), x, layers)
        return _dot("bld,vd->blv", _rmsnorm(x, final_norm), embed,
                    precision)

    return run(layers, w["embed"], w["final_norm"], tokens)


def widest_gap(logits, rows, positions, tokens) -> float:
    """max over (row, position, token) of max(logits[row, position]) -
    logits[row, position, token]: how far the tokens fall below the best."""
    sel = logits[rows, positions]                       # (K, V)
    best = jnp.max(sel, axis=-1)
    got = jnp.take_along_axis(sel, tokens[:, None], axis=-1)[:, 0]
    return float(jnp.max(best - got))


def top_tokens(logits, rows, positions):
    return jnp.argmax(logits[rows, positions], axis=-1)
