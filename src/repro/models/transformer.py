"""Model assembly: embedding -> scanned block periods -> tied head.

Layers are executed as ``lax.scan`` over *pattern periods* (the repeating
block pattern of the config: length 1 for dense archs, 2 for gemma2, 8 for
jamba).  Parameters are stacked with a leading ``n_periods`` dimension per
pattern position — this keeps compile time flat in depth (crucial for the
40-cell x 2-mesh dry-run) and is the standard production layout for big
JAX models.

Three entry points:

* ``forward_train``  — full-sequence causal (or bidirectional) forward
* ``decode_step``    — one token with KV caches / SSM states
* ``init_params`` / ``init_decode_state`` — parameter & cache construction
  (both usable under ``jax.eval_shape`` for the dry-run).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from ..configs.base import ArchConfig, LayerSpec
from .attention import AttnParams, attn_forward, init_attn
from .layers import cross_entropy, rmsnorm, softcap, swiglu
from .mamba import MambaParams, init_mamba, init_ssm_state, mamba_forward
from .moe import MoEParams, init_moe, moe_forward

Params = Dict[str, Any]


def n_periods(cfg: ArchConfig) -> int:
    period = len(cfg.block_pattern)
    assert cfg.n_layers % period == 0, (cfg.n_layers, period)
    return cfg.n_layers // period


# ----------------------------------------------------------------- params --

def _init_block(cfg: ArchConfig, spec: LayerSpec, key, dtype) -> Params:
    keys = jax.random.split(key, 4)
    p: Params = {"ln1": jnp.zeros((cfg.d_model,), dtype=dtype)}
    if spec.mixer == "attn":
        p["attn"] = init_attn(cfg, keys[0], dtype)
    else:
        p["ssm"] = init_mamba(cfg, keys[0], dtype)
    if spec.ffn != "none":
        p["ln2"] = jnp.zeros((cfg.d_model,), dtype=dtype)
    if spec.ffn in ("dense", "moe+dense"):
        s = cfg.d_model ** -0.5
        p["mlp"] = {
            "w_gate": (jax.random.normal(keys[1],
                                         (cfg.d_model, cfg.d_ff)) * s
                       ).astype(dtype),
            "w_up": (jax.random.normal(keys[2],
                                       (cfg.d_model, cfg.d_ff)) * s
                     ).astype(dtype),
            "w_down": (jax.random.normal(keys[3], (cfg.d_ff, cfg.d_model))
                       * cfg.d_ff ** -0.5).astype(dtype),
        }
    if spec.ffn in ("moe", "moe+dense"):
        p["moe"] = init_moe(cfg, keys[1], dtype)
    return p


def init_params(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    """Random parameters from ``key``, built in one jitted program.

    Each pattern position's blocks come out of a ``lax.scan`` over the
    periods, whose stacked outputs are written in place: the device holds
    the weights plus about one layer of temporaries, never every layer
    twice (the per-layer arrays and their stack).
    """
    return _init_params(cfg, key, dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_params(cfg: ArchConfig, key, dtype) -> Params:
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    params: Params = {}
    if cfg.frontend == "none":
        params["embed"] = (jax.random.normal(
            k_embed, (cfg.vocab, cfg.d_model)) * cfg.d_model ** -0.5
        ).astype(dtype)
    else:
        # modality frontend stub: linear projection of precomputed embeddings
        params["frontend_proj"] = (jax.random.normal(
            k_embed, (cfg.frontend_dim, cfg.d_model))
            * cfg.frontend_dim ** -0.5).astype(dtype)
        params["head"] = (jax.random.normal(
            k_head, (cfg.d_model, cfg.vocab)) * cfg.d_model ** -0.5
        ).astype(dtype)
    period = len(cfg.block_pattern)
    block_keys = jax.random.split(k_blocks, n_periods(cfg) * period)
    params["blocks"] = {}
    for pi, spec in enumerate(cfg.block_pattern):
        # block (per, pi) takes key per * period + pi
        _, stacked = jax.lax.scan(
            lambda _, k, spec=spec: (None, _init_block(cfg, spec, k, dtype)),
            None, block_keys[pi::period])
        params["blocks"][f"p{pi}"] = stacked
    params["final_norm"] = jnp.zeros((cfg.d_model,), dtype=dtype)
    return params


# ---------------------------------------------------------------- forward --

def _block_forward(cfg: ArchConfig, spec: LayerSpec, p: Params,
                   x: jax.Array, *, window_cap: int = 0,
                   cache: Optional[Any] = None,
                   cache_index: Optional[jax.Array] = None,
                   positions: Optional[jax.Array] = None,
                   mask_offset: Optional[jax.Array] = None):
    new_cache = None
    h = rmsnorm(x, p["ln1"])
    if spec.mixer == "attn":
        window = spec.window
        if window_cap:
            window = min(window or window_cap, window_cap)
        ap = p["attn"] if isinstance(p["attn"], AttnParams) \
            else AttnParams(*p["attn"])
        y, kv = attn_forward(cfg, ap, h, window=window,
                             positions=positions, kv_cache=cache,
                             cache_index=cache_index,
                             mask_offset=mask_offset)
        new_cache = kv
    else:
        if cache is not None:
            y, st = mamba_forward(cfg, MambaParams(*p["ssm"]), h,
                                  state=cache, return_state=True)
            new_cache = st
        else:
            y = mamba_forward(cfg, MambaParams(*p["ssm"]), h)
    x = x + y
    if spec.ffn == "none":
        return x, new_cache
    h = rmsnorm(x, p["ln2"])
    y = 0.0
    if spec.ffn in ("dense", "moe+dense"):
        y = y + swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"])
    if spec.ffn in ("moe", "moe+dense"):
        y = y + moe_forward(cfg, MoEParams(*p["moe"]), h)
    return x + y, new_cache


def _embed(cfg: ArchConfig, params: Params, inputs: jax.Array) -> jax.Array:
    if cfg.frontend == "none":
        mesh = jax.sharding.get_abstract_mesh()
        if AxisType.Explicit not in mesh.axis_types:
            return params["embed"][inputs]
        # with explicit mesh axes the vocab-sharded table's gather has no
        # unambiguous output sharding: the rows take the tokens' sharding
        spec = jax.typeof(inputs).sharding.spec
        return params["embed"].at[inputs].get(out_sharding=P(*spec, None))
    return jnp.einsum("bsf,fd->bsd", inputs, params["frontend_proj"])


def _head(cfg: ArchConfig, params: Params, x: jax.Array) -> jax.Array:
    x = rmsnorm(x, params["final_norm"])
    if cfg.frontend == "none":
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
    return softcap(logits, cfg.logit_softcap)


def _constrain(x: jax.Array, act_spec) -> jax.Array:
    if act_spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, act_spec)


def _remat_policy(name):
    if name is None or name == "full":
        return None
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(name)


def forward_hidden(cfg: ArchConfig, params: Params, inputs: jax.Array,
                   *, window_cap: int = 0, remat: bool = False,
                   remat_policy=None, act_spec=None) -> jax.Array:
    """Embedding + scanned blocks + final norm -> hidden states (B, S, d).

    ``remat=True`` applies per-period activation checkpointing: the scan
    stores only the carried hidden state and recomputes block internals in
    the backward pass (keeps the memory term off the attention S^2 and MoE
    dispatch intermediates).

    ``act_spec`` (a PartitionSpec for (B, S, d)) pins the hidden-state
    sharding at every period boundary — without it GSPMD may keep
    activations replicated and turn the FSDP weight sharding into per-matmul
    partial-sum all-reduces (observed in the dry-run baseline).
    """
    x = _constrain(_embed(cfg, params, inputs), act_spec)

    def period(x, pblocks):
        for pi, spec in enumerate(cfg.block_pattern):
            x, _ = _block_forward(cfg, spec, pblocks[f"p{pi}"], x,
                                  window_cap=window_cap)
            x = _constrain(x, act_spec)
        return x, None

    if remat:
        pol = _remat_policy(remat_policy)
        fn = jax.checkpoint(period, policy=pol) if pol is not None \
            else jax.checkpoint(period)
    else:
        fn = period
    x, _ = jax.lax.scan(fn, x, params["blocks"])
    return rmsnorm(x, params["final_norm"])


def forward(cfg: ArchConfig, params: Params, inputs: jax.Array,
            *, window_cap: int = 0, remat: bool = False) -> jax.Array:
    """Full-sequence forward -> logits (B, S, V)."""
    x = forward_hidden(cfg, params, inputs, window_cap=window_cap,
                       remat=remat)
    return _head_logits(cfg, params, x)


def _head_logits(cfg: ArchConfig, params: Params, x: jax.Array) -> jax.Array:
    if cfg.frontend == "none":
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
    return softcap(logits, cfg.logit_softcap)


def chunked_cross_entropy(cfg: ArchConfig, params: Params, x: jax.Array,
                          labels: jax.Array, chunk: int = 512) -> jax.Array:
    """Sequence-chunked CE: never materializes full (B, S, V) logits.

    Each chunk's logits are produced, reduced to (logZ - gold) and
    discarded; ``jax.checkpoint`` makes the backward recompute them
    chunk-by-chunk as well.
    """
    b, s, d = x.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nch = s // chunk
    xs = jnp.moveaxis(x.reshape(b, nch, chunk, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, nch, chunk), 1, 0)

    @jax.checkpoint
    def step(acc, inp):
        xc, lc = inp
        logits = _head_logits(cfg, params, xc).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None],
                                   axis=-1).squeeze(-1)
        return acc + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (b * s)


def loss_fn(cfg: ArchConfig, params: Params, inputs: jax.Array,
            labels: jax.Array, *, act_spec=None,
            remat_policy=None) -> jax.Array:
    x = forward_hidden(cfg, params, inputs, remat=True,
                       remat_policy=remat_policy, act_spec=act_spec)
    return chunked_cross_entropy(cfg, params, x, labels)


# ----------------------------------------------------------------- decode --

def init_decode_state(cfg: ArchConfig, batch: int, ctx_len: int,
                      dtype=jnp.bfloat16) -> Dict[str, Any]:
    """KV caches / SSM states stacked over periods, per pattern position."""
    np_ = n_periods(cfg)
    hd = cfg.head_dim_
    caches: Dict[str, Any] = {}
    for pi, spec in enumerate(cfg.block_pattern):
        if spec.mixer == "attn":
            ctx = ctx_len
            if cfg.long_context_kv_cap and ctx_len > cfg.long_context_kv_cap:
                ctx = cfg.long_context_kv_cap
            if spec.window:
                ctx = min(ctx, max(spec.window, 1))
            shape = (np_, batch, cfg.n_kv_heads, ctx, hd)
            caches[f"p{pi}"] = (jnp.zeros(shape, dtype=dtype),
                                jnp.zeros(shape, dtype=dtype))
        else:
            caches[f"p{pi}"] = jnp.zeros(
                (np_, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                 cfg.ssm_state), dtype=jnp.float32)
    return caches


def decode_step(cfg: ArchConfig, params: Params, caches: Dict[str, Any],
                token: jax.Array, index: jax.Array):
    """One decode step.  token: (B, 1) int (or (B, 1, F) frames).

    Returns (logits (B, 1, V), new caches).  ``index`` is the absolute
    position; attention caches with capped context store at
    ``index % ctx`` (ring buffer).
    """
    x = _embed(cfg, params, token)

    def period(x, inp):
        pblocks, pcaches = inp
        new = {}
        for pi, spec in enumerate(cfg.block_pattern):
            cache = pcaches[f"p{pi}"]
            if spec.mixer == "attn":
                ctx = cache[0].shape[2]
                idx = index % ctx                      # ring slot
                moff = jnp.minimum(index, ctx - 1)     # wrapped => attend all
                pos = index[None] if index.ndim == 0 else index
            else:
                idx, moff, pos = None, None, None
            x, nc = _block_forward(cfg, spec, pblocks[f"p{pi}"], x,
                                   cache=cache, cache_index=idx,
                                   positions=pos, mask_offset=moff)
            new[f"p{pi}"] = nc
        return x, new

    x, new_caches = jax.lax.scan(period, x, (params["blocks"], caches))
    return _head(cfg, params, x), new_caches
