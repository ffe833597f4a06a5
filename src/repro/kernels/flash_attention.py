"""Fused flash attention Pallas kernel (TPU target, interpret-validated).

One kernel covers every attention flavour used by the assigned
architectures:

* **GQA** — kv heads are *indexed*, not materialized: the k/v BlockSpec
  index map divides the query-head grid coordinate by the group size, so no
  repeated kv tensors ever hit VMEM (TPU-native adaptation; a CUDA port would
  have broadcast in shared memory instead).
* **causal masking** with per-block early exit (blocks strictly above the
  diagonal contribute nothing and are masked wholesale),
* **local (sliding-window) attention** — gemma2's alternating layers,
* **logit softcapping** — gemma2's ``cap * tanh(logits / cap)``.

Online softmax keeps running max/denominator in VMEM scratch across the kv
grid dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

_NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 nkv: int, bq: int, bkv: int, scale: float, causal: bool,
                 window: int, softcap: float):
    i = pl.program_id(2)   # query block
    j = pl.program_id(3)   # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                      # (bq, d)
    k = k_ref[0, 0]                      # (bkv, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    k_pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = jnp.ones((bq, bkv), dtype=jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                  # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0, 0],
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nkv - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def attn_vmem_bytes(bq: int, bkv: int, d: int, itemsize: int = 4) -> int:
    """VMEM working set of one grid step: the double-buffered q/k/v/o
    blocks + f32 scratch (running max and denominator are (bq, 1)
    columns, lane-padded to 128)."""
    blocks = 2 * itemsize * (bq * d + 2 * bkv * d + bq * d)
    scratch = 4 * (2 * bq * 128 + bq * d)
    return blocks + scratch


def attn_grid_steps(b: int, h: int, sq: int, skv: int,
                    bq: int, bkv: int) -> int:
    """Grid steps of one attention call at blocks (bq, bkv)."""
    return b * h * (sq // bq) * (skv // bkv)


def attn_proxy_problem(bq: int, bkv: int, d: int,
                       steps_per_dim: int = 2) -> tuple:
    """(b, h, sq, skv, d) of the canonical small problem measuring
    blocks (bq, bkv): one batch/head, ``steps_per_dim`` query and kv
    blocks — enough to exercise the online-softmax revisiting pattern
    (see :func:`repro.kernels.matmul.proxy_problem`)."""
    return (1, 1, bq * steps_per_dim, bkv * steps_per_dim, d)


@functools.partial(jax.jit, static_argnames=(
    "bq", "bkv", "causal", "window", "softcap", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    bq: int = 128, bkv: int = 128, causal: bool = True,
                    window: int = 0, softcap: float = 0.0,
                    interpret: bool = False) -> jax.Array:
    """Attention over (B, Hq, Sq, D) queries and (B, Hkv, Skv, D) kv.

    Hq must be a multiple of Hkv (GQA); ``window > 0`` enables sliding-window
    attention; ``softcap > 0`` applies gemma2-style logit soft-capping.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, bq, skv, bkv)
    grid = (b, hq, sq // bq, skv // bkv)
    scale = 1.0 / (d ** 0.5)
    return pl.pallas_call(
        functools.partial(
            _attn_kernel, nkv=grid[3], bq=bq, bkv=bkv, scale=scale,
            causal=causal, window=window, softcap=softcap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, i, j: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bb, h, i, j: (bb, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            _VMEM((bq, 1), jnp.float32),   # running max
            _VMEM((bq, 1), jnp.float32),   # running denominator
            _VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
