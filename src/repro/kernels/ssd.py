"""Mamba-2 SSD (state-space duality) chunked Pallas kernel.

The SSD recurrence  h_t = h_{t-1} * exp(a*dt_t) + dt_t * x_t ⊗ b_t,
y_t = c_t · h_t  is computed chunk-wise (the paper-recommended dual form):
within a chunk of length Q the output is a masked, decay-weighted
"attention" matmul (MXU-friendly); across chunks a (P, N) state is carried
in VMEM scratch along the innermost (sequential) grid dimension — the same
revisiting pattern the flash-attention kernel uses for its softmax state.

Layout: the wrapper moves heads in front of the sequence, so every block's
last two dimensions are (chunk, P), (chunk, N) or (chunk, 1) — the TPU
tiling rule wants the last two block dims to be multiples of (8, 128) or
equal to the array's.  The per-head decay rate is folded into a per-step
log-decay ``a * dt`` outside the kernel, and the within-chunk cumulative
sum of it is a lower-triangular matmul (Mosaic has no cumsum).

Grid: (batch, head, n_chunks); b/c projections are group-indexed in the
BlockSpec (G groups shared across H heads, like GQA).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))        # contract the last dims: a @ b.T
_TN = (((0,), (0,)), ((), ()))        # contract the first dims: a.T @ b


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, o_ref, h_ref, *,
                q: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)       # (Q, P)
    dt = dt_ref[0, 0]                          # (Q, 1)
    da = da_ref[0, 0]                          # (Q, 1) log-decay a * dt
    b = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)       # (Q, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # inclusive cumsum s_j = sum_{k<=j} da_k laid along lanes: a ones-row
    # times the upper-triangular spread of da (exact at HIGHEST precision)
    spread = jnp.where(row <= col, jnp.broadcast_to(da, (q, q)), 0.0)
    s_row = jnp.dot(jnp.ones((8, q), jnp.float32), spread,
                    precision=_HIGHEST,
                    preferred_element_type=jnp.float32)[:1]   # (1, Q)
    s_col = jnp.transpose(s_row)               # (Q, 1)
    decay = jnp.where(row >= col, jnp.exp(s_col - s_row), 0.0)
    # intra-chunk: masked decay-weighted attention, g[i, j] = dt_j c_i.b_j
    g = jax.lax.dot_general(c, b * dt, _NT,
                            preferred_element_type=jnp.float32)
    y = jnp.dot(g * decay, x, preferred_element_type=jnp.float32)
    # inter-chunk: contribution of the carried (P, N) state
    h = h_ref[...]
    y = y + jnp.exp(s_col) * jax.lax.dot_general(
        c, h, _NT, preferred_element_type=jnp.float32)
    # state update for the next chunk
    s_last = jnp.sum(da)                       # scalar: whole-chunk decay
    w = dt * jnp.exp(s_last - s_col)           # (Q, 1)
    h_ref[...] = jnp.exp(s_last) * h + jax.lax.dot_general(
        x, b * w, _TN, preferred_element_type=jnp.float32)
    o_ref[0, 0] = y.astype(o_ref.dtype)


def ssd_vmem_bytes(chunk: int, p: int, n: int, itemsize: int = 4) -> int:
    """VMEM working set of one grid step: the double-buffered x/b/c/o
    blocks, the dt and log-decay columns (lane-padded to 128), and the
    f32 (P, N) state scratch."""
    blocks = itemsize * (2 * chunk * p + 2 * chunk * n) + 4 * 2 * chunk * 128
    return 2 * blocks + 4 * p * n


def ssd_grid_steps(b: int, l: int, h: int, chunk: int) -> int:
    """Grid steps of one SSD call at chunk length ``chunk``."""
    return b * h * (l // chunk)


def ssd_proxy_problem(chunk: int, p: int, n: int,
                      steps_per_dim: int = 2) -> tuple:
    """(b, l, h, p, g, n) of the canonical small problem measuring
    ``chunk``: one batch/head/group, ``steps_per_dim`` chunks — enough to
    exercise the carried-state revisiting pattern (see
    :func:`repro.kernels.matmul.proxy_problem`)."""
    return (1, chunk * steps_per_dim, 1, p, 1, n)


def _heads_first(t: jax.Array) -> jax.Array:
    return jnp.swapaxes(t, 1, 2)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x: jax.Array, dt: jax.Array, a_log: jax.Array, b: jax.Array,
        c: jax.Array, *, chunk: int = 128,
        interpret: bool = False) -> jax.Array:
    """Chunked SSD scan.  x: (B,L,H,P); dt: (B,L,H); a_log: (H,);
    b/c: (B,L,G,N) with H % G == 0.  Returns (B,L,H,P)."""
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    assert H % G == 0
    rep = H // G
    chunk = min(chunk, L)
    assert L % chunk == 0, (L, chunk)
    dt_h = _heads_first(dt)[..., None].astype(jnp.float32)     # (B,H,L,1)
    da_h = dt_h * -jnp.exp(a_log.astype(jnp.float32))[None, :, None, None]

    def step_block(width):
        return pl.BlockSpec((1, 1, chunk, width),
                            lambda bb, h, ch: (bb, h, ch, 0))

    def group_block(width):
        return pl.BlockSpec((1, 1, chunk, width),
                            lambda bb, h, ch, r=rep: (bb, h // r, ch, 0))

    y = pl.pallas_call(
        functools.partial(_ssd_kernel, q=chunk),
        grid=(B, H, L // chunk),
        in_specs=[step_block(P), step_block(1), step_block(1),
                  group_block(N), group_block(N)],
        out_specs=step_block(P),
        out_shape=jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(_heads_first(x), dt_h, da_h, _heads_first(b), _heads_first(c))
    return _heads_first(y)
