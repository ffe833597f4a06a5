"""Tiled MXU matmul Pallas kernel — the TPU ``dgemm`` analogue.

The BlockSpec tile sizes (bm, bn, bk) are the TPU counterpart of the paper's
algorithmic block size b: they fix the VMEM working set
(2*(bm*bk + bk*bn + bm*bn) floats of double-buffered blocks plus the
bm*bn accumulator) and the MXU utilization, and are selected by
the model-based tile tuner (``repro.perf.tile_tuner``) instead of exhaustive
sweeps.  Accumulation is f32 in a VMEM scratch buffer across the k grid
dimension (revisiting-output pattern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def vmem_bytes(bm: int, bn: int, bk: int, itemsize: int = 4) -> int:
    """VMEM working set of one grid step: the x, y and output blocks, each
    double-buffered by the Pallas pipeline, plus the f32 accumulator."""
    return 2 * itemsize * (bm * bk + bk * bn + bm * bn) + 4 * bm * bn


def tile_legal(m: int, n: int, k: int, bm: int, bn: int, bk: int,
               vmem_limit: int = 16 * 2 ** 20) -> bool:
    """MXU alignment (multiples of 128 where the dim allows) + VMEM bound.

    The bound counts double-buffered blocks (:func:`vmem_bytes`) against
    the compiler's default scoped VMEM (16 MiB), so every tile admitted
    here compiles: on TPU v5e, f32 (1024, 1024, 512) counts 20 MiB and is
    refused, as the compiler refuses it.  This is the TPU analogue of the
    paper's cache-driven constraints on leading dimensions and block
    sizes (§3.1.3).
    """
    if m % bm or n % bn or k % bk:
        return False
    for b, d in ((bm, m), (bn, n), (bk, k)):
        if d >= 128 and b % 128:
            return False
    return vmem_bytes(bm, bn, bk) <= vmem_limit


def grid_steps(m: int, n: int, k: int, bm: int, bn: int, bk: int) -> int:
    """Grid steps one (m, n, k) problem runs at tiles (bm, bn, bk)."""
    return (m // bm) * (n // bn) * (k // bk)


def proxy_problem(bm: int, bn: int, bk: int,
                  steps_per_dim: int = 2) -> tuple:
    """The canonical small problem that measures tiles (bm, bn, bk).

    The device measurement protocol (:mod:`repro.tc.device`) times a tile
    config on this problem — ``steps_per_dim`` grid steps in each grid
    dimension, so the revisiting-output accumulation pattern is exercised
    — and models the *per-grid-step* cost; a full problem's compute term
    is then that cost scaled by :func:`grid_steps`, exactly the paper's
    measure-the-kernel / predict-the-blocked-algorithm split (§4.6).
    """
    return (bm * steps_per_dim, bn * steps_per_dim, bk * steps_per_dim)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(x: jax.Array, y: jax.Array, *, bm: int = 128, bn: int = 128,
           bk: int = 128, interpret: bool = False) -> jax.Array:
    """``x @ y`` via a tiled Pallas kernel with explicit VMEM blocking."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"tiles ({bm},{bn},{bk}) must divide ({m},{n},{k})"
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[_VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, y)
