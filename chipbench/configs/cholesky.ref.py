"""Plain reference of the ``cholesky`` configuration.

The inputs, what a solved problem is worth, the answer that a run is
judged by, and a Cholesky factorization written without the program: in
float64 with numpy for the reference, and in bfloat16 for the control
that the comparison must fail.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

#: max |L - L_ref| / max |L_ref| over the checked problems.  Readings it
#: was set from (PERF.md, "Limits"): the program at float32 with the
#: chip's default matmul precision, and the bfloat16 control.
FACTOR_LIMIT = 5e-4
#: block size of the bfloat16 control
_CONTROL_BLOCK = 128


def make_inputs(sizes: Sequence[int], key: int) -> Dict[int, np.ndarray]:
    """One symmetric positive definite float32 matrix per size,
    ``G G^T + n I`` with standard normal ``G``, made on the device from
    ``key`` (exact float32 products) and kept on the host."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def spd(k, eye):
        g = jax.random.normal(k, eye.shape, jnp.float32)
        with jax.default_matmul_precision("highest"):
            return g @ g.T + eye.shape[0] * eye

    out = {}
    for i, n in enumerate(sizes):
        k = jax.random.fold_in(jax.random.PRNGKey(key), i)
        out[n] = np.asarray(spd(k, jnp.eye(n, dtype=jnp.float32)))
    return out


def useful_flops(n: int) -> float:
    """FLOPs of one Cholesky factorization of order ``n``."""
    return n ** 3 / 3.0


def answer(stored: np.ndarray) -> np.ndarray:
    """The factor in a matrix that the factorization overwrote: its lower
    triangle."""
    return np.tril(stored)


def reference(a: np.ndarray) -> np.ndarray:
    return np.linalg.cholesky(np.asarray(a, np.float64))


def error(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


def control(a: np.ndarray) -> np.ndarray:
    """Right-looking blocked Cholesky in bfloat16: every stored value is
    rounded to bfloat16, products are summed in float32."""
    a = _bf16(a)
    n = a.shape[0]
    for k in range(0, n, _CONTROL_BLOCK):
        e = min(k + _CONTROL_BLOCK, n)
        l11 = _bf16(np.linalg.cholesky(a[k:e, k:e]))
        a[k:e, k:e] = l11
        if e < n:
            l21 = _bf16(np.linalg.solve(l11, a[e:, k:e].T).T)
            a[e:, k:e] = l21
            a[e:, e:] = _bf16(a[e:, e:] - l21 @ l21.T)
    return np.tril(a)
