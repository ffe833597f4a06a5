"""Dense linear algebra kernels (the BLAS/unblocked-LAPACK layer) in JAX.

Every kernel is exposed through a uniform :class:`KernelDef` so that the
performance-model generator (``repro.core.modelgen``), the blocked-algorithm
tracers (``repro.dla.trace``) and the execution engine all speak the same
vocabulary: ``(kernel name, case, sizes)``.

Cases encode the paper's *flag arguments* (§3.1.1): transpositions, side,
uplo, unit-diagonal.  Scalar arguments are restricted to the special values
the paper identifies ({-1, 0, 1, other}, §3.1.2) and are part of the case.
Leading dimensions/increments do not exist for dense JAX arrays (§ DESIGN.md
hardware-adaptation notes).

Each kernel carries its minimal FLOP count and the maximal monomial exponents
it implies for the polynomial basis (§3.2.4).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Case = Tuple
Sizes = Tuple[int, ...]

_DTYPE = jnp.float32  # double-precision analogue on TPU-class hardware


# ----------------------------------------------------------------- helpers --

def _rng(seed: int = 0):
    return np.random.default_rng(seed)


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), dtype=_DTYPE)


def _spd(rng, n):
    a = rng.standard_normal((n, n))
    return jnp.asarray(a @ a.T + n * np.eye(n), dtype=_DTYPE)


def _lower_nonsing(rng, n):
    a = np.tril(rng.standard_normal((n, n)))
    np.fill_diagonal(a, np.abs(a.diagonal()) + n)
    return jnp.asarray(a, dtype=_DTYPE)


# ------------------------------------------------------------- level 3 ops --

@functools.lru_cache(maxsize=None)
def _gemm_fn(transA: str, transB: str, alpha: float, beta: float):
    def f(A, B, C):
        a = A.T if transA == "T" else A
        b = B.T if transB == "T" else B
        return beta * C + alpha * (a @ b)
    f.__name__ = f.__qualname__ = "gemm"
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _syrk_fn(uplo: str, trans: str, alpha: float, beta: float):
    # C := beta C + alpha A A^T (trans=N) or beta C + alpha A^T A (trans=T)
    def f(A, C):
        aat = A @ A.T if trans == "N" else A.T @ A
        return beta * C + alpha * aat
    f.__name__ = f.__qualname__ = "syrk"
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _syr2k_fn(uplo: str, trans: str, alpha: float, beta: float):
    def f(A, B, C):
        if trans == "N":
            upd = A @ B.T + B @ A.T
        else:
            upd = A.T @ B + B.T @ A
        return beta * C + alpha * upd
    f.__name__ = f.__qualname__ = "syr2k"
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _symm_fn(side: str, uplo: str, alpha: float, beta: float):
    def f(A, B, C):
        sym = jnp.tril(A) + jnp.tril(A, -1).T if uplo == "L" else \
            jnp.triu(A) + jnp.triu(A, 1).T
        prod = sym @ B if side == "L" else B @ sym
        return beta * C + alpha * prod
    f.__name__ = f.__qualname__ = "symm"
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _trsm_fn(side: str, uplo: str, transA: str, diag: str, alpha: float):
    def f(A, B):
        return alpha * lax.linalg.triangular_solve(
            A, B,
            left_side=(side == "L"),
            lower=(uplo == "L"),
            transpose_a=(transA == "T"),
            unit_diagonal=(diag == "U"),
        )
    f.__name__ = f.__qualname__ = "trsm"
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _trmm_fn(side: str, uplo: str, transA: str, diag: str, alpha: float):
    def f(A, B):
        tri = jnp.tril(A) if uplo == "L" else jnp.triu(A)
        if diag == "U":
            tri = tri - jnp.diag(jnp.diag(tri)) + jnp.eye(tri.shape[0],
                                                          dtype=tri.dtype)
        t = tri.T if transA == "T" else tri
        return alpha * (t @ B) if side == "L" else alpha * (B @ t)
    f.__name__ = f.__qualname__ = "trmm"
    return jax.jit(f)


# ---------------------------------------------------- unblocked LAPACK ops --

@jax.jit
def _potf2(A):
    """Unblocked lower Cholesky (the dpotf2 analogue)."""
    return lax.linalg.cholesky(A)


@jax.jit
def _trti2(A):
    """Unblocked lower-triangular inversion via solve against identity."""
    eye = jnp.eye(A.shape[0], dtype=A.dtype)
    return lax.linalg.triangular_solve(A, eye, left_side=True, lower=True)


@jax.jit
def _lauu2(A):
    """A := L^T L for lower-triangular L stored in A (dlauu2, lower)."""
    L = jnp.tril(A)
    return L.T @ L


@jax.jit
def _sygs2(A, L):
    """A := L^{-1} A L^{-T} (dsygs2 itype=1, lower)."""
    t = lax.linalg.triangular_solve(L, A, left_side=True, lower=True)
    return lax.linalg.triangular_solve(L, t.T, left_side=True, lower=True).T


@jax.jit
def _getf2_nopiv(A):
    """Unblocked LU without pivoting of an m x nb panel (m >= nb)."""
    m, nb = A.shape

    def body(k, a):
        col = a[:, k] / a[k, k]
        col = jnp.where(jnp.arange(m) > k, col, a[:, k])
        a = a.at[:, k].set(col)
        mask = ((jnp.arange(m)[:, None] > k) & (jnp.arange(nb)[None, :] > k))
        update = jnp.outer(col, a[k, :])
        return jnp.where(mask, a - update, a)

    return lax.fori_loop(0, min(m, nb), body, A)


@jax.jit
def _geqr2(A):
    """Unblocked QR panel: returns stacked (R upper, V lower-unit, tau)."""
    q, r = jnp.linalg.qr(A, mode="reduced")
    return q, r


@jax.jit
def _trsyl(A, B, C):
    """Unblocked triangular Sylvester solve A X + X B = C.

    A (m x m) and B (n x n) upper triangular.  Column-by-column
    back-substitution: (A + b_jj I) x_j = c_j - X[:, :j] B[:j, j].
    """
    m, n = C.shape
    eye = jnp.eye(m, dtype=C.dtype)

    def col(carry, j):
        X = carry
        rhs = C[:, j] - X @ (B[:, j] * (jnp.arange(n) < j))
        xj = jnp.linalg.solve(A + B[j, j] * eye, rhs)
        X = X.at[:, j].set(xj)
        return X, None

    X0 = jnp.zeros_like(C)
    X, _ = lax.scan(col, X0, jnp.arange(n))
    return X


# ------------------------------------------------------------- kernel defs --

@dataclass(frozen=True)
class KernelDef:
    name: str
    cases: Tuple[Case, ...]
    #: minimal FLOP count as a function of (case, sizes)
    flops: Callable[[Case, Sizes], float]
    #: maximal monomial exponents for the model basis
    cost_exponents: Callable[[Case], Sequence[Tuple[int, ...]]]
    #: build operands for a concrete invocation
    make_operands: Callable[[Case, Sizes, object], Tuple]
    #: execute one invocation (returns device array(s))
    run: Callable[[Case, Tuple], object]

    def make_call(self, case: Case, sizes: Sizes,
                  seed: int = 0) -> Callable[[], None]:
        """Zero-arg synchronous callable for the model generator.

        The timed call includes the host->device operand conversion and the
        device->host result fetch, because that is exactly what the blocked
        algorithms' ExecEngine does per kernel invocation — the paper's
        principle of modeling the call as the algorithm makes it (§3.2.3).
        """
        ops_np = tuple(np.asarray(o)
                       for o in self.make_operands(case, sizes, _rng(seed)))

        def call():
            out = self.run(case, tuple(jnp.asarray(o) for o in ops_np))
            jax.tree_util.tree_map(np.asarray, out)

        return call


def _gemm_flops(case, sizes):
    m, n, k = sizes
    return 2.0 * m * n * k


def _trsm_flops(case, sizes):
    side = case[0]
    m, n = sizes
    return float(m * m * n) if side == "L" else float(m * n * n)


KERNELS: Dict[str, KernelDef] = {}


def _register(kd: KernelDef):
    KERNELS[kd.name] = kd
    return kd


GEMM = _register(KernelDef(
    name="gemm",
    cases=(("N", "N", 1, 1), ("N", "T", -1, 1), ("T", "N", -1, 1),
           ("N", "N", -1, 1), ("T", "N", 1, 1), ("N", "T", 1, 1),
           ("N", "N", 1, 0), ("N", "N", -1, 0), ("T", "N", 1, 0)),
    flops=_gemm_flops,
    cost_exponents=lambda case: [(1, 1, 1)],
    make_operands=lambda case, s, rng: (
        _rand(rng, *((s[0], s[2]) if case[0] == "N" else (s[2], s[0]))),
        _rand(rng, *((s[2], s[1]) if case[1] == "N" else (s[1], s[2]))),
        _rand(rng, s[0], s[1]),
    ),
    run=lambda case, ops: _gemm_fn(case[0], case[1], float(case[2]),
                                   float(case[3]))(*ops),
))

SYRK = _register(KernelDef(
    name="syrk",
    cases=(("L", "N", -1, 1), ("L", "T", -1, 1), ("L", "T", 1, 1)),
    flops=lambda case, s: float(s[0] * s[0] * s[1]),  # n^2 k
    cost_exponents=lambda case: [(2, 1)],
    make_operands=lambda case, s, rng: (
        _rand(rng, *((s[0], s[1]) if case[1] == "N" else (s[1], s[0]))),
        _rand(rng, s[0], s[0]),
    ),
    run=lambda case, ops: _syrk_fn(case[0], case[1], float(case[2]),
                                   float(case[3]))(*ops),
))

SYR2K = _register(KernelDef(
    name="syr2k",
    cases=(("L", "N", -1, 1),),
    flops=lambda case, s: float(2 * s[0] * s[0] * s[1]),
    cost_exponents=lambda case: [(2, 1)],
    make_operands=lambda case, s, rng: (
        _rand(rng, s[0], s[1]), _rand(rng, s[0], s[1]),
        _rand(rng, s[0], s[0]),
    ),
    run=lambda case, ops: _syr2k_fn(case[0], case[1], float(case[2]),
                                    float(case[3]))(*ops),
))

SYMM = _register(KernelDef(
    name="symm",
    cases=(("R", "L", -0.5, 1), ("L", "L", 1, 0)),
    flops=lambda case, s: float(2 * s[0] * s[1] *
                                (s[1] if case[0] == "R" else s[0])),
    cost_exponents=lambda case: [(1, 2)] if case[0] == "R" else [(2, 1)],
    make_operands=lambda case, s, rng: (
        _rand(rng, *((s[1], s[1]) if case[0] == "R" else (s[0], s[0]))),
        _rand(rng, s[0], s[1]),
        _rand(rng, s[0], s[1]),
    ),
    run=lambda case, ops: _symm_fn(case[0], case[1], float(case[2]),
                                   float(case[3]))(*ops),
))

TRSM = _register(KernelDef(
    name="trsm",
    cases=(("L", "L", "N", "N", 1), ("L", "L", "N", "N", -1),
           ("R", "L", "T", "N", 1), ("R", "L", "N", "N", -1),
           ("L", "L", "N", "U", 1), ("L", "U", "N", "N", 1)),
    flops=_trsm_flops,
    cost_exponents=lambda case: [(2, 1)] if case[0] == "L" else [(1, 2)],
    make_operands=lambda case, s, rng: (
        _lower_nonsing(rng, s[0] if case[0] == "L" else s[1]).T
        if case[1] == "U" else
        _lower_nonsing(rng, s[0] if case[0] == "L" else s[1]),
        _rand(rng, s[0], s[1]),
    ),
    run=lambda case, ops: _trsm_fn(case[0], case[1], case[2], case[3],
                                   float(case[4]))(*ops),
))

TRMM = _register(KernelDef(
    name="trmm",
    cases=(("R", "L", "N", "N", 1), ("L", "L", "T", "N", 1),
           ("L", "L", "N", "N", 1), ("L", "L", "N", "U", 1),
           ("R", "L", "N", "N", -1), ("L", "L", "N", "N", -1),
           ("L", "U", "T", "N", 1)),
    flops=lambda case, s: float(s[0] ** 2 * s[1]) if case[0] == "L"
    else float(s[0] * s[1] ** 2),
    cost_exponents=lambda case: [(2, 1)] if case[0] == "L" else [(1, 2)],
    make_operands=lambda case, s, rng: (
        _lower_nonsing(rng, s[0] if case[0] == "L" else s[1]),
        _rand(rng, s[0], s[1]),
    ),
    run=lambda case, ops: _trmm_fn(case[0], case[1], case[2], case[3],
                                   float(case[4]))(*ops),
))

POTF2 = _register(KernelDef(
    name="potf2",
    cases=(("L",),),
    flops=lambda case, s: s[0] ** 3 / 3.0,
    cost_exponents=lambda case: [(3,)],
    make_operands=lambda case, s, rng: (_spd(rng, s[0]),),
    run=lambda case, ops: _potf2(*ops),
))

TRTI2 = _register(KernelDef(
    name="trti2",
    cases=(("L", "N"),),
    flops=lambda case, s: s[0] ** 3 / 3.0,
    cost_exponents=lambda case: [(3,)],
    make_operands=lambda case, s, rng: (_lower_nonsing(rng, s[0]),),
    run=lambda case, ops: _trti2(*ops),
))

LAUU2 = _register(KernelDef(
    name="lauu2",
    cases=(("L",),),
    flops=lambda case, s: s[0] ** 3 / 3.0,
    cost_exponents=lambda case: [(3,)],
    make_operands=lambda case, s, rng: (_lower_nonsing(rng, s[0]),),
    run=lambda case, ops: _lauu2(*ops),
))

SYGS2 = _register(KernelDef(
    name="sygs2",
    cases=((1, "L"),),
    flops=lambda case, s: 2.0 * s[0] ** 3,
    cost_exponents=lambda case: [(3,)],
    make_operands=lambda case, s, rng: (_spd(rng, s[0]),
                                        _lower_nonsing(rng, s[0])),
    run=lambda case, ops: _sygs2(*ops),
))

GETF2 = _register(KernelDef(
    name="getf2",
    cases=(("NP",),),  # non-pivoted panel (see DESIGN.md §8.5)
    flops=lambda case, s: float(s[0] * s[1] ** 2 - s[1] ** 3 / 3.0),
    cost_exponents=lambda case: [(1, 2), (0, 3)],
    make_operands=lambda case, s, rng: (
        jnp.asarray(rng.standard_normal((s[0], s[1])) +
                    np.eye(s[0], s[1]) * s[0], dtype=_DTYPE),),
    run=lambda case, ops: _getf2_nopiv(ops[0]),
))

GEQR2 = _register(KernelDef(
    name="geqr2",
    cases=(("N",),),
    flops=lambda case, s: float(2 * s[0] * s[1] ** 2),
    cost_exponents=lambda case: [(1, 2)],
    make_operands=lambda case, s, rng: (_rand(rng, s[0], s[1]),),
    run=lambda case, ops: _geqr2(*ops),
))

TRSYL = _register(KernelDef(
    name="trsyl",
    cases=(("N", "N", 1),),
    flops=lambda case, s: float(s[0] ** 2 * s[1] + s[0] * s[1] ** 2),
    cost_exponents=lambda case: [(2, 1), (1, 2)],
    make_operands=lambda case, s, rng: (
        jnp.asarray(np.triu(rng.standard_normal((s[0], s[0]))) +
                    np.eye(s[0]) * s[0], dtype=_DTYPE),
        jnp.asarray(np.triu(rng.standard_normal((s[1], s[1]))) +
                    np.eye(s[1]) * s[1], dtype=_DTYPE),
        _rand(rng, s[0], s[1]),
    ),
    run=lambda case, ops: _trsyl(*ops),
))


def kernel_flops(name: str, case: Case, sizes: Sizes) -> float:
    return KERNELS[name].flops(tuple(case), tuple(sizes))
