"""Integration tests: blocked algorithms vs numpy/LAPACK oracles (Ch. 1/4)."""

import numpy as np
import pytest

from repro.dla import ExecEngine, TraceEngine, blocked
from repro.dla.engine import Matrix
from repro.dla.tracers import (CHOLESKY_TRACERS, LAPACK_TRACERS,
                               SYLVESTER_TRACERS, TRTRI_TRACERS,
                               required_kernel_cases)
from repro.dla.kernels import KERNELS

RNG = np.random.default_rng(42)
N, B = 96, 32


def _spd(n):
    a = RNG.standard_normal((n, n))
    return a @ a.T + n * np.eye(n)


def _lower(n):
    a = np.tril(RNG.standard_normal((n, n)))
    np.fill_diagonal(a, np.abs(a.diagonal()) + n)
    return a


@pytest.mark.parametrize("variant", [1, 2, 3])
def test_potrf_variants(variant):
    A0 = _spd(N)
    ref = np.linalg.cholesky(A0)
    eng = ExecEngine()
    A = eng.bind("A", A0)
    blocked.potrf(eng, A, N, B, variant=variant)
    np.testing.assert_allclose(np.tril(eng.mats["A"]), ref,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("variant", list(range(1, 9)))
def test_trtri_variants(variant):
    L0 = _lower(N)
    ref = np.linalg.inv(L0)
    eng = ExecEngine()
    A = eng.bind("A", L0)
    blocked.trtri(eng, A, N, B, variant=variant)
    np.testing.assert_allclose(np.tril(eng.mats["A"]), ref,
                               rtol=2e-4, atol=2e-4)


def test_lauum():
    L0 = _lower(N)
    eng = ExecEngine()
    A = eng.bind("A", L0)
    blocked.lauum(eng, A, N, B)
    ref = np.tril(np.tril(L0).T @ np.tril(L0))
    np.testing.assert_allclose(np.tril(eng.mats["A"]), ref,
                               rtol=2e-4, atol=2e-4)


def test_sygst():
    A0, L0 = _spd(N), _lower(N)
    eng = ExecEngine()
    A, L = eng.bind("A", A0), eng.bind("L", L0)
    blocked.sygst(eng, A, L, N, B)
    Li = np.linalg.inv(np.tril(L0))
    ref = np.tril(Li @ A0 @ Li.T)
    np.testing.assert_allclose(np.tril(eng.mats["A"]), ref,
                               rtol=2e-3, atol=2e-3)


def test_getrf():
    M0 = RNG.standard_normal((N, N)) + N * np.eye(N)
    eng = ExecEngine()
    A = eng.bind("A", M0)
    blocked.getrf(eng, A, N, B)
    R = eng.mats["A"]
    L = np.tril(R, -1) + np.eye(N)
    U = np.triu(R)
    np.testing.assert_allclose(L @ U, M0, rtol=2e-4, atol=2e-4)


def test_geqrf():
    m = 128
    M0 = RNG.standard_normal((m, N))
    eng = ExecEngine()
    A = eng.bind("A", M0)
    fac = blocked.geqrf_exec(eng, A, m, N, B)
    Rfac = np.triu(eng.mats["A"][:N, :N])
    Q = np.eye(m)
    for k, V, T in fac:
        H = np.eye(m)
        H[k:, k:] = np.eye(m - k) - V @ T @ V.T
        Q = Q @ H
    R_full = np.zeros((m, N))
    R_full[:N] = Rfac
    np.testing.assert_allclose(Q @ R_full, M0, rtol=1e-4, atol=1e-4)
    # Q orthogonal
    np.testing.assert_allclose(Q.T @ Q, np.eye(m), atol=1e-8)


@pytest.mark.slow
@pytest.mark.parametrize("alg", blocked.SYLVESTER_ALGORITHMS)
def test_sylvester_algorithms(alg):
    m, n = 64, 96
    Au = np.triu(RNG.standard_normal((m, m))) + m * np.eye(m)
    Bu = np.triu(RNG.standard_normal((n, n))) + n * np.eye(n)
    C0 = RNG.standard_normal((m, n))
    X = np.linalg.solve(
        np.kron(np.eye(n), Au) + np.kron(Bu.T, np.eye(m)),
        C0.flatten(order="F")).reshape((m, n), order="F")
    eng = ExecEngine()
    Am, Bm, Cm = eng.bind("A", Au), eng.bind("B", Bu), eng.bind("C", C0)
    blocked.sylvester(eng, Am, Bm, Cm, m, n, 32, algorithm=alg)
    np.testing.assert_allclose(eng.mats["C"], X, rtol=2e-4, atol=2e-4)


def test_trace_matches_execution_structure():
    """The traced call sequence must be identical to the executed one."""
    class RecordingExec(ExecEngine):
        def __init__(self):
            super().__init__()
            self.seq = []

        def _call(self, name, case, out, *ops):
            self.seq.append((name, tuple(case)))
            return super()._call(name, case, out, *ops)

    eng = RecordingExec()
    A = eng.bind("A", _spd(N))
    blocked.potrf(eng, A, N, B, variant=3)
    tr = TraceEngine()
    blocked.potrf(tr, Matrix("A", N, N), N, B, variant=3)
    traced = [(c.kernel, tuple(c.case)) for c in tr.calls
              if all(s > 0 for s in c.sizes)]
    assert traced == eng.seq


def test_all_traced_cases_have_kernels():
    need = required_kernel_cases()
    for kernel, cases in need.items():
        have = set(map(tuple, KERNELS[kernel].cases))
        missing = {c for c in cases if tuple(c) not in have}
        assert not missing, f"{kernel}: unregistered cases {missing}"


def test_tracer_call_counts_scale():
    calls_small = CHOLESKY_TRACERS["potrf3"](256, 64)
    calls_large = CHOLESKY_TRACERS["potrf3"](512, 64)
    assert len(calls_large) == 2 * len(calls_small)
    assert len(TRTRI_TRACERS) == 8
    assert len(SYLVESTER_TRACERS) == 8
    assert set(LAPACK_TRACERS) == {"lauum", "sygst", "trtri", "potrf",
                                   "getrf", "geqrf"}
