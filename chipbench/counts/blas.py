"""Least operations and bytes of each BLAS/LAPACK call, from its shapes.

A call is ``(kernel, case, sizes)`` as the blocked algorithms issue it.
The counts are what the call cannot do without: the triangle of a
symmetric or triangular operand, not the square the program may touch.
So the least time they give is a lower bound on any kernel's time, and a
share of it cannot pass 100%.  Operands are float32 (4 bytes).
"""

from __future__ import annotations

from typing import Sequence, Tuple

WORD = 4


def _tri(n: int) -> int:
    return n * (n + 1) // 2


def call_counts(kernel: str, case: Tuple, sizes: Sequence[int]
                ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call; a zero-size call does nothing."""
    if min(sizes) == 0:
        return 0.0, 0.0
    if kernel == "gemm":                    # C(m,n) += A(m,k) B(k,n)
        m, n, k = sizes
        beta = case[3]
        words = m * k + k * n + m * n * (2 if beta != 0 else 1)
        return 2.0 * m * n * k, float(WORD * words)
    if kernel == "syrk":                    # C(n,n) += A(n,k) A^T, triangle
        n, k = sizes
        beta = case[3]
        words = n * k + _tri(n) * (2 if beta != 0 else 1)
        return float(_tri(n) * 2 * k), float(WORD * words)
    if kernel == "trsm":                    # B(m,n) := B op(A)^-1, triangular
        m, n = sizes
        side = case[0]
        t = n if side == "R" else m
        return float(m * n * t), float(WORD * (_tri(t) + 2 * m * n))
    if kernel == "potf2":                   # L L^T := A(n,n), a triangle
        (n,) = sizes
        return n ** 3 / 3.0, float(WORD * 2 * _tri(n))
    raise KeyError(f"no counts for kernel {kernel!r}")


def least_seconds(kernel: str, case: Tuple, sizes: Sequence[int],
                  peak_flops: float, peak_bw: float) -> float:
    """The larger of FLOPs over the peak and bytes over the bandwidth."""
    flops, nbytes = call_counts(kernel, case, sizes)
    return max(flops / peak_flops, nbytes / peak_bw)
