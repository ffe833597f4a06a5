"""Benchmark: block-size optimization (paper §4.6, Figs 4.19/4.20).

Predict the optimal block size for blocked Cholesky (variant 3) and
triangular inversion (variant 3) at several problem sizes; compare with the
empirical optimum and report the paper's *performance yield*
t_meas(b_opt)/t_meas(b_pred) — the paper achieves >= 96-99%.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro.core import optimize_block_size, performance_yield
from repro.dla import ExecEngine, blocked
from repro.dla.tracers import potrf_tracer, trtri_tracer

from .common import build_model_set, lower_nonsing, median_time, spd

SIZES = (160, 256)
CANDIDATES = (16, 32, 48, 64, 96, 128)


def _measured_profile(kind: str, n: int) -> Dict[int, float]:
    out = {}
    A0 = spd(n) if kind == "potrf" else lower_nonsing(n)
    for b in CANDIDATES:
        def run(b=b):
            eng = ExecEngine()
            A = eng.bind("A", A0)
            if kind == "potrf":
                blocked.potrf(eng, A, n, b, variant=3)
            else:
                blocked.trtri(eng, A, n, b, variant=3)
            return eng.mats["A"]
        out[b] = median_time(run, 5)
    return out


def run(report: List[str]) -> None:
    import time

    ms, _ = build_model_set()
    for kind, tracer in (("potrf", potrf_tracer(3)),
                         ("trtri", trtri_tracer(3))):
        for n in SIZES:
            t0 = time.perf_counter()
            b_pred, profile = optimize_block_size(tracer, ms, n, CANDIDATES)
            t_batched = time.perf_counter() - t0
            t0 = time.perf_counter()
            b_scalar, prof_scalar = optimize_block_size(tracer, ms, n,
                                                        CANDIDATES,
                                                        batched=False)
            t_scalar = time.perf_counter() - t0
            # candidates tied at float level may swap argmins between the
            # paths; the two profiles at either argmin must still agree
            assert (b_scalar == b_pred
                    or abs(prof_scalar[b_scalar] - profile[b_pred])
                    <= 1e-9 * max(prof_scalar[b_scalar], 1e-300)), \
                (b_scalar, b_pred)
            measured = _measured_profile(kind, n)
            b_opt, yld = performance_yield(measured, b_pred)
            report.append(
                f"{kind} n={n:4d}: b_pred={b_pred:3d} b_opt={b_opt:3d} "
                f"yield={yld:6.1%} "
                f"(t_pred(b)={profile[b_pred] * 1e3:.2f}ms "
                f"t_meas(b_pred)={measured[b_pred] * 1e3:.2f}ms "
                f"sweep {t_scalar * 1e3:.1f}ms->{t_batched * 1e3:.1f}ms "
                f"{t_scalar / t_batched:.0f}x)")


def main() -> None:
    report: List[str] = []
    run(report)
    print("\n".join(report))


if __name__ == "__main__":
    main()
