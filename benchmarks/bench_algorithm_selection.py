"""Benchmark: algorithm selection (paper §4.5, Figs 4.12/4.14/4.17).

Rank the 3 Cholesky variants, 8 triangular-inversion variants, and 8
Sylvester combinations by model prediction; verify against exhaustive
timing; report winner agreement and the prediction-vs-measurement speedup
(the paper reports 100x-1500x).
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.dla import ExecEngine, blocked
from repro.dla.tracers import (CHOLESKY_TRACERS, SYLVESTER_TRACERS,
                               TRTRI_TRACERS)
from repro.core import rank_algorithms

from .common import build_model_set, lower_nonsing, median_time, spd

N, B = 224, 48


def _measure_all(catalog: str) -> Dict[str, float]:
    out = {}
    if catalog == "cholesky":
        A0 = spd(N)
        for v in (1, 2, 3):
            def run(v=v):
                eng = ExecEngine()
                blocked.potrf(eng, eng.bind("A", A0), N, B, variant=v)
                return eng.mats["A"]
            out[f"potrf{v}"] = median_time(run, 5)
    elif catalog == "trtri":
        L0 = lower_nonsing(N)
        for v in range(1, 9):
            def run(v=v):
                eng = ExecEngine()
                blocked.trtri(eng, eng.bind("A", L0), N, B, variant=v)
                return eng.mats["A"]
            out[f"trtri{v}"] = median_time(run, 5)
    else:  # sylvester
        rng = np.random.default_rng(0)
        Au = np.triu(rng.standard_normal((N, N))) + N * np.eye(N)
        Bu = np.triu(rng.standard_normal((N, N))) + N * np.eye(N)
        C0 = rng.standard_normal((N, N))
        for alg in blocked.SYLVESTER_ALGORITHMS:
            def run(alg=alg):
                eng = ExecEngine()
                blocked.sylvester(eng, eng.bind("A", Au), eng.bind("B", Bu),
                                  eng.bind("C", C0), N, N, B, algorithm=alg)
                return eng.mats["C"]
            out[alg] = median_time(run, 3)
    return out


def run(report: List[str]) -> None:
    ms, _ = build_model_set()
    for catalog, tracers in (("cholesky", CHOLESKY_TRACERS),
                             ("trtri", TRTRI_TRACERS),
                             ("sylvester", SYLVESTER_TRACERS)):
        t0 = time.perf_counter()
        ranked = rank_algorithms(tracers, ms, N, B)
        t_pred = time.perf_counter() - t0
        t0 = time.perf_counter()
        ranked_scalar = rank_algorithms(tracers, ms, N, B, batched=False)
        t_pred_scalar = time.perf_counter() - t0
        # numerically tied variants may swap winners between the two paths'
        # summation orders — only a >1e-9 relative disagreement is a bug
        assert (ranked_scalar[0].name == ranked[0].name
                or abs(ranked_scalar[0].runtime.med - ranked[0].runtime.med)
                <= 1e-9 * max(ranked_scalar[0].runtime.med, 1e-300))
        t0 = time.perf_counter()
        measured = _measure_all(catalog)
        t_meas = time.perf_counter() - t0
        pred_winner = ranked[0].name
        meas_sorted = sorted(measured, key=measured.get)
        meas_winner = meas_sorted[0]
        # "correct" = predicted winner within 5% of the measured optimum
        within = measured[pred_winner] <= 1.05 * measured[meas_winner]
        worst = meas_sorted[-1]
        spread = measured[worst] / measured[meas_winner]
        report.append(
            f"{catalog:10s} algs={len(tracers)} "
            f"pred_winner={pred_winner:8s} meas_winner={meas_winner:8s} "
            f"agree={'Y' if within else 'N'} spread={spread:5.2f}x "
            f"pred_time={t_pred * 1e3:7.1f}ms "
            f"(scalar {t_pred_scalar * 1e3:7.1f}ms, "
            f"{t_pred_scalar / t_pred:4.0f}x) meas_time={t_meas:5.1f}s "
            f"speedup={t_meas / t_pred:7.0f}x")


def main() -> None:
    report: List[str] = []
    run(report)
    print("\n".join(report))


if __name__ == "__main__":
    main()
