"""Benchmark: blocked-algorithm prediction accuracy (paper Table 4.3).

For each blocked LAPACK algorithm, compare model-based runtime predictions
against measured executions over a range of problem sizes; report the
median-runtime absolute relative error (the paper's t_ARE^med).
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.core import predict_runtime
from repro.dla import ExecEngine, blocked
from repro.dla.tracers import (getrf_tracer, lauum_tracer, potrf_tracer,
                               trtri_tracer)

from .common import build_model_set, lower_nonsing, median_time, spd

SIZES = (96, 160, 224, 288)
BLOCK = 48


def _exec_fns(n: int):
    A_spd, A_low = spd(n), lower_nonsing(n)
    rng = np.random.default_rng(1)
    A_gen = rng.standard_normal((n, n)) + n * np.eye(n)

    def run_potrf():
        eng = ExecEngine()
        blocked.potrf(eng, eng.bind("A", A_spd), n, BLOCK, variant=3)
        return eng.mats["A"]

    def run_trtri():
        eng = ExecEngine()
        blocked.trtri(eng, eng.bind("A", A_low), n, BLOCK, variant=3)
        return eng.mats["A"]

    def run_lauum():
        eng = ExecEngine()
        blocked.lauum(eng, eng.bind("A", A_low), n, BLOCK)
        return eng.mats["A"]

    def run_getrf():
        eng = ExecEngine()
        blocked.getrf(eng, eng.bind("A", A_gen), n, BLOCK)
        return eng.mats["A"]

    return {"potrf3": run_potrf, "trtri3": run_trtri, "lauum": run_lauum,
            "getrf": run_getrf}


TRACERS = {"potrf3": potrf_tracer(3), "trtri3": trtri_tracer(3),
           "lauum": lauum_tracer(), "getrf": getrf_tracer()}


def run(report: List[str]) -> None:
    ms, gen_s = build_model_set()
    header = f"{'algorithm':10s} " + " ".join(f"n={n:4d}" for n in SIZES) \
        + "   avg_ARE"
    report.append(header)
    for name, tracer in TRACERS.items():
        ares = []
        t_pred_total = 0.0
        for n in SIZES:
            t0 = time.perf_counter()
            pred = predict_runtime(tracer(n, BLOCK), ms).med
            t_pred_total += time.perf_counter() - t0
            meas = median_time(_exec_fns(n)[name], repetitions=5)
            ares.append(abs(pred - meas) / meas)
        avg = float(np.mean(ares))
        row = f"{name:10s} " + " ".join(f"{a:6.1%}" for a in ares) + \
            f"   {avg:6.1%}"
        report.append(row)
        report.append(
            f"  ({name}: prediction {t_pred_total * 1e3:.1f} ms total)")


def main() -> None:
    report: List[str] = []
    run(report)
    print("\n".join(report))


if __name__ == "__main__":
    main()
