"""Ch. 6 workflow on the tc subsystem: pick the fastest tensor-contraction
algorithm — batched-kernel candidates included — from deduplicated
cache-aware micro-benchmarks, at a fraction of one execution's cost.

    PYTHONPATH=src python examples/contraction_selection.py [--fast]
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np                                          # noqa: E402

from repro.core.contractions import (ContractionSpec,       # noqa: E402
                                     measure_contraction)
from repro.tc import ContractionPredictor, is_batched_kernel  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--n", type=int, default=64)
    args = ap.parse_args()
    n = 32 if args.fast else args.n

    # a batched contraction: C[bik] = sum_j A[bij] * B[bjk] — the batched
    # gemm kernel turns the whole contraction into ONE kernel call
    spec = ContractionSpec.parse("bij,bjk->bik")
    sizes = dict(b=8, i=n, j=n, k=n)

    t0 = time.perf_counter()
    pred = ContractionPredictor(spec, sizes, repetitions=3)
    ranked = pred.rank()
    t_pred = time.perf_counter() - t0
    n_batched = sum(is_batched_kernel(a.kernel) for a in pred.algorithms)
    print(f"== {spec.einsum_expr()} with sizes {sizes}: "
          f"{len(pred.algorithms)} candidates "
          f"({n_batched} batched-kernel) ==")
    print(f"   deduplicated micro-benchmark suite: "
          f"{pred.n_benchmarks} benchmarks for {len(pred.algorithms)} "
          f"algorithms, {pred.suite.cost_seconds:.2f}s; "
          f"ranking took {t_pred:.2f}s total")
    for r in ranked[:5]:
        tag = " (batched kernel)" if is_batched_kernel(r.algorithm.kernel) \
            else ""
        print(f"   {r.name:34s} predicted {r.runtime.med * 1e3:9.2f} ms"
              f"{tag}")
    print("   ...")
    print(f"   {ranked[-1].name:34s} predicted "
          f"{ranked[-1].runtime.med * 1e3:9.2f} ms")

    # a re-rank reuses the same suite measurements + compiled batch
    t0 = time.perf_counter()
    pred.rank()
    print(f"   re-rank: {(time.perf_counter() - t0) * 1e3:.1f} ms")

    print("== validate: execute best and median ==")
    rng = np.random.default_rng(0)
    A = rng.standard_normal([sizes[i] for i in spec.a_idx]).astype(np.float32)
    B = rng.standard_normal([sizes[i] for i in spec.b_idx]).astype(np.float32)
    best, median = ranked[0], ranked[len(ranked) // 2]
    t_best = measure_contraction(best.algorithm, A, B, sizes, 3).med
    t_median = measure_contraction(median.algorithm, A, B, sizes, 3).med
    print(f"   best:   {t_best * 1e3:9.2f} ms measured ({best.name})")
    print(f"   median: {t_median * 1e3:9.2f} ms measured "
          f"({t_median / t_best:.0f}x slower, {median.name})")
    frac = pred.prediction_cost_fraction(t_median)
    print(f"   suite cost = {frac:.2f}x one median-candidate execution "
          f"({'OK: fraction' if frac < 1 else 'NOT a fraction'})")
    assert t_best < t_median
    print("contraction_selection OK")


if __name__ == "__main__":
    main()
