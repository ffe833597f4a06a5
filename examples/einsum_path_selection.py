"""Einsum-path selection on the tc chain layer: pick the fastest pairwise
contraction path of a multi-operand einsum — per-step algorithms included —
from one shared deduplicated micro-benchmark suite.

    PYTHONPATH=src python examples/einsum_path_selection.py [--fast]
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np                                          # noqa: E402

from repro.tc import (ChainPredictor, ChainSpec,            # noqa: E402
                      execute_chain, execute_chain_reference,
                      validate_paths)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--n", type=int, default=48)
    args = ap.parse_args()
    n = 24 if args.fast else args.n

    # a 4-operand chain: the two early contractions (over i,j and k,l)
    # force real loop nests, and the middle index b ties the halves
    chain = ChainSpec.parse("aij,ijb,bkl,klc->ac")
    sizes = dict(a=8, b=8, c=8, i=n, j=n, k=n, l=n)

    # every enumerated path computes the same einsum — bit-equal on
    # integer-valued operands (any association order sums exact integers)
    validate_paths(chain, sizes)
    print(f"== {chain.einsum_expr()} with sizes {sizes}: all "
          f"{len(chain.paths())} paths validated bit-equal ==")

    t0 = time.perf_counter()
    pred = ChainPredictor(chain, sizes, repetitions=3,
                          memory_limit_bytes=64 * 2 ** 20)
    ranked = pred.rank_paths()
    t_pred = time.perf_counter() - t0
    print(f"   {len(pred.paths)} memory-feasible paths, "
          f"{pred.n_benchmarks} shared micro-benchmarks "
          f"({pred.suite.requests} requested), ranking took {t_pred:.2f}s")
    for r in ranked:
        steps = " ; ".join(s.name for s in r.steps)
        print(f"   {r.name:16s} predicted {r.runtime.med * 1e3:9.2f} ms"
              f"  [{steps}]")

    # a re-rank reuses the same suite measurements + compiled batches
    t0 = time.perf_counter()
    pred.rank_paths()
    print(f"   re-rank: {(time.perf_counter() - t0) * 1e3:.1f} ms")

    # the step-by-step per-algorithm oracle on the same measurements
    oracle = pred.rank_paths_oracle(fresh=False)
    print(f"   per-algorithm oracle top path: {oracle[0].name} "
          f"({'agrees' if oracle[0].name == ranked[0].name else 'DISAGREES'})")

    print("== validate: execute predicted-best and predicted-worst ==")
    rng = np.random.default_rng(0)
    ops = [rng.standard_normal([sizes[i] for i in idx]).astype(np.float32)
           for idx in chain.operands]
    best, worst = ranked[0], ranked[-1]
    t0 = time.perf_counter()
    out = execute_chain(chain, best, ops, sizes)
    t_best = time.perf_counter() - t0
    t0 = time.perf_counter()
    execute_chain(chain, worst, ops, sizes)
    t_worst = time.perf_counter() - t0
    # norm-relative error: float32 chains legitimately differ from the
    # one-shot einsum by association order, element-wise near cancellations
    ref = execute_chain_reference(chain, ops)
    err = np.linalg.norm(out - ref) / np.linalg.norm(ref)
    print(f"   best:  {t_best * 1e3:9.2f} ms measured ({best.name}), "
          f"rel err {err:.1e}")
    print(f"   worst: {t_worst * 1e3:9.2f} ms measured "
          f"({t_worst / t_best:.0f}x slower, {worst.name})")
    frac = pred.prediction_cost_fraction(t_worst)
    print(f"   suite cost = {frac:.2f}x one worst-path execution "
          f"(amortizes across chains; a fraction only at realistic sizes "
          f"— see the smoke benchmark)")
    assert err < 1e-3 and t_best < t_worst
    print("einsum_path_selection OK")


if __name__ == "__main__":
    main()
