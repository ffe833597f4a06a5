"""Benchmark: einsum-path (contraction-chain) prediction (beyond-paper).

Full mode: for small demonstration chains, rank every candidate pairwise
contraction path through :class:`repro.tc.ChainPredictor`, execute the
predicted-best and predicted-worst paths with their selected per-step
algorithms, and report winner agreement, the measured spread between
paths, micro-benchmark deduplication across steps, and the prediction
cost as a fraction of the chosen chain's execution.

Smoke mode (the CI lane): a 4-operand chain whose steps contract two
indices each — no kernel can absorb a second contracted index, so even
the best per-step algorithm is a genuine loop nest and one chain
execution dwarfs the (deduplicated, canonically-shared) micro-benchmark
suite.  The candidate set is restricted to the gemm/gemv/gevm kernel
classes without batched variants: a batched one-call candidate's
micro-benchmark IS a step execution (cost fraction -> the repetition
count, never "a fraction"), and each extra kernel class costs one XLA
compile per distinct signature — the full-mode run keeps the complete
set.  The ``tc_chain_*`` metrics CI tracks across commits: suite cost,
path-rank time, oracle agreement on the top-ranked path, and the suite
cost as a fraction of ONE execution of the chosen chain (< 0.25
required).  A ``tc_sweep_chain_*`` section re-ranks the same chain across
three values of ``a`` from the SAME suite — size-sweep autotuning at the
einsum-path level, with the total suite cost still a fraction of one
chosen-chain execution.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from repro.tc import (ChainPredictor, ChainSpec, PredictorSession,
                      execute_chain, execute_chain_reference)

from .common import best_of as _best_of
from .common import is_smoke

#: full-mode demonstration chains
CASES = [
    ("ij,jk,kl->il", dict(i=48, j=48, k=48, l=48), None),
    ("aij,ijb,bk->ak", dict(a=24, b=24, i=32, j=32, k=24), 64 * 2 ** 20),
]

#: smoke chain: steps 0.1 and 2.3 contract TWO indices (i,j / k,l), so
#: their fastest algorithms still loop over a full index extent
SMOKE_CHAIN = "aij,ijb,bkl,klc->ac"
SMOKE_SIZES = dict(a=4, b=4, c=4, i=2048, j=2048, k=2048, l=2048)
#: prune outer-product detours (aij x bkl etc.) whose intermediates the
#: suite could never afford to benchmark
SMOKE_LIMIT = 96 * 2 ** 20
SMOKE_REPETITIONS = 2
SMOKE_LOOP_PERMS = 2
SMOKE_KERNELS = ("gemm", "gemv", "gevm")
#: chain-level size-sweep grid: vary ``a`` (a loop/batch-like output
#: dimension) so most step signatures are shared with the a=4 run above
SWEEP_A = (4, 8, 16)


def _operands(chain: ChainSpec, sizes, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal([sizes[i] for i in idx]).astype(np.float32)
            for idx in chain.operands]


def _run_full(report: List[str]) -> None:
    for expr, sizes, limit in CASES:
        chain = ChainSpec.parse(expr)
        t0 = time.perf_counter()
        pred = ChainPredictor(chain, sizes, repetitions=3,
                              memory_limit_bytes=limit)
        ranked = pred.rank_paths()
        t_pred = time.perf_counter() - t0
        best, worst = ranked[0], ranked[-1]
        ops = _operands(chain, sizes)
        t0 = time.perf_counter()
        out_best = execute_chain(chain, best, ops, sizes)
        t_best = time.perf_counter() - t0
        t0 = time.perf_counter()
        execute_chain(chain, worst, ops, sizes)
        t_worst = time.perf_counter() - t0
        # norm-relative: float32 chains differ from the one-shot einsum by
        # association order, element-wise near cancellations
        ref = execute_chain_reference(chain, ops)
        ok = np.linalg.norm(out_best - ref) / np.linalg.norm(ref) < 1e-3
        report.append(
            f"{expr:18s} paths={len(pred.paths):2d} "
            f"benchmarks={pred.n_benchmarks:3d} best={best.name:14s} "
            f"pred={t_pred:5.1f}s exec best/worst="
            f"{t_best:6.2f}/{t_worst:6.2f}s "
            f"({t_worst / max(t_best, 1e-9):4.1f}x) "
            f"correct={'Y' if ok else 'N'} "
            f"cost/exec={pred.prediction_cost_fraction(t_best):5.2f}")


def _run_smoke(report: List[str], results: Dict[str, object]) -> None:
    chain = ChainSpec.parse(SMOKE_CHAIN)
    sess = PredictorSession(repetitions=SMOKE_REPETITIONS)
    pred = sess.chain_predictor(chain, SMOKE_SIZES,
                                include_batched=False,
                                kernels=SMOKE_KERNELS,
                                max_loop_perms=SMOKE_LOOP_PERMS,
                                memory_limit_bytes=SMOKE_LIMIT)
    ranked_np = pred.rank_paths()                   # suite runs here once
    t_suite = pred.suite.cost_seconds
    t_np = _best_of(pred.rank_paths, 3)

    # the step-by-step per-algorithm scalar oracle on the SAME measurements
    # (fresh=True would re-measure: only top-1 agreement would be noise-
    # robust, and the smoke lane must stay deterministic)
    oracle = pred.rank_paths_oracle(fresh=False)
    oracle_top_agree = oracle[0].name == ranked_np[0].name

    # ONE execution of the chosen chain as the cost-fraction denominator:
    # the acceptance bar is suite cost < 0.25 of the runtime it predicts
    best = ranked_np[0]
    ops = _operands(chain, SMOKE_SIZES)
    t0 = time.perf_counter()
    execute_chain(chain, best, ops, SMOKE_SIZES)
    t_exec = time.perf_counter() - t0
    fraction = pred.prediction_cost_fraction(t_exec)

    n_steps = sum(len(p.steps) for p in pred.paths)
    report.append(
        f"tc_chain {SMOKE_CHAIN} sizes={SMOKE_SIZES}: "
        f"paths={len(pred.paths)} steps={n_steps} "
        f"benchmarks={pred.n_benchmarks} suite={t_suite:5.2f}s")
    report.append(
        f"  rank: numpy={t_np * 1e3:6.2f}ms "
        f"oracle-top {'==' if oracle_top_agree else '!='} "
        f"winner={best.name} "
        f"steps={'|'.join(s.name[:24] for s in best.steps)}")
    report.append(
        f"  exec chosen chain: {t_exec:5.2f}s -> suite cost fraction "
        f"{fraction:5.3f} ({'<' if fraction < 0.25 else '>='} 0.25 target)")
    results.update({
        "tc_chain_paths": len(pred.paths),
        "tc_chain_steps": n_steps,
        "tc_chain_benchmarks": pred.n_benchmarks,
        "tc_chain_suite_s": t_suite,
        "tc_chain_rank_numpy_s": t_np,
        "tc_chain_oracle_agree": bool(oracle_top_agree),
        "tc_chain_exec_s": t_exec,
        "tc_chain_cost_frac": fraction,
    })

    # ---- chain-level size sweep: 3 values of a, SAME suite ----
    # the a=4 ranking above already measured most step signatures; new
    # points only measure the signatures whose shapes contain a
    before = pred.suite.counters()
    grid = [dict(SMOKE_SIZES, a=a) for a in SWEEP_A]
    sweep = sess.rank_einsum_sweep(chain, grid, include_batched=False,
                                   kernels=SMOKE_KERNELS,
                                   max_loop_perms=SMOKE_LOOP_PERMS,
                                   memory_limit_bytes=SMOKE_LIMIT)
    added = pred.suite.counters()
    new_benchmarks = int(added["n_benchmarks"] - before["n_benchmarks"])
    sweep_fraction = sweep.cost_fraction(t_exec)
    report.append(
        f"tc_sweep_chain a={list(SWEEP_A)}: points={len(grid)} "
        f"new_benchmarks={new_benchmarks} (total {sweep.n_benchmarks}) "
        f"winners={'|'.join(w.name for w in sweep.winners)} -> "
        f"total suite cost fraction {sweep_fraction:5.3f} "
        f"({'<' if sweep_fraction < 0.25 else '>='} 0.25 target)")
    results.update({
        "tc_sweep_chain_points": len(grid),
        "tc_sweep_chain_new_benchmarks": new_benchmarks,
        "tc_sweep_chain_suite_s": sweep.suite.cost_seconds,
        "tc_sweep_chain_cost_frac": sweep_fraction,
    })


def run(report: List[str],
        results: Optional[Dict[str, object]] = None) -> None:
    if is_smoke():
        _run_smoke(report, results if results is not None else {})
    else:
        _run_full(report)


def main() -> None:
    report: List[str] = []
    run(report)
    print("\n".join(report))


if __name__ == "__main__":
    main()
