"""Benchmark: batched prediction engine vs scalar per-call prediction.

The paper's promise is *instantaneous* model-based selection (§4.5/§4.6).
This suite times a block-size sweep and a multi-variant ranking on the scalar
per-call reference path vs the vectorized :class:`PredictionEngine`, checks
that both select the same configuration with statistics agreeing to ~1e-10,
and reports the sweep speedup.  It also times a fixed 64-candidate sweep
re-traced every call and trace-cached — the ``sweep64_*`` metrics CI
tracks across commits.  The models are analytic
(measurement-free, ``common.synthetic_model_set``), so the suite runs
identically on any machine — it is also the CI smoke lane's
perf-trajectory probe.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from repro.core import (PredictionEngine, optimize_block_size,
                        predict_runtime, rank_algorithms)
from repro.core.sampler import STATS
from repro.dla.tracers import CHOLESKY_TRACERS, TRTRI_TRACERS, potrf_tracer

from .common import best_of as _best_of
from .common import is_smoke, synthetic_model_set


def run(report: List[str],
        results: Optional[Dict[str, object]] = None) -> None:
    smoke = is_smoke()
    n = 256 if smoke else 768
    n_cand = 16 if smoke else 64
    reps = 1 if smoke else 3
    candidates = [8 * (i + 1) for i in range(n_cand)]
    ms = synthetic_model_set()
    tracer = potrf_tracer(3)

    def scalar_sweep():
        profile = {b: predict_runtime(tracer(n, b), ms).med
                   for b in candidates}
        return min(profile, key=profile.get), profile

    # ---- block-size sweep: scalar loop vs batched engine ----
    b_scalar, prof_scalar = scalar_sweep()
    b_batched, prof_batched = optimize_block_size(tracer, ms, n, candidates)
    max_rel = max(abs(prof_batched[b] - prof_scalar[b]) /
                  max(prof_scalar[b], 1e-300) for b in candidates)
    t_scalar = _best_of(scalar_sweep, reps)
    t_batched = _best_of(lambda: optimize_block_size(
        tracer, ms, n, candidates), reps)
    speedup = t_scalar / t_batched
    report.append(
        f"blocksize sweep n={n} |grid|={n_cand}: "
        f"scalar={t_scalar * 1e3:8.1f}ms batched={t_batched * 1e3:6.1f}ms "
        f"speedup={speedup:6.1f}x argmin {'==' if b_scalar == b_batched else '!='} "
        f"(b={b_batched}) max_rel_diff={max_rel:.1e}")

    # ---- multi-variant ranking (11 algorithms in one compiled batch) ----
    tracers = {**CHOLESKY_TRACERS, **TRTRI_TRACERS}
    b_rank = candidates[len(candidates) // 2]

    def scalar_rank():
        runtimes = {name: predict_runtime(t(n, b_rank), ms)
                    for name, t in tracers.items()}
        return sorted(runtimes.items(), key=lambda kv: kv[1].med)

    ranked_scalar = scalar_rank()
    t_rank_scalar = _best_of(scalar_rank, reps)
    ranked_batched = rank_algorithms(tracers, ms, n, b_rank)
    t_rank_batched = _best_of(lambda: rank_algorithms(
        tracers, ms, n, b_rank), reps)
    # variants with numerically-tied predictions may swap under the two
    # paths' different summation orders; only a >1e-9 inversion is a mismatch
    order_agree = all(
        name == b.name
        or abs(rt.med - b.runtime.med) <= 1e-9 * max(abs(rt.med), 1e-300)
        for (name, rt), b in zip(ranked_scalar, ranked_batched))
    report.append(
        f"ranking {len(tracers)} variants n={n} b={b_rank}: "
        f"scalar={t_rank_scalar * 1e3:8.1f}ms "
        f"batched={t_rank_batched * 1e3:6.1f}ms "
        f"speedup={t_rank_scalar / t_rank_batched:6.1f}x "
        f"order {'==' if order_agree else '!='} winner={ranked_batched[0].name}")

    # ---- the fixed 64-candidate sweep (the CI metric) ----
    # re-tracing the candidates per call, as a fresh engine does
    cand64 = [8 * (i + 1) for i in range(64)]
    t_np64 = _best_of(lambda: PredictionEngine(ms).sweep(
        tracer, n, cand64), max(reps, 15))
    # trace-cached: the candidate set is compiled once, so a sweep is one
    # evaluation of the cached batch
    eng_np = PredictionEngine(ms)
    eng_np.sweep(tracer, n, cand64)
    t_npc64 = _best_of(lambda: eng_np.sweep(tracer, n, cand64),
                       max(reps, 15))
    report.append(
        f"64-candidate sweep n={n}: numpy={t_np64 * 1e3:6.2f}ms "
        f"numpy+cache={t_npc64 * 1e3:6.2f}ms "
        f"cache_speedup={t_np64 / t_npc64:4.1f}x")

    # ---- full (n, b) grid in one shot ----
    engine = PredictionEngine(ms)
    ns = [128, 192, 256] if smoke else [256, 512, 768, 1024]
    t0 = time.perf_counter()
    grid = engine.grid(tracer, ns, candidates)
    t_grid = time.perf_counter() - t0
    med = grid[..., STATS.index("med")]
    report.append(
        f"(n, b) grid {len(ns)}x{n_cand} = {len(ns) * n_cand} configs: "
        f"{t_grid * 1e3:6.1f}ms "
        f"({t_grid / (len(ns) * n_cand) * 1e6:6.1f}us/config), "
        f"argmin_b per n: "
        + " ".join(f"n={nn}:b={candidates[int(i)]}"
                   for nn, i in zip(ns, med.argmin(axis=1))))

    if results is not None:
        results.update({
            "n": n, "grid_size": n_cand,
            "sweep_scalar_s": t_scalar, "sweep_batched_s": t_batched,
            "sweep_speedup": speedup,
            "argmin_agree": bool(b_scalar == b_batched),
            "max_rel_diff": float(max_rel),
            "rank_scalar_s": t_rank_scalar,
            "rank_batched_s": t_rank_batched,
            "rank_order_agree": bool(order_agree),
            "sweep64_numpy_s": t_np64,
            "sweep64_numpy_cached_s": t_npc64,
            "grid_configs": len(ns) * n_cand, "grid_s": t_grid,
        })


def main() -> None:
    report: List[str] = []
    run(report)
    print("\n".join(report))


if __name__ == "__main__":
    main()
