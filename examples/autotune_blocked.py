"""The paper's core workflow: select the fastest blocked algorithm and a
near-optimal block size WITHOUT executing any candidate (§4.5/§4.6).

1. generate measurement-based performance models for the kernels (once per
   platform — cached under experiments/models/),
2. rank the 3 Cholesky variants and the 8 triangular-inversion variants by
   predicted runtime,
3. pick the block size by predicted argmin,
4. validate the selections against real timed executions.

    PYTHONPATH=src python examples/autotune_blocked.py [--fast]
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np                                          # noqa: E402

from benchmarks.common import (build_model_set, lower_nonsing,  # noqa: E402
                               median_time, spd)
from repro.core import optimize_block_size, rank_algorithms  # noqa: E402
from repro.dla import ExecEngine, blocked                   # noqa: E402
from repro.dla.tracers import CHOLESKY_TRACERS, TRTRI_TRACERS  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--n", type=int, default=224)
    args = ap.parse_args()
    n = 128 if args.fast else args.n
    b_candidates = (16, 32, 48, 64, 96)

    print("== generating / loading kernel performance models ==")
    ms, gen_s = build_model_set()
    print(f"   model set ready ({gen_s:.0f}s generation)")

    print(f"== Cholesky: rank 3 variants at n={n} (no execution) ==")
    t0 = time.perf_counter()
    ranked = rank_algorithms(CHOLESKY_TRACERS, ms, n, 48)
    t_rank = time.perf_counter() - t0
    for r in ranked:
        print(f"   {r.name}: predicted {r.runtime.med * 1e3:7.2f} ms")
    best = ranked[0].name
    print(f"   predicted winner: {best}  ({t_rank * 1e3:.0f} ms to rank)")

    print("== validate against execution ==")
    A0 = spd(n)
    meas = {}
    for v in (1, 2, 3):
        def run(v=v):
            eng = ExecEngine()
            blocked.potrf(eng, eng.bind("A", A0), n, 48, variant=v)
            return eng.mats["A"]
        meas[f"potrf{v}"] = median_time(run, 5)
        print(f"   potrf{v}: measured {meas[f'potrf{v}'] * 1e3:7.2f} ms")
    meas_best = min(meas, key=meas.get)
    print(f"   measured winner: {meas_best} "
          f"({'MATCH' if meas_best == best else 'within-noise mismatch'})")

    print(f"== block-size optimization for {best} ==")
    variant = int(best[-1])
    tracer = CHOLESKY_TRACERS[best]
    b_pred, profile = optimize_block_size(tracer, ms, n, b_candidates)
    print("   predicted profile: " +
          " ".join(f"b={b}:{t * 1e3:.2f}ms" for b, t in profile.items()))
    meas_profile = {}
    for b in b_candidates:
        def run(b=b):
            eng = ExecEngine()
            blocked.potrf(eng, eng.bind("A", A0), n, b, variant=variant)
            return eng.mats["A"]
        meas_profile[b] = median_time(run, 5)
    b_opt = min(meas_profile, key=meas_profile.get)
    yld = meas_profile[b_opt] / meas_profile[b_pred]
    print(f"   b_pred={b_pred} b_opt={b_opt} performance yield={yld:.1%}")

    print("== triangular inversion: rank all 8 variants ==")
    ranked = rank_algorithms(TRTRI_TRACERS, ms, n, 48)
    for r in ranked[:3]:
        print(f"   {r.name}: predicted {r.runtime.med * 1e3:7.2f} ms")
    print(f"   ... ({len(ranked)} variants ranked)")
    print("autotune_blocked OK")


if __name__ == "__main__":
    main()
