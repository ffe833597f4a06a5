"""Shared benchmark utilities: kernel model generation with disk caching."""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import (GeneratorConfig, KernelBenchmark, ModelSet,
                        PerformanceModel, generate_model)
from repro.core.grids import Domain
from repro.dla.kernels import KERNELS

ROOT = Path(__file__).resolve().parents[1]
MODEL_DIR = ROOT / "experiments" / "models"

#: smoke mode: tiny sizes, single repetition, measurement-free models —
#: toggled by ``benchmarks.run --smoke`` so CI can track the perf trajectory
SMOKE = False


def set_smoke(on: bool = True) -> None:
    global SMOKE
    SMOKE = on


def is_smoke() -> bool:
    return SMOKE

#: the kernel/case catalog every blocked algorithm in the benchmarks needs
DEFAULT_SPECS: List[Tuple[str, Tuple, Tuple[int, ...], Tuple[int, ...]]] = [
    ("potf2", (("L",),), (16,), (304,)),
    ("trti2", (("L", "N"),), (16,), (304,)),
    ("lauu2", (("L",),), (16,), (304,)),
    ("getf2", (("NP",),), (16, 16), (304, 144)),
    ("trsyl", (("N", "N", 1),), (16, 16), (144, 144)),
    ("trsm", (("R", "L", "T", "N", 1), ("L", "L", "N", "N", -1),
              ("R", "L", "N", "N", -1), ("L", "L", "N", "U", 1)),
     (16, 16), (304, 304)),
    ("trmm", (("R", "L", "N", "N", 1), ("L", "L", "N", "N", 1),
              ("R", "L", "N", "N", -1), ("L", "L", "N", "N", -1),
              ("L", "L", "T", "N", 1)),
     (16, 16), (304, 304)),
    ("syrk", (("L", "N", -1, 1), ("L", "T", 1, 1)),
     (16, 16), (304, 304)),
    ("gemm", (("N", "T", -1, 1), ("N", "N", -1, 1), ("N", "N", 1, 1),
              ("T", "N", 1, 1), ("N", "N", 1, 0), ("N", "N", -1, 0)),
     (16, 16, 16), (208, 208, 208)),
]

BENCH_GEN_CONFIG = GeneratorConfig(overfit=0, oversampling=2,
                                   repetitions=5, error_bound=0.04,
                                   min_width=64, max_pieces=6)


def build_model_set(specs=DEFAULT_SPECS,
                    config: GeneratorConfig = BENCH_GEN_CONFIG,
                    cache: str = "default",
                    verbose: bool = True) -> Tuple[ModelSet, float]:
    """Generate (or load cached) models; returns (set, generation seconds).

    The cache file records the platform fingerprint it was measured on;
    a cache from another platform (models fitted on a CPU, read on the
    chip) refuses to load instead of passing for this platform's models.
    """
    from repro.store import PlatformFingerprint, current_fingerprint

    MODEL_DIR.mkdir(parents=True, exist_ok=True)
    cache_file = MODEL_DIR / f"{cache}.json"
    here = current_fingerprint()
    if cache_file.exists():
        data = json.loads(cache_file.read_text())
        theirs = PlatformFingerprint.from_dict(data.get("fingerprint", {}))
        if theirs != here:
            raise RuntimeError(
                f"{cache_file} was measured on another platform (fields "
                f"{', '.join(here.mismatches(theirs))} differ); delete it "
                f"to re-measure here")
        ms = ModelSet()
        for d in data["models"]:
            ms.add(PerformanceModel.from_dict(d))
        return ms, data.get("gen_seconds", 0.0)
    ms = ModelSet()
    t0 = time.perf_counter()
    for name, cases, lo, hi in specs:
        kd = KERNELS[name]
        bench = KernelBenchmark(
            name=name, cases=cases, domain=Domain(lo, hi),
            cost_exponents=kd.cost_exponents,
            make_call=lambda case, sizes, _kd=kd: _kd.make_call(case, sizes),
        )
        model, report = generate_model(bench, config)
        ms.add(model)
        if verbose:
            print(f"  [modelgen] {name}: {report.measured_points} pts, "
                  f"{sum(report.pieces_per_case.values())} pieces, "
                  f"{report.seconds:.1f}s", flush=True)
    gen_s = time.perf_counter() - t0
    cache_file.write_text(json.dumps({
        "fingerprint": here.as_dict(),
        "gen_seconds": gen_s,
        "models": [m.to_dict() for m in ms.models.values()],
    }))
    return ms, gen_s


def best_of(fn, repetitions: int) -> float:
    """Best-of-N wall time of ``fn()`` — the shared timing protocol behind
    the CI-tracked smoke metrics (one copy, so the suites cannot drift)."""
    best = float("inf")
    for _ in range(repetitions):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def median_time(fn, repetitions: int = 5) -> float:
    if SMOKE:
        repetitions = 1
    fn()  # warm-up
    ts = []
    for _ in range(repetitions):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


#: synthetic-model calibration: an arbitrary but fixed machine balance
SYNTH_RATE_FLOPS = 5e10
SYNTH_OVERHEAD_S = 2e-6


def synthetic_model_set(specs=DEFAULT_SPECS,
                        points_per_dim: int = 5) -> ModelSet:
    """Measurement-free model set fitted to analytic flop counts.

    Every kernel/case in ``specs`` gets two polynomial pieces (the domain is
    bisected once) fitted to ``flops / rate + overhead`` with slightly spread
    per-statistic factors, through the real relative-LSQ pipeline — so bases,
    scales and piece lookup behave exactly like measured models, without
    timing a single kernel.  Prediction-path benchmarks and the CI smoke lane
    run on this set.
    """
    from repro.core import Piece, fit_relative, monomial_basis
    from repro.core.grids import grid_points
    from repro.dla.kernels import kernel_flops

    stat_factor = {"min": 0.97, "med": 1.0, "max": 1.08, "mean": 1.01}
    ms = ModelSet()
    for name, cases, lo, hi in specs:
        kd = KERNELS[name]
        model = PerformanceModel(kernel=name, setup="synthetic")
        for case in cases:
            basis = monomial_basis(kd.cost_exponents(case))
            dom = Domain(lo, hi)
            lo_half, hi_half, _ = dom.split()
            for sub in (lo_half, hi_half):
                pts = grid_points(sub, [points_per_dim] * dom.ndim,
                                  kind="cartesian", round_to=8)
                arr = np.asarray(pts, dtype=np.float64)
                base = np.asarray([kernel_flops(name, case, p) for p in pts])
                # analytic counts can dip negative outside a kernel's valid
                # shape regime (e.g. getf2 panels wider than tall): floor them
                base = np.maximum(base, 1.0) / SYNTH_RATE_FLOPS \
                    + SYNTH_OVERHEAD_S
                polys = {s: fit_relative(arr, base * f, basis)
                         for s, f in stat_factor.items()}
                polys["std"] = fit_relative(
                    arr, np.maximum(base * 0.02, 1e-9), basis)
                model.add_piece(case, Piece(domain=sub, polys=polys))
        ms.add(model)
    return ms


def catalog_synthetic_model_set(n: int = 264, b: int = 56) -> ModelSet:
    """Synthetic models covering every (kernel, case) the full tracer catalog
    (``repro.dla.tracers.ALL_TRACERS``) emits — the model set the
    engine-vs-scalar equivalence tests sweep the whole catalog against."""
    from repro.dla.tracers import required_kernel_cases

    dims: Dict[str, int] = {}
    need = required_kernel_cases(n=n, b=b, dims=dims)
    specs = [(kernel, tuple(sorted(cases, key=repr)),
              (16,) * dims[kernel], (304,) * dims[kernel])
             for kernel, cases in sorted(need.items())]
    return synthetic_model_set(specs)


def spd(n: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n))
    return a @ a.T + n * np.eye(n)


def lower_nonsing(n: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    a = np.tril(rng.standard_normal((n, n)))
    np.fill_diagonal(a, np.abs(a.diagonal()) + n)
    return a
