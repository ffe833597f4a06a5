"""End-to-end system tests: the paper's pipeline from model generation to
algorithm selection, exercised on real timed JAX kernels (small sizes)."""

import numpy as np
import pytest

# real model generation = measured kernel timings: nightly lane only
pytestmark = pytest.mark.slow

from repro.core import (GeneratorConfig, KernelBenchmark, ModelSet,
                        generate_model, predict_runtime, rank_algorithms)
from repro.core.grids import Domain
from repro.dla.kernels import KERNELS
from repro.dla.tracers import CHOLESKY_TRACERS


@pytest.fixture(scope="module")
def cholesky_models():
    """Generate real measured models for the Cholesky kernel set (small)."""
    cfg = GeneratorConfig(overfit=0, oversampling=2, repetitions=3,
                          error_bound=0.10, min_width=64, max_pieces=8)
    ms = ModelSet()
    specs = [
        ("potf2", (("L",),), Domain((16,), (160,))),
        ("trsm", (("R", "L", "T", "N", 1), ("L", "L", "N", "N", -1),
                  ("R", "L", "N", "N", -1)),
         Domain((16, 16), (160, 160))),
        ("syrk", (("L", "N", -1, 1),), Domain((16, 16), (160, 160))),
        ("gemm", (("N", "T", -1, 1),), Domain((16, 16, 16),
                                              (160, 160, 160))),
        ("trmm", (("R", "L", "N", "N", 1), ("L", "L", "N", "N", 1)),
         Domain((16, 16), (160, 160))),
        ("trti2", (("L", "N"),), Domain((16,), (160,))),
    ]
    for kname, cases, dom in specs:
        kd = KERNELS[kname]
        bench = KernelBenchmark(
            name=kname, cases=cases, domain=dom,
            cost_exponents=kd.cost_exponents,
            make_call=lambda case, sizes, _kd=kd: _kd.make_call(case, sizes),
        )
        model, _ = generate_model(bench, cfg)
        ms.add(model)
    return ms


def test_end_to_end_prediction_sane(cholesky_models):
    """Predict blocked Cholesky runtime; compare order of magnitude against
    a real execution (detailed accuracy lives in the benchmarks)."""
    import time

    from repro.dla import ExecEngine, blocked

    ms = cholesky_models
    n, b = 128, 32
    calls = CHOLESKY_TRACERS["potrf3"](n, b)
    pred = predict_runtime(calls, ms)

    rng = np.random.default_rng(0)
    a = rng.standard_normal((n, n))
    A0 = a @ a.T + n * np.eye(n)
    eng = ExecEngine()
    blocked.potrf(eng, eng.bind("A", A0), n, b, variant=3)  # warm-up
    times = []
    for _ in range(5):
        eng = ExecEngine()
        A = eng.bind("A", A0)
        t0 = time.perf_counter()
        blocked.potrf(eng, A, n, b, variant=3)
        eng.mats["A"]           # the finished factor, as a caller gets it
        times.append(time.perf_counter() - t0)
    measured = sorted(times)[len(times) // 2]
    assert pred.med > 0
    # engine adds python/slicing overhead over pure kernels: generous band
    assert pred.med < measured * 5 and measured < pred.med * 50


def test_variant_ranking_is_produced(cholesky_models):
    ranked = rank_algorithms(CHOLESKY_TRACERS, cholesky_models, 128, 32)
    assert len(ranked) == 3
    assert ranked[0].runtime.med <= ranked[-1].runtime.med


def test_trtri_ranking_with_same_models(cholesky_models):
    from repro.dla.tracers import TRTRI_TRACERS

    tracers = {k: TRTRI_TRACERS[k] for k in ("trtri1", "trtri5")}
    ranked = rank_algorithms(tracers, cholesky_models, 128, 32)
    assert len(ranked) == 2
