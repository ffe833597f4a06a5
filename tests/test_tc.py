"""Tests for repro.tc: batched kernels, suite deduplication, predictor."""

import numpy as np
import pytest

from repro.core.contractions import (ContractionSpec, cold_pool_size,
                                     execute, execute_reference,
                                     rank_contraction_algorithms)
from repro.core.contractions import generate_algorithms as loop_algorithms
from repro.core.sampler import STATS, Stats
from repro.core.selection import select_contraction_algorithm
from repro.tc import (COLD, WARM, ContractionPredictor, MicroBenchmarkSuite,
                      benchmark_key, canonical_equation, generate_algorithms,
                      is_batched_kernel, kernel_batch_dims,
                      rank_contraction_sweep, slice_call_bytes,
                      validate_algorithms)

RNG = np.random.default_rng(7)


def fake_measure(key, repetitions):
    """Deterministic synthetic timing, a pure function of the signature."""
    t = 1e-9 * key.call_bytes + 2e-6 + 5e-7 * key.classes.count("cold")
    stats = Stats(min=0.95 * t, med=t, max=1.1 * t, mean=1.01 * t,
                  std=0.02 * t)
    return stats, 1e-3


def fake_suite(repetitions=4):
    return MicroBenchmarkSuite(repetitions=repetitions,
                               measure_fn=fake_measure)


# ------------------------------------------------------- batched kernels --

def test_batched_algorithms_bij_bjk():
    spec = ContractionSpec.parse("bij,bjk->bik")
    algs = generate_algorithms(spec)
    loop_only = loop_algorithms(spec)
    batched = [a for a in algs if is_batched_kernel(a.kernel)]
    assert len(algs) == len(loop_only) + len(batched)
    assert batched
    # the batch index is no longer loop-only: every surviving batched
    # algorithm absorbs it into the kernel call
    for alg in batched:
        assert "b" in alg.kernel_dims, alg.name
        assert "b" not in alg.loop_order, alg.name
    # the whole contraction as ONE batched matmul
    one_call = [a for a in batched
                if a.kernel == "gemm_batch" and not a.loop_order]
    assert len(one_call) == 1
    assert one_call[0].kernel_equation() == "bij,bjk->bik"
    # every generated algorithm (loop-only and batched) matches the einsum
    validate_algorithms(spec, algs, dict(b=3, i=4, j=5, k=6), rng=RNG)


def test_batched_algorithms_three_index():
    # no shared batch index: the batched kernels absorb free output indices
    # (broadcasting the operand that lacks them) instead
    spec = ContractionSpec.parse("abc,cd->abd")
    algs = generate_algorithms(spec)
    batched = [a for a in algs if is_batched_kernel(a.kernel)]
    assert batched
    assert any(a.kernel == "gemm_batch" and not a.loop_order
               and a.kernel_equation() == "abc,cd->abd" for a in batched)
    validate_algorithms(spec, algs, dict(a=3, b=4, c=5, d=6), rng=RNG)


def test_batched_generation_deduplicates_equations():
    # a batched gemv over the full free range IS a gemm: candidates whose
    # kernel equation + loop order coincide with an existing algorithm are
    # dropped, so no two algorithms are operationally identical
    spec = ContractionSpec.parse("bij,bjk->bik")
    algs = generate_algorithms(spec)
    keys = [(a.kernel_equation(), a.loop_order) for a in algs]
    assert len(keys) == len(set(keys))


def test_loop_only_generation_unchanged():
    spec = ContractionSpec.parse("abc=ai,ibc")
    assert len(generate_algorithms(spec, include_batched=False)) == \
        len(loop_algorithms(spec)) == 36


# ------------------------------------------------------------ pool sizing --

def test_cold_pool_size_scales_with_repetitions():
    cache = 32 * 2 ** 20
    # tiny calls: the old hard cap of 8 would cycle back into cache for
    # repetitions > 8; now every call gets its own buffer
    assert cold_pool_size(20, 1024, cache) == 21
    assert cold_pool_size(5, 1024, cache) == 6
    # big calls: a few buffers already span the cache
    assert cold_pool_size(100, 16 * 2 ** 20, cache) == 3
    assert cold_pool_size(1, 64, cache) == 2   # floor


# ------------------------------------------------------------------ suite --

def _one_call_gemm_batch(spec):
    return next(a for a in generate_algorithms(spec)
                if a.kernel == "gemm_batch" and not a.loop_order)


def test_batched_kernel_classes_are_per_batch_slice():
    # strided batch access: the cache working set of a batched kernel is
    # ONE slice's operands.  At b=16, n=512 the stacked call is 48 MB
    # (beyond the 32 MB capacity) but a slice is 3 MB — the operands must
    # classify WARM, where whole-operand accounting said cold.
    spec = ContractionSpec.parse("bij,bjk->bik")
    alg = _one_call_gemm_batch(spec)
    assert kernel_batch_dims(alg) == ("b",)
    sizes = dict(b=16, i=512, j=512, k=512)
    assert slice_call_bytes(alg, sizes) == 4 * 3 * 512 * 512
    assert benchmark_key(alg, sizes).classes == (WARM, WARM)
    # a slice that itself overflows the cache stays cold
    big = dict(b=2, i=2048, j=2048, k=2048)
    assert benchmark_key(alg, big).classes == (COLD, COLD)
    # plain kernels are untouched by the slice rule
    plain = next(a for a in loop_algorithms(spec) if a.kernel == "gemm")
    assert kernel_batch_dims(plain) == ()
    a_sh, b_sh, o_sh = plain.kernel_shapes(sizes)
    assert slice_call_bytes(plain, sizes) == 4 * (
        np.prod(a_sh) + np.prod(b_sh) + np.prod(o_sh))


def test_benchmark_keys_canonicalize_equations():
    # einsum is invariant under index renaming: ij,jk->ik and ik,kl->il at
    # equal shapes are ONE measurement (what lets chain steps share a suite)
    assert canonical_equation("ik,kl->il") == "ab,bc->ac"
    assert canonical_equation("bij,bjk->bik") == "abc,acd->abd"
    a1 = loop_algorithms(ContractionSpec.parse("ij,jk->ik"))
    a2 = loop_algorithms(ContractionSpec.parse("ik,kl->il"))
    sizes1 = dict(i=8, j=8, k=8)
    sizes2 = dict(i=8, k=8, l=8)
    keys1 = {benchmark_key(a, sizes1) for a in a1}
    keys2 = {benchmark_key(a, sizes2) for a in a2}
    assert keys1 == keys2
    suite = fake_suite()
    for a in a1:
        suite.benchmark(a, sizes1)
    n = suite.n_benchmarks
    for a in a2:
        suite.benchmark(a, sizes2)
    assert suite.n_benchmarks == n     # nothing new to measure


def test_arrival_override_forces_cold():
    spec = ContractionSpec.parse("ij,jk->ik")
    sizes = dict(i=8, j=8, k=8)
    alg = loop_algorithms(spec)[0]
    warm = benchmark_key(alg, sizes)
    assert warm.classes == (WARM, WARM)
    forced = benchmark_key(alg, sizes, arrival={"A": COLD})
    assert forced.classes == (COLD, WARM)
    # WARM arrival defers to the access distance: no-op on a warm operand
    assert benchmark_key(alg, sizes, arrival={"A": WARM}) == warm
    # distinct keys => distinct measurements in the suite
    suite = fake_suite()
    mb_warm = suite.benchmark(alg, sizes)
    mb_cold = suite.benchmark(alg, sizes, arrival={"A": COLD})
    assert mb_warm.key != mb_cold.key
    assert suite.n_benchmarks == 2


def test_suite_deduplicates_and_accounts_cost():
    spec = ContractionSpec.parse("bij,bjk->bik")
    sizes = dict(b=4, i=16, j=16, k=16)
    suite = fake_suite()
    algs = generate_algorithms(spec)
    results = [suite.benchmark(a, sizes) for a in algs]
    assert suite.requests == len(algs)
    assert suite.n_benchmarks < len(algs)       # strict deduplication
    assert suite.cost_seconds > 0
    # shared: algorithms with equal keys got the identical result object
    by_key = {}
    for alg, mb in zip(algs, results):
        key = benchmark_key(alg, sizes)
        assert mb.key == key
        assert by_key.setdefault(key, mb) is mb


def test_oracle_measurements_do_not_inflate_suite_cost():
    spec = ContractionSpec.parse("bij,bjk->bik")
    sizes = dict(b=2, i=4, j=4, k=4)
    pred = ContractionPredictor(spec, sizes, suite=fake_suite())
    pred.rank()
    cost = pred.suite.cost_seconds
    pred.rank_oracle()               # validation must not change the metric
    assert pred.suite.cost_seconds == cost
    assert pred.suite.oracle_cost_seconds > 0
    assert pred.prediction_cost_fraction(1.0) == pytest.approx(cost)


def test_repetitions_suite_conflict_raises():
    suite = fake_suite(repetitions=4)
    with pytest.raises(ValueError):
        ContractionPredictor("bij,bjk->bik", dict(b=2, i=4, j=4, k=4),
                             suite=suite, repetitions=3)
    # matching or unspecified repetitions are fine
    ContractionPredictor("bij,bjk->bik", dict(b=2, i=4, j=4, k=4),
                         suite=suite, repetitions=4)
    ContractionPredictor("bij,bjk->bik", dict(b=2, i=4, j=4, k=4),
                         suite=suite)


def test_suite_real_measurement_tiny():
    # the real cache-aware path on a tiny kernel: sane stats + overhead
    spec = ContractionSpec.parse("ab=ai,ib")
    sizes = dict(a=4, b=4, i=4)
    suite = MicroBenchmarkSuite(repetitions=2)
    alg = loop_algorithms(spec)[0]
    mb = suite.benchmark(alg, sizes)
    assert mb.stats.med > 0 and mb.first > 0
    assert suite.cost_seconds >= mb.seconds > 0


# -------------------------------------------------------------- predictor --

def test_predictor_matches_oracle_and_backends():
    spec = ContractionSpec.parse("bij,bjk->bik")
    sizes = dict(b=4, i=16, j=16, k=16)
    pred = ContractionPredictor(spec, sizes, suite=fake_suite())
    ranked = pred.rank()
    assert pred.suite.n_benchmarks < len(pred.algorithms)
    assert any(is_batched_kernel(r.algorithm.kernel) for r in ranked)
    # un-deduplicated per-algorithm oracle: identical ordering and stats
    oracle = pred.rank_oracle()
    assert [r.name for r in ranked] == [r.name for r in oracle]
    for s in STATS:
        np.testing.assert_allclose(
            [getattr(r.runtime, s) for r in ranked],
            [getattr(r.runtime, s) for r in oracle], rtol=1e-12)
    # a repeated prediction re-evaluates the same compiled batch: same bits
    np.testing.assert_array_equal(pred.predict(), pred.predict())
    assert [r.name for r in pred.rank()] == [r.name for r in ranked]


def test_predictor_includes_first_call_overhead_once():
    spec = ContractionSpec.parse("bij,bjk->bik")
    sizes = dict(b=4, i=8, j=8, k=8)
    pred = ContractionPredictor(spec, sizes, suite=fake_suite())
    for r in pred.rank():
        mb = pred.suite.results[r.benchmark]
        expect = mb.first + mb.stats.med * r.n_iterations
        np.testing.assert_allclose(r.runtime.med, expect, rtol=1e-12)
        # std of n uncorrelated calls adds in quadrature (Eq. 4.3)
        np.testing.assert_allclose(r.runtime.std,
                                   mb.stats.std * r.n_iterations ** 0.5,
                                   rtol=1e-12)


def test_predictor_reuses_trace_cache():
    pred = ContractionPredictor("bij,bjk->bik", dict(b=2, i=4, j=4, k=4),
                                suite=fake_suite())
    pred.rank()
    requests, benchmarks = pred.suite.requests, pred.suite.n_benchmarks
    hits = pred.cache.hits
    pred.rank()                      # compiled batch + measurements reused
    assert pred.cache.hits > hits
    assert pred.suite.requests == requests
    assert pred.suite.n_benchmarks == benchmarks


def test_rank_contraction_algorithms_batched_routes_through_tc():
    spec = ContractionSpec.parse("bij,bjk->bik")
    sizes = dict(b=2, i=4, j=4, k=4)
    suite = fake_suite()
    ranked = rank_contraction_algorithms(spec, sizes, suite=suite)
    assert suite.requests > 0        # went through the shared suite
    assert any(is_batched_kernel(a.kernel) for a, _ in ranked)
    ts = [t for _, t in ranked]
    assert ts == sorted(ts) and all(t > 0 for t in ts)
    with pytest.raises(ValueError):
        rank_contraction_algorithms(spec, sizes, batched=False,
                                    suite=suite)


def test_select_contraction_algorithm():
    suite = fake_suite()
    pred = ContractionPredictor("bij,bjk->bik", dict(b=2, i=4, j=4, k=4),
                                suite=suite)
    name = select_contraction_algorithm("bij,bjk->bik",
                                        dict(b=2, i=4, j=4, k=4),
                                        predictor=pred)
    assert name == pred.rank()[0].name
    # a predictor built for a different contraction (or sizes) must not
    # silently answer for the requested one
    with pytest.raises(ValueError):
        select_contraction_algorithm("ai,ib->ab", dict(a=4, i=4, b=4),
                                     predictor=pred)
    with pytest.raises(ValueError):
        select_contraction_algorithm("bij,bjk->bik",
                                     dict(b=3, i=4, j=4, k=4),
                                     predictor=pred)


def test_prediction_cost_fraction():
    pred = ContractionPredictor("bij,bjk->bik", dict(b=2, i=4, j=4, k=4),
                                suite=fake_suite())
    pred.prepare()
    frac = pred.prediction_cost_fraction(1.0)
    assert frac == pytest.approx(pred.suite.cost_seconds)


# ------------------------------------------------------------ size sweep --

SWEEP_GRID = [dict(b=2, i=8, j=8, k=8), dict(b=4, i=8, j=8, k=8),
              dict(b=8, i=8, j=8, k=8)]


def test_size_sweep_matches_independent_predictors():
    """Every size point of a shared-suite sweep ranks exactly like a
    fresh standalone predictor at that size (deterministic measure_fn:
    shared measurements are bit-interchangeable)."""
    sweep = rank_contraction_sweep("bij,bjk->bik", SWEEP_GRID,
                                   suite=fake_suite())
    assert len(sweep.rankings) == len(SWEEP_GRID)
    for sizes, ranking in zip(SWEEP_GRID, sweep.rankings):
        solo = ContractionPredictor("bij,bjk->bik", sizes,
                                    suite=fake_suite()).rank()
        assert [r.name for r in ranking] == [r.name for r in solo]
        assert [r.runtime for r in ranking] == [r.runtime for r in solo]
    assert [w.name for w in sweep.winners] == \
        [r[0].name for r in sweep.rankings]


def test_size_sweep_measures_only_new_keys():
    """One shared suite across the grid: identical keys are measured
    once, and sweeping a loop-only dimension (b with batched kernels
    excluded: no kernel shape contains b) measures NOTHING new."""
    suite = fake_suite()
    sweep = rank_contraction_sweep("bij,bjk->bik", SWEEP_GRID, suite=suite)
    assert suite.n_benchmarks < suite.requests
    assert sweep.n_benchmarks == suite.n_benchmarks
    loop_only = fake_suite()
    rank_contraction_sweep("bij,bjk->bik", SWEEP_GRID[:1], suite=loop_only,
                           include_batched=False)
    first_point = loop_only.counters()
    rank_contraction_sweep("bij,bjk->bik", SWEEP_GRID, suite=loop_only,
                           include_batched=False)
    assert loop_only.n_benchmarks == first_point["n_benchmarks"]
    assert loop_only.cost_seconds == first_point["cost_seconds"]


def test_size_sweep_core_entry_point_and_errors():
    per_point = rank_contraction_algorithms("bij,bjk->bik",
                                            sizes_grid=SWEEP_GRID,
                                            suite=fake_suite())
    assert len(per_point) == len(SWEEP_GRID)
    sweep = rank_contraction_sweep("bij,bjk->bik", SWEEP_GRID,
                                   suite=fake_suite())
    for got, ranking in zip(per_point, sweep.rankings):
        assert [a.name for a, _ in got] == [r.name for r in ranking]
        assert [t for _, t in got] == [r.runtime.med for r in ranking]
    # the shared TraceCache is reachable through the core entry too
    from repro.core.predict import TraceCache
    cache = TraceCache()
    rank_contraction_algorithms("bij,bjk->bik", sizes_grid=SWEEP_GRID,
                                suite=fake_suite(), cache=cache)
    assert cache.misses > 0        # compiled batches built on the shared cache
    with pytest.raises(ValueError, match="cache"):
        rank_contraction_algorithms("bij,bjk->bik", SWEEP_GRID[0],
                                    batched=False, cache=TraceCache())
    with pytest.raises(ValueError, match="not both"):
        rank_contraction_algorithms("bij,bjk->bik", SWEEP_GRID[0],
                                    sizes_grid=SWEEP_GRID)
    with pytest.raises(ValueError, match="batched"):
        rank_contraction_algorithms("bij,bjk->bik", sizes_grid=SWEEP_GRID,
                                    batched=False)
    with pytest.raises(ValueError, match="sizes"):
        rank_contraction_algorithms("bij,bjk->bik")
    with pytest.raises(ValueError, match="size point"):
        rank_contraction_sweep("bij,bjk->bik", [], suite=fake_suite())
    with pytest.raises(ValueError, match="repetitions"):
        rank_contraction_sweep("bij,bjk->bik", SWEEP_GRID,
                               suite=fake_suite(repetitions=4),
                               repetitions=3)


# ---------------------------------------------- batched execution (slow) --

@pytest.mark.slow
def test_batched_algorithms_larger_sizes():
    spec = ContractionSpec.parse("bij,bjk->bik")
    sizes = dict(b=6, i=24, j=20, k=16)
    algs = [a for a in generate_algorithms(spec)
            if is_batched_kernel(a.kernel)]
    A = RNG.standard_normal([sizes[i] for i in spec.a_idx]).astype(np.float32)
    B = RNG.standard_normal([sizes[i] for i in spec.b_idx]).astype(np.float32)
    ref = execute_reference(spec, A, B)
    for alg in algs:
        np.testing.assert_allclose(execute(alg, A, B, sizes), ref,
                                   rtol=2e-4, atol=2e-4, err_msg=alg.name)
