"""Equivalence + speedup tests for the batched prediction engine.

The scalar per-call path (`estimate` / `predict_runtime`) is the reference
oracle; the batched path (`estimate_batch` / `PredictionEngine`) must agree
to ~1e-10 across random models, out-of-domain (clamped) points and degenerate
zero-size calls, to ~1e-8 across the full tracer catalog, and beat the
scalar block-size sweep by >= 10x.  A cached ``sweep`` must return
bit-identical results to an uncached one.
"""

import time

import numpy as np
import pytest

from benchmarks.common import catalog_synthetic_model_set
from repro.core import (Domain, KernelCall, ModelSet, PerformanceModel,
                        Piece, PredictionEngine, TraceCache, compile_calls,
                        fit_relative, monomial_basis,
                        optimize_algorithm_and_block_size,
                        optimize_block_size, predict_runtime, rank_algorithms,
                        select_algorithm)
from repro.core.sampler import STATS, Stats
from repro.dla.tracers import (ALL_TRACERS, CHOLESKY_TRACERS, LAPACK_TRACERS,
                               SYLVESTER_TRACERS, TRTRI_TRACERS)

#: agreement with the scalar oracle across the tracer catalog
CATALOG_REL = 1e-8

TRACER_FAMILIES = {"cholesky": CHOLESKY_TRACERS, "trtri": TRTRI_TRACERS,
                   "sylvester": SYLVESTER_TRACERS, "lapack": LAPACK_TRACERS,
                   "catalog": ALL_TRACERS}


def _rel_close(a, b, tol=1e-10):
    return abs(a - b) <= tol * max(1.0, abs(a), abs(b))


@pytest.fixture(scope="module")
def catalog_models():
    return catalog_synthetic_model_set()


def _family_seqs(family):
    # one (n, b) for the whole family: every kernel, degenerate tail calls
    # included, with deliberately uneven group sizes
    return [tracer(264, 56) for tracer in TRACER_FAMILIES[family].values()]


def _scalar_reference(seqs, models):
    return np.array([[getattr(predict_runtime(seq, models), s)
                      for s in STATS] for seq in seqs])


def _random_model(rng, kernel="k", ndim=2, n_pieces=3, cases=(("C",),)):
    """A random piecewise model fitted through the real relative-LSQ path."""
    m = PerformanceModel(kernel=kernel)
    for case in cases:
        edges = np.sort(rng.integers(2, 64, size=n_pieces - 1)) * 8
        bounds = [8] + [int(e) + 8 for e in edges] + [600]
        for i in range(n_pieces):
            dom = Domain(tuple([bounds[i]] + [8] * (ndim - 1)),
                         tuple([bounds[i + 1]] + [512] * (ndim - 1)))
            axes = [np.linspace(l, h, 5) for l, h in zip(dom.lo, dom.hi)]
            pts = np.stack(np.meshgrid(*axes, indexing="ij"),
                           axis=-1).reshape(-1, ndim)
            coef = rng.uniform(1e-10, 1e-8)
            const = rng.uniform(1e-7, 1e-5)
            vals = coef * np.prod(pts, axis=1) * pts[:, 0] + const
            basis = monomial_basis([tuple([2] + [1] * (ndim - 1))])
            polys = {s: fit_relative(pts, vals * f, basis)
                     for s, f in (("min", 0.95), ("med", 1.0), ("max", 1.1),
                                  ("mean", 1.01))}
            # std on a different (constant) basis: exercises stacking groups
            polys["std"] = fit_relative(pts, np.full(len(pts), const * 0.05),
                                        [tuple([0] * ndim)])
            m.add_piece(case, Piece(domain=dom, polys=polys))
    return m


@pytest.mark.parametrize("ndim", [1, 2, 3, 4])
def test_estimate_batch_matches_scalar_random_models(ndim):
    rng = np.random.default_rng(ndim)
    model = _random_model(rng, ndim=ndim)
    # in-domain, out-of-domain (clamped both sides) and boundary points
    pts = rng.integers(-64, 900, size=(300, ndim))
    batch = model.estimate_batch(("C",), pts.astype(np.float64))
    for i, p in enumerate(pts):
        scalar = model.estimate(("C",), tuple(int(x) for x in p))
        for j, s in enumerate(STATS):
            assert _rel_close(batch[i, j], scalar[s]), (p, s)


def test_degenerate_zero_size_rows_are_zero():
    rng = np.random.default_rng(7)
    model = _random_model(rng, ndim=2)
    pts = np.array([[0, 64], [64, 0], [-8, 128], [64, 64]], dtype=np.float64)
    batch = model.estimate_batch(("C",), pts)
    assert np.all(batch[:3] == 0.0)
    assert np.all(batch[3] > 0.0)


def test_degenerate_calls_need_no_model_like_scalar_path():
    """All-degenerate calls to an unmodeled case estimate to zero without a
    case lookup (scalar parity); any live call to it still raises KeyError."""
    rng = np.random.default_rng(13)
    model = _random_model(rng, ndim=2)
    ms = ModelSet({"k": model})
    degen = [KernelCall("k", ("MISSING",), (0, 64))]
    ref = predict_runtime(degen, ms)
    got = PredictionEngine(ms).predict_stats([degen])[0]
    assert got == ref == Stats(0, 0, 0, 0, 0)
    with pytest.raises(KeyError):
        PredictionEngine(ms).predict_batch(
            [degen + [KernelCall("k", ("MISSING",), (64, 64))]])


def test_estimate_batch_no_extrapolate_raises():
    rng = np.random.default_rng(11)
    model = _random_model(rng, ndim=2)
    cm = model.cases[("C",)]
    with pytest.raises(KeyError):
        cm.estimate_batch(np.array([[10_000.0, 10_000.0]]),
                          extrapolate=False)


def _tracer_for(kernel, case=("C",), calls_per_iter=3):
    """Cheap synthetic blocked-algorithm tracer: n/b iterations of shrinking
    panels, mimicking a Cholesky-style call sequence with degenerate tails."""
    def tracer(n, b):
        out = []
        for i in range(max(1, n // b)):
            rest = n - (i + 1) * b  # hits 0 on the last iteration: Example 4.1
            for _ in range(calls_per_iter):
                out.append(KernelCall(kernel, case, (b, max(rest, 0))))
        return out
    return tracer


def test_prediction_engine_matches_predict_runtime():
    rng = np.random.default_rng(3)
    ms = ModelSet({"k": _random_model(rng, "k"),
                   "k2": _random_model(rng, "k2")})
    engine = PredictionEngine(ms)
    seqs = [_tracer_for("k")(n, b) + _tracer_for("k2")(n, b)
            for n, b in ((512, 32), (512, 8), (96, 96), (256, 40))]
    batch = engine.predict_stats(seqs)
    for seq, got in zip(seqs, batch):
        ref = predict_runtime(seq, ms)
        for s in STATS:
            assert _rel_close(getattr(got, s), getattr(ref, s)), s


@pytest.mark.parametrize("family", list(TRACER_FAMILIES))
def test_compile_calls_groups_and_counts(family):
    """Every real call lands in its (kernel, case) group, in config order,
    and the scatter index sends it to its own config, in group order."""
    seqs = _family_seqs(family)
    compiled = compile_calls(seqs)
    assert compiled.n_configs == len(seqs)
    assert compiled.n_calls == sum(len(seq) for seq in seqs)
    keys = [(g.kernel, g.case) for g in compiled.groups]
    assert len(set(keys)) == len(keys)
    assert set(keys) == {(c.kernel, c.case) for seq in seqs for c in seq}
    for g in compiled.groups:
        assert g.sizes.shape[0] == g.config.shape[0]
        assert np.all(np.diff(g.config) >= 0)
        for i, seq in enumerate(seqs):
            want = [c.sizes for c in seq if (c.kernel, c.case) == (g.kernel,
                                                                   g.case)]
            got = g.sizes[g.config == i]
            assert got.shape[0] == len(want)
            if want:
                np.testing.assert_array_equal(got, np.asarray(want, float))
    np.testing.assert_array_equal(
        compiled.flat_config,
        np.concatenate([g.config for g in compiled.groups]))
    assert compiled.flat_config.shape == (compiled.n_calls,)


@pytest.mark.parametrize("family", list(TRACER_FAMILIES))
def test_engine_matches_scalar_oracle_per_tracer_family(catalog_models,
                                                        family):
    seqs = _family_seqs(family)
    got = PredictionEngine(catalog_models).predict_compiled(
        compile_calls(seqs))
    np.testing.assert_allclose(got, _scalar_reference(seqs, catalog_models),
                               rtol=CATALOG_REL, atol=0)


def test_empty_and_all_degenerate_batches(catalog_models):
    eng = PredictionEngine(catalog_models)
    # configs with no calls at all predict all-zero statistics
    out = eng.predict_batch([[], []])
    assert out.shape == (2, len(STATS))
    assert np.all(out == 0.0)
    # an unmodeled case whose every call is degenerate needs no model;
    # a live call to it still raises (scalar-path parity)
    degen = [KernelCall("gemm", ("MISSING",), (0, 64, 64))]
    assert np.all(eng.predict_batch([degen]) == 0.0)
    with pytest.raises(KeyError):
        eng.predict_batch(
            [degen + [KernelCall("gemm", ("MISSING",), (64, 64, 64))]])


def test_trace_engine_compile_roundtrip():
    from repro.dla import TraceEngine, blocked
    from repro.dla.engine import Matrix

    eng = TraceEngine()
    blocked.potrf(eng, Matrix("A", 128, 128), 128, 32, variant=3)
    compiled = eng.compile()
    assert compiled.n_configs == 1
    assert compiled.n_calls == len(eng.calls)
    assert {g.kernel for g in compiled.groups} <= \
        {"potf2", "trsm", "syrk", "gemm"}


def _scalar_ranking(tracers, ms, n, b, stat):
    """The oracle ranking: predict_runtime per variant, stable sort."""
    runtimes = {name: predict_runtime(tracer(n, b), ms)
                for name, tracer in tracers.items()}
    return sorted(runtimes.items(), key=lambda kv: getattr(kv[1], stat))


@pytest.mark.parametrize("stat", ["min", "med", "max", "mean"])
def test_rank_algorithms_batched_matches_scalar(stat):
    rng = np.random.default_rng(5)
    ms = ModelSet({"fast": _random_model(rng, "fast"),
                   "slow": _random_model(rng, "slow")})
    tracers = {"a": _tracer_for("slow"), "b": _tracer_for("fast"),
               "c": _tracer_for("slow", calls_per_iter=5)}
    got = rank_algorithms(tracers, ms, 512, 64, stat=stat)
    ref = _scalar_ranking(tracers, ms, 512, 64, stat)
    assert [r.name for r in got] == [name for name, _ in ref]
    for g, (_, r) in zip(got, ref):
        for s in STATS:
            assert _rel_close(getattr(g.runtime, s), getattr(r, s)), s


@pytest.mark.parametrize("stat", ["min", "med", "max", "mean"])
def test_select_algorithm_matches_scalar_oracle(stat):
    """select_algorithm's batched winner equals the scalar oracle's
    (predict_runtime per variant) and rank_algorithms' top entry."""
    rng = np.random.default_rng(7)
    ms = ModelSet({"fast": _random_model(rng, "fast"),
                   "slow": _random_model(rng, "slow")})
    tracers = {"a": _tracer_for("slow"), "b": _tracer_for("fast"),
               "c": _tracer_for("slow", calls_per_iter=5)}
    got = select_algorithm(tracers, ms, 512, 64, stat=stat)
    assert got == _scalar_ranking(tracers, ms, 512, 64, stat)[0][0]
    assert got == rank_algorithms(tracers, ms, 512, 64, stat=stat)[0].name


def test_block_size_sweep_identical_and_10x_faster():
    """Acceptance: >= 64-candidate sweep, identical argmin, stats to 1e-10,
    >= 10x speedup over the scalar per-call loop."""
    rng = np.random.default_rng(17)
    ms = ModelSet({"k": _random_model(rng, "k", n_pieces=4)})
    tracer = _tracer_for("k")
    n = 1024
    candidates = [8 * (i + 1) for i in range(64)]

    def scalar_sweep():
        profile = {b: predict_runtime(tracer(n, b), ms).med
                   for b in candidates}
        return min(profile, key=profile.get), profile

    b_batched, prof_batched = optimize_block_size(tracer, ms, n, candidates)
    b_scalar, prof_scalar = scalar_sweep()
    assert b_batched == b_scalar
    assert set(prof_batched) == set(prof_scalar)
    for b in candidates:
        assert _rel_close(prof_batched[b], prof_scalar[b])

    def best_of(fn, reps=3):
        fn()  # warm-up: BLAS/allocator init must not skew the comparison
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_scalar = best_of(scalar_sweep)
    t_batched = best_of(lambda: optimize_block_size(tracer, ms, n,
                                                    candidates))
    assert t_scalar / t_batched >= 10.0, (t_scalar, t_batched)


@pytest.mark.parametrize("stat", ["min", "med", "max", "mean"])
def test_joint_optimization_matches_scalar(stat):
    rng = np.random.default_rng(23)
    ms = ModelSet({"k": _random_model(rng, "k"),
                   "k2": _random_model(rng, "k2")})
    tracers = {"a": _tracer_for("k"), "b": _tracer_for("k2")}
    candidates = [16, 32, 64, 128]
    got = optimize_algorithm_and_block_size(tracers, ms, 512, candidates,
                                            stat=stat)
    # the oracle: every (variant, b) through predict_runtime, first
    # minimum in variant then candidate order
    ref = None
    for name, tracer in tracers.items():
        for b in candidates:
            t = getattr(predict_runtime(tracer(512, b), ms), stat)
            if ref is None or t < ref[2]:
                ref = (name, b, t)
    assert got[:2] == ref[:2]
    assert _rel_close(got[2], ref[2])


def test_rank_traced_configs_matches_rank_algorithms():
    """The perf-layer config-ranking bridge agrees with core selection."""
    from repro.perf import rank_traced_configs

    rng = np.random.default_rng(31)
    ms = ModelSet({"k": _random_model(rng, "k"),
                   "k2": _random_model(rng, "k2")})
    tracers = {"a": _tracer_for("k"), "b": _tracer_for("k2")}
    got = rank_traced_configs(tracers, ms, 512, 64)
    ref = rank_algorithms(tracers, ms, 512, 64)
    assert [r.name for r in got] == [r.name for r in ref]
    for g, r in zip(got, ref):
        assert _rel_close(g.predicted_s, r.runtime.med)
        assert _rel_close(g.runtime.std, r.runtime.std)


def test_grid_prediction_shape_and_values():
    rng = np.random.default_rng(29)
    ms = ModelSet({"k": _random_model(rng, "k")})
    engine = PredictionEngine(ms)
    tracer = _tracer_for("k")
    ns, bs = [128, 256], [16, 32, 64]
    grid = engine.grid(tracer, ns, bs)
    assert grid.shape == (len(ns), len(bs), len(STATS))
    for i, n in enumerate(ns):
        for j, b in enumerate(bs):
            ref = predict_runtime(tracer(n, b), ms)
            for k, s in enumerate(STATS):
                assert _rel_close(grid[i, j, k], getattr(ref, s)), (n, b, s)


@pytest.mark.parametrize("engine", ["implicit", "own"])
def test_default_engine_answers_each_model_set_interleaved(engine):
    """No trace cache holds a model: two model sets selecting in turn over
    the same tracers and sizes each get their own fresh-engine answer,
    whether engine-less (the shared bounded cache) or each on a caller's
    own engine kept across the turns."""
    tracers = {"a": _tracer_for("k"), "b": _tracer_for("k2"),
               "c": _tracer_for("k", calls_per_iter=3)}
    sets = [ModelSet({"k": _random_model(np.random.default_rng(seed), "k"),
                      "k2": _random_model(np.random.default_rng(seed + 1),
                                          "k2")})
            for seed in (41, 43)]
    candidates = [16, 32, 64]
    want = [optimize_algorithm_and_block_size(
        tracers, ms, 512, candidates, engine=PredictionEngine(ms))
        for ms in sets]
    assert want[0] != want[1]
    engines = [PredictionEngine(ms) if engine == "own" else None
               for ms in sets]
    for _ in range(2):
        for ms, eng, ref in zip(sets, engines, want):
            assert optimize_algorithm_and_block_size(
                tracers, ms, 512, candidates, engine=eng) == ref


def test_default_engine_sees_a_replaced_kernel_model():
    """Replacing a kernel model with ``ModelSet.add`` changes the next
    engine-less answer, though its batch comes from the shared cache."""
    tracers = {"a": _tracer_for("k"), "b": _tracer_for("k2")}
    ms = ModelSet({"k": _random_model(np.random.default_rng(51), "k"),
                   "k2": _random_model(np.random.default_rng(52), "k2")})
    before = rank_algorithms(tracers, ms, 384, 32)
    ms.add(_random_model(np.random.default_rng(53), "k"))
    after = rank_algorithms(tracers, ms, 384, 32)
    ref = _scalar_ranking(tracers, ms, 384, 32, "med")
    assert [r.runtime for r in after] != [r.runtime for r in before]
    assert [r.name for r in after] == [name for name, _ in ref]
    for g, (_, r) in zip(after, ref):
        assert _rel_close(g.runtime.med, r.med)


def test_default_engine_cache_evicts_least_recently_used():
    """Engine-less selections past the shared cache's bound evict the
    least recently used entries, and no table ever holds more."""
    from repro.core import predict

    cache = predict._IMPLICIT_CACHE
    bound = predict.IMPLICIT_CACHE_ENTRIES
    ms = ModelSet({"k": _random_model(np.random.default_rng(61), "k")})
    tracer = _tracer_for("k")
    ns = list(range(8, 8 + 2 * bound))
    for i, n in enumerate(ns):
        optimize_block_size(tracer, ms, n, [8])
        optimize_block_size(tracer, ms, ns[0], [8])   # keep it recent
        assert len(cache._calls) <= bound
        assert len(cache._compiled) <= bound
    held = {key[2] for key in cache._compiled if key[1] is tracer}
    assert ns[0] in held and ns[-1] in held
    assert ns[1] not in held and ns[-bound] not in held
    # a whole-batch hit reads no call sequence, so ns[0]'s has aged out
    assert {key[1] for key in cache._calls if key[0] is tracer} == \
        set(ns[-bound:])


def test_caller_engine_keeps_its_own_cache_and_counts():
    """An engine a caller builds keeps its own cache, and selections on it
    count one miss per traced configuration and one hit per configuration
    served from the cache, whole batch or not."""
    from repro.core import predict

    tracers = {"a": _tracer_for("k"), "b": _tracer_for("k2"),
               "c": _tracer_for("k", calls_per_iter=2)}
    ms = ModelSet({"k": _random_model(np.random.default_rng(71), "k"),
                   "k2": _random_model(np.random.default_rng(72), "k2")})
    eng = PredictionEngine(ms)
    assert eng.cache is not predict._IMPLICIT_CACHE
    candidates = [16, 32, 64]
    configs = len(tracers) * len(candidates)
    first = optimize_algorithm_and_block_size(tracers, ms, 512, candidates,
                                              engine=eng)
    assert (eng.cache.misses, eng.cache.hits) == (configs, 0)
    assert optimize_algorithm_and_block_size(
        tracers, ms, 512, candidates, engine=eng) == first
    assert (eng.cache.misses, eng.cache.hits) == (configs, configs)
    rank_algorithms(tracers, ms, 512, 32, engine=eng)  # traced at b=32
    assert (eng.cache.misses, eng.cache.hits) == \
        (configs, configs + len(tracers))


def test_supplied_engine_on_other_models_rejected(catalog_models):
    """A supplied engine must have been built on the models argument: a
    mismatch must not silently answer from the engine's set."""
    other = catalog_synthetic_model_set()
    with pytest.raises(ValueError, match="different ModelSet"):
        rank_algorithms(CHOLESKY_TRACERS, catalog_models, 264, 56,
                        engine=PredictionEngine(other))
    ranked = rank_algorithms(CHOLESKY_TRACERS, catalog_models, 264, 56,
                             engine=PredictionEngine(catalog_models))
    assert rank_algorithms(CHOLESKY_TRACERS, catalog_models, 264,
                           56) == ranked


def test_cached_sweep_bit_identical_to_uncached(catalog_models):
    tracer = CHOLESKY_TRACERS["potrf3"]
    candidates = [8 * (i + 1) for i in range(16)]
    eng = PredictionEngine(catalog_models)
    first = eng.sweep(tracer, 256, candidates)
    assert (eng.cache.hits, eng.cache.misses) == (0, len(candidates))
    again = eng.sweep(tracer, 256, candidates)
    # the compiled batch is reused outright: zero extra traces (one
    # whole-batch hit), and the prediction is bit-identical
    assert eng.cache.misses == len(candidates)
    assert eng.cache.hits == 1
    np.testing.assert_array_equal(again, first)
    # an uncached engine computes the same bits
    uncached = PredictionEngine(catalog_models).sweep(tracer, 256,
                                                      candidates)
    np.testing.assert_array_equal(uncached, first)
    # the sweep artifact itself is one object, reusable via predict_compiled
    compiled = eng.compile_sweep(tracer, 256, candidates)
    assert compiled is eng.compile_sweep(tracer, 256, candidates)
    np.testing.assert_array_equal(eng.predict_compiled(compiled), first)


def test_trace_cache_shared_across_engines(catalog_models):
    cache = TraceCache()
    first = PredictionEngine(catalog_models, cache=cache)
    second = PredictionEngine(catalog_models, cache=cache)
    tracer = TRTRI_TRACERS["trtri1"]
    ns, bs = [128, 192], [16, 32, 48]
    grid = first.grid(tracer, ns, bs)
    misses = cache.misses
    again = second.grid(tracer, ns, bs)
    assert cache.misses == misses  # the second engine re-traced nothing
    assert grid.shape == (len(ns), len(bs), len(STATS))
    np.testing.assert_array_equal(again, grid)


def test_compile_traces_helper_matches_per_config_compile(catalog_models):
    from repro.dla import Matrix, blocked, compile_traces

    fns = [lambda e, b=b: blocked.potrf(e, Matrix("A", 128, 128), 128, b, 2)
           for b in (16, 32)]
    compiled = compile_traces(fns)
    assert compiled.n_configs == 2
    stats = PredictionEngine(catalog_models).predict_compiled(compiled)
    assert stats.shape == (2, len(STATS))
    assert np.all(stats[:, :4] > 0)
