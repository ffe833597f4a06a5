"""Tests for repro.tc.session: the unified PredictorSession entry point
and the one-release deprecation shims on the legacy call forms."""

import warnings

import numpy as np
import pytest

from repro.core.contractions import (ContractionSpec,
                                     rank_contraction_algorithms)
from repro.core.sampler import Stats
from repro.core.selection import (rank_einsum_paths,
                                  select_contraction_algorithm,
                                  select_einsum_path)
from repro.tc import (COLD, WARM, ChainPredictor, ContractionPredictor,
                      MicroBenchmarkSuite, PredictorSession)

SPEC = "ij,jk->ik"
SIZES = dict(i=6, j=5, k=4)
CHAIN = "ij,jk,kl->il"
CHAIN_SIZES = dict(i=4, j=5, k=6, l=3)


def fake_measure(key, repetitions):
    t = 1e-9 * key.call_bytes + 2e-6 + 5e-7 * key.classes.count("cold")
    stats = Stats(min=0.95 * t, med=t, max=1.1 * t, mean=1.01 * t,
                  std=0.02 * t)
    return stats, 1e-3


def fake_suite(repetitions=4):
    return MicroBenchmarkSuite(repetitions=repetitions,
                               measure_fn=fake_measure)


def fake_session(**kwargs):
    return PredictorSession(suite=fake_suite(), **kwargs)


# ------------------------------------------------------- session routing --

def test_session_contraction_ranking_matches_predictor():
    sess = fake_session()
    direct = ContractionPredictor(SPEC, SIZES, suite=fake_suite())
    got = sess.rank_contraction_algorithms(SPEC, SIZES)
    want = direct.rank(stat="med")
    assert [r.name for r in got] == [r.name for r in want]
    np.testing.assert_allclose([r.runtime.med for r in got],
                               [r.runtime.med for r in want])


def test_session_select_is_rank_head():
    sess = fake_session()
    assert sess.select_contraction_algorithm(SPEC, SIZES) == \
        sess.rank_contraction_algorithms(SPEC, SIZES)[0].name


def test_session_chain_ranking_matches_predictor():
    sess = fake_session()
    direct = ChainPredictor(CHAIN, CHAIN_SIZES, suite=fake_suite())
    got = sess.rank_einsum_paths(CHAIN, CHAIN_SIZES)
    want = direct.rank_paths(stat="med")
    assert [r.name for r in got] == [r.name for r in want]
    assert sess.select_einsum_path(CHAIN, CHAIN_SIZES).name == \
        got[0].name


def test_session_memoizes_predictors_and_shares_suite():
    sess = fake_session()
    p1 = sess.contraction_predictor(SPEC, SIZES)
    p2 = sess.contraction_predictor(SPEC, SIZES)
    assert p1 is p2
    sess.rank_contraction_algorithms(SPEC, SIZES)
    n = sess.suite.n_benchmarks
    sess.rank_contraction_algorithms(SPEC, SIZES)
    assert sess.suite.n_benchmarks == n  # second ranking: all shared
    # a different arrival state is a different predictor
    p3 = sess.contraction_predictor(SPEC, SIZES, arrival={"A": COLD})
    assert p3 is not p1


def test_session_sweeps_share_one_suite():
    sess = fake_session()
    grid = [dict(SIZES), dict(i=8, j=5, k=4)]
    sweep = sess.rank_contraction_sweep(SPEC, grid)
    assert len(sweep.rankings) == 2
    assert sweep.suite is sess.suite
    chain_sweep = sess.rank_einsum_sweep(CHAIN, [dict(CHAIN_SIZES)])
    assert chain_sweep.suite is sess.suite


def test_session_counters_expose_suite_and_trace_cache():
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    counters = sess.counters()
    assert counters["n_benchmarks"] > 0
    assert counters["trace_misses"] > 0
    sess.rank_contraction_algorithms(SPEC, SIZES)
    assert sess.counters()["trace_hits"] >= counters["trace_hits"]


def test_session_repetitions_conflicts_with_suite():
    with pytest.raises(ValueError, match="repetitions"):
        PredictorSession(suite=fake_suite(repetitions=4), repetitions=3)


# --------------------------------------------------------------- shims --

def test_legacy_rank_contraction_algorithms_warns_and_matches():
    sess = fake_session()
    want = [(a.name, t) for a, t in
            _session_ranked_tuples(sess)]
    with pytest.warns(DeprecationWarning, match="PredictorSession"):
        got = rank_contraction_algorithms(ContractionSpec.parse(SPEC),
                                          SIZES, suite=fake_suite())
    assert [(a.name, t) for a, t in got] == want


def _session_ranked_tuples(sess):
    return [(r.algorithm, r.runtime.med)
            for r in sess.rank_contraction_algorithms(SPEC, SIZES)]


def test_legacy_sizes_grid_warns_and_matches():
    grid = [dict(SIZES), dict(i=8, j=5, k=4)]
    with pytest.warns(DeprecationWarning, match="sizes_grid"):
        got = rank_contraction_algorithms(ContractionSpec.parse(SPEC),
                                          sizes_grid=grid,
                                          suite=fake_suite())
    sweep = fake_session().rank_contraction_sweep(SPEC, grid)
    assert [[a.name for a, _ in point] for point in got] == \
        [[r.name for r in ranking] for ranking in sweep.rankings]


def test_legacy_select_contraction_algorithm_via_session_kwarg():
    sess = fake_session()
    # session= is the undeprecated spelling: no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        name = select_contraction_algorithm(SPEC, SIZES, session=sess)
    assert name == sess.select_contraction_algorithm(SPEC, SIZES)


def test_legacy_predictor_kwarg_warns():
    pred = ContractionPredictor(SPEC, SIZES, suite=fake_suite())
    with pytest.warns(DeprecationWarning, match="predictor"):
        name = select_contraction_algorithm(SPEC, SIZES, predictor=pred)
    assert name == fake_session().select_contraction_algorithm(SPEC, SIZES)


def test_legacy_rank_einsum_paths_warns_and_matches():
    sess = fake_session()
    want = [r.name for r in sess.rank_einsum_paths(CHAIN, CHAIN_SIZES)]
    pred = ChainPredictor(CHAIN, CHAIN_SIZES, suite=fake_suite())
    with pytest.warns(DeprecationWarning, match="predictor"):
        got = rank_einsum_paths(CHAIN, CHAIN_SIZES, predictor=pred)
    assert [r.name for r in got] == want
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        via_session = select_einsum_path(CHAIN, CHAIN_SIZES, session=sess)
    assert via_session.name == want[0]


def test_session_conflicts_with_legacy_kwargs():
    sess = fake_session()
    with pytest.raises(ValueError, match="session"):
        rank_contraction_algorithms(ContractionSpec.parse(SPEC), SIZES,
                                    session=sess, suite=fake_suite())
    with pytest.raises(ValueError, match="session"):
        select_contraction_algorithm(SPEC, SIZES, session=sess,
                                     repetitions=3)
    pred = ChainPredictor(CHAIN, CHAIN_SIZES, suite=fake_suite())
    with pytest.raises(ValueError, match="session"):
        rank_einsum_paths(CHAIN, CHAIN_SIZES, session=sess,
                          predictor=pred)
    with pytest.raises(ValueError, match="session"):
        rank_contraction_algorithms(ContractionSpec.parse(SPEC), SIZES,
                                    batched=False, session=sess)


def test_legacy_error_contracts_preserved():
    spec = ContractionSpec.parse(SPEC)
    with pytest.raises(ValueError, match="not both"):
        rank_contraction_algorithms(spec, SIZES, sizes_grid=[SIZES])
    with pytest.raises(ValueError, match="sizes"):
        rank_contraction_algorithms(spec)
    with pytest.raises(ValueError, match="mode"):
        rank_einsum_paths(CHAIN, CHAIN_SIZES, sizes_grid=[CHAIN_SIZES])
    with pytest.raises(ValueError, match="suite"):
        rank_einsum_paths(CHAIN, CHAIN_SIZES, suite=fake_suite())
    with pytest.raises(ValueError, match="repetitions"):
        select_contraction_algorithm(
            SPEC, SIZES, repetitions=3,
            predictor=ContractionPredictor(SPEC, SIZES,
                                           suite=fake_suite()))


# ------------------------------------------------------- serving facade --

def test_session_step_cost_model_facade():
    from repro.configs import get_config, reduced
    from repro.serve.scheduler import StepCostModel

    cfg = reduced(get_config("deepseek-7b"), n_layers=2, d_model=16,
                  d_ff=32, vocab=64)
    sess = fake_session()
    model = sess.step_cost_model(cfg, slots=3)
    assert isinstance(model, StepCostModel)
    assert model.slots == 3
    # the static-batch engine steps at full width whatever the occupancy
    assert model.tick_cost(1, WARM) == model.tick_cost(3, WARM)
    assert model.tick_cost(2, COLD) == model.tick_cost(3, COLD)
    assert model.tick_cost(1, WARM) > 0
    assert model.n_benchmarks > 0
    # model building went through THIS session's shared suite
    assert sess.suite.n_benchmarks == model.n_benchmarks
