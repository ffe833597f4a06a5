"""Tests for repro.store: persistence, warm start, drift, tournament.

The store's headline contract: a ``PredictorSession`` warm-started from
a saved ``ModelStore`` produces **bit-identical** rankings to the
in-memory session the store was captured from, with **zero** new
micro-benchmarks (the suite's ``measured`` counter proves it).  The
in-memory session is the equivalence oracle for every warm-started
session — see the oracle table in ``docs/architecture.md``.  All
measurement here goes through an injected deterministic ``measure_fn``,
so equality checks are exact, not statistical.
"""

import json
import warnings

import numpy as np
import pytest

from benchmarks.common import catalog_synthetic_model_set
from repro.core import PredictionEngine, compile_calls
from repro.core.fitting import fit_relative
from repro.core.grids import Domain
from repro.core.model import ModelSet, PerformanceModel, Piece
from repro.core.sampler import STATS, Stats
from repro.dla.tracers import ALL_TRACERS
from repro.store import (SCHEMA_VERSION, DriftProbe, ModelStore,
                         PlatformFingerprint, Snapshot, StoreMismatchError,
                         current_fingerprint, frozen_workloads,
                         kendall_tau, run_tournament, workload)
from repro.tc import PredictorSession
from repro.tc.suite import MicroBenchmark, MicroBenchmarkSuite

SPEC = "bij,bjk->bik"
SIZES = dict(b=4, i=16, j=16, k=16)
CHAIN = "ab,bc,cd->ad"
CHAIN_SIZES = dict(a=8, b=8, c=8, d=8)
SWEEP_GRID = [dict(SIZES, b=b) for b in (4, 8)]


def fake_measure(key, repetitions):
    """Deterministic pure function of the key: exact reproducibility."""
    t = 1e-9 * key.call_bytes + 2e-6 + 5e-7 * key.classes.count("cold")
    return Stats(0.95 * t, t, 1.1 * t, 1.01 * t, 0.02 * t), 1e-3


def scaled_measure(factor):
    """A measure_fn reading ``factor``x slower than :func:`fake_measure`."""
    def fn(key, repetitions):
        s, first = fake_measure(key, repetitions)
        return Stats(s.min * factor, s.med * factor, s.max * factor,
                     s.mean * factor, s.std * factor), first
    return fn


def fake_suite(**kw):
    return MicroBenchmarkSuite(measure_fn=fake_measure, **kw)


def fake_session(**kw):
    return PredictorSession(suite=fake_suite(), **kw)


def rank_everything(sess):
    """Contraction + chain + sweep rankings as comparable value tuples.

    ``Stats`` is a frozen dataclass of floats, so the extracted
    ``(name, runtime)`` pairs compare field-exactly — equality between
    two sessions' outputs is bit-identity of the predictions.
    """
    contraction = [(r.name, r.runtime) for r in
                   sess.rank_contraction_algorithms(SPEC, SIZES)]
    chain = [(r.name, r.runtime) for r in
             sess.rank_einsum_paths(CHAIN, CHAIN_SIZES, max_loop_perms=2)]
    sweep = [[(r.name, r.runtime) for r in ranking]
             for ranking in sess.rank_contraction_sweep(SPEC,
                                                        SWEEP_GRID).rankings]
    return contraction, chain, sweep


# ------------------------------------------------------------- round trip --

def test_store_round_trips_measurements_exactly(tmp_path):
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    store = sess.save_store()
    path = tmp_path / "store.json"
    store.save(path)
    loaded = ModelStore.load(path, fingerprint=store.fingerprint)
    # MicroBenchmark/Stats are frozen dataclasses (== is field-exact) and
    # json floats round-trip via repr, so this is bit-exact equality
    assert loaded.measurements == store.measurements
    assert loaded.suite_meta == store.suite_meta
    assert loaded.fingerprint == store.fingerprint


def test_store_refuses_non_finite_measurements():
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    store = ModelStore.from_suite(sess.suite)
    key = next(iter(store.measurements))
    bad = Stats(0.0, float("nan"), 0.0, 0.0, 0.0)
    store.measurements[key] = MicroBenchmark(key=key, stats=bad,
                                             first=0.0, seconds=0.0)
    with pytest.raises(ValueError, match="non-finite"):
        store.to_payload()


def test_suite_protocol_conflict_on_merge():
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    store = ModelStore.from_suite(sess.suite)
    with pytest.raises(ValueError, match="measurement protocol"):
        store.add_suite(fake_suite(repetitions=3))


# ------------------------------------------------------- refusal to load --

def test_fingerprint_mismatch_refuses_and_lists_fields(tmp_path):
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    path = tmp_path / "store.json"
    sess.save_store(path)
    other = PlatformFingerprint(
        cpu="other-cpu", cores=1, backend="other", device_kind="other",
        libraries="other", dtype="float64", repro_version="0.0.0")
    with pytest.raises(StoreMismatchError) as err:
        ModelStore.load(path, fingerprint=other)
    # the refusal names every differing field
    for field in ("cpu", "backend", "dtype"):
        assert field in str(err.value)
    # the escape hatch loads anyway, keeping the STORED fingerprint
    loaded = ModelStore.load(path, fingerprint=other, allow_mismatch=True)
    assert loaded.n_keys == len(sess.suite.results)
    assert loaded.fingerprint == current_fingerprint()


def test_schema_bump_refuses_even_with_allow_mismatch(tmp_path):
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    path = tmp_path / "store.json"
    sess.save_store(path)
    payload = json.loads(path.read_text())
    assert payload["schema_version"] == SCHEMA_VERSION
    payload["schema_version"] = SCHEMA_VERSION + 1
    path.write_text(json.dumps(payload))
    with pytest.raises(StoreMismatchError, match="schema_version"):
        ModelStore.load(path)
    with pytest.raises(StoreMismatchError, match="schema_version"):
        ModelStore.load(path, allow_mismatch=True)   # schema gap is final


def test_store_of_round_trip_kernel_timings_refuses(tmp_path):
    """A store written before the kernel-call convention was recorded
    timed a host round trip per kernel call: its fingerprint lacks
    ``kernel_call``, and it models another program."""
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    path = tmp_path / "store.json"
    sess.save_store(path)
    payload = json.loads(path.read_text())
    assert payload["fingerprint"]["kernel_call"] == "device-resident"
    del payload["fingerprint"]["kernel_call"]
    path.write_text(json.dumps(payload))
    with pytest.raises(StoreMismatchError,
                       match="kernel_call: stored='missing'"):
        ModelStore.load(path)


def test_session_store_and_suite_are_exclusive():
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    store = sess.save_store()
    with pytest.raises(ValueError, match="store= or suite="):
        PredictorSession(store=store, suite=fake_suite())
    # repetitions may restate the stored protocol, never contradict it
    with pytest.raises(ValueError, match="repetitions"):
        PredictorSession(store=store, repetitions=3)
    PredictorSession(store=store, repetitions=5)     # matches: fine


# ---------------------------------------------------------- warm start --

def test_warm_started_rankings_bit_identical_with_zero_measurements(
        tmp_path):
    sess = fake_session()
    in_memory = rank_everything(sess)
    path = tmp_path / "store.json"
    sess.save_store(path)

    warm = PredictorSession(store=str(path))
    warm_rankings = rank_everything(warm)
    counters = warm.counters()
    assert counters["measured"] == 0, "warm start must not re-measure"
    assert counters["loaded"] == len(sess.suite.results)
    assert warm_rankings == in_memory


def test_warm_start_amortized_cost_accounting():
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    store = sess.save_store()
    warm = PredictorSession(store=store)
    warm.rank_contraction_algorithms(SPEC, SIZES)
    suite = warm.suite
    assert suite.cost_seconds == 0.0            # nothing measured here
    assert suite.loaded_cost_seconds > 0.0      # but not claimed free
    assert suite.cost_fraction(1.0) == 0.0      # marginal cost: zero
    assert suite.cost_fraction(1.0, include_loaded=True) == \
        pytest.approx(suite.loaded_cost_seconds)


def test_counters_partition_by_provenance():
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    store = sess.save_store()

    warm = PredictorSession(store=store)
    warm.suite.measure_fn = fake_measure
    c = warm.counters()
    assert c["loaded"] == len(store.measurements) and c["measured"] == 0

    # a NEW problem measures fresh benchmarks on top of the loaded ones
    warm.rank_contraction_algorithms("ij,jk->ik", dict(i=8, j=8, k=8))
    c = warm.counters()
    assert c["measured"] > 0
    # refresh moves a loaded key into the refreshed bucket: the three
    # buckets always partition n_benchmarks
    key = sorted(store.measurements, key=str)[0]
    warm.suite.refresh(key)
    c = warm.counters()
    assert c["refreshed"] == 1
    assert c["loaded"] == len(store.measurements) - 1
    assert c["loaded"] + c["measured"] + c["refreshed"] == c["n_benchmarks"]
    # refreshing an already-refreshed key does not double-count
    warm.suite.refresh(key)
    assert warm.counters()["refreshed"] == 1


# ----------------------------------------------------------------- drift --

def test_drift_probe_ratios_and_threshold():
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    probe = DriftProbe(sess.suite, max_keys=4, threshold=1.5,
                       measure_fn=scaled_measure(2.0))
    readings = probe.probe()
    assert 0 < len(readings) <= 4
    for r in readings:
        assert r.ratio == pytest.approx(2.0)
    assert len(probe.stale()) == len(readings)
    assert probe.max_ratio() == pytest.approx(2.0)
    # a wider band declares the same readings healthy
    lax = DriftProbe(sess.suite, max_keys=4, threshold=2.5,
                     measure_fn=scaled_measure(2.0))
    assert lax.stale() == []
    # speedups are drift too: the band is two-sided
    fast = DriftProbe(sess.suite, max_keys=4, threshold=1.5,
                      measure_fn=scaled_measure(0.4))
    assert len(fast.stale()) == len(fast.probe())


def test_drift_probe_subset_is_deterministic():
    sess = fake_session()
    rank_everything(sess)
    assert len(sess.suite.results) > 6
    a = DriftProbe(sess.suite, max_keys=6).keys()
    b = DriftProbe(sess.suite, max_keys=6).keys()
    assert a == b and len(a) == 6
    assert len(set(a)) == 6


def test_drift_probe_does_not_touch_suite_counters():
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    before = sess.counters()
    DriftProbe(sess.suite, measure_fn=scaled_measure(3.0)).probe()
    assert sess.counters() == before


def test_drift_refresh_repairs_in_place():
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    probe = DriftProbe(sess.suite, max_keys=4, threshold=1.5,
                       measure_fn=scaled_measure(2.0))
    stale_keys = [r.key for r in probe.stale()]
    replaced = probe.refresh()
    assert [mb.key for mb in replaced] == stale_keys
    assert sess.counters()["refreshed"] == len(stale_keys)
    # repaired measurements now match the drifted platform: re-probing
    # against the same backend reads ratio 1
    again = DriftProbe(sess.suite, max_keys=4, threshold=1.5,
                       measure_fn=scaled_measure(2.0))
    assert again.stale() == []
    # and the suite's own measure_fn was restored after the repair
    assert sess.suite.measure_fn is fake_measure


def test_session_check_drift_warns_and_refreshes():
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        readings = sess.check_drift(measure_fn=scaled_measure(2.0),
                                    refresh=True)
    assert any("model drift" in str(w.message) for w in caught)
    assert all(r.ratio == pytest.approx(2.0) for r in readings)
    assert sess.counters()["refreshed"] == len(readings)
    # the refreshed keys now reflect the drifted platform: re-probing
    # against it is quiet
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sess.check_drift(measure_fn=scaled_measure(2.0))
    assert not caught


def test_drift_probe_rejects_degenerate_threshold():
    with pytest.raises(ValueError, match="threshold"):
        DriftProbe(fake_suite(), threshold=1.0)


# ------------------------------------------------------------ tournament --

def _two_snapshots(tmp_path):
    """A faithful store and a rank-scrambling distorted copy."""
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    faithful = sess.save_store(tmp_path / "faithful.json")
    distorted = ModelStore.load(tmp_path / "faithful.json",
                                fingerprint=faithful.fingerprint)
    for i, key in enumerate(sorted(distorted.measurements, key=str)):
        mb = distorted.measurements[key]
        f = 1.0 + 0.9 * ((i * 7919) % 13) / 13   # non-uniform: breaks order
        s = mb.stats
        distorted.measurements[key] = MicroBenchmark(
            key=key, stats=Stats(s.min * f, s.med * f, s.max * f,
                                 s.mean * f, s.std), first=mb.first,
            seconds=mb.seconds)
    return faithful, distorted


def test_tournament_scores_and_orders_snapshots(tmp_path):
    faithful, distorted = _two_snapshots(tmp_path)
    loads = [workload("contraction", "contraction", SPEC, SIZES)]
    result = run_tournament(
        [Snapshot("distorted", distorted), Snapshot("faithful", faithful)],
        loads, oracle_session=fake_session(), measure_fn=fake_measure)
    assert result.scores[0].name == "faithful"
    winner = result.winner
    assert winner.rel_err == 0.0
    assert winner.top1_rate == 1.0
    assert winner.rank_agreement == 1.0
    assert winner.new_benchmarks == 0
    loser = result.scores[-1]
    assert loser.rel_err > 0.0


def test_tournament_payload_is_schema_stamped(tmp_path):
    faithful, distorted = _two_snapshots(tmp_path)
    loads = [workload("contraction", "contraction", SPEC, SIZES)]
    result = run_tournament(
        [Snapshot("a", faithful), Snapshot("b", distorted)], loads,
        oracle_session=fake_session(), measure_fn=fake_measure)
    path = tmp_path / "TOURNAMENT.json"
    result.save(path)
    payload = json.loads(path.read_text())
    assert payload["schema_version"] == SCHEMA_VERSION
    assert len(payload["scoreboard"]) == 2
    row = payload["scoreboard"][0]
    for field in ("name", "rel_err", "top1_rate", "rank_agreement",
                  "suite_cost_seconds", "new_benchmarks"):
        assert field in row
    assert payload["workloads"] == ["contraction"]


def test_tournament_needs_two_snapshots(tmp_path):
    faithful, _ = _two_snapshots(tmp_path)
    with pytest.raises(ValueError, match="at least 2"):
        run_tournament([Snapshot("only", faithful)])


def test_kendall_tau_reference_values():
    assert kendall_tau("abcd", "abcd") == 1.0
    assert kendall_tau("abcd", "dcba") == -1.0
    # one adjacent swap in 4 elements: 5 concordant pairs, 1 discordant
    assert kendall_tau("abcd", "abdc") == pytest.approx(4 / 6)
    # disjoint / trivial orderings have nothing to disagree about
    assert kendall_tau("ab", "cd") == 1.0
    assert kendall_tau("a", "a") == 1.0


def test_frozen_workloads_match_bench_smoke_constants():
    """The tournament's frozen literals mirror the bench smoke specs —
    if a bench spec moves, this test pins the decision: either move the
    frozen workloads too (breaking cross-commit score comparability, on
    purpose) or keep them frozen and update this pin."""
    import benchmarks.bench_contractions as bc
    import benchmarks.bench_einsum_paths as bp
    import benchmarks.bench_serving as bs
    by_name = {w.name: w for w in frozen_workloads()}
    contraction = by_name["contraction_smoke"]
    assert contraction.expr == bc.SMOKE_SPEC
    assert dict(contraction.sizes) == bc.SMOKE_SIZES
    chain = by_name["einsum_path_smoke"]
    assert chain.expr == bp.SMOKE_CHAIN
    assert dict(chain.sizes) == bp.SMOKE_SIZES
    opts = dict(chain.options)
    assert opts["kernels"] == bp.SMOKE_KERNELS
    assert opts["max_loop_perms"] == bp.SMOKE_LOOP_PERMS
    assert opts["memory_limit_bytes"] == bp.SMOKE_LIMIT
    serving = by_name["serving_step_proj"]
    sizes = dict(serving.sizes)
    assert sizes["j"] == bs.SMOKE_ARCH["d_model"]
    assert sizes["k"] == bs.SMOKE_ARCH["d_ff"]
    assert sizes["b"] == bs.SLOTS
    # the smoke subset drops only the expensive chain workload
    assert {w.name for w in frozen_workloads(smoke=True)} == \
        {"contraction_smoke", "serving_step_proj"}


# --------------------------------------- model save/load round-trip (io) --

def _quadratic_model(kernel="gemm"):
    """A tiny fitted model whose case is a NESTED tuple, like the tc
    per-signature cases."""
    xs = [[float(n)] for n in range(4, 44, 4)]
    case = ("ab,bc->ac", (8, 8), (8, 8), (8, 8), ("warm", "cold"))
    basis = [(0,), (1,), (2,)]
    m = PerformanceModel(kernel=kernel, setup="test")
    polys = {}
    for j, stat in enumerate(STATS):
        ys = [(1 + 0.1 * j) * (2e-9 * x[0] ** 2 + 1e-6) for x in xs]
        polys[stat] = fit_relative(xs, ys, basis)
    m.add_piece(case, Piece(domain=Domain((4,), (40,)), polys=polys))
    return m, case


def test_performance_model_from_dict_freezes_nested_cases(tmp_path):
    m, case = _quadratic_model()
    path = tmp_path / "model.json"
    m.save(str(path))
    loaded = PerformanceModel.load(str(path))
    # the json round trip turns the case's nested tuples into lists;
    # from_dict must freeze them back or the case neither hashes nor
    # matches the tuples lookups are keyed by
    assert list(loaded.cases) == [case]
    assert loaded.estimate(case, (16,)) == m.estimate(case, (16,))


def test_model_set_save_load_predict_compiled_bit_equal(tmp_path):
    """The regression the store layer depends on: ModelSet artifacts
    survive a save/load cycle, proven by BIT-equal ``predict_compiled``
    output over the full tracer catalog."""
    models = catalog_synthetic_model_set()
    seqs = [tracer(264, 56) for tracer in ALL_TRACERS.values()]
    compiled = compile_calls(seqs)
    before = PredictionEngine(models).predict_compiled(compiled)
    path = tmp_path / "models.json"
    models.save(str(path))
    loaded = ModelSet.load(str(path))
    assert set(loaded.models) == set(models.models)
    after = PredictionEngine(loaded).predict_compiled(compiled)
    np.testing.assert_array_equal(after, before)


def test_model_sets_round_trip_through_store(tmp_path):
    sess = fake_session()
    sess.rank_contraction_algorithms(SPEC, SIZES)
    path = tmp_path / "store.json"
    store = sess.save_store(path)
    assert len(store.model_sets) == 1
    loaded = ModelStore.load(path, fingerprint=store.fingerprint)
    (name, ms), = loaded.model_sets.items()
    original = store.model_set(name)
    assert json.dumps(ms.to_dict(), sort_keys=True) == \
        json.dumps(original.to_dict(), sort_keys=True)
