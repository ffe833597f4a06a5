"""Tests for repro.tc.chains: path enumeration, cache-state propagation,
chain composition, and the selection entry points."""

import numpy as np
import pytest

from repro.core.sampler import STATS, Stats
from repro.core.selection import rank_einsum_paths, select_einsum_path
from repro.tc import (COLD, WARM, ChainPredictor, ChainSpec,
                      MicroBenchmarkSuite, execute_chain,
                      execute_chain_reference, execute_path_reference,
                      rank_einsum_sweep, validate_paths)

RNG = np.random.default_rng(11)


def fake_measure(key, repetitions):
    """Deterministic synthetic timing, a pure function of the signature."""
    t = 1e-9 * key.call_bytes + 2e-6 + 5e-7 * key.classes.count("cold")
    stats = Stats(min=0.95 * t, med=t, max=1.1 * t, mean=1.01 * t,
                  std=0.02 * t)
    return stats, 1e-3


def fake_suite(repetitions=4, **kw):
    return MicroBenchmarkSuite(repetitions=repetitions,
                               measure_fn=fake_measure, **kw)


# ------------------------------------------------------------ spec/paths --

def test_parse_and_validation():
    c = ChainSpec.parse("ij,jk,kl->il")
    assert c.operands == ("ij", "jk", "kl") and c.out_idx == "il"
    assert c.einsum_expr() == "ij,jk,kl->il"
    assert ChainSpec.parse(c) is c
    with pytest.raises(ValueError):   # too many operands
        ChainSpec.parse("ab,bc,cd,de,ef,fg->ag")
    with pytest.raises(ValueError):   # diagonal within an operand
        ChainSpec.parse("ii,ij->j")
    with pytest.raises(ValueError):   # output index in no operand
        ChainSpec.parse("ij,jk->iz")
    with pytest.raises(ValueError):   # private index = sum reduction
        ChainSpec.parse("ijx,jk->ik")


def test_paths_counts_and_dedup():
    # unordered binary trees over N leaves: (2N-3)!! -> 3, 15 for N = 3, 4
    assert len(ChainSpec.parse("ij,jk,kl->il").paths()) == 3
    paths4 = ChainSpec.parse("ij,jk,kl,lm->im").paths()
    assert len(paths4) == 15
    assert len({p.name for p in paths4}) == 15
    # every path of an N-operand chain has N-1 steps, ending at the output
    for p in paths4:
        assert len(p.steps) == 3
        assert p.steps[-1].spec.out_idx == "im"


def test_paths_operational_dedup():
    # three identical operands: all three trees perform the same two step
    # contractions, so the operational dedup collapses them to ONE path
    assert len(ChainSpec.parse("ij,ij,ij->ij").paths()) == 1


def test_hyperedge_index_kept_as_batch():
    # an index shared by 3 operands must survive the first pairwise step
    # (it is still needed downstream), becoming a batch index of that step
    c = ChainSpec.parse("bi,bj,bk->ijk")
    for p in c.paths():
        first = p.steps[0].spec
        assert "b" in first.out_idx
        assert first.batch == ("b",)


def test_every_path_executes_bit_equal():
    # integer-valued operands: every association order sums the same exact
    # integers, so all 15 paths must be BIT-equal to the full einsum
    sizes = dict(i=4, j=5, k=6, l=3, m=4)
    validate_paths("ij,jk,kl,lm->im", sizes, rng=RNG)
    # and explicitly, not just via the helper:
    chain = ChainSpec.parse("ij,jk,kl,lm->im")
    ops = [RNG.integers(-3, 4, size=[sizes[i] for i in idx]
                        ).astype(np.float64) for idx in chain.operands]
    ref = execute_chain_reference(chain, ops)
    for p in chain.paths():
        assert np.array_equal(execute_path_reference(chain, p, ops), ref), \
            p.name


# -------------------------------------------------------- chain predictor --

def test_chain_totals_compose_with_first_once_per_signature():
    # uniform extents: the three steps of ((0.1).(2.3)) all lower to the
    # same canonical gemm signature, so the chain total must count the
    # first-call overhead ONCE, not three times
    sizes = {i: 8 for i in "ijklm"}
    pred = ChainPredictor("ij,jk,kl,lm->im", sizes, suite=fake_suite())
    ranked = pred.rank_paths()
    for r in ranked:
        keys = set()
        dup_first = 0.0
        for s in r.steps:
            if s.benchmark in keys:
                dup_first += s.first
            keys.add(s.benchmark)
        total = sum(s.runtime.med for s in r.steps) - dup_first
        np.testing.assert_allclose(r.runtime.med, total, rtol=1e-12)
        np.testing.assert_allclose(
            r.runtime.std,
            sum(s.runtime.std ** 2 for s in r.steps) ** 0.5, rtol=1e-12)
    best = ranked[0]
    # the balanced path's three uniform gemm steps share one signature
    assert best.name == "((0.1).(2.3))"
    assert len({s.benchmark for s in best.steps}) == 1
    assert best.runtime.med == pytest.approx(
        sum(s.runtime.med for s in best.steps) - 2 * best.steps[0].first)


def test_steps_share_one_suite_across_paths():
    sizes = {i: 8 for i in "ijklm"}
    suite = fake_suite()
    pred = ChainPredictor("ij,jk,kl,lm->im", sizes, suite=suite)
    pred.rank_paths()
    # canonical relabeling: renamed-but-identical steps (ij,jk->ik vs
    # kl,lm->km, ...) collapse onto shared signatures
    assert suite.n_benchmarks < suite.requests / 3
    n = suite.n_benchmarks
    pred.rank_paths()    # measurements fully reused
    assert suite.n_benchmarks == n


def test_intermediate_arrival_propagation():
    # i*k huge: the first step's output (64 MB) cannot fit the 32 MB cache,
    # so the consuming step must see it COLD regardless of loop structure
    sizes = dict(i=4096, j=4, k=4096, l=4)
    pred = ChainPredictor("ij,jk,kl->il", sizes, suite=fake_suite())
    big = next(p for p in pred.paths
               if p.steps[0].spec.out_idx == "ik")
    consuming = big.steps[1]
    op = "A" if consuming.inputs[0] >= 3 else "B"
    assert pred.arrival_classes(consuming) == {op: COLD}
    # the override flips algorithms whose in-loop distance alone says WARM
    stepped = pred.step_predictor(consuming)
    flipped = [a for a in stepped.algorithms
               if pred.suite.key_for(a, sizes).classes !=
               pred.suite.key_for(a, sizes,
                                  arrival={op: COLD}).classes]
    assert flipped
    # small intermediates arrive WARM: the propagated class defers to the
    # access distance and the keys coincide with the standalone ones
    small = ChainPredictor("ij,jk,kl->il", {i: 8 for i in "ijkl"},
                           suite=fake_suite())
    step = small.paths[0].steps[1]
    assert set(small.arrival_classes(step).values()) <= {WARM}


def test_backends_and_oracle_agree():
    sizes = {i: 8 for i in "ijklm"}
    pred = ChainPredictor("ij,jk,kl,lm->im", sizes, suite=fake_suite())
    ranked = pred.rank_paths()
    # a repeated ranking re-evaluates the cached batches: same answer
    assert pred.rank_paths() == ranked
    oracle = pred.rank_paths_oracle(fresh=False)
    assert [r.name for r in oracle] == [r.name for r in ranked]
    for s in STATS:
        np.testing.assert_allclose(
            [getattr(r.runtime, s) for r in ranked],
            [getattr(r.runtime, s) for r in oracle], rtol=1e-8)
    # fresh oracle re-measures per candidate without touching the suite's
    # accounted prediction cost
    cost = pred.suite.cost_seconds
    pred.rank_paths_oracle(fresh=True)
    assert pred.suite.cost_seconds == cost
    assert pred.suite.oracle_cost_seconds > 0


def test_memory_limit_prunes_outer_products():
    sizes = {i: 8 for i in "ijkl"}
    # the (0.2) pairing of ij,jk,kl shares no index: its intermediate is
    # the full 4-index outer product (16 KB at n=8)
    pred = ChainPredictor("ij,jk,kl->il", sizes, suite=fake_suite(),
                          memory_limit_bytes=8 * 1024)
    assert len(pred.paths) == 2
    for p in pred.paths:
        assert all(b <= 8 * 1024 for b in p.intermediate_bytes(sizes)[:-1])
    with pytest.raises(ValueError):
        ChainPredictor("ij,jk,kl->il", sizes, suite=fake_suite(),
                       memory_limit_bytes=16)


def test_execute_chain_matches_reference():
    sizes = {i: 6 for i in "ijklm"}
    chain = ChainSpec.parse("ij,jk,kl,lm->im")
    pred = ChainPredictor(chain, sizes, suite=fake_suite())
    best = pred.select_path()
    ops = [RNG.standard_normal([sizes[i] for i in idx]).astype(np.float32)
           for idx in chain.operands]
    got = execute_chain(chain, best, ops, sizes)
    ref = execute_chain_reference(chain, ops)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------- selection --

def test_rank_and_select_einsum_path():
    sizes = {i: 8 for i in "ijkl"}
    pred = ChainPredictor("ij,jk,kl->il", sizes, suite=fake_suite())
    ranked = rank_einsum_paths("ij,jk,kl->il", sizes, predictor=pred)
    assert [r.name for r in ranked] == \
        [r.name for r in pred.rank_paths()]
    best = select_einsum_path("ij,jk,kl->il", sizes, predictor=pred)
    assert best.name == ranked[0].name
    # a predictor built for a different einsum (or sizes) must not
    # silently answer for the requested one
    with pytest.raises(ValueError):
        select_einsum_path("ij,jk->ik", dict(i=8, j=8, k=8),
                           predictor=pred)
    with pytest.raises(ValueError):
        select_einsum_path("ij,jk,kl->il", {i: 9 for i in "ijkl"},
                           predictor=pred)
    with pytest.raises(ValueError):   # repetitions fixed by the suite
        select_einsum_path("ij,jk,kl->il", sizes, predictor=pred,
                           repetitions=3)


def test_repetitions_suite_conflict_raises():
    with pytest.raises(ValueError):
        ChainPredictor("ij,jk,kl->il", {i: 8 for i in "ijkl"},
                       suite=fake_suite(repetitions=4), repetitions=3)


# ------------------------------------------------------------ size sweep --

CHAIN_SWEEP_GRID = [{i: 4 for i in "ijkl"}, {i: 6 for i in "ijkl"},
                    {i: 8 for i in "ijkl"}]


def test_chain_size_sweep_matches_independent_predictors():
    """Every size point of a shared-suite chain sweep ranks exactly like
    a fresh standalone ChainPredictor at that size."""
    sweep = rank_einsum_sweep("ij,jk,kl->il", CHAIN_SWEEP_GRID,
                              suite=fake_suite())
    assert len(sweep.rankings) == len(CHAIN_SWEEP_GRID)
    assert sweep.n_benchmarks < sweep.suite.requests   # cross-point dedup
    for sizes, ranking in zip(CHAIN_SWEEP_GRID, sweep.rankings):
        solo = ChainPredictor("ij,jk,kl->il", sizes,
                              suite=fake_suite()).rank_paths()
        assert [r.name for r in ranking] == [r.name for r in solo]
        assert [r.runtime for r in ranking] == [r.runtime for r in solo]
    assert [w.name for w in sweep.winners] == \
        [r[0].name for r in sweep.rankings]


def test_chain_size_sweep_core_entry_point_and_errors():
    suite = fake_suite()
    per_point = rank_einsum_paths("ij,jk,kl->il",
                                  sizes_grid=CHAIN_SWEEP_GRID,
                                  suite=suite)
    assert len(per_point) == len(CHAIN_SWEEP_GRID)
    for ranking in per_point:
        assert ranking[0].runtime.med <= ranking[-1].runtime.med
    # the core entry extended the SHARED suite, no fresh measurements
    sweep = rank_einsum_sweep("ij,jk,kl->il", CHAIN_SWEEP_GRID,
                              suite=fake_suite())
    assert suite.n_benchmarks == sweep.n_benchmarks
    assert [r.name for r in per_point[0]] == \
        [r.name for r in sweep.rankings[0]]
    with pytest.raises(ValueError, match="mode"):
        rank_einsum_paths("ij,jk,kl->il", CHAIN_SWEEP_GRID[0],
                          sizes_grid=CHAIN_SWEEP_GRID)
    with pytest.raises(ValueError, match="suite"):
        rank_einsum_paths("ij,jk,kl->il", CHAIN_SWEEP_GRID[0],
                          suite=fake_suite())
    with pytest.raises(ValueError, match="sizes"):
        rank_einsum_paths("ij,jk,kl->il")
    with pytest.raises(ValueError, match="size point"):
        rank_einsum_sweep("ij,jk,kl->il", [], suite=fake_suite())
    with pytest.raises(ValueError, match="repetitions"):
        rank_einsum_sweep("ij,jk,kl->il", CHAIN_SWEEP_GRID,
                          suite=fake_suite(repetitions=4), repetitions=3)
    # a size point where NO path survives the memory limit names itself
    with pytest.raises(ValueError, match="size point"):
        rank_einsum_sweep("ij,jk,kl->il",
                          [CHAIN_SWEEP_GRID[0], {i: 64 for i in "ijkl"}],
                          suite=fake_suite(), memory_limit_bytes=1)
