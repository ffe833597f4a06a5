"""Tests for the adaptive-refinement generator (paper §3.2.5, §3.3).

The generator was dormant until the size-parametric suite models started
driving it; these tests pin its contract directly, with analytic sample
functions instead of real measurements: convergence on smooth curves,
splitting on curves one polynomial cannot capture, measurement caching
(no point is ever sampled twice), the fresh-measurement budget, and the
deterministic point ordering the parametric layer's bit-stability
guarantees rest on.
"""

import pytest

from repro.core.grids import Domain, grid_points
from repro.core.refinement import GeneratorConfig, _Cache, refine
from repro.core.sampler import STATS, Stats


def analytic_sample_fn(fn, log=None):
    """SampleFn evaluating an analytic runtime curve ``fn(point) -> sec``.

    ``log`` (optional list) records every batch of points requested, in
    request order, so tests can assert on sampling behaviour.
    """

    def sample(points):
        if log is not None:
            log.append(tuple(points))
        return {p: Stats.from_samples([fn(p)]) for p in points}

    return sample


def counting_sample_fn(fn):
    """Like :func:`analytic_sample_fn` but counts samples per point."""
    counts = {}

    def sample(points):
        out = {}
        for p in points:
            counts[p] = counts.get(p, 0) + 1
            out[p] = Stats.from_samples([fn(p)])
        return out

    return sample, counts


# a cheap configuration: linear basis (overfit=0), 3 points per dim
CHEAP = GeneratorConfig(overfit=0, oversampling=1, grid="cartesian",
                        error_bound=0.02, min_width=16, round_to=8)

LINEAR = lambda p: 2e-9 * p[0] + 1e-6  # exactly in the linear basis's span


def kinked(p):
    """A performance cliff at x=128: no single linear fit works."""
    x = p[0]
    return 1e-6 * x if x <= 128 else 2.5e-6 * x - 1.92e-4


# ------------------------------------------------------------ convergence --


def test_refine_linear_curve_one_piece():
    dom = Domain((32,), (256,))
    pieces = refine(dom, analytic_sample_fn(LINEAR), [(1,)], CHEAP)
    assert len(pieces) == 1
    piece = pieces[0]
    assert piece.domain == dom
    assert set(piece.polys) == set(STATS)
    # data in the basis span -> the fit reproduces the curve everywhere
    # in the domain, not just at sampled points
    for x in (32, 40, 100, 200, 256):
        est = piece.estimate((x,))
        assert est["med"] == pytest.approx(LINEAR((x,)), rel=1e-9)


def test_refine_splits_on_performance_cliff():
    dom = Domain((32,), (256,))
    pieces = refine(dom, analytic_sample_fn(kinked), [(1,)], CHEAP)
    assert len(pieces) > 1
    # the pieces tile the original domain without gaps or overlap
    spans = sorted((p.domain.lo[0], p.domain.hi[0]) for p in pieces)
    assert spans[0][0] == dom.lo[0] and spans[-1][1] == dom.hi[0]
    for (_, hi_a), (lo_b, _) in zip(spans, spans[1:]):
        assert hi_a == lo_b
    # away from the cliff the local linear fits are accurate
    for x in (40, 64, 224, 248):
        piece = next(p for p in pieces if p.domain.contains((x,)))
        assert piece.estimate((x,))["med"] == \
            pytest.approx(kinked((x,)), rel=CHEAP.error_bound)


def test_refine_2d_multilinear_curve():
    dom = Domain((32, 32), (128, 128))
    fn = lambda p: 1e-9 * p[0] * p[1] + 5e-7
    pieces = refine(dom, analytic_sample_fn(fn), [(1, 1)], CHEAP)
    assert len(pieces) == 1
    assert pieces[0].estimate((100, 50))["med"] == \
        pytest.approx(fn((100, 50)), rel=1e-9)


# ---------------------------------------------------------------- caching --


def test_cache_never_resamples():
    fn, counts = counting_sample_fn(LINEAR)
    cache = _Cache(fn)
    pts = [(32,), (64,), (96,)]
    first = cache.get(pts)
    again = cache.get(pts)
    assert first == again
    assert cache.measured_points == len(pts)
    assert all(c == 1 for c in counts.values())


def test_refine_never_resamples_across_levels():
    # the cliff forces several refinement levels; shared grid points (the
    # domain endpoints reappear in the halves) must be measured only once
    fn, counts = counting_sample_fn(kinked)
    pieces = refine(Domain((32,), (256,)), fn, [(1,)], CHEAP)
    assert len(pieces) > 1
    assert counts and all(c == 1 for c in counts.values())


def test_refine_known_points_served_without_sampling():
    dom = Domain((32,), (256,))
    # pre-measure exactly the root grid the cheap config will request
    grid = grid_points(dom, [2 + CHEAP.oversampling], kind=CHEAP.grid,
                       round_to=CHEAP.round_to)
    known = {p: Stats.from_samples([LINEAR(p)]) for p in grid}
    fn, counts = counting_sample_fn(LINEAR)
    pieces = refine(dom, fn, [(1,)], CHEAP, known=known)
    # the linear curve converges at the root -> zero fresh measurements
    assert len(pieces) == 1
    assert counts == {}


# ----------------------------------------------------------------- budget --


def test_max_points_budget_stops_refinement():
    dom = Domain((32,), (256,))
    fn, counts = counting_sample_fn(kinked)
    budget = 3  # the cheap root grid is exactly 3 points
    config = GeneratorConfig(**{**CHEAP.__dict__, "max_points": budget})
    pieces = refine(dom, fn, [(1,)], config)
    # the root fit misses the cliff, but the budget forbids splitting
    assert len(pieces) == 1
    assert sum(counts.values()) == budget


def test_known_points_do_not_consume_budget():
    dom = Domain((32,), (256,))
    grid = grid_points(dom, [3], kind="cartesian", round_to=8)
    known = {p: Stats.from_samples([kinked(p)]) for p in grid}
    fn, counts = counting_sample_fn(kinked)
    config = GeneratorConfig(**{**CHEAP.__dict__, "max_points": 6})
    pieces = refine(dom, fn, [(1,)], config, known=known)
    # the root grid came for free, so the budget still allows splitting
    assert len(pieces) > 1
    assert 0 < sum(counts.values()) <= config.max_points


@pytest.mark.parametrize("budget", [4, 5, 7, 10])
def test_max_points_budget_is_a_hard_bound(budget):
    """Fresh points never exceed the budget once the root grid is in, and
    the pieces still tile the whole domain: a half whose grid would
    overrun the budget keeps its parent's fit."""
    dom = Domain((32,), (256,))
    free_fn, free_counts = counting_sample_fn(kinked)
    refine(dom, free_fn, [(1,)], CHEAP)
    fn, counts = counting_sample_fn(kinked)
    config = GeneratorConfig(**{**CHEAP.__dict__, "max_points": budget})
    pieces = refine(dom, fn, [(1,)], config)
    # the budget binds: unbounded refinement samples more
    assert sum(counts.values()) <= budget < sum(free_counts.values())
    covered = sorted((p.domain.lo[0], p.domain.hi[0]) for p in pieces)
    assert covered[0][0] == 32 and covered[-1][1] == 256
    assert all(a[1] <= b[0] + 8 for a, b in zip(covered, covered[1:]))


# ---------------------------------------------------------- determinism ----


def test_refine_point_ordering_deterministic():
    runs = []
    for _ in range(2):
        log = []
        pieces = refine(Domain((32,), (256,)), analytic_sample_fn(kinked, log),
                        [(1,)], CHEAP)
        runs.append((log, pieces))
    (log_a, pieces_a), (log_b, pieces_b) = runs
    assert log_a == log_b  # identical batches, in identical order
    assert len(pieces_a) == len(pieces_b)
    for pa, pb in zip(pieces_a, pieces_b):
        assert pa.domain == pb.domain
        for s in STATS:
            assert pa.polys[s].coeffs.tolist() == pb.polys[s].coeffs.tolist()


# --------------------------------------------------- Stats.from_samples ----


def test_stats_single_sample():
    s = Stats.from_samples([3.5e-6])
    assert s.min == s.med == s.max == s.mean == 3.5e-6
    assert s.std == 0.0


def test_stats_zero_variance():
    s = Stats.from_samples([2e-6] * 7)
    assert s.min == s.med == s.max == s.mean == 2e-6
    assert s.std == 0.0


def test_stats_empty_raises():
    with pytest.raises(ValueError):
        Stats.from_samples([])


def test_stats_even_count_median_interpolates():
    s = Stats.from_samples([1.0, 2.0, 3.0, 4.0])
    assert s.med == 2.5
    assert s.min == 1.0 and s.max == 4.0 and s.mean == 2.5
