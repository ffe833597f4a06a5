"""The pick's regret, on a fake solver whose candidates take fixed times
on a fake clock: the sweep finds the fastest, the head-to-head pairs
read the known ratio, one slow pair does not move it, and the garbage
collector is switched on again however the timing ends."""

import gc

import pytest

from common import BENCH, load_module

blocked = load_module(BENCH / "kinds" / "blocked.py")

SECONDS = {("potrf1", 64): 1.30, ("potrf1", 128): 1.10,
           ("potrf2", 64): 1.20, ("potrf2", 128): 1.00}


class Clock:
    now = 0.0

    def perf_counter(self):
        return self.now


class FakeSolver:
    """Each candidate takes its seconds in ``SECONDS`` on ``clock``; the
    calls numbered in ``slow`` (counted from one) take ``by`` times as
    long."""

    def __init__(self, clock, slow=(), by=1.0, fail_at=0):
        self.tracers = {"potrf1": None, "potrf2": None}
        self.block_sizes = [64, 128]
        self.clock, self.slow, self.by, self.fail_at = clock, slow, by, fail_at
        self.calls = []

    def execute(self, a, name, b):
        self.calls.append((name, b))
        if len(self.calls) == self.fail_at:
            raise RuntimeError("the device went away")
        scale = self.by if len(self.calls) in self.slow else 1.0
        self.clock.now += SECONDS[(name, b)] * scale


#: two runs to a timing, so that one slow run is half of its timing
SIZES = {"regret_repetitions": 3, "regret_pairs": 15, "regret_calls": 2,
         "regret_limit": 0.05}
#: runs before the pairs: one of each candidate to load its programs,
#: then three rounds of every candidate
SWEEP = 4 + 3 * 4 * 2


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(blocked, "time", c)
    return c


def test_head_to_head_reads_the_known_ratio(clock):
    got = blocked.regret(FakeSolver(clock), None, ("potrf1", 128), SIZES)
    assert got["fastest"] == ["potrf2", 128]
    assert got["regret"] == pytest.approx(0.10)
    assert len(got["ratios"]) == 15
    assert got["seconds"]["potrf1/64"] == pytest.approx(1.30)


@pytest.mark.parametrize("slow", ["pick", "fastest"])
def test_one_slow_pair_does_not_move_it(clock, slow):
    # the first pair times the pick first, then the fastest
    solver = FakeSolver(clock, slow={SWEEP + (1 if slow == "pick" else 3)},
                        by=5.0)
    got = blocked.regret(solver, None, ("potrf1", 128), SIZES)
    assert max(got["ratios"]) > 1.5 or min(got["ratios"]) < 0.5
    assert got["regret"] == pytest.approx(0.10)


def test_the_fastest_pick_reads_zero_and_times_no_pairs(clock):
    solver = FakeSolver(clock)
    got = blocked.regret(solver, None, ("potrf2", 128), SIZES)
    assert got["regret"] == 0.0 and got["ratios"] == []
    assert len(solver.calls) == SWEEP


@pytest.mark.parametrize("fail_at", [5, SWEEP + 3])
def test_the_collector_is_on_again_after_a_failure(clock, fail_at):
    assert gc.isenabled()
    with pytest.raises(RuntimeError):
        blocked.regret(FakeSolver(clock, fail_at=fail_at), None,
                       ("potrf1", 128), SIZES)
    assert gc.isenabled()


def test_the_collector_is_paused_while_timing(clock):
    seen = []

    class Watching(FakeSolver):
        def execute(self, a, name, b):
            seen.append(gc.isenabled())
            super().execute(a, name, b)

    blocked.regret(Watching(clock), None, ("potrf1", 64), SIZES)
    assert seen and not any(seen)
    assert gc.isenabled()


def test_every_wrong_pick_is_read_against_the_fastest(clock):
    solver = FakeSolver(clock)
    found = blocked.regret(solver, None, ("potrf2", 128), SIZES)
    got = blocked.wrong_picks({"sizes": SIZES, "regret": found,
                               "solver": solver, "regret_input": None})
    assert set(got) == {"potrf1/64", "potrf1/128", "potrf2/64"}
    for key, r in got.items():
        name, b = key.split("/")
        want = SECONDS[(name, int(b))] / SECONDS[("potrf2", 128)] - 1.0
        assert r["regret"] == pytest.approx(want)
        assert r["margin"] == pytest.approx(want)
        assert r["fails"]


def test_the_summary_holds_every_slower_pick_to_the_limit():
    import controls
    seed = {"potrf1/64": {"regret": 0.20, "margin": 0.15, "fails": True},
            "potrf2/64": {"regret": 0.01, "margin": 0.02, "fails": False}}
    got = controls.wrong_pick_summary([seed, seed])
    assert got["every_slower_pick_fails"]
    assert got["candidates"]["potrf1/64"]["regret"] == [0.20, 0.20]
    missed = dict(seed, **{"potrf1/64": dict(seed["potrf1/64"],
                                             fails=False)})
    assert not controls.wrong_pick_summary([seed, missed])[
        "every_slower_pick_fails"]


def test_a_pick_that_beats_the_sweeps_fastest_reads_zero(clock):
    # two slow timings of the pick in the sweep make another candidate its
    # fastest; head to head the pick is the faster of the two
    solver = FakeSolver(clock, slow={4 + 7, 4 + 8 + 7}, by=3.0)
    got = blocked.regret(solver, None, ("potrf2", 128), SIZES)
    assert got["fastest"] == ["potrf1", 128]
    assert min(got["ratios"]) < 1.0 and got["regret"] == 0.0
