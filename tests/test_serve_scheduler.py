"""Tests for repro.serve.scheduler: decision logic on scripted step-cost
models, FIFO equivalence with the pre-refactor engine loop, interleaved
prefill correctness, stats synchronization, and the tick-overhead budget."""

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import init_params
from repro.serve import (FifoScheduler, ModelGuidedScheduler, Plan, Request,
                         ServeEngine, StepCostModel)
from repro.tc.suite import COLD, WARM


def scripted_model(slots, *, warm=1.0, cold=None, per_occ=None):
    """A StepCostModel with scripted (not measured) tick costs."""
    cold = warm if cold is None else cold
    tick_s = {}
    for occ in range(1, slots + 1):
        w = per_occ[occ - 1] if per_occ is not None else warm
        tick_s[(occ, WARM)] = w
        tick_s[(occ, COLD)] = cold if per_occ is None else w
    return StepCostModel(tick_s=tick_s, slots=slots)


class FakeEngine:
    """Duck-typed engine state for pure decision tests (no jax)."""

    def __init__(self, slots):
        self.slots = slots
        self.active = {}
        self.prefilling = {}
        self.prefill_done = {}

    def free_slots(self):
        return [s for s in range(self.slots)
                if s not in self.active and s not in self.prefilling]


def req(uid, prompt_len=4, max_new=8):
    return Request(uid=uid,
                   prompt=np.ones(prompt_len, dtype=np.int32),
                   max_new_tokens=max_new)


# ----------------------------------------------------- decision logic --

def test_plan_trivial_cases():
    sched = ModelGuidedScheduler(scripted_model(2))
    eng = FakeEngine(2)
    assert sched.plan(eng, []) == Plan()          # nothing waiting
    eng.active = {0: req(0), 1: req(1)}
    assert sched.plan(eng, [req(2)]) == Plan()    # no free slot


def test_idle_engine_admits_immediately():
    # ties between defer and admit must admit: an idle engine with one
    # waiting request serves it NOW, not after max_defer passes
    sched = ModelGuidedScheduler(scripted_model(2))
    eng = FakeEngine(2)
    r = req(0)
    plan = sched.plan(eng, [r])
    assert plan.admit_interleaved == (r,)


def test_shortest_job_admitted_first():
    # one free slot, two waiting: admitting the shorter request first
    # minimizes the predicted sum of completion times
    sched = ModelGuidedScheduler(scripted_model(2))
    eng = FakeEngine(2)
    eng.active = {0: req(9, max_new=50)}
    long_req = req(1, prompt_len=40, max_new=16)
    short_req = req(2, prompt_len=4, max_new=4)
    plan = sched.plan(eng, [long_req, short_req])
    assert plan.admit_interleaved == (short_req,)


def test_defer_when_occupancy_is_expensive():
    # scripted occupancy-dependent costs: adding a lane makes every tick
    # 50x more expensive, so deferring wins while a lane is busy
    sched = ModelGuidedScheduler(
        scripted_model(2, per_occ=[1.0, 50.0]), max_defer=3)
    eng = FakeEngine(2)
    eng.active = {0: req(9, max_new=3)}
    r = req(1, prompt_len=2, max_new=2)
    assert sched.plan(eng, [r]) == Plan()


def test_force_admit_bounds_starvation():
    sched = ModelGuidedScheduler(
        scripted_model(2, per_occ=[1.0, 50.0]), max_defer=3)
    eng = FakeEngine(2)
    eng.active = {0: req(9, max_new=3)}
    r = req(1, prompt_len=2, max_new=2)
    for _ in range(3):
        assert sched.plan(eng, [r]) == Plan()
    plan = sched.plan(eng, [r])
    assert plan.admit_interleaved == (r,)


def test_model_tick_cost_clamps_occupancy():
    model = scripted_model(2, warm=1.0, cold=3.0)
    assert model.tick_cost(0) == model.tick_cost(1)
    assert model.tick_cost(99) == model.tick_cost(2)
    assert model.tick_cost(1, COLD) == 3.0
    assert model.service_ticks(req(0, prompt_len=5, max_new=7)) == 12


def test_tick_overhead_stays_sub_ms():
    # planning is dict lookups plus a bounded rollout: one plan() runs at
    # most window + 1 rollouts (defer + one per candidate), each at most
    # `horizon` simulated ticks — counted, not wall-clocked
    sched = ModelGuidedScheduler(scripted_model(4), window=4, horizon=64)
    eng = FakeEngine(4)
    eng.active = {0: req(90, max_new=32), 1: req(91, max_new=7)}
    waiting = [req(i, prompt_len=4 + 11 * (i % 4), max_new=8)
               for i in range(8)]
    n = 200
    for _ in range(n):
        sched.plan(eng, waiting)
    assert 0 < sched.rollouts <= n * (1 + sched.window)
    assert 0 < sched.rollout_ticks <= sched.rollouts * sched.horizon


# ------------------------------------------------- engine equivalence --

CFG = reduced(get_config("deepseek-7b"), n_layers=2, d_model=64, d_ff=128,
              vocab=128)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _engine(params):
    return ServeEngine(CFG, params, batch_slots=3, ctx_len=64)


def _trace(n=5):
    rng = np.random.default_rng(3)
    return [Request(uid=i,
                    prompt=rng.integers(1, CFG.vocab,
                                        size=int(rng.integers(2, 9))
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 6)))
            for i in range(n)]


def test_requests_keep_no_logits_unless_asked(params):
    """A served request holds no past step's logits on the device unless
    it sets ``keep_logits``; one that does keeps one row per token."""
    reqs = _trace()
    reqs[0].keep_logits = True
    _engine(params).run(reqs)
    assert all(r.done for r in reqs)
    assert len(reqs[0].out_logits) == len(reqs[0].out_tokens)
    assert all(r.out_logits == [] for r in reqs[1:])


def test_fifo_policy_matches_legacy_loop(params):
    fifo = _engine(params)
    reqs = _trace()
    fifo.run(reqs, scheduler=FifoScheduler())

    # the pre-refactor loop, driven by hand through the step hooks, on an
    # identical trace — same admissions, same steps, same tokens
    legacy = _engine(params)
    reqs2 = _trace()
    queue = list(reqs2)
    while queue or legacy.active:
        while queue and legacy.add_request(queue[0]):
            queue.pop(0)
        legacy.advance()

    assert {r.uid: r.out_tokens for r in reqs} == \
        {r.uid: r.out_tokens for r in reqs2}
    assert all(r.done for r in reqs)


def test_interleaved_prefill_matches_blocking_for_lone_request(params):
    # a lone request prefilled one token per fused step produces exactly
    # the tokens the blocking prefill produces
    blocking = _engine(params)
    r1 = _trace(1)[0]
    blocking.add_request(r1)
    while blocking.active:
        blocking.advance()

    interleaved = _engine(params)
    r2 = _trace(1)[0]
    interleaved.begin_prefill(r2)
    while interleaved.active or interleaved.prefilling:
        interleaved.advance()

    assert r2.out_tokens == r1.out_tokens
    assert r2.done


def test_begin_prefill_rejects_busy_slot(params):
    eng = _engine(params)
    r1, r2 = _trace(2)
    slot = eng.begin_prefill(r1)
    with pytest.raises(ValueError, match="not free"):
        eng.begin_prefill(r2, slot=slot)
    eng.prefilling.clear()
    eng.prefill_done.clear()
    eng.active = {s: r1 for s in range(eng.slots)}
    with pytest.raises(ValueError, match="free slot"):
        eng.begin_prefill(r2)


def test_stats_synchronized_and_latencies_tracked(params):
    eng = _engine(params)
    reqs = _trace(4)
    stats = eng.run(reqs, scheduler=FifoScheduler())
    assert stats.prefill_s > 0.0
    assert stats.decode_s > 0.0
    assert stats.ticks > 0
    assert len(stats.latencies_s) == len(reqs)
    assert all(lat > 0 for lat in stats.latencies_s)
    assert stats.latency_ms(99) >= stats.latency_ms(50) > 0.0
    for r in reqs:
        assert r.latency_s is not None and r.latency_s > 0


def test_guided_run_serves_everything(params):
    eng = _engine(params)
    sched = ModelGuidedScheduler(
        scripted_model(3, warm=1e-3, cold=2e-3))
    reqs = _trace(6)
    stats = eng.run(reqs, scheduler=sched)
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    assert stats.ticks > 0
    assert sched.rollouts <= stats.ticks * (1 + sched.window)


def test_served_logits_match_forward_across_slot_reuse():
    """Every served token's logits equal the reference forward pass over
    prompt + output, for a pure-SSM model whose requests outnumber the
    slots: a reused slot starts from a reset state, and prefill lanes
    interleave with decode lanes."""
    from repro.models import forward

    cfg = reduced(get_config("mamba2-2.7b"))
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=np.float32)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, max_new_tokens=4, keep_logits=True,
                    prompt=rng.integers(0, cfg.vocab, int(rng.integers(2, 10)),
                                        dtype=np.int32))
            for i in range(5)]
    eng = ServeEngine(cfg, params, batch_slots=2, ctx_len=32)
    eng.run(reqs, scheduler=ModelGuidedScheduler(scripted_model(2)))

    seqs = np.zeros((len(reqs), 32), np.int32)
    for i, r in enumerate(reqs):
        s = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1], np.int32)])
        seqs[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(cfg, params, seqs))
    for i, r in enumerate(reqs):
        p = len(r.prompt)
        served = np.stack([np.asarray(lg[slot, 0]) for lg, slot in r.out_logits])
        # float32 throughout on the CPU: recurrence vs chunked scan only
        np.testing.assert_allclose(served, want[i, p - 1:p - 1 + 4],
                                   rtol=1e-4, atol=1e-4)
