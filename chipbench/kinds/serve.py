"""Drive a served model: open-loop arrivals on ``ServeEngine``.

Set-up builds the program's configuration from the sizes file, makes the
weights on the device from the seed (the reference's initializers), builds
the model-guided scheduler (``ModelGuidedScheduler`` over the
``StepCostModel`` that ``PredictorSession`` measures), compiles the
engine's programs on a few short requests, and serves the mix's ramp.

The loop is a copy of ``repro.serve.scheduler.serve_loop`` that takes
requests as they arrive: ``serve_loop`` serves a fixed list.  It calls
the engine's step hooks as ``serve_loop`` does (``plan``,
``begin_prefill``, ``advance``), sends each request at its scheduled
time (``generator.schedule``), and stamps each output token when the step
that made it returns.  A request's time to first token runs from its
scheduled arrival; the part of it spent waiting to be admitted to a slot
is noted apart (``notes.wait_p50_s``; its 90th percentile is the counter
``wait_p90_s``, which ``serve.slot_wait_p90_ms`` reads).

After the window, a sample of the requests sent in it goes through the
plain reference: the mix's longest and every ``check_every``-th from an
offset drawn from the seed, marked before they are sent, so that the
engine keeps their logits.  Two numbers decide ``correct``: how far the
served logits lie from the reference's (``logit_err``), and the widest
gap by which a served token's reference logit lies below the reference's
best at its position (``served_gap``).
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List, Sequence, Set

import numpy as np

from common import (Check, CompileCounter, Outcome, Spans, device_info,
                    host_rng, jax_seed, load_module, percentile,
                    recorded_trace, reference_path)
from generator import Arrival, expect, longest_request, schedule

#: keys of the sizes file that the program's configuration takes as is
ARCH_KEYS = ("n_layers", "d_model", "vocab", "ssm_state", "ssm_heads",
             "ssm_head_dim", "ssm_groups", "ssm_expand", "ssm_chunk")
#: fused steps recorded under the profiler in a traced run
TRACED_STEPS = 60
#: the reference's sequence length is rounded up to a multiple of this,
#: so that one compiled reference serves every run of a mix
REF_ALIGN = 64
#: seconds the loop may run on after the window for every request sent in
#: it to finish; a request that does not is counted as failed
DRAIN_LIMIT_S = 60.0


def program_config(sizes: Dict[str, Any]):
    """The program's configuration with the sizes file's values."""
    from repro.configs import get_config
    cfg = get_config(sizes["program_config"])
    return dataclasses.replace(cfg, **{k: sizes[k] for k in ARCH_KEYS})


class Loop:
    """Requests sent at their scheduled times, one fused step per tick."""

    def __init__(self, engine, scheduler, arrivals: Sequence[Arrival],
                 spans: Spans, marked: Set[int] = frozenset()):
        from repro.serve import Request
        self.Request = Request
        self.engine = engine
        self.scheduler = scheduler
        self.arrivals = arrivals
        self.marked = marked          # arrivals whose logits are kept
        self.spans = spans
        self.start = 0.0
        self.sent = 0
        self.waiting: List[Any] = []
        self.requests: List[Any] = []
        self.stamps: Dict[int, List[float]] = {}  # uid -> token times
        self.admitted: Dict[int, float] = {}      # uid -> time admitted
        self.lane_steps = {"prefill": 0, "decode": 0}
        self.steps = 0

    def send_due(self, now: float) -> None:
        while self.sent < len(self.arrivals) and \
                self.start + self.arrivals[self.sent].at_s <= now:
            a = self.arrivals[self.sent]
            req = self.Request(uid=self.sent, prompt=a.prompt,
                               max_new_tokens=a.max_new_tokens,
                               keep_logits=self.sent in self.marked)
            req.submitted_s = self.start + a.at_s
            req.phase = a.phase
            self.sent += 1
            self.waiting.append(req)
            self.requests.append(req)
            self.stamps[req.uid] = []

    def tick(self) -> None:
        engine, stats = self.engine, self.engine.stats
        now = time.perf_counter()
        self.send_due(now)
        if not (self.waiting or engine.active or engine.prefilling):
            if self.sent < len(self.arrivals):      # idle until the next
                due = self.start + self.arrivals[self.sent].at_s
                time.sleep(min(max(due - now, 0.0), 0.002))
            return
        with self.spans.span("plan"):
            t_plan = time.perf_counter()
            plan = self.scheduler.plan(engine, self.waiting)
            stats.tick_overhead_s += time.perf_counter() - t_plan
        stats.ticks += 1
        with self.spans.span("admit"):
            for req in plan.admit_blocking:
                if not engine.add_request(req):
                    break
                self.waiting.remove(req)
                self.admitted[req.uid] = time.perf_counter()
            for req in plan.admit_interleaved:
                if not engine.free_slots():
                    break
                engine.begin_prefill(req)
                self.waiting.remove(req)
                self.admitted[req.uid] = time.perf_counter()
        decoding = list(engine.active.values())
        self.lane_steps["prefill"] += len(engine.prefilling)
        self.lane_steps["decode"] += len(decoding)
        if not decoding and not engine.prefilling:
            return
        with self.spans.span("advance"):
            finished = engine.advance()
        self.steps += 1
        now = time.perf_counter()
        for req in decoding:
            self.stamps[req.uid].append(now)
        for req in finished:
            req.finished_s = now


def marked(arrivals: Sequence[Arrival], mix, seed: int) -> Set[int]:
    """Which requests of the window keep their logits for the check: the
    mix's ``check_longest`` longest, and every ``check_every``-th from an
    offset drawn from the seed.  A kept token pins its step's whole
    output on the device, so the sample is marked before it is sent, not
    chosen from every request after the window."""
    window = [i for i, a in enumerate(arrivals) if a.phase == "window"]
    every = mix["check_every"]
    offset = int(host_rng(seed, "checked").integers(every))
    longest = sorted(window, key=lambda i: (
        -len(arrivals[i].prompt) - arrivals[i].max_new_tokens, i))
    return set(window[offset::every]) | set(longest[:mix["check_longest"]])


def _warm_up(engine, scheduler, cfg, spans: Spans) -> None:
    """Compile every program the window calls: the step, the slot reset
    and the argmax, with every slot busy, on requests of three tokens."""
    rng = np.random.default_rng(0)
    loop = Loop(engine, scheduler, [
        Arrival(at_s=0.0, prompt=rng.integers(0, cfg.vocab, 3,
                                              dtype=np.int32),
                max_new_tokens=2, phase="ramp")
        for _ in range(engine.slots)], spans)
    loop.start = time.perf_counter()
    loop.send_due(loop.start)
    while loop.waiting or engine.active or engine.prefilling:
        loop.tick()


def build(config, sizes, seed: int, spans: Spans):
    """The reference, the program's configuration, weights made from the
    seed, the scheduler and a warmed-up engine."""
    import jax
    from repro.models import init_params
    from repro.serve import ServeEngine
    from repro.serve.engine import EngineStats
    from repro.tc import PredictorSession

    cfg = program_config(sizes)
    dtype = getattr(jax.numpy, sizes["dtype"])
    shapes = jax.eval_shape(lambda: init_params(
        cfg, jax.random.PRNGKey(0), dtype=dtype))
    ref = load_module(reference_path(config))
    params = ref.make_params(sizes, shapes, jax_seed(seed, "weights"))
    jax.block_until_ready(params)
    scheduler = PredictorSession().guided_scheduler(cfg, slots=sizes["slots"])
    engine = ServeEngine(cfg, params, batch_slots=sizes["slots"],
                         ctx_len=sizes["ctx_len"], dtype=dtype,
                         matmul_precision=sizes["matmul_precision"])
    _warm_up(engine, scheduler, cfg, spans)
    engine.stats = EngineStats()
    return ref, cfg, params, engine, scheduler


def run(*, cell, config, sizes, mix, seed: int, seconds: float, trace: bool,
        t_start: float, hold: bool = False) -> Outcome:
    """One run of the cell; ``hold`` keeps the weights and the checked
    requests on the outcome (``held``) for the control's readings
    (``chipbench/controls.py``)."""
    from repro.serve.engine import EngineStats

    expect(mix, "open_loop")
    spans = Spans()
    compiles = CompileCounter()
    notes: Dict[str, Any] = {}
    ref, cfg, params, engine, scheduler = build(config, sizes, seed, spans)

    arrivals = schedule(mix, cfg.vocab, seed, seconds)
    loop = Loop(engine, scheduler, arrivals, spans,
                marked(arrivals, mix, seed))
    loop.start = time.perf_counter()
    while time.perf_counter() < loop.start + mix["ramp_s"]:       # ramp
        loop.tick()

    # ------------------------------------------------------------ window --
    spans.seconds.clear()
    engine.stats = EngineStats()
    loop.lane_steps = {"prefill": 0, "decode": 0}
    loop.steps = 0
    compiles.active = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    while time.perf_counter() - t0 < seconds:
        loop.tick()
    t1 = time.perf_counter()
    compiles.active = False
    window_s = t1 - t0
    counters: Dict[str, Any] = {
        "window_s": window_s, "steps": loop.steps,
        "lane_steps": dict(loop.lane_steps),
        "ticks": engine.stats.ticks,
        "tick_overhead_s": engine.stats.tick_overhead_s,
        "compiles_in_window": compiles.count}

    reduced = None
    if trace:
        loop.steps = 0
        with recorded_trace(spans, cell["name"]) as rec:
            while loop.steps < TRACED_STEPS:
                loop.tick()
        reduced = rec["reduced"]
        counters["traced_steps"] = loop.steps

    # drain: the load goes on (the mix's tail) until every request sent in
    # the window has finished
    sent = sum(1 for a in arrivals if a.phase == "window")
    t_drain = time.perf_counter()
    while time.perf_counter() - t_drain < DRAIN_LIMIT_S:
        in_window = [r for r in loop.requests if r.phase == "window"]
        if len(in_window) == sent and all(r.done for r in in_window):
            break
        loop.tick()

    tokens = sum(1 for r in loop.requests for t in loop.stamps[r.uid]
                 if t0 < t <= t1)
    ttft = [loop.stamps[r.uid][0] - r.submitted_s for r in in_window
            if loop.stamps[r.uid]]
    itl = [b - a for r in loop.requests
           for a, b in zip(loop.stamps[r.uid], loop.stamps[r.uid][1:])
           if t0 < b <= t1]
    waits = [loop.admitted[r.uid] - r.submitted_s for r in in_window
             if r.uid in loop.admitted]
    done = [r for r in in_window if r.done]
    failed = sent - len(done)
    counters["wait_p90_s"] = percentile(waits, 90) if waits else None
    e2e = {"serve_out_tokens_per_s": tokens / window_s,
           "serve_ttft_p50_s": percentile(ttft, 50) if ttft else float("inf"),
           "serve_ttft_p90_s": percentile(ttft, 90) if ttft else float("inf"),
           "serve_itl_p90_ms": 1e3 * percentile(itl, 90),
           "setup_s": setup_s}

    device = device_info(cell["chips"])
    step_model_ms = {cls: 1e3 * scheduler.model.tick_cost(sizes["slots"], cls)
                     for cls in ("warm", "cold")}
    t_check = time.perf_counter()
    checked = sample_requests(done)
    served = served_rows(checked)
    # free the program's state before the reference runs
    engine.caches = None
    for r in loop.requests:
        r.out_logits = []
    del engine, scheduler, loop
    gc.collect()
    check = Check()
    numbers = check_served(ref, sizes, params, checked, served, mix)
    check.add("logit_err", numbers["logit_err"], ref.LOGIT_LIMIT)
    check.add("served_gap", numbers["served_gap"], ref.GAP_LIMIT)
    notes.update(requests=sent, finished=len(done), checked=len(checked),
                 checked_tokens=numbers["tokens"], logit=numbers["logit"],
                 compiles_in_window=compiles.count,
                 ttft_samples=len(ttft), itl_samples=len(itl),
                 wait_p50_s=percentile(waits, 50) if waits else None,
                 step_model_ms=step_model_ms,
                 drain_s=t_check - t_drain,
                 check_s=time.perf_counter() - t_check,
                 run_s=time.perf_counter() - t_start)
    outcome = Outcome(e2e=e2e, counters=counters, check=check,
                      attempted=sent, failed=failed, device=device,
                      spans=spans, sizes=sizes, reduced=reduced, notes=notes)
    if hold:
        outcome.held = {"ref": ref, "sizes": sizes, "params": params,
                        "requests": checked, "mix": mix}
    return outcome


def sample_requests(done: List[Any]) -> List[Any]:
    """The finished requests that kept their logits, the longest first."""
    kept = [r for r in done if r.keep_logits]
    return sorted(kept, key=lambda r: (-len(r.prompt) - len(r.out_tokens),
                                       r.uid))


def served_rows(reqs: List[Any]) -> np.ndarray:
    """(tokens, vocab) float32: the logits each served token was chosen
    from, copied to the host."""
    import jax.numpy as jnp
    if not reqs:
        return np.zeros((0, 0), np.float32)
    rows = [lg[slot, 0] for r in reqs for lg, slot in r.out_logits]
    return np.asarray(jnp.stack(rows))


def reference_batch(reqs: List[Any], length: int):
    """Token rows (prompt, then every served token but the last), padded
    to ``length``, and the (row, position, served token) of every served
    token."""
    seqs = np.zeros((len(reqs), length), np.int32)
    rows, positions, served = [], [], []
    for i, r in enumerate(reqs):
        s = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1],
                                                  np.int32)])
        seqs[i, :len(s)] = s
        for j, tok in enumerate(r.out_tokens):
            rows.append(i)
            positions.append(len(r.prompt) - 1 + j)
            served.append(tok)
    return seqs, (np.asarray(rows), np.asarray(positions),
                  np.asarray(served, np.int32))


def ref_length(mix) -> int:
    return -(-longest_request(mix) // REF_ALIGN) * REF_ALIGN


def logit_numbers(got, want) -> Dict[str, float]:
    """How far the rows ``got`` lie from ``want`` (tokens, vocab): per row
    max |got - want| / max |want|, its largest (``row_max``) and its mean
    (``row_mean``) over rows; and max |got - want| over max |want| over
    every row (``global``)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    diff = np.max(np.abs(got - want), axis=-1)
    scale = np.max(np.abs(want), axis=-1)
    rows = diff / scale
    return {"row_max": float(np.max(rows)), "row_mean": float(np.mean(rows)),
            "global": float(np.max(diff) / np.max(scale))}


def check_served(ref, sizes, params, reqs, served, mix) -> Dict[str, Any]:
    """The reference over every checked request's prompt and served
    tokens: how far the served logits lie from its logits (``logit_err``),
    and the widest gap by which a served token's logit lies below its
    best (``served_gap``)."""
    import jax.numpy as jnp
    if not reqs:
        return {"logit_err": float("inf"), "served_gap": float("inf"),
                "tokens": 0, "logit": {}}
    seqs, (rows, positions, tokens) = reference_batch(reqs, ref_length(mix))
    logits = ref.forward(sizes, params, jnp.asarray(seqs),
                         sizes["matmul_precision"])
    want = np.asarray(logits[rows, positions])
    gap = ref.widest_gap(logits, rows, positions, jnp.asarray(tokens))
    numbers = logit_numbers(served, want)
    return {"logit_err": numbers[ref.LOGIT_NUMBER], "served_gap": gap,
            "tokens": len(tokens), "logit": numbers}


def control(held, precision: str = "high") -> Dict[str, float]:
    """The control's numbers: the reference at ``precision`` (the
    precision below the configuration's) in the program's place, at the
    checked positions: its logits against the reference's, and the widest
    gap, under the reference, of the tokens it puts first."""
    import jax.numpy as jnp
    ref, sizes = held["ref"], held["sizes"]
    if not held["requests"]:
        raise ValueError("no checked request finished in the window")
    seqs, (rows, positions, _) = reference_batch(held["requests"],
                                                 ref_length(held["mix"]))
    tokens = jnp.asarray(seqs)
    low = ref.forward(sizes, held["params"], tokens, precision)
    low_rows = np.asarray(low[rows, positions])
    low_top = ref.top_tokens(low, rows, positions)
    del low
    want = ref.forward(sizes, held["params"], tokens,
                       sizes["matmul_precision"])
    numbers = logit_numbers(low_rows, np.asarray(want[rows, positions]))
    return {"logit_err": numbers[ref.LOGIT_NUMBER],
            "served_gap": ref.widest_gap(want, rows, positions, low_top),
            **{f"logit.{k}": v for k, v in numbers.items()}}
