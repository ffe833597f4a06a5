"""Drive a blocked dense linear algebra configuration: predict, pick, run.

Set-up loads the kernel models from the checkout's model store, or
generates them on the chip with the configuration's generator settings
and stores them (the first run in a checkout; the generation is left out
of ``setup_s`` and reported as ``notes.model_generation_s``), makes one
input per size from the seed, and runs each size's pick once, so that
every program the window calls is compiled.

The window is a closed stream of problems: for each, the program's
selection (``core.selection.optimize_algorithm_and_block_size``) picks
the variant and block size from the models, and the pick runs on
``dla.engine.ExecEngine``.  The end-to-end metric is the useful FLOPs of
every problem solved over the window.  After the window two numbers
decide ``correct``: the factors of the first problem of each size (the
seed draws their order) against the plain reference (``factor_err``),
and the selection (``pick_regret``): every candidate is timed on the same
matrix at ``regret_size`` to find the fastest, and the window's pick is
then timed head to head against that fastest, in ``regret_pairs``
back-to-back pairs, each timing ``regret_calls`` runs, with the garbage
collector paused; the regret is the median of the pairs' ratios, less
one.  A traced run also records a few
more problems under the profiler, before the check.
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

from common import (STATE, Check, CompileCounter, Outcome, Spans,
                    reference_path, device_info, jax_seed, load_module,
                    recorded_trace)
from generator import expect, problem_sizes

#: problems recorded under the profiler in a traced run: two rounds of
#: every size
TRACED_ROUNDS = 2


def kernel_box(tracers, sizes, block_sizes) -> Dict[Tuple, Tuple]:
    """{(kernel, case): (lo, hi)}: the box of non-degenerate sizes each
    kernel case of ``tracers`` takes over every size and block size."""
    box: Dict[Tuple, Tuple] = {}
    for tracer in tracers.values():
        for n in sizes:
            for b in block_sizes:
                for call in tracer(n, b):
                    if min(call.sizes) == 0:
                        continue
                    lo, hi = box.get((call.kernel, call.case),
                                     (call.sizes, call.sizes))
                    box[(call.kernel, call.case)] = (
                        tuple(map(min, lo, call.sizes)),
                        tuple(map(max, hi, call.sizes)))
    return box


def load_models(name: str, sizes: Dict[str, Any], tracers):
    """The configuration's kernel models: from the store, or generated
    on this chip and stored.  Returns (models, seconds generating)."""
    from repro.core import GeneratorConfig, KernelBenchmark, generate_model
    from repro.core.grids import Domain
    from repro.core.model import ModelSet
    from repro.dla.kernels import KERNELS
    from repro.store import ModelStore

    from repro.store.modelstore import StoreMismatchError
    path = STATE / "models" / f"{name}.json"
    if path.exists():
        try:
            return ModelStore.load(path).model_set(name), 0.0
        except StoreMismatchError:
            pass                     # measured on another platform
    t0 = time.perf_counter()
    config = GeneratorConfig(**sizes["generator"])
    models = ModelSet()
    for (kernel, case), (lo, hi) in sorted(kernel_box(
            tracers, sizes["sizes"], sizes["block_sizes"]).items()):
        kd = KERNELS[kernel]
        bench = KernelBenchmark(name=kernel, cases=(case,),
                                domain=Domain(lo, hi),
                                cost_exponents=kd.cost_exponents,
                                make_call=kd.make_call)
        model, _ = generate_model(bench, config)
        models.add(model)
    store = ModelStore()
    store.add_model_set(name, models)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    store.save(tmp)
    tmp.replace(path)
    return ModelStore.load(path).model_set(name), time.perf_counter() - t0


class Solver:
    """The program's product path for one configuration: select, then
    execute the pick."""

    def __init__(self, sizes: Dict[str, Any], models, spans: Spans):
        from repro.dla import blocked, tracers
        self.tracers = getattr(tracers, sizes["tracers"])
        self.algorithm = getattr(blocked, sizes["algorithm"])
        self.prefix = sizes["algorithm"]
        self.block_sizes = list(sizes["block_sizes"])
        self.models = models
        self.spans = spans
        self.calls: List[Tuple[int, str, int]] = []   # (n, variant, b)

    def select(self, n: int) -> Tuple[str, int]:
        from repro.core.selection import optimize_algorithm_and_block_size
        with self.spans.span("select"):
            name, b, _ = optimize_algorithm_and_block_size(
                self.tracers, self.models, n, self.block_sizes)
        return name, b

    def execute(self, a: np.ndarray, name: str, b: int) -> np.ndarray:
        from repro.dla import ExecEngine
        n = a.shape[0]
        with self.spans.span("execute"):
            eng = ExecEngine()
            mat = eng.bind("A", a)
            self.algorithm(eng, mat, n, b, int(name[len(self.prefix):]))
        self.calls.append((n, name, b))
        return eng.mats["A"]

    def solve(self, a: np.ndarray) -> np.ndarray:
        name, b = self.select(a.shape[0])
        return self.execute(a, name, b)


@contextlib.contextmanager
def collector_paused() -> Iterator[None]:
    """Collect garbage, then keep the collector paused while the block
    times its calls; it is switched on again however the block ends."""
    gc.collect()
    was_on = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was_on:
            gc.enable()


def timed(solver: Solver, a: np.ndarray, cand: Tuple[str, int],
          calls: int) -> float:
    """Seconds of one run of ``cand`` on ``a``: the mean of ``calls`` runs
    back to back, so that the host clock's error is a small part of the
    time it reads (one run at n=2048 takes about 6 ms on a TPU v5e)."""
    t0 = time.perf_counter()
    for _ in range(calls):
        solver.execute(a, *cand)
    return (time.perf_counter() - t0) / calls


def sweep(solver: Solver, a: np.ndarray, repetitions: int,
          calls: int) -> Dict[Tuple[str, int], float]:
    """Median seconds of every candidate on ``a`` over ``repetitions``
    round-robin rounds, after one run of each that loads its programs."""
    cands = [(name, b) for name in solver.tracers for b in solver.block_sizes]
    for c in cands:
        solver.execute(a, *c)
    times: Dict[Tuple[str, int], List[float]] = {c: [] for c in cands}
    for _ in range(repetitions):
        for c in cands:
            times[c].append(timed(solver, a, c, calls))
    return {c: float(np.median(v)) for c, v in times.items()}


def head_to_head(solver: Solver, a: np.ndarray, pick: Tuple[str, int],
                 fastest: Tuple[str, int], pairs: int,
                 calls: int) -> List[float]:
    """``pairs`` ratios of the pick's seconds over the fastest's on ``a``,
    each of two timings back to back, which of them goes first
    alternating."""
    ratios = []
    for i in range(pairs):
        if i % 2:
            t_fast = timed(solver, a, fastest, calls)
            t_pick = timed(solver, a, pick, calls)
        else:
            t_pick = timed(solver, a, pick, calls)
            t_fast = timed(solver, a, fastest, calls)
        ratios.append(t_pick / t_fast)
    return ratios


def pair_regret(solver: Solver, a: np.ndarray, pick: Tuple[str, int],
                fastest: Tuple[str, int], pairs: int,
                calls: int) -> Dict[str, Any]:
    """The regret of ``pick`` against ``fastest``: the median of their
    head-to-head ratios, less one; 0 where the pick is the fastest, or
    where it beats the sweep's fastest head to head (it is then the
    faster of the two)."""
    if tuple(pick) == tuple(fastest):
        return {"regret": 0.0, "ratios": []}
    with collector_paused():
        ratios = head_to_head(solver, a, pick, fastest, pairs, calls)
    return {"regret": max(0.0, float(np.median(ratios)) - 1.0),
            "ratios": ratios}


def regret(solver: Solver, a: np.ndarray, pick: Tuple[str, int],
           sizes: Dict[str, Any]) -> Dict[str, Any]:
    """The fastest of every candidate on ``a`` (``sweep``), and the regret
    of ``pick`` against it, timed head to head (``pair_regret``), with the
    configuration's ``regret_repetitions``, ``regret_pairs`` and
    ``regret_calls``."""
    calls = sizes["regret_calls"]
    with collector_paused():
        seconds = sweep(solver, a, sizes["regret_repetitions"], calls)
    fastest = min(seconds, key=seconds.get)
    return {"pick": list(pick), "fastest": list(fastest),
            **pair_regret(solver, a, pick, fastest, sizes["regret_pairs"],
                          calls),
            "seconds": {f"{n}/{b}": t for (n, b), t in seconds.items()}}


def run(*, cell, config, sizes, mix, seed: int, seconds: float, trace: bool,
        t_start: float, hold: bool = False) -> Outcome:
    """One run of the cell; ``hold`` keeps its inputs on the outcome
    (``held``) for the control's readings (``chipbench/controls.py``)."""
    expect(mix, "closed_stream")
    ref = load_module(reference_path(config))
    spans = Spans()
    compiles = CompileCounter()
    notes: Dict[str, Any] = {}

    from repro.dla import tracers
    models, generation_s = load_models(
        config["name"], sizes, getattr(tracers, sizes["tracers"]))
    notes["model_generation_s"] = generation_s
    solver = Solver(sizes, models, spans)
    inputs = ref.make_inputs(sizes["sizes"], jax_seed(seed, "inputs"))
    for n in sizes["sizes"]:                     # compile every pick
        solver.solve(inputs[n])
    stream = problem_sizes(sizes["sizes"], seed)
    # the first round: every size once, in the order the seed drew
    checked = set(range(sizes["checked_rounds"] * len(sizes["sizes"])))
    spans.seconds.clear()
    solver.calls.clear()

    # ------------------------------------------------------------ window --
    answers: Dict[int, Tuple[int, np.ndarray]] = {}
    flops = 0.0
    done = 0
    compiles.active = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start - generation_s
    while True:
        n = next(stream)
        out = solver.solve(inputs[n])
        if done in checked:
            answers[done] = (n, ref.answer(out))
        flops += ref.useful_flops(n)
        done += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    compiles.active = False
    picks = {n: (name, b) for n, name, b in solver.calls}
    counters: Dict[str, Any] = {
        "problems": done, "window_s": elapsed,
        "select_s": list(spans.seconds.get("select", [])),
        "compiles_in_window": compiles.count}

    reduced = None
    if trace:
        solver.calls.clear()
        with recorded_trace(spans, cell["name"]) as rec:
            for _ in range(TRACED_ROUNDS * len(sizes["sizes"])):
                solver.solve(inputs[next(stream)])
        reduced = rec["reduced"]
        counters["traced_calls"] = list(solver.calls)

    device = device_info(cell["chips"])
    t_check = time.perf_counter()
    check = Check()
    n = sizes["regret_size"]
    if n in picks:
        counters["regret"] = regret(solver, inputs[n], picks[n], sizes)
        notes["regret"] = dict(counters["regret"],
                               s=time.perf_counter() - t_check)
    check.add("pick_regret", counters["regret"]["regret"]
              if n in picks else float("inf"), sizes["regret_limit"])
    missing = sorted(checked - set(answers))
    worst = max((ref.error(got, ref.reference(inputs[n]))
                 for n, got in answers.values()), default=float("inf"))
    check.add("factor_err", worst if not missing else float("inf"),
              ref.FACTOR_LIMIT)
    notes.update(problems=done, checked=len(answers),
                 compiles_in_window=compiles.count,
                 check_s=time.perf_counter() - t_check,
                 run_s=time.perf_counter() - t_start)
    outcome = Outcome(
        e2e={"blocked_gflop_per_s": flops / elapsed / 1e9,
             "setup_s": setup_s},
        counters=counters, check=check, attempted=done, failed=0,
        device=device, spans=spans, sizes=sizes, reduced=reduced,
        notes=notes)
    if hold:
        outcome.held = {"ref": ref, "inputs": [inputs[n] for n, _ in
                                               answers.values()],
                        "solver": solver, "sizes": sizes,
                        "regret_input": inputs.get(sizes["regret_size"]),
                        "regret": counters.get("regret")}
    return outcome


def control(held) -> Dict[str, float]:
    """The control's number: the factor error of the reference computed
    in the precision below the configuration's, on the checked inputs."""
    ref = held["ref"]
    return {"factor_err": max(ref.error(ref.control(a), ref.reference(a))
                              for a in held["inputs"])}


def wrong_picks(held) -> Dict[str, Dict[str, Any]]:
    """The regret check with the window's pick at ``regret_size`` replaced
    by each other candidate in turn: each one's head-to-head regret
    against the run's fastest, its margin over the fastest in the run's
    sweep, and whether it fails the limit."""
    sizes, found = held["sizes"], held["regret"]
    fastest = tuple(found["fastest"])
    best = found["seconds"]["/".join(map(str, fastest))]
    out = {}
    for key, seconds in found["seconds"].items():
        name, b = key.split("/")
        if [name, int(b)] == found["pick"]:
            continue
        got = pair_regret(held["solver"], held["regret_input"],
                          (name, int(b)), fastest, sizes["regret_pairs"],
                          sizes["regret_calls"])
        out[key] = {"regret": got["regret"], "margin": seconds / best - 1.0,
                    "fails": got["regret"] > sizes["regret_limit"]}
    return out
