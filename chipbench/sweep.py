"""Find the highest rate a served cell sustains: one sweep on the chip.

    python3 chipbench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates <r> [<r> ...]

In one process: the cell's set-up once, then for each rate (requests per
second) the cell's mix at that rate, its ramp and a window of
``--seconds``, and a drain without the tail.  Each rate prints one JSON
line: requests sent in the window and finished in it per second, output
tokens per second, the time to first token (median and 90th percentile),
and the queue at the window's close.  The rate in a cell's mix is set
from these lines once, below the highest rate at which the finished
requests keep up with the sent ones; the benchmark's own runs never
sweep.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run
from common import BENCH, ROOT, Spans, load_json, load_module, percentile


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = bench_run.by_name(bench["workloads"], args.workload, "workload")
    config = bench_run.by_name(bench["configs"], cell["config"],
                               "configuration")
    bench_run.enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("the sweep runs on a TPU", file=sys.stderr)
        return bench_run.NO_DEVICE
    from generator import schedule
    from repro.serve.engine import EngineStats
    sizes = load_json(ROOT / config["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    serve = load_module(BENCH / "kinds" / "serve.py")
    spans = Spans()
    _, cfg, _, engine, scheduler = serve.build(config, sizes, args.seed,
                                               spans)
    for rate in args.rates:
        arrivals = [a for a in schedule(dict(mix, rate_per_s=rate), cfg.vocab,
                                        args.seed, args.seconds)
                    if a.phase != "tail"]
        loop = serve.Loop(engine, scheduler, arrivals, spans)
        engine.stats = EngineStats()
        loop.start = time.perf_counter()
        while time.perf_counter() < loop.start + mix["ramp_s"]:
            loop.tick()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < args.seconds:
            loop.tick()
        t1 = time.perf_counter()
        queued = len(loop.waiting)
        while loop.sent < len(arrivals) or loop.waiting or engine.active \
                or engine.prefilling:
            loop.tick()
        window = [r for r in loop.requests if r.phase == "window"]
        ttft = [loop.stamps[r.uid][0] - r.submitted_s for r in window]
        tokens = sum(1 for r in loop.requests for t in loop.stamps[r.uid]
                     if t0 < t <= t1)
        print(json.dumps({
            "rate_per_s": rate, "sent_per_s": len(window) / (t1 - t0),
            "finished_per_s": sum(1 for r in window if r.finished_s <= t1)
            / (t1 - t0),
            "tokens_per_s": tokens / (t1 - t0),
            "ttft_p50_s": percentile(ttft, 50),
            "ttft_p90_s": percentile(ttft, 90),
            "queued_at_close": queued,
            "step_ms": 1e3 * (t1 - t0) / max(engine.stats.ticks, 1)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
