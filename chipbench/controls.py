"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/controls.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, in one process: one run of the cell (a short window at
the cell's own load and sizes), its compared numbers (the program's
readings), and the control's readings on the same inputs: the reference
computed in the precision below the one the configuration states.

With ``--fault altered_token`` the served cell's engine alters each
token where it is produced (the next token id), and the program's
readings are those of that fault.  Each
seed's readings are printed as one JSON line, with whether the control,
held to the same limits, comes out correct (it must not); the last line
holds, for each compared number, the largest program reading and the
smallest control reading.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run as bench_run
from common import BENCH, ROOT, Check, load_json, load_module


def alter_tokens() -> None:
    """Plant a fault in the served path: each token the engine produces
    is replaced by the next token id, where it is produced."""
    from repro.serve import ServeEngine
    advance = ServeEngine.advance

    def altered(self):
        before = {id(r): len(r.out_tokens) for r in self.active.values()}
        done = advance(self)
        for r in list(self.active.values()) + done:
            if len(r.out_tokens) > before.get(id(r), len(r.out_tokens)):
                r.out_tokens[-1] = (r.out_tokens[-1] + 1) % self.cfg.vocab
        return done

    ServeEngine.advance = altered


FAULTS = {"altered_token": alter_tokens}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--fault", choices=sorted(FAULTS))
    args = p.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = bench_run.by_name(bench["workloads"], args.workload, "workload")
    config = bench_run.by_name(bench["configs"], cell["config"],
                               "configuration")
    bench_run.enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("the readings are taken on a TPU", file=sys.stderr)
        return bench_run.NO_DEVICE
    sizes = load_json(ROOT / config["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    kind = load_module(BENCH / "kinds" / f"{sizes['kind']}.py")
    if args.fault:
        FAULTS[args.fault]()
    program, control = [], []
    for seed in args.seeds:
        out = kind.run(cell=cell, config=config, sizes=sizes, mix=mix,
                       seed=seed, seconds=args.seconds, trace=False,
                       t_start=time.perf_counter(), hold=True)
        program.append({k: v["value"] for k, v in out.check.items.items()})
        program[-1].update({f"logit.{k}": v for k, v in
                            out.notes.get("logit", {}).items()})
        control.append(kind.control(out.held))
        # the control in the program's place, held to the same limits
        held_to = Check()
        for name, item in out.check.items.items():
            if name in control[-1]:
                held_to.add(name, control[-1][name], item["limit"])
        print(json.dumps({"seed": seed, "program": program[-1],
                          "control": control[-1],
                          "control_correct": held_to.correct,
                          "checks": out.check.items,
                          "notes": out.notes}), flush=True)
        del out
        gc.collect()
    print(json.dumps({
        "workload": args.workload, "seeds": len(program),
        "program_max": {k: max(p[k] for p in program) for k in program[0]},
        "control_min": {k: min(c[k] for c in control) for k in control[0]}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
