"""Run one cell of the benchmark once, on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name:
``BENCHMARK.json`` at the repository root names them, the configuration's
sizes are ``chipbench/configs/<config>.json`` (its plain reference beside
it, ``<config>.ref.py``), the mix is ``chipbench/traffic/<mix>.json``, the
code that drives a configuration's kind is ``chipbench/kinds/<kind>.py``
and each per-layer metric is read by ``chipbench/metrics/<metric>.py``.

With ``--trace 0`` the last line of standard output is one JSON object
with the cell's end-to-end metrics; with ``--trace 1`` the run also
records a profiler trace of a short stretch after the measured window
and the object holds the per-layer metrics, the device's busy time and a
breakdown.  Off a TPU, or with fewer chips than the cell asks for, the
run prints no result and exits with code 3.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from common import JAX_CACHE, ROOT, load_json, load_module  # noqa: E402

NO_DEVICE = 3


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The end-to-end (``kind='end_to_end'``) or per-layer metrics that
    ``cell`` reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, in the checkout's fixed
    ``chipbench/.state/jax_cache``, for every program however fast it
    compiles; the program's own switch turns it on."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(JAX_CACHE)
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable()


def run_cell(bench: dict, cell: dict, args, *, t_start: float) -> dict:
    """Drive one cell and assemble its result line (any platform)."""
    config = by_name(bench["configs"], cell["config"], "configuration")
    sizes = load_json(ROOT / config["file"])
    kind = load_module(BENCH / "kinds" / f"{sizes['kind']}.py")
    outcome = kind.run(cell=cell, config=config, sizes=sizes,
                       mix=load_json(BENCH / "traffic"
                                     / f"{cell['traffic']}.json"),
                       seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), t_start=t_start)
    device = outcome.device
    if args.trace:
        metrics = {}
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(outcome)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(device, busy_s=outcome.reduced.busy_s,
                      window_s=outcome.reduced.window_s)
    else:
        metrics = {m["name"]: {"value": outcome.e2e[m["name"]],
                               "unit": m["unit"]}
                   for m in cell_metrics(bench, cell["name"], "end_to_end")}
    line = {"correct": outcome.check.correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": device}
    if args.trace:
        line["breakdown"] = outcome.reduced.breakdown()
    line["notes"] = outcome.notes
    line["checks"] = outcome.check.items
    return line


def report(line: dict) -> None:
    for name, item in line["checks"].items():
        print(f"check {name} = {item['value']!r} (limit {item['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = by_name(bench["workloads"], args.workload, "workload")
    enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return NO_DEVICE
    report(run_cell(bench, cell, args, t_start=T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
