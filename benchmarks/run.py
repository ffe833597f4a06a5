"""Benchmark driver: one section per paper table/figure.

Run with ``PYTHONPATH=src python -m benchmarks.run [--only <name>]``.

``--smoke`` runs the CI fast lane and writes a ``BENCH_smoke.json``
artifact so CI can track the prediction-path performance trajectory per
PR.  Its ``batched_sweep`` probe is measurement-free (tiny sizes, 1
repetition, synthetic models); the ``contractions`` probe necessarily
runs real (but tiny, deduplicated) kernel micro-benchmarks plus one
pinned contraction execution, so its ``tc_rank64_*`` timings carry
shared-runner noise — the cross-commit comparison only warns, never
fails.
"""

from __future__ import annotations

import argparse
import json
import platform
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

from . import (bench_algorithm_selection, bench_batched_sweep,
               bench_blocksize, bench_cache_effects, bench_contractions,
               bench_einsum_paths, bench_model_accuracy, bench_model_store,
               bench_prediction_accuracy, bench_roofline, bench_serving,
               bench_tile_tuner, common)

SUITES = {
    "model_accuracy": (bench_model_accuracy,
                       "paper §3.3 / Fig 3.13: model accuracy vs cost"),
    "cache_effects": (bench_cache_effects,
                      "paper §2.1.4 / Ch 5: warm-vs-cold kernel timings"),
    "prediction_accuracy": (bench_prediction_accuracy,
                            "paper Tab 4.3: blocked-algorithm prediction"),
    "algorithm_selection": (bench_algorithm_selection,
                            "paper §4.5: variant ranking + speedup"),
    "blocksize": (bench_blocksize,
                  "paper §4.6: block-size optimization yield"),
    "batched_sweep": (bench_batched_sweep,
                      "beyond-paper: batched engine vs scalar prediction"),
    "contractions": (bench_contractions,
                     "paper Ch 6: contraction micro-benchmark prediction"),
    "einsum_paths": (bench_einsum_paths,
                     "beyond-paper: einsum-path (chain) prediction"),
    "serving": (bench_serving,
                "beyond-paper: model-guided serving vs FIFO baseline"),
    "model_store": (bench_model_store,
                    "beyond-paper: store warm start, drift, tournament"),
    "tile_tuner": (bench_tile_tuner,
                   "beyond-paper: Pallas BlockSpec tile selection"),
    "roofline": (bench_roofline,
                 "deliverable (g): per-cell roofline table"),
}

#: the CI smoke lane: the measurement-free prediction-path probe, the
#: (cheap, deduplicated) contraction probes with their tc_rank64_* and
#: tc_chain_* metrics, the model-guided-serving probe (serve_*), the
#: model-store warm-start/tournament probe (store_*/tournament_*), and
#: the measured tile-selection economics probe (tile_*)
SMOKE_SUITES = ("batched_sweep", "contractions", "einsum_paths", "serving",
                "model_store", "tile_tuner")


def _run_suite(name: str, mod, desc: str, smoke: bool) -> dict:
    print(f"\n===== {name}: {desc} =====", flush=True)
    t0 = time.perf_counter()
    report: list = []
    metrics: dict = {}
    ok = True
    try:
        if smoke and name in SMOKE_SUITES:
            mod.run(report, results=metrics)
        else:
            mod.run(report)
        print("\n".join(report))
    except Exception:
        ok = False
        tb = traceback.format_exc()
        print(tb, flush=True)
    seconds = time.perf_counter() - t0
    print(f"[{name}: {seconds:.1f}s]", flush=True)
    result = {"ok": ok, "seconds": seconds, "report": report,
              "metrics": metrics}
    if not ok:
        result["traceback"] = tb   # carried into the CI smoke artifact
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--suites", default=None,
                    help="comma-separated suite filter (a multi-suite "
                         "--only); combines with --smoke, so the fast lane "
                         "can time each smoke suite independently")
    ap.add_argument("--smoke", action="store_true",
                    help="the CI fast lane: tiny sizes, synthetic models "
                         "(batched_sweep) + deduplicated real contraction "
                         "micro-benchmarks (contractions); writes the "
                         "BENCH_smoke.json artifact")
    ap.add_argument("--out", default="BENCH_smoke.json",
                    help="smoke-artifact path (with --smoke)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        common.set_smoke(True)
    if args.only and args.suites:
        raise SystemExit("pass --only or --suites, not both")
    selected = None
    if args.only:
        selected = [args.only]
    elif args.suites:
        selected = [s.strip() for s in args.suites.split(",") if s.strip()]
    unknown = [s for s in selected or [] if s not in SUITES]
    if unknown:
        raise SystemExit(f"unknown suite(s) {', '.join(unknown)}; "
                         f"choose from: {', '.join(SUITES)}")
    names = [n for n in SUITES
             if (selected is None or n in selected)
             and (not args.smoke or n in SMOKE_SUITES)]
    if not names:
        raise SystemExit(f"no suites selected ({selected!r} is not in the "
                         f"smoke lane: {', '.join(SMOKE_SUITES)})")
    results = {name: _run_suite(name, *SUITES[name], smoke=args.smoke)
               for name in names}
    failures = sum(not r["ok"] for r in results.values())
    if args.smoke:
        artifact = {
            "mode": "smoke",
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "python": platform.python_version(),
            "machine": platform.machine(),
            "suites": results,
        }
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"\nwrote {args.out}")
    if failures:
        raise SystemExit(f"{failures} benchmark suites failed")


if __name__ == "__main__":
    main()
