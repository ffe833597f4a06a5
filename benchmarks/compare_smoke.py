"""Compare two ``BENCH_smoke.json`` artifacts and warn on perf regressions.

Usage::

    python -m benchmarks.compare_smoke PREVIOUS.json CURRENT.json \
        [--threshold 1.5]

CI downloads the previous run's smoke artifact and calls this after the
current one is written.  A tracked metric that grew by more than
``threshold`` x emits a GitHub Actions ``::warning::`` annotation (the job
still passes — smoke timings on shared runners are noisy, so regressions
are flagged for a human, not hard-failed).  Unreadable artifacts are also
only warned about.

Besides cross-commit trends, the CURRENT artifact alone is checked for
model-guided serving falling below its FIFO baseline
(:data:`SERVING_RATIOS`) — also when no previous artifact exists (pass
``-`` as PREVIOUS).

One check DOES fail the job: a metric the current artifact carries that
neither :data:`METRICS` nor :data:`UNTRACKED` lists.  A number computed
on every PR but watched by nobody is a blind spot; adding the metric to
a table (trended, or waived with a reason) is a one-line fix.  The
``reprolint`` metric-tracking checker enforces the same invariant
statically at the bench call sites — both read the literal tables below,
so source and CI can never disagree on what is tracked.
"""

from __future__ import annotations

import argparse
import json

#: (suite, metric, higher_better) triples trended across commits — the
#: declarative heart of the smoke lane.  ``higher_better`` inverts the
#: comparison ratio (a drop below 1/threshold warns).  reprolint's
#: metric-tracking checker parses this literal, so keep it constants-only.
METRICS = (
    ("batched_sweep", "sweep64_numpy_s", False),
    ("batched_sweep", "sweep64_numpy_cached_s", False),
    ("batched_sweep", "sweep_batched_s", False),
    ("batched_sweep", "grid_s", False),
    ("contractions", "tc_rank64_suite_s", False),
    ("contractions", "tc_rank64_rank_numpy_s", False),
    ("contractions", "tc_sweep_suite_s", False),
    ("contractions", "tc_sweep_rank_numpy_s", False),
    ("contractions", "tc_param_refine_suite_s", False),
    ("einsum_paths", "tc_chain_suite_s", False),
    ("einsum_paths", "tc_chain_rank_numpy_s", False),
    ("serving", "serve_p99_ms", False),
    ("serving", "serve_tick_overhead_ms", False),
    ("serving", "serve_goodput_tok_s", True),
    ("model_store", "store_warmstart_ms", False),
    ("model_store", "tournament_rank_agreement", True),
    ("tile_tuner", "tile_sweep_s", False),
    ("tile_tuner", "tile_warm_rank_ms", False),
)

#: (suite, metric) pairs a smoke bench emits that CI deliberately does
#: NOT trend — each group states why.  An emitted metric in neither
#: table fails the lane (see :func:`check_tracking`).
UNTRACKED = (
    # problem-shape descriptors: constants unless the bench is edited
    ("batched_sweep", "n"),
    ("batched_sweep", "grid_size"),
    ("batched_sweep", "grid_configs"),
    ("contractions", "tc_rank64_algorithms"),
    ("contractions", "tc_rank64_batched_algorithms"),
    ("contractions", "tc_rank64_benchmarks"),
    ("contractions", "tc_sweep_points"),
    ("contractions", "tc_sweep_benchmarks"),
    ("contractions", "tc_sweep_new_benchmarks"),
    ("contractions", "tc_param_signatures"),
    ("contractions", "tc_param_refine_measured"),
    ("contractions", "tc_param_predicted"),
    ("einsum_paths", "tc_chain_paths"),
    ("einsum_paths", "tc_chain_steps"),
    ("einsum_paths", "tc_chain_benchmarks"),
    ("einsum_paths", "tc_sweep_chain_points"),
    ("einsum_paths", "tc_sweep_chain_new_benchmarks"),
    # correctness booleans: the tier-1 tests already hard-pin these;
    # trending a 0/1 across commits adds nothing
    ("batched_sweep", "argmin_agree"),
    ("batched_sweep", "rank_order_agree"),
    ("contractions", "tc_rank64_oracle_agree"),
    ("contractions", "tc_param_top1_agree"),
    ("einsum_paths", "tc_chain_oracle_agree"),
    # numerical-agreement magnitudes: bounded by in-bench assertions
    ("batched_sweep", "max_rel_diff"),
    # scalar-path / one-shot reference timings and derived speedups: the
    # slow side of a ratio whose fast side is already trended above
    ("batched_sweep", "sweep_scalar_s"),
    ("batched_sweep", "sweep_speedup"),
    ("batched_sweep", "rank_scalar_s"),
    ("batched_sweep", "rank_batched_s"),
    # single-execution denominators and their cost fractions: one real
    # kernel execution each — too noisy on shared runners to trend
    ("contractions", "tc_rank64_exec_s"),
    ("contractions", "tc_rank64_cost_frac"),
    ("contractions", "tc_sweep_cost_frac"),
    ("contractions", "tc_param_cost_frac"),
    # holdout prediction error vs ONE fresh oracle measurement per
    # candidate — real-timing noise dominates; the deterministic band
    # is pinned in tests/test_parametric.py instead
    ("contractions", "tc_param_holdout_relerr"),
    ("einsum_paths", "tc_chain_exec_s"),
    ("einsum_paths", "tc_chain_cost_frac"),
    ("einsum_paths", "tc_sweep_chain_suite_s"),
    ("einsum_paths", "tc_sweep_chain_cost_frac"),
    # serving: FIFO-baseline percentiles and ratios — the guided/FIFO
    # comparison is already enforced by SERVING_RATIOS
    ("serving", "serve_model_build_s"),
    ("serving", "serve_p50_ms"),
    ("serving", "serve_fifo_p50_ms"),
    ("serving", "serve_fifo_p99_ms"),
    ("serving", "serve_goodput_ratio"),
    ("serving", "serve_p99_ratio"),
    # model store: shape descriptors and invariants the bench itself (or
    # tier-1 tests) already pin hard — zero new measurements and
    # bit-identical rankings fail the bench, not a trend line
    ("model_store", "store_keys"),
    ("model_store", "store_bytes"),
    ("model_store", "store_measure_s"),
    ("model_store", "store_save_ms"),
    ("model_store", "store_new_measurements"),
    ("model_store", "store_roundtrip_identical"),
    ("model_store", "store_drift_probed"),
    # drift ratio and prev-run fingerprint hit: shared-runner facts
    # (thermal wobble, runner-image rotation), informative but untrendable
    ("model_store", "store_drift_max_ratio"),
    ("model_store", "store_prev_hit"),
    # tournament: snapshot count is a constant; the winner's secondary
    # scores back up the trended rank_agreement headline
    ("model_store", "tournament_snapshots"),
    ("model_store", "tournament_top1_rate"),
    ("model_store", "tournament_rel_err"),
    ("model_store", "tournament_oracle_cost_s"),
    # tile tuner: table descriptors are constants; the exhaustive
    # execution denominator and its cost fraction carry one real kernel
    # execution per (shape, candidate) — too noisy to trend, and the
    # bench hard-asserts the fraction < 0.25 in place
    ("tile_tuner", "tile_shapes"),
    ("tile_tuner", "tile_configs"),
    ("tile_tuner", "tile_exec_s"),
    ("tile_tuner", "tile_sweep_cost_frac"),
    # measured-vs-analytic top-1 and the transfer shares are platform
    # facts (interpret mode inflates per-step proxy cost; transfer
    # bandwidths are the runner's); tier-1 tests pin the invariants
    ("tile_tuner", "tile_top1_agree"),
    ("tile_tuner", "tile_h2d_share"),
    ("tile_tuner", "tile_d2h_share"),
    # warm-store contract metrics: zero new measurements and identical
    # totals fail the bench itself, not a trend line
    ("tile_tuner", "tile_warm_new_measurements"),
    ("tile_tuner", "tile_warm_identical"),
)

#: derived views used by the comparison code below (and by older callers)
TRACKED = tuple((s, m) for s, m, _ in METRICS)
HIGHER_BETTER = frozenset((s, m) for s, m, hb in METRICS if hb)

#: (suite, guided metric, baseline metric) pairs checked WITHIN one
#: artifact: the model-guided scheduler falling below its FIFO baseline
#: means the predictions stopped paying for themselves
SERVING_RATIOS = (
    ("serving", "serve_goodput_tok_s", "serve_fifo_goodput_tok_s"),
)


def _metric(artifact: dict, suite: str, name: str):
    value = artifact.get("suites", {}).get(suite, {}).get("metrics",
                                                          {}).get(name)
    return float(value) if isinstance(value, (int, float)) else None


def compare(prev: dict, curr: dict, threshold: float) -> int:
    """Print a comparison table; return the number of flagged regressions."""
    flagged = 0
    for suite, name in TRACKED:
        old, new = _metric(prev, suite, name), _metric(curr, suite, name)
        if old is None or new is None or old <= 0:
            print(f"  {suite}.{name}: not comparable "
                  f"(old={old!r} new={new!r})")
            continue
        # higher-is-better metrics regress when they SHRINK: invert the
        # ratio so one threshold covers both directions
        ratio = old / new if (suite, name) in HIGHER_BETTER and new > 0 \
            else new / old
        line = f"  {suite}.{name}: {old:.4g} -> {new:.4g} ({ratio:.2f}x)"
        if ratio > threshold:
            flagged += 1
            direction = "dropped" if (suite, name) in HIGHER_BETTER \
                else "slowed"
            print(f"::warning title=smoke perf regression::{suite}.{name} "
                  f"{direction} {ratio:.2f}x ({old:.4g} -> {new:.4g}, "
                  f"threshold {threshold}x)")
        print(line)
    return flagged


def check_serving_ratios(curr: dict) -> int:
    """Warn when model-guided serving loses to its FIFO baseline."""
    flagged = 0
    for suite, guided_name, fifo_name in SERVING_RATIOS:
        guided = _metric(curr, suite, guided_name)
        fifo = _metric(curr, suite, fifo_name)
        if guided is None or fifo is None or fifo <= 0:
            print(f"  {suite}.{guided_name} vs {fifo_name}: not comparable "
                  f"(guided={guided!r} fifo={fifo!r})")
            continue
        ratio = guided / fifo
        if ratio < 1.0:
            flagged += 1
            print(f"::warning title=model-guided serving below FIFO::"
                  f"{suite}.{guided_name} = {guided:.4g} < "
                  f"{suite}.{fifo_name} = {fifo:.4g} ({ratio:.2f}x) — "
                  f"the step-cost predictions stopped paying for "
                  f"themselves")
        print(f"  {suite}.{guided_name}: {guided:.4g} vs "
              f"{fifo_name}: {fifo:.4g} ({ratio:.2f}x)")
    return flagged


def check_tracking(curr: dict) -> int:
    """HARD check: every metric in the artifact is in METRICS/UNTRACKED.

    Returns the number of unknown metrics (the only condition that fails
    the smoke lane — unlike timings it is deterministic, and the fix is
    a one-line table entry here).  Ratio-table metric names also count
    as known: they are consumed within one artifact, not trended.
    """
    known = set(TRACKED) | set(UNTRACKED)
    for suite, a, b in SERVING_RATIOS:
        known.update({(suite, a), (suite, b)})
    unknown = 0
    for suite, payload in curr.get("suites", {}).items():
        for name in payload.get("metrics", {}):
            if (suite, name) not in known:
                unknown += 1
                print(f"::error title=untracked smoke metric::"
                      f"{suite}.{name} is emitted but appears in neither "
                      f"METRICS nor UNTRACKED in benchmarks/"
                      f"compare_smoke.py — add it (trended, or waived "
                      f"with a reason)")
    return unknown


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("previous",
                    help="previous BENCH_smoke.json ('-' when none exists: "
                         "only the current artifact's serving ratios are "
                         "checked)")
    ap.add_argument("current", help="current BENCH_smoke.json")
    ap.add_argument("--threshold", type=float, default=1.5,
                    help="warn when metric grows by more than this factor")
    args = ap.parse_args()
    try:
        prev = None
        if args.previous != "-":
            with open(args.previous) as f:
                prev = json.load(f)
        with open(args.current) as f:
            curr = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        # warn-only contract: a truncated/missing artifact (e.g. a previous
        # run killed mid-write) must not fail the lane for unrelated commits
        print(f"::warning title=smoke comparison skipped::"
              f"cannot read artifacts: {e}")
        return
    flagged = 0
    if prev is not None:
        print(f"smoke comparison (warn beyond {args.threshold}x):")
        flagged += compare(prev, curr, args.threshold)
    else:
        print("no previous artifact; cross-commit comparison skipped")
    print("serving ratios (model-guided must not lose to FIFO):")
    flagged += check_serving_ratios(curr)
    print(f"{flagged} regression(s) flagged" if flagged
          else "no regressions flagged")
    unknown = check_tracking(curr)
    if unknown:
        raise SystemExit(f"{unknown} untracked smoke metric(s)")


if __name__ == "__main__":
    main()
