"""Every cell of BENCHMARK.json driven on the CPU at a tiny size: the run
hands back every end-to-end metric the cell reports, and every per-layer
reader of the cell reads that run without raising.

The command refuses to run off a TPU, so each cell's kind is driven
directly at the size its rehearsal (``test_rehearsal.py``) cuts it to.
A metric whose ``workloads`` list names a cell whose kind cannot produce
it fails here, not on the chip.
"""

import json

import pytest

from common import BENCH, ROOT, load_json, load_module
from test_rehearsal import blocked_case, drive, serve_case

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
#: the rehearsal's tiny case of each kind of configuration
CASES = {"blocked": blocked_case, "serve": serve_case}
#: sources a reader can find in a run recorded without the profiler
UNTRACED = ("host_clock", "program_counter", "program_span")


def reports(cell, kind, spec=SPEC):
    return [m for m in spec[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def kind_of(cell):
    config = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    return load_json(ROOT / config["file"])["kind"]


def assert_reports(out, cell, spec=SPEC):
    for m in reports(cell, "end_to_end", spec):
        assert out.e2e[m["name"]] > 0, m["name"]
    for m in reports(cell, "per_layer", spec):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(out)
        if m["source"] in UNTRACED:
            assert value is not None and value >= 0, m["name"]


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_a_cell_reports_its_metrics(state, cell):
    case = CASES[kind_of(cell)]()
    case["cell"] = {"name": cell["name"], "chips": cell["chips"]}
    assert_reports(drive(case, seconds=2.0), cell)


def test_a_served_cell_that_lists_the_serving_metrics_reports_them(state):
    # the served cell's metrics, kept beside the benchmark, listed by a
    # served cell as the PR that adds one will list them
    case = serve_case()
    cell = case["cell"]
    spec = json.loads(json.dumps(SPEC))
    pending = load_json(BENCH / "serve_metrics.json")
    listed = []
    for kind in ("end_to_end", "per_layer"):
        for m in pending[kind]:
            spec[kind].append(dict(m, workloads=[cell["name"]]))
            listed.append(m["name"])
    assert {"serve_out_tokens_per_s", "serve_ttft_p50_s", "serve_itl_p90_ms",
            "serve.slot_wait_p90_ms"} <= set(listed)
    assert_reports(drive(case, seconds=2.0), cell, spec)
