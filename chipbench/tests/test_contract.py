"""BENCHMARK.json and the files it names: every cell finds its
configuration, mix, kind and metric readers by name."""

import json
import re
from types import SimpleNamespace

from common import BENCH, ROOT, Check, Outcome, Spans

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def e2e_of(cell):
    return {m["name"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", [cell])}


def test_top_level_keys_and_window():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    cells = 24                                      # the most a later PR has
    total = (2 + 14 * cells) * (SPEC["run_seconds"] + 60) \
        + cells * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_bounds():
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in SPEC[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]


def test_every_name_finds_its_files():
    kinds = set()
    for c in SPEC["configs"]:
        sizes = ROOT / c["file"]
        assert sizes.exists() and sizes.with_suffix(".ref.py").exists()
        kind = json.loads(sizes.read_text())["kind"]
        kinds.add(kind)
        assert (BENCH / "kinds" / f"{kind}.py").exists()
    for w in SPEC["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert w["config"] in {c["name"] for c in SPEC["configs"]}
        assert w["chips"] == 1
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in SPEC["workloads"]:
        e2e = e2e_of(w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = [m for m in SPEC["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])]
        assert layers
        for m in layers:
            assert m["moves"] in e2e


def test_a_layer_has_one_name():
    by_module = {}
    for m in SPEC["per_layer"]:
        by_module.setdefault(m["layer"].split(" ")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_module.values())


def test_off_a_tpu_the_command_prints_no_result(tmp_path):
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", str(2 ** 33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 3, proc.stderr
    assert proc.stdout == ""
    assert "TPU" in proc.stderr


def test_a_metric_lists_cells_of_the_file():
    # a metric reported by no cell cannot stand in BENCHMARK.json: its
    # workloads, where given, name one cell or more, each in the file
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        if "workloads" in m:
            assert isinstance(m["workloads"], list) and m["workloads"]
            assert set(m["workloads"]) <= cells, m["name"]


#: metrics of the served cell, kept beside the benchmark until it has one
PENDING = json.loads((BENCH / "serve_metrics.json").read_text())


def pending(kind):
    return {m["name"] for m in PENDING[kind]}


def test_the_serving_metrics_wait_outside_the_benchmark():
    import run
    assert pending("end_to_end") == {"serve_out_tokens_per_s",
                                     "serve_ttft_p50_s", "serve_itl_p90_ms"}
    assert "serve.slot_wait_p90_ms" in pending("per_layer")
    layers = {m["layer"] for m in SPEC["per_layer"]}
    for m in PENDING["end_to_end"] + PENDING["per_layer"]:
        assert "workloads" not in m
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in PENDING["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in PENDING["per_layer"]:
        assert m["moves"] in pending("end_to_end")
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        # a layer the benchmark already names keeps its name
        module = m["layer"].split(" ")[0]
        assert all(l == m["layer"] for l in layers
                   if l.split(" ")[0] == module)
    for kind in ("end_to_end", "per_layer"):
        assert not pending(kind) & {m["name"] for m in SPEC[kind]}
        for w in SPEC["workloads"]:
            reported = {m["name"] for m in run.cell_metrics(SPEC, w["name"],
                                                            kind)}
            assert not reported & pending(kind)


def test_run_cell_never_looks_up_a_metric_of_no_cell(monkeypatch):
    # the kind hands back only the cell's own end-to-end metrics, and
    # every module the run loads is recorded
    import run
    loaded = []
    for cell in SPEC["workloads"]:
        e2e = {m["name"]: 1.0
               for m in run.cell_metrics(SPEC, cell["name"], "end_to_end")}
        check = Check()
        check.add("n", 0.0, 1.0)
        outcome = Outcome(
            e2e=e2e, counters={}, check=check, attempted=1, failed=0,
            device={}, spans=Spans(), sizes={},
            reduced=SimpleNamespace(busy_s=1.0, window_s=2.0,
                                    breakdown=lambda: {}))

        def load_module(path, outcome=outcome):
            loaded.append(path.stem)
            return SimpleNamespace(run=lambda **_: outcome,
                                   read=lambda _run: 1.0)

        monkeypatch.setattr(run, "load_module", load_module)
        for trace in (0, 1):
            args = SimpleNamespace(seed=1, seconds=1.0, trace=trace)
            line = run.run_cell(SPEC, cell, args, t_start=0.0)
            assert not set(line["metrics"]) & pending("end_to_end")
            assert not set(line["metrics"]) & pending("per_layer")
    assert loaded and not set(loaded) & pending("per_layer")


def test_no_file_names_the_rescaled_step_time():
    # the served step's share of peak, taken out: it was the step time
    # rescaled, and counted empty slots
    gone = "serve." + "step_mfu"
    assert not (BENCH / "metrics" / f"{gone}.py").exists()
    files = [ROOT / "BENCHMARK.json"] + [
        p for p in BENCH.rglob("*") if p.suffix in (".py", ".json", ".md")
        and ".state" not in p.parts]
    assert not [p for p in files if gone in p.read_text()]
