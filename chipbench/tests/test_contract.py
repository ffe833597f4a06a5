"""BENCHMARK.json and the files it names: every cell finds its
configuration, mix, kind and metric readers by name."""

import json
import re

from common import BENCH, ROOT

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
