"""Tests for the reprolint static-analysis package (tools/lint).

Each checker gets positive (finding fires) and negative (clean code
passes) fixtures built from inline sources, plus pragma suppression, a
baseline round-trip, and — the gate that matters — a self-check that the
repository itself lints clean, since CI runs ``python -m tools.lint`` as
a hard step before the test lane.
"""

from __future__ import annotations

import json
import sys
import textwrap
from collections import Counter
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:                 # tools/ is not on PYTHONPATH
    sys.path.insert(0, str(ROOT))

from tools.lint import (REGISTRY, Finding, load_baseline,       # noqa: E402
                        run_lint, write_baseline)
from tools.lint.core import FileContext, python_snippets        # noqa: E402
from tools.lint.checkers.deprecated_kwargs import (             # noqa: E402
    deprecated_call_findings)


def ctx(source: str, rel: str = "src/repro/x.py") -> FileContext:
    return FileContext(rel, textwrap.dedent(source))


def findings_of(checker_id: str, source: str,
                rel: str = "src/repro/x.py"):
    return list(REGISTRY[checker_id].check_file(ctx(source, rel)))


# ---------------------------------------------------------------- host-sync --

HOT_SYNC = """
    import jax

    # reprolint: hot-path
    def tick(state):
        jax.block_until_ready(state)
        return state
"""


def test_host_sync_flags_marked_hot_path():
    out = findings_of("host-sync", HOT_SYNC)
    assert len(out) == 1
    assert "block_until_ready" in out[0].message
    assert out[0].line == 6


def test_host_sync_ignores_cold_functions():
    assert not findings_of("host-sync", """
        import jax

        def offline_report(state):
            jax.block_until_ready(state)   # fine: not a hot context
            return state
    """)


def test_host_sync_flags_jitted_bodies_and_nested_defs():
    out = findings_of("host-sync", """
        import jax

        @jax.jit
        def step(x):
            def inner():
                return float(x)
            return inner()
    """)
    assert len(out) == 1 and "float()" in out[0].message


@pytest.mark.parametrize("stmt,tag", [
    ("x.item()", ".item()"),
    ("np.asarray(x)", "np.asarray"),
    ("float(x)", "float()"),
])
def test_host_sync_forms(stmt, tag):
    out = findings_of("host-sync", f"""
        import numpy as np

        # reprolint: hot-path
        def tick(x):
            return {stmt}
    """)
    assert len(out) == 1 and tag in out[0].message


def test_host_sync_float_of_literal_is_fine():
    assert not findings_of("host-sync", """
        # reprolint: hot-path
        def tick(x):
            return float("inf")
    """)


def test_host_sync_hot_paths_table_matches_repo():
    """The built-in hot-path table only names functions that exist (a
    rename would silently drop coverage)."""
    import ast as ast_mod

    from tools.lint.checkers.host_sync import HOT_PATHS
    for rel, quals in HOT_PATHS.items():
        tree = ast_mod.parse((ROOT / rel).read_text())
        names = set()
        for node in ast_mod.walk(tree):
            if isinstance(node, ast_mod.ClassDef):
                names.update(f"{node.name}.{m.name}" for m in node.body
                             if isinstance(m, ast_mod.FunctionDef))
            elif isinstance(node, ast_mod.FunctionDef):
                names.add(node.name)
        missing = quals - names
        assert not missing, (rel, missing)


# ------------------------------------------------------------------ retrace --

def test_retrace_flags_undeclared_bool_param():
    out = findings_of("retrace", """
        import jax

        @jax.jit
        def f(x, causal: bool = False):
            return x
    """)
    assert len(out) == 1 and "causal" in out[0].message


def test_retrace_static_argnames_is_clean():
    assert not findings_of("retrace", """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("causal",))
        def f(x, causal: bool = False):
            return x
    """)


def test_retrace_static_argnums_resolves_positions():
    assert not findings_of("retrace", """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnums=(1,))
        def f(x, mode="fast"):
            return x
    """)


def test_retrace_flags_branch_on_traced_param():
    out = findings_of("retrace", """
        import jax

        @jax.jit
        def f(x, n):
            if n > 3:
                return x * 2
            return x
    """)
    assert len(out) == 1 and "branches on traced" in out[0].message


def test_retrace_int_default_not_flagged():
    """Python int/float defaults trace as weak-typed operands without
    retracing — only branching on them is a hazard."""
    assert not findings_of("retrace", """
        import jax

        @jax.jit
        def f(x, scale=2.0, shift=1):
            return x * scale + shift
    """)


def test_retrace_jit_call_site_resolves_module_def():
    out = findings_of("retrace", """
        import jax

        def f(x, interpret=False):
            return x

        g = jax.jit(f)
    """)
    assert len(out) == 1 and "interpret" in out[0].message


# ---------------------------------------------------------- deprecated-kwarg --

def test_deprecated_kwarg_flags_legacy_call():
    out = findings_of("deprecated-kwarg", """
        from repro.tc import rank_contraction_sweep

        sweep = rank_contraction_sweep(spec, grid, suite=s, cache=c)
    """)
    assert len(out) == 1
    assert "PredictorSession" in out[0].message
    assert "cache=" in out[0].message and "suite=" in out[0].message


def test_deprecated_kwarg_session_form_is_clean():
    assert not findings_of("deprecated-kwarg", """
        sweep = rank_contraction_sweep(spec, grid, session=sess)
        ranked = sess.rank_contraction_sweep(spec, grid)
    """)


def test_deprecated_kwarg_explicit_none_forwarding_is_clean():
    assert not findings_of("deprecated-kwarg", """
        ranked = rank_einsum_paths(chain, sizes, repetitions=None,
                                   session=sess)
    """)


def test_deprecated_call_findings_reusable_entry():
    """tools/check_docs.py consumes this function directly."""
    import ast as ast_mod
    tree = ast_mod.parse(
        "rank_einsum_sweep(c, g, suite=s)")
    out = deprecated_call_findings(tree, "docs/x.md")
    assert len(out) == 1 and out[0].path == "docs/x.md"


# ----------------------------------------------------------- oracle-coverage --

def _oracle_repo(tmp_path: Path, test_body: str,
                 entry: str = "select_algorithm") -> Path:
    (tmp_path / "src").mkdir()
    (tmp_path / "tests").mkdir()
    (tmp_path / "src" / "sel.py").write_text(textwrap.dedent(f"""
        def {entry}(*args, **kwargs):
            return "x"
    """))
    (tmp_path / "tests" / "test_sel.py").write_text(
        textwrap.dedent(test_body))
    return tmp_path


def _oracle_findings(root: Path, entry: str = "select_algorithm"):
    ctxs = [FileContext("src/sel.py", (root / "src" / "sel.py").read_text())]
    return [f for f in REGISTRY["oracle-coverage"].check_repo(ctxs, root)
            if entry in f.message]


def test_oracle_coverage_flags_untested_entry_point(tmp_path):
    out = _oracle_findings(_oracle_repo(tmp_path, """
        def test_nothing():
            pass
    """))
    assert len(out) == 1 and "no test module" in out[0].message
    assert (out[0].path, out[0].line) == ("src/sel.py", 2)


def test_oracle_coverage_flags_unpinned_fast_path(tmp_path):
    out = _oracle_findings(_oracle_repo(tmp_path, """
        def test_select():
            assert select_algorithm(t, m, 512, 64) == "a"
    """))
    assert len(out) == 1 and "unpinned" in out[0].message


def test_oracle_coverage_oracle_kwarg_satisfies(tmp_path):
    entry = "rank_contraction_algorithms"
    out = _oracle_findings(_oracle_repo(tmp_path, """
        def test_rank():
            got = rank_contraction_algorithms(spec, sizes)
            ref = rank_contraction_algorithms(spec, sizes, batched=False)
            assert got == ref
    """, entry), entry)
    assert not out


def test_oracle_coverage_oracle_call_satisfies(tmp_path):
    out = _oracle_findings(_oracle_repo(tmp_path, """
        def test_select():
            assert select_algorithm(t, m, 512, 64) == "a"
            assert predict_runtime(calls, m).med > 0
    """))
    assert not out


def _refine_repo(tmp_path: Path, test_body: str) -> Path:
    (tmp_path / "src").mkdir()
    (tmp_path / "tests").mkdir()
    (tmp_path / "src" / "param.py").write_text(textwrap.dedent("""
        def refine_parametric(spec, grid):
            return {}
    """))
    (tmp_path / "tests" / "test_param.py").write_text(
        textwrap.dedent(test_body))
    return tmp_path


def _refine_findings(root: Path):
    ctxs = [FileContext("src/param.py",
                        (root / "src" / "param.py").read_text())]
    return [f for f in REGISTRY["oracle-coverage"].check_repo(ctxs, root)
            if "refine_parametric" in f.message]


def test_oracle_coverage_flags_unpinned_refine_parametric(tmp_path):
    # parametric predictions without a measured-oracle comparison are
    # exactly the "plausible but unpinned fast path" the checker exists for
    out = _refine_findings(_refine_repo(tmp_path, """
        def test_refine():
            assert sess.refine_parametric(spec, grid)["measured"] > 0
    """))
    assert len(out) == 1 and "unpinned" in out[0].message
    assert "benchmark_fresh" in out[0].message
    assert (out[0].path, out[0].line) == ("src/param.py", 2)


def test_oracle_coverage_measured_oracle_pins_refine_parametric(tmp_path):
    out = _refine_findings(_refine_repo(tmp_path, """
        def test_refine():
            sess.refine_parametric(spec, grid)
            fresh = suite.benchmark_fresh(alg, sizes)
            assert predicted.med == pytest.approx(fresh.stats.med)
    """))
    assert not out


def test_oracle_coverage_rank_oracle_pins_refine_parametric(tmp_path):
    out = _refine_findings(_refine_repo(tmp_path, """
        def test_refine():
            sess.refine_parametric(spec, grid)
            assert ranking[0].name == pred.rank_oracle()[0].name
    """))
    assert not out


# ----------------------------------------------------------- metric-tracking --

_RUN_PY = """
    SUITES = {
        "alpha": (bench_alpha, "desc"),
    }
    SMOKE_SUITES = ("alpha",)
"""

_COMPARE_PY = """
    METRICS = (
        ("alpha", "alpha_rank_s", False),
    )
    UNTRACKED = (
        ("alpha", "alpha_points"),
    )
    SERVING_RATIOS = ()
"""


def _metric_findings(bench_body: str, compare_py: str = _COMPARE_PY):
    ctxs = [
        FileContext("benchmarks/run.py", textwrap.dedent(_RUN_PY)),
        FileContext("benchmarks/compare_smoke.py",
                    textwrap.dedent(compare_py)),
        FileContext("benchmarks/bench_alpha.py",
                    textwrap.dedent(bench_body)),
    ]
    return list(REGISTRY["metric-tracking"].check_repo(ctxs, ROOT))


def test_metric_tracking_clean_when_all_known():
    assert not _metric_findings("""
        def run(report, results=None):
            results.update({"alpha_rank_s": 0.1})
            results["alpha_points"] = 3
    """)


def test_metric_tracking_flags_unknown_metric():
    out = _metric_findings("""
        def run(report, results=None):
            results.update({"alpha_rank_s": 0.1, "alpha_mystery": 1})
            results["alpha_points"] = 3
    """)
    assert len(out) == 1 and "alpha_mystery" in out[0].message
    assert out[0].path == "benchmarks/bench_alpha.py"


def test_metric_tracking_flags_non_literal_key():
    out = _metric_findings("""
        def run(report, results=None):
            results[f"alpha_{n}_s"] = 0.1
            results.update({"alpha_rank_s": 0.1})
            results["alpha_points"] = 3
    """)
    assert len(out) == 1 and "non-literal" in out[0].message


def test_metric_tracking_flags_unit_alias():
    out = _metric_findings("""
        def run(report, results=None):
            results.update({"alpha_rank_s": 0.1, "alpha_cost_msec": 2})
    """)
    msgs = " | ".join(f.message for f in out)
    assert "alpha_cost_msec" in msgs and "'_ms'" in msgs


def test_metric_tracking_flags_stale_table_row():
    out = _metric_findings("""
        def run(report, results=None):
            results.update({"alpha_rank_s": 0.1})
    """)
    assert len(out) == 1 and "stale table row" in out[0].message
    assert out[0].path == "benchmarks/compare_smoke.py"


# ------------------------------------------------------------ store-schema --

def test_store_schema_clean_writer():
    assert not findings_of("store-schema", """
        import json
        SCHEMA_VERSION = 1

        def save(path, data):
            payload = {"schema_version": SCHEMA_VERSION, "data": data}
            with open(path, "w") as f:
                json.dump(payload, f)
    """, rel="src/repro/store/writer.py")


def test_store_schema_imported_constant_is_clean():
    assert not findings_of("store-schema", """
        import json
        from .modelstore import SCHEMA_VERSION

        def save(path):
            json.dump({"schema_version": SCHEMA_VERSION}, open(path, "w"))
    """, rel="src/repro/store/other.py")


def test_store_schema_flags_writer_without_constant():
    out = findings_of("store-schema", """
        import json

        def save(path, data):
            json.dump({"data": data}, open(path, "w"))
    """, rel="src/repro/store/writer.py")
    assert len(out) == 1 and "SCHEMA_VERSION" in out[0].message


def test_store_schema_flags_unstamped_payload():
    out = findings_of("store-schema", """
        import json
        SCHEMA_VERSION = 1

        def save(path, data):
            json.dump({"data": data}, open(path, "w"))
    """, rel="src/repro/store/writer.py")
    assert len(out) == 1 and "schema_version" in out[0].message


def test_store_schema_flags_hardcoded_version_everywhere():
    out = findings_of("store-schema", """
        payload = {"schema_version": 1}
    """, rel="benchmarks/bench_x.py")
    assert len(out) == 1 and "hard-coded" in out[0].message


def test_store_schema_ignores_json_outside_store_package():
    assert not findings_of("store-schema", """
        import json

        def save(path, data):
            json.dump({"data": data}, open(path, "w"))
    """, rel="src/repro/core/model.py")


# ------------------------------------------------- pragmas, baseline, runner --

def test_pragma_suppresses_on_line_and_line_above():
    trailing = ctx("""
        import jax

        # reprolint: hot-path
        def tick(state):
            jax.block_until_ready(state)  # reprolint: allow[host-sync]
    """)
    above = ctx("""
        import jax

        # reprolint: hot-path
        def tick(state):
            # reprolint: allow[host-sync]
            jax.block_until_ready(state)
    """)
    for c in (trailing, above):
        f = list(REGISTRY["host-sync"].check_file(c))[0]
        assert c.allowed(f.checker, f.line)
    assert not above.allowed("retrace", 6)      # other checkers unaffected
    assert ctx("x = 1  # reprolint: allow[*]").allowed("host-sync", 1)


def test_docs_snippets_are_linted_with_md_line_numbers(tmp_path):
    (tmp_path / "docs").mkdir()
    (tmp_path / "src").mkdir()
    md = tmp_path / "docs" / "guide.md"
    md.write_text(textwrap.dedent("""\
        # Guide

        ```python
        sweep = rank_contraction_sweep(spec, grid, suite=s)
        ```
    """))
    result = run_lint(tmp_path, baseline=Counter())
    assert [(f.checker, f.path, f.line) for f in result.findings] == \
        [("deprecated-kwarg", "docs/guide.md", 4)]


def test_python_snippets_honors_skip_mark(tmp_path):
    md = tmp_path / "x.md"
    md.write_text(textwrap.dedent("""\
        <!-- docs-check: skip -->
        ```python
        not python at all (
        ```
        ```python
        ok = 1
        ```
    """))
    assert [src for _, src in python_snippets(md)] == ["ok = 1"]


def test_baseline_round_trip(tmp_path):
    f1 = Finding("host-sync", "src/a.py", 10, "msg one")
    f2 = Finding("retrace", "src/b.py", 20, "msg two")
    path = tmp_path / "baseline.json"
    write_baseline([f1, f2], path)
    base = load_baseline(path)
    assert base == Counter({f1.key(): 1, f2.key(): 1})
    # line numbers are deliberately absent: drift must not invalidate it
    assert "10" not in json.dumps(json.loads(path.read_text())["findings"])


def test_baseline_grandfathers_exactly_once(tmp_path):
    (tmp_path / "src").mkdir()
    (tmp_path / "src" / "a.py").write_text(textwrap.dedent("""
        import jax

        # reprolint: hot-path
        def tick(state):
            jax.block_until_ready(state)
            return state
    """))
    live = run_lint(tmp_path, baseline=Counter())
    assert len(live.findings) == 1
    base = Counter({f.key(): 1 for f in live.findings})
    again = run_lint(tmp_path, baseline=base)
    assert not again.findings and len(again.baselined) == 1


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    (tmp_path / "src").mkdir()
    (tmp_path / "src" / "bad.py").write_text("def f(:\n")
    result = run_lint(tmp_path, baseline=Counter())
    assert [f.checker for f in result.findings] == ["parse"]


def test_finding_render_formats():
    f = Finding("host-sync", "src/a.py", 3, "boom")
    assert f.render() == "src/a.py:3: [host-sync] boom"
    assert f.render_github() == \
        "::error file=src/a.py,line=3,title=reprolint host-sync::boom"


def test_registry_has_the_six_checkers():
    assert set(REGISTRY) == {"host-sync", "retrace", "deprecated-kwarg",
                             "oracle-coverage", "metric-tracking",
                             "store-schema"}


# -------------------------------------------------------------- repo gate --

def test_repository_lints_clean():
    """The gate CI enforces: the repo itself has no active findings."""
    result = run_lint(ROOT)
    assert not result.findings, "\n" + "\n".join(
        f.render() for f in result.findings)
