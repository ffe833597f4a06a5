"""metric-tracking: every smoke metric is tracked or explicitly waived.

The CI fast lane's whole value is its trend line: ``benchmarks/run.py
--smoke`` writes ``BENCH_smoke.json`` and ``benchmarks/compare_smoke.py``
compares it against the previous commit's artifact.  A metric a bench
emits but the comparison tables don't know about is a silent blind spot
— the number is computed every PR and watched by nobody.  This checker
closes the loop statically (pure AST, no jax import, so it fits the
<10s lint budget):

* parse ``benchmarks/run.py`` for ``SMOKE_SUITES`` and the ``SUITES``
  module mapping;
* extract every metric key each smoke bench writes (literal
  ``results.update({...})`` dicts and ``results["key"] = ...``
  assignments — non-literal keys are themselves flagged, since a key
  the linter cannot read is a key the tables cannot list);
* parse ``benchmarks/compare_smoke.py`` for the declarative ``METRICS``
  / ``UNTRACKED`` tables (plus the ``SERVING_RATIOS`` metric
  references, which count as known);
* flag emitted-but-unknown keys at their emit site, table entries no
  bench emits anymore (stale rows), and unit-suffix aliases — timings
  are ``_s``/``_ms``, rates ``_tok_s``, ratios-of-totals ``_frac``.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from ..core import Checker, FileContext, Finding, register

RUN = "benchmarks/run.py"
COMPARE = "benchmarks/compare_smoke.py"

#: suffix alias -> the canonical unit suffix the repo's metrics use
UNIT_ALIASES = {
    "_sec": "_s", "_secs": "_s", "_seconds": "_s",
    "_msec": "_ms", "_msecs": "_ms", "_millis": "_ms",
    "_milliseconds": "_ms",
    "_toks_s": "_tok_s", "_tok_per_s": "_tok_s", "_tokens_per_s": "_tok_s",
    "_fraction": "_frac", "_pct": "_frac", "_percent": "_frac",
}


def _assigned_literal(tree: ast.AST, name: str) -> Optional[ast.expr]:
    """The value node of a module-level ``name = <literal>`` assignment."""
    for node in tree.body if isinstance(tree, ast.Module) else []:
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        else:
            continue
        for t in targets:
            if isinstance(t, ast.Name) and t.id == name:
                return node.value
    return None


def _str_elts(node: Optional[ast.expr]) -> List[str]:
    if not isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return []
    return [e.value for e in node.elts
            if isinstance(e, ast.Constant) and isinstance(e.value, str)]


def _tuple_rows(node: Optional[ast.expr]) -> List[Tuple]:
    """Rows of a literal tuple/list-of-tuples table (constants only)."""
    rows: List[Tuple] = []
    if not isinstance(node, (ast.Tuple, ast.List)):
        return rows
    for e in node.elts:
        if isinstance(e, (ast.Tuple, ast.List)) and all(
                isinstance(x, ast.Constant) for x in e.elts):
            rows.append(tuple(x.value for x in e.elts))
    return rows


def _smoke_modules(run_tree: ast.AST) -> Dict[str, str]:
    """smoke suite name -> bench module name, from run.py's literals."""
    smoke = set(_str_elts(_assigned_literal(run_tree, "SMOKE_SUITES")))
    suites = _assigned_literal(run_tree, "SUITES")
    out: Dict[str, str] = {}
    if not isinstance(suites, ast.Dict):
        return out
    for key, value in zip(suites.keys, suites.values):
        if not (isinstance(key, ast.Constant) and key.value in smoke):
            continue
        mod = value.elts[0] if isinstance(value, (ast.Tuple, ast.List)) \
            and value.elts else value
        if isinstance(mod, ast.Name):
            out[key.value] = mod.id
        elif isinstance(mod, ast.Attribute):
            out[key.value] = mod.attr
    return out


def _emitted_keys(tree: ast.AST) -> List[Tuple[str, int]]:
    """(metric key, line) for each literal write into ``results``."""
    out: List[Tuple[str, int]] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "update" and \
                isinstance(node.func.value, ast.Name) and \
                node.func.value.id == "results":
            for arg in node.args:
                if isinstance(arg, ast.Dict):
                    for k in arg.keys:
                        if isinstance(k, ast.Constant) and \
                                isinstance(k.value, str):
                            out.append((k.value, k.lineno))
                        elif k is not None:
                            out.append(("", k.lineno))   # non-literal
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Subscript) and \
                        isinstance(t.value, ast.Name) and \
                        t.value.id == "results":
                    s = t.slice
                    if isinstance(s, ast.Constant) and \
                            isinstance(s.value, str):
                        out.append((s.value, t.lineno))
                    else:
                        out.append(("", t.lineno))       # non-literal
    return out


@register
class MetricTrackingChecker(Checker):
    id = "metric-tracking"
    description = ("every metric a smoke bench emits appears in "
                   "compare_smoke's METRICS or UNTRACKED tables, with "
                   "canonical unit suffixes (_s/_ms/_tok_s/_frac)")

    def check_repo(self, ctxs: Sequence[FileContext],
                   root: Path) -> Iterable[Finding]:
        by_rel = {c.rel: c for c in ctxs}
        run_ctx, cmp_ctx = by_rel.get(RUN), by_rel.get(COMPARE)
        if run_ctx is None or cmp_ctx is None:
            return   # nothing to cross-reference (partial lint run)

        metrics_node = _assigned_literal(cmp_ctx.tree, "METRICS")
        known: Dict[str, Set[str]] = {}
        tracked_rows: List[Tuple[str, str]] = []
        if metrics_node is None:
            # pre-refactor layout: TRACKED pairs only
            for suite, metric in _tuple_rows(
                    _assigned_literal(cmp_ctx.tree, "TRACKED")):
                known.setdefault(suite, set()).add(metric)
                tracked_rows.append((suite, metric))
        else:
            for row in _tuple_rows(metrics_node):
                suite, metric = row[0], row[1]
                known.setdefault(suite, set()).add(metric)
                tracked_rows.append((suite, metric))
        for suite, metric in _tuple_rows(
                _assigned_literal(cmp_ctx.tree, "UNTRACKED")):
            known.setdefault(suite, set()).add(metric)
            tracked_rows.append((suite, metric))
        for row in _tuple_rows(_assigned_literal(cmp_ctx.tree,
                                                 "SERVING_RATIOS")):
            for metric in row[1:]:
                known.setdefault(row[0], set()).add(metric)

        emitted: Dict[str, Set[str]] = {}
        for suite, mod in sorted(_smoke_modules(run_ctx.tree).items()):
            ctx = by_rel.get(f"benchmarks/{mod}.py")
            if ctx is None:
                continue
            emitted.setdefault(suite, set())
            for key, line in _emitted_keys(ctx.tree):
                if not key:
                    yield Finding(
                        self.id, ctx.rel, line,
                        f"suite {suite} writes a non-literal metric key "
                        f"— compare_smoke's tables can only list literal "
                        f"keys, so this metric is untrackable")
                    continue
                emitted[suite].add(key)
                if key not in known.get(suite, set()):
                    yield Finding(
                        self.id, ctx.rel, line,
                        f"suite {suite} emits metric {key!r} that "
                        f"compare_smoke knows nothing about — add it to "
                        f"METRICS (to trend it) or UNTRACKED (to waive "
                        f"it, with a reason)")
                for alias, canon in UNIT_ALIASES.items():
                    if key.endswith(alias):
                        yield Finding(
                            self.id, ctx.rel, line,
                            f"metric {key!r} uses unit suffix "
                            f"'{alias}' — the repo's canonical suffix "
                            f"is '{canon}' (_s/_ms/_tok_s/_frac)")
                        break

        table_line = metrics_node.lineno if metrics_node is not None else 1
        for suite, metric in tracked_rows:
            if suite in emitted and metric not in emitted[suite]:
                yield Finding(
                    self.id, cmp_ctx.rel, table_line,
                    f"stale table row: suite {suite} no longer emits "
                    f"metric {metric!r} — drop the row or restore the "
                    f"metric")
