"""deprecated-kwarg: ranking entry points go through PredictorSession.

PR 6 unified every ranking/selection entry point behind
:class:`repro.tc.PredictorSession` — one object owning the suite and
trace cache — and deprecated the per-call resource kwargs behind
one-release shims.  The shims keep old *external* callers working, but
internal code, benchmarks, examples and docs must not keep the legacy
spelling alive: every such call builds a throwaway session, re-measures
what a shared session would have reused, and teaches readers the dead
API.  This checker is the single source of truth for the rule —
``tools/check_docs.py`` imports :data:`DEPRECATED_KWARGS` and
:func:`deprecated_call_findings` so docs snippets and source share one
definition.

The sanctioned implementation sites (the session's own delegation to
the low-level sweep functions, and the shim plumbing itself) carry
``# reprolint: allow[deprecated-kwarg]`` pragmas with justifications.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Mapping, Sequence

from ..core import Checker, FileContext, Finding, register

#: entry point -> the per-call resource kwargs PR 6 deprecated on it.
#: (`session=` replaced them; tools/check_docs.py reuses this table for
#: docs snippets, so source and docs can never disagree on the rule.)
DEPRECATED_KWARGS: Mapping[str, Sequence[str]] = {
    "rank_contraction_algorithms": ("suite", "cache", "repetitions",
                                    "sizes_grid"),
    "select_contraction_algorithm": ("repetitions", "predictor"),
    "rank_einsum_paths": ("suite", "cache", "repetitions", "sizes_grid",
                          "predictor"),
    "select_einsum_path": ("repetitions", "predictor"),
    "rank_contraction_sweep": ("suite", "cache", "repetitions"),
    "rank_einsum_sweep": ("suite", "cache", "repetitions"),
}


def _called_name(func: ast.expr) -> str:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return ""


def deprecated_call_findings(tree: ast.AST, rel: str,
                             checker_id: str = "deprecated-kwarg",
                             ) -> List[Finding]:
    """Findings for every call of a tabled entry point passing a
    deprecated kwarg (shared with tools/check_docs.py)."""
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = _called_name(node.func)
        kwargs = DEPRECATED_KWARGS.get(fn)
        if not kwargs:
            continue
        used = [kw.arg for kw in node.keywords
                if kw.arg in kwargs and not _is_none(kw.value)]
        if used:
            out.append(Finding(
                checker_id, rel, node.lineno,
                f"{fn}() called with deprecated kwarg(s) "
                f"{', '.join(k + '=' for k in sorted(used))} — construct "
                f"a repro.tc.PredictorSession and use its methods "
                f"(session= owns the suite/cache)"))
    return out


def _is_none(node: ast.expr) -> bool:
    """Forwarding ``repetitions=None`` explicitly is the shim plumbing's own
    idiom and behaviorally identical to omitting the kwarg."""
    return isinstance(node, ast.Constant) and node.value is None


@register
class DeprecatedKwargChecker(Checker):
    id = "deprecated-kwarg"
    description = ("no internal/bench/example/docs call to a ranking "
                   "entry point with the PR-6-deprecated per-call "
                   "kwargs (suite=/cache=/predictor=/...)")

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        return deprecated_call_findings(ctx.tree, ctx.rel, self.id)
