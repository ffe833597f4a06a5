"""oracle-coverage: every prediction fast path is test-pinned to its oracle.

``docs/architecture.md`` states the convention: every batched or
composed fast path keeps its original scalar implementation alive as an
*equivalence oracle*, and tests pin the two against each other — that is
what keeps a 30x speedup from silently becoming a 30x wrong answer.
This checker makes the convention structural: for each ranking/
prediction entry point in :data:`ORACLE_PAIRS`, at least one module
under ``tests/`` must both invoke the entry point AND invoke one of its
oracle forms.  An oracle form is either a called name
(``predict_runtime``, ``rank_oracle``, ``FifoScheduler``) or the
``batched=False`` keyword that switches a contraction entry point onto
its per-algorithm path.

Findings anchor at the entry point's ``def``/``class`` site in ``src/``
— the owner of an uncovered fast path is the code, not the test suite.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterable, Mapping, Sequence, Set, Tuple

from ..core import Checker, FileContext, Finding, register

#: entry point -> acceptable oracle forms (any one suffices).  Names are
#: called-function names in a test module; "kwarg=value" entries match a
#: literal keyword (the scalar-path switch).  Kept declarative so a new
#: fast path is one line here + one test, per the architecture.md table.
ORACLE_PAIRS: Mapping[str, Sequence[str]] = {
    # blocked-algorithm selection (paper §4.5/§4.6) and the batched
    # engine under it vs the scalar per-call path
    "rank_algorithms": ("predict_runtime",),
    "select_algorithm": ("predict_runtime",),
    "optimize_block_size": ("predict_runtime",),
    "predict_compiled": ("predict_runtime",),
    # contraction ranking (Ch. 6) vs the fresh per-algorithm §6.2 oracle
    "rank_contraction_algorithms": ("rank_oracle", "batched=False"),
    "select_contraction_algorithm": ("rank_oracle", "batched=False"),
    "rank_contraction_sweep": ("rank_oracle",),
    # einsum-path chains vs the step-by-step per-algorithm oracle
    "rank_einsum_paths": ("rank_paths_oracle",),
    "select_einsum_path": ("rank_paths_oracle",),
    "rank_einsum_sweep": ("rank_paths_oracle",),
    # model-guided serving vs the action-for-action FIFO baseline
    "ModelGuidedScheduler": ("FifoScheduler",),
    # size-parametric suite models vs the exact-shape measurement path
    "refine_parametric": ("benchmark_fresh", "rank_oracle"),
    # the unified session fronts all of the above; its tests must reach
    # a scalar path at least once
    "PredictorSession": ("rank_oracle", "rank_paths_oracle",
                        "batched=False"),
    # measured-model tile selection vs the analytic three-term oracle
    # (the pre-device model, kept alive as `analytic=True` fallback)
    "select_tiles": ("predict_tile_time", "analytic=True"),
    "rank_device_tiles": ("predict_tile_time", "analytic=True"),
}


def _module_calls(tree: ast.AST) -> Tuple[Set[str], Set[str]]:
    """(called names, 'kwarg=value' literals) used by one test module."""
    names: Set[str] = set()
    kwargs: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name):
            names.add(f.id)
        elif isinstance(f, ast.Attribute):
            names.add(f.attr)
        for kw in node.keywords:
            if kw.arg and isinstance(kw.value, ast.Constant):
                kwargs.add(f"{kw.arg}={kw.value.value!r}".replace("'", ""))
    return names, kwargs


def _def_sites(ctxs: Sequence[FileContext]) -> Dict[str, Tuple[str, int]]:
    """entry-point name -> (path, line) of its def/class in src/."""
    out: Dict[str, Tuple[str, int]] = {}
    for ctx in ctxs:
        if not ctx.rel.startswith("src/"):
            continue
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)) and \
                    node.name in ORACLE_PAIRS and node.name not in out:
                out[node.name] = (ctx.rel, node.lineno)
    return out


@register
class OracleCoverageChecker(Checker):
    id = "oracle-coverage"
    description = ("every ranking/prediction entry point has a test that "
                   "also invokes its equivalence oracle")

    def check_repo(self, ctxs: Sequence[FileContext],
                   root: Path) -> Iterable[Finding]:
        tests_dir = root / "tests"
        modules = []
        for path in sorted(tests_dir.glob("test_*.py")):
            try:
                tree = ast.parse(path.read_text())
            except SyntaxError:
                continue            # the parse checker owns that report
            modules.append((path.name, *_module_calls(tree)))
        sites = _def_sites(ctxs)
        for entry, oracles in ORACLE_PAIRS.items():
            if entry not in sites:
                continue   # not defined in the linted sources (partial run)
            calling = [(name, names, kwargs)
                       for name, names, kwargs in modules
                       if entry in names]
            path, line = sites[entry]
            if not calling:
                yield Finding(
                    self.id, path, line,
                    f"entry point {entry} is invoked by no test module — "
                    f"add a test pinning it against one of its oracles "
                    f"({', '.join(oracles)})")
                continue
            covered = any(
                any((o in names) or (o in kwargs) for o in oracles)
                for _, names, kwargs in calling)
            if not covered:
                mods = ", ".join(m for m, _, _ in calling)
                yield Finding(
                    self.id, path, line,
                    f"entry point {entry} is tested ({mods}) but no such "
                    f"module invokes its equivalence oracle "
                    f"({', '.join(oracles)}) — the fast path is unpinned")
