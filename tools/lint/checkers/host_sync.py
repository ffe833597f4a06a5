"""host-sync: no device synchronization inside dispatch-free hot paths.

The paper's promise — predictions "at merely a fraction of a
contraction's runtime" — survives only while the hot paths stay
dispatch-free: the serve engine's step hooks run one fused step each,
and the scheduler tick budget (< 1 ms) assumes planning never blocks on
the device.  One stray ``block_until_ready`` (or a ``float()`` /
``np.asarray`` D2H pull) re-serializes the pipeline and the regression
is silent until a benchmark notices.

Flagged synchronization forms (syntactic — no type inference, so
legitimate sites carry a ``# reprolint: allow[host-sync]`` pragma with a
justification):

* ``jax.block_until_ready(x)`` / ``x.block_until_ready()``,
* ``x.item()``,
* ``np.asarray(x)`` / ``np.array(x)`` (device -> host transfer),
* ``float(x)`` on a non-literal (forces the value to the host).

Hot contexts:

* bodies of jit-decorated functions (and of functions/lambdas passed to
  ``jax.jit`` in the same module) — a sync here is either a trace-time
  error waiting to happen or a per-call dispatch break;
* the serve/engine tick and scheduler rollout loops, plus the §6.2
  measurement kernel, via the :data:`HOT_PATHS` table;
* any function whose ``def`` line carries ``# reprolint: hot-path``.

Nested functions inherit their enclosing hot context (the §6.2 timed
``call()`` closure is exactly such a nest).
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Mapping, Optional, Set, Tuple

from ..core import Checker, FileContext, Finding, register
from ._jit import collect_jit_sites, is_jit_decorated

#: path -> qualnames that are hot by construction: the per-tick serve
#: loop + scheduler rollout (PR 6's < 1 ms budget), the fused engine's
#: step hooks, and the §6.2 measurement protocol (its sync placement is
#: the measurement, so its one sync is pragma-justified in place)
HOT_PATHS: Mapping[str, Set[str]] = {
    "src/repro/serve/engine.py": {
        "ServeEngine.advance", "ServeEngine.add_request",
    },
    "src/repro/serve/scheduler.py": {
        "serve_loop", "FifoScheduler.plan", "ModelGuidedScheduler.plan",
        "ModelGuidedScheduler._rollout", "StepCostModel.tick_cost",
    },
    "src/repro/train/train_loop.py": {"train"},
    "src/repro/core/contractions.py": {"run_kernel_benchmark"},
    # the device-resident tile sweep: per-config dispatches chain through
    # a donated token and ONLY the sweep-end drain may sync (its single
    # block_until_ready is pragma-justified in place)
    "src/repro/tc/device.py": {"DeviceSuite._sweep"},
}

#: receivers recognized as numpy for the D2H-transfer forms
_NUMPY_NAMES = {"np", "numpy"}


def sync_reason(node: ast.AST) -> Optional[str]:
    """If ``node`` is a host-synchronizing call, why it synchronizes."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr == "block_until_ready":
            return ("block_until_ready blocks the host until the device "
                    "queue drains")
        if f.attr == "item" and not node.args and not node.keywords:
            return ".item() pulls a device scalar to the host"
        if f.attr in ("asarray", "array") and \
                isinstance(f.value, ast.Name) and \
                f.value.id in _NUMPY_NAMES:
            return (f"np.{f.attr}() on a device value is a blocking "
                    f"device->host transfer")
    elif isinstance(f, ast.Name) and f.id == "float" and node.args and \
            not isinstance(node.args[0], ast.Constant):
        return "float() forces the value to the host (implicit sync)"
    return None


def _function_nodes(ctx: FileContext):
    """(qualname, node, enclosing-class) for every def, qualnames built
    with ``Class.method`` / ``outer.<locals>.inner`` collapsed to the
    pragmatic ``Class.method`` and ``outer`` forms used by HOT_PATHS."""
    out: List[Tuple[str, ast.AST]] = []

    def visit(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                out.append((qual, child))
                visit(child, f"{qual}.")
            elif isinstance(child, ast.ClassDef):
                visit(child, f"{prefix}{child.name}.")
            else:
                visit(child, prefix)

    visit(ctx.tree, "")
    return out


@register
class HostSyncChecker(Checker):
    id = "host-sync"
    description = ("no block_until_ready/.item()/np.asarray/float() "
                   "inside jitted bodies or the serve/measurement hot "
                   "paths")

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        hot_qualnames = HOT_PATHS.get(ctx.rel, set())
        hot_bodies: List[Tuple[str, ast.AST]] = []
        covered: Set[int] = set()   # defs inside an already-hot body

        def add_hot(qual: str, node: ast.AST) -> None:
            if id(node) in covered:
                return              # its enclosing hot body walks it
            hot_bodies.append((qual, node))
            for sub in ast.walk(node):
                covered.add(id(sub))

        # _function_nodes visits outer defs before inner ones, so an
        # enclosing hot function claims its nested defs (the §6.2 timed
        # call() closure) before they are considered separately
        for qual, node in _function_nodes(ctx):
            if (qual in hot_qualnames or
                    is_jit_decorated(node) or
                    ctx.is_hot_marked(node.lineno)):
                add_hot(qual, node)

        # functions / lambdas jitted at call sites in this module
        for site in collect_jit_sites(ctx.tree):
            if site.form in ("call", "lambda"):
                add_hot(getattr(site.fn, "name", "<lambda>"), site.fn)

        for qual, fn in hot_bodies:
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for stmt in body:
                for node in ast.walk(stmt):
                    reason = sync_reason(node)
                    if reason is None:
                        continue
                    yield Finding(
                        self.id, ctx.rel, node.lineno,
                        f"host sync in hot path {qual}(): {reason}; keep "
                        f"the hot path dispatch-free or annotate with "
                        f"`# reprolint: allow[host-sync]` + justification")
