"""retrace: jit sites must declare Python-config parameters static.

The "compile once per platform, run forever" economics of the jitted
kernel, tile and serve-step programs die quietly when
a jit boundary treats a Python config value as a traced operand: every
new bool/str/tuple value either retraces the whole program or raises a
``TracerBoolConversionError`` deep inside the body.  Both hazards are
visible statically:

* **undeclared config param** — a parameter of a jitted function whose
  default is a Python bool, string, or tuple/list of constants (the
  classic tile-size/flag signature, e.g. ``interpret: bool = False``)
  but is not listed in ``static_argnames``/``static_argnums``;
* **traced branch** — a Python ``if``/``while`` inside a jitted body
  whose condition references a non-static parameter: at trace time the
  condition is a tracer, so the branch either crashes or silently bakes
  in one side.

Int/float defaults are deliberately NOT flagged: jax traces Python
scalars as weak-typed array operands without retracing, so they are
only a hazard when branched on — which the second rule catches.
Closure variables (the ``_gemm_fn(transA, ...)`` factory idiom, where
``lru_cache`` pins one closure per config) are legitimate and are not
parameters, so they never trigger either rule.
"""

from __future__ import annotations

import ast
from typing import Iterable, Set

from ..core import Checker, FileContext, Finding, register
from ._jit import JitSite, collect_jit_sites

#: parameter names that are never operands (self/cls)
_IGNORED = {"self", "cls"}


def _config_default(node: ast.expr) -> str:
    """'' if not a Python-config default, else a short type tag."""
    if isinstance(node, ast.Constant) and isinstance(node.value, bool):
        return "bool"
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return "str"
    if isinstance(node, (ast.Tuple, ast.List)) and all(
            isinstance(e, ast.Constant) for e in node.elts):
        return "tuple of constants"
    return ""


def _param_defaults(site: JitSite):
    """(param name, default node) pairs of the jitted function."""
    a = site.fn.args
    pos = list(a.posonlyargs) + list(a.args)
    for arg, default in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        yield arg.arg, default
    for arg, default in zip(a.kwonlyargs, a.kw_defaults):
        if default is not None:
            yield arg.arg, default


def _names_in(node: ast.expr) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


@register
class RetraceChecker(Checker):
    id = "retrace"
    description = ("jitted functions must declare bool/str/tuple config "
                   "params in static_argnames and not branch on traced "
                   "params")

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        for site in collect_jit_sites(ctx.tree):
            name = getattr(site.fn, "name", "<lambda>")

            for pname, default in _param_defaults(site):
                tag = _config_default(default)
                if tag and pname not in site.static and \
                        pname not in _IGNORED:
                    yield Finding(
                        self.id, ctx.rel, site.fn.lineno,
                        f"jitted {name}() parameter {pname}= has a "
                        f"Python-config default ({tag}) but is not in "
                        f"static_argnames — every distinct value "
                        f"retraces (or fails tracing); declare it "
                        f"static")

            if isinstance(site.fn, ast.Lambda):
                continue   # a lambda body has no if/while statements
            traced = {a.arg for a in site.params} - site.static - _IGNORED
            for node in ast.walk(site.fn):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                on = _names_in(node.test) & traced
                if on:
                    yield Finding(
                        self.id, ctx.rel, node.lineno,
                        f"jitted {name}() branches on traced "
                        f"parameter(s) {', '.join(sorted(on))} — at "
                        f"trace time the condition is a tracer "
                        f"(TracerBoolConversionError) or bakes in one "
                        f"side; use lax.cond/jnp.where or declare the "
                        f"parameter static")
