#!/usr/bin/env python
"""Docs lint: internal links must resolve, runnable snippets must run.

Usage::

    python tools/check_docs.py [--no-exec] [files...]

Checks every ``docs/*.md`` file plus ``README.md`` (or an explicit file
list):

* **links** — every relative markdown link ``[text](target)`` must point
  at an existing file, and ``#fragment`` anchors must match a heading in
  the target document (GitHub slug rules: lowercase, punctuation
  stripped, spaces to dashes);
* **wiki-links** — ``[[slug]]`` cross-references (prose shorthand for a
  sibling document) must resolve to ``docs/<slug>.md``;
* **snippets** — every fenced ```` ```python ```` block is executed, in
  file order, in one shared namespace per file (so later snippets can
  build on earlier ones).  Put ``<!-- docs-check: skip -->`` on the line
  directly above a fence to exclude a block (e.g. pseudocode);
* **deprecated kwargs** — python snippets must not call the legacy
  prediction entry points with the kwargs the ``PredictorSession``
  redesign deprecated (``suite=``/``cache=``/``repetitions=``/
  ``sizes_grid=``/``predictor=``): docs are the first
  thing readers copy, so the old API must not reappear in examples.

Exit code 0 when everything passes; 1 with a per-finding report
otherwise.  The CI fast lane runs this after the tests, and
``tests/test_docs.py`` runs the link check in-process.
"""

from __future__ import annotations

import argparse
import ast
import re
import sys
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))                    # for the tools.lint import

from tools.lint.checkers.deprecated_kwargs import (      # noqa: E402
    deprecated_call_findings)


def _rel(path: Path) -> str:
    """Repo-relative rendering when possible, absolute otherwise (explicit
    file arguments may live outside the repo)."""
    try:
        return str(path.relative_to(ROOT))
    except ValueError:
        return str(path)


_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_WIKI_LINK = re.compile(r"(?<!\[)\[\[([A-Za-z0-9._-]+)\]\](?!\])")
_HEADING = re.compile(r"^#{1,6}\s+(.*)$")
_FENCE = re.compile(r"^```(\w*)\s*$")
_SKIP_MARK = "<!-- docs-check: skip -->"


def doc_files(explicit: List[str]) -> List[Path]:
    """The files to lint: an explicit list, or docs/*.md + README.md."""
    if explicit:
        return [Path(f).resolve() for f in explicit]
    files = sorted((ROOT / "docs").glob("*.md"))
    readme = ROOT / "README.md"
    if readme.exists():
        files.append(readme)
    return files


def github_slug(heading: str) -> str:
    """GitHub's anchor slug for a heading (sufficient approximation:
    inline code/emphasis markers dropped, punctuation stripped,
    lowercased, spaces to dashes)."""
    text = re.sub(r"[`*_]", "", heading.strip())
    text = re.sub(r"[^\w\- ]", "", text.lower())
    return text.replace(" ", "-")


def anchors_of(path: Path) -> set:
    """All heading anchors a markdown file exposes."""
    out = set()
    in_fence = False
    for line in path.read_text().splitlines():
        if _FENCE.match(line):
            in_fence = not in_fence
            continue
        if in_fence:
            continue
        m = _HEADING.match(line)
        if m:
            out.add(github_slug(m.group(1)))
    return out


def check_links(path: Path) -> List[str]:
    """Unresolvable relative links/anchors in one file, as messages."""
    problems = []
    anchor_cache: Dict[Path, set] = {}
    in_fence = False
    for ln, line in enumerate(path.read_text().splitlines(), 1):
        if _FENCE.match(line):
            in_fence = not in_fence
            continue
        if in_fence:
            continue
        for target in _LINK.findall(line):
            if re.match(r"^[a-z][a-z0-9+.-]*:", target):   # http:, mailto:
                continue
            ref, _, frag = target.partition("#")
            dest = (path.parent / ref).resolve() if ref else path
            if ref and not dest.exists():
                problems.append(f"{_rel(path)}:{ln}: "
                                f"broken link -> {target}")
                continue
            if frag and dest.suffix == ".md":
                if dest not in anchor_cache:
                    anchor_cache[dest] = anchors_of(dest)
                if frag not in anchor_cache[dest]:
                    problems.append(f"{_rel(path)}:{ln}: "
                                    f"missing anchor -> {target}")
    return problems


def check_wiki_links(path: Path) -> List[str]:
    """Unresolvable ``[[slug]]`` cross-references, as messages.

    A wiki-link names a sibling document by slug: ``[[serving-prediction]]``
    must resolve to ``docs/serving-prediction.md``.  Fenced code blocks are
    exempt (``[[...]]`` is valid syntax in several languages).
    """
    problems = []
    in_fence = False
    for ln, line in enumerate(path.read_text().splitlines(), 1):
        if _FENCE.match(line):
            in_fence = not in_fence
            continue
        if in_fence:
            continue
        for slug in _WIKI_LINK.findall(line):
            target = ROOT / "docs" / f"{slug}.md"
            if not target.exists():
                problems.append(f"{_rel(path)}:{ln}: broken wiki-link -> "
                                f"[[{slug}]] (no docs/{slug}.md)")
    return problems


def check_deprecated_kwargs(path: Path) -> List[str]:
    """Doc snippets calling legacy entry points with deprecated kwargs.

    The shims keep the old forms *working* for one release, but docs are
    what readers copy — they must demonstrate the
    ``repro.tc.PredictorSession`` spelling exclusively.  The rule itself
    lives in ``tools/lint/checkers/deprecated_kwargs.py`` (reprolint's
    deprecated-kwarg checker) so docs and source share one definition.
    """
    problems = []
    for start, src in snippets_of(path):
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue                 # run_snippets reports the real error
        ast.increment_lineno(tree, start)   # anchor into the .md file
        for f in deprecated_call_findings(tree, _rel(path)):
            problems.append(f"{f.path}:{f.line}: snippet: {f.message}")
    return problems


def snippets_of(path: Path) -> List[Tuple[int, str]]:
    """(start line, source) of every runnable python snippet in a file."""
    out = []
    lines = path.read_text().splitlines()
    i = 0
    while i < len(lines):
        m = _FENCE.match(lines[i])
        if not m:
            i += 1
            continue
        lang = m.group(1)
        skip = i > 0 and _SKIP_MARK in lines[i - 1]
        start = i + 1
        i += 1
        block = []
        while i < len(lines) and not _FENCE.match(lines[i]):
            block.append(lines[i])
            i += 1
        i += 1                                   # closing fence
        if lang == "python" and not skip:
            out.append((start, "\n".join(block)))
    return out


def run_snippets(path: Path) -> List[str]:
    """Execute a file's snippets in one shared namespace; return errors."""
    namespace: Dict[str, object] = {"__name__": f"docs:{path.name}"}
    for start, src in snippets_of(path):
        try:
            code = compile(src, f"{_rel(path)}:{start}", "exec")
            exec(code, namespace)                # noqa: S102 (docs lint)
        except Exception as e:                   # noqa: BLE001 (report all)
            return [f"{_rel(path)}:{start}: snippet failed: "
                    f"{type(e).__name__}: {e}"]
    return []


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("files", nargs="*",
                    help="markdown files (default: docs/*.md + README.md)")
    ap.add_argument("--no-exec", action="store_true",
                    help="check links only, skip snippet execution")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))        # snippets import repro.*

    problems: List[str] = []
    n_snippets = 0
    for path in doc_files(args.files):
        problems += check_links(path)
        problems += check_wiki_links(path)
        problems += check_deprecated_kwargs(path)
        if not args.no_exec:
            snips = snippets_of(path)
            n_snippets += len(snips)
            problems += run_snippets(path)
    if problems:
        print("\n".join(problems))
        print(f"docs check FAILED: {len(problems)} problem(s)")
        return 1
    mode = "links only" if args.no_exec else \
        f"links + {n_snippets} snippets executed"
    print(f"docs check OK ({mode})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
