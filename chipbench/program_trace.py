"""The program's own spans in a traced run's profiler trace.

The program marks its layers with ``jax.profiler.TraceAnnotation`` spans
on the trace's clock, with counters as the spans' stats: ``dla.exec.*``
around each step of a kernel call in ``dla.engine.ExecEngine``
(``upload`` and ``download`` carry ``bytes``), ``predict.*`` around the
selection's tracing, compiling and evaluating.  Over the ``bench.window``
of the newest trace under ``chipbench/.state/traces/``, this module
computes:

- ``idle``: each idle gap of the device, given to the innermost program
  span (``dla.*``, ``predict.*``) that covers its midpoint, else to the
  innermost harness span (``bench.*``), else to ``outside``; seconds by
  span name, averaged over the chips;
- ``seconds``, ``bytes``, ``count``: the spans' durations in the window,
  their ``bytes`` stats and their number, by span name.

A program without these spans leaves ``count`` without them, and the
readers of ``chipbench/metrics/`` return nothing.  The trace is checked
to be the run's own: its window and busy time equal the run's reduction
(``trace_reduce``) within a microsecond.
"""

from __future__ import annotations

import functools
import glob
import os
from collections import defaultdict
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import common
import trace_reduce as tr

PROGRAM = ("dla.", "predict.")
HARNESS = "bench."
#: the spans of a kernel call in which data moves between host and device
TRANSFERS = ("dla.exec.upload", "dla.exec.download")
#: how far the trace's window and busy time may be from the run's, seconds
SAME_RUN_S = 1e-6

#: (start, end, name, stats) of one host span, times in nanoseconds
Span = Tuple[float, float, str, Dict[str, Any]]


@dataclass
class Split:
    window_s: float
    busy_s: float
    idle: Dict[str, float]
    seconds: Dict[str, float]
    bytes: Dict[str, float]
    count: Dict[str, int]


def innermost(spans: Sequence[Span], points: Sequence[float]
              ) -> List[Optional[str]]:
    """For each of ``points`` (ascending), the name of the innermost of
    ``spans`` (sorted by start, outer first; nested) that covers it, or
    ``None``."""
    out: List[Optional[str]] = []
    stack: List[Span] = []
    i = 0
    for t in points:
        while i < len(spans) and spans[i][0] <= t:
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


def split(spans: Sequence[Span], chips: Sequence[Sequence[tr.Interval]],
          lo: float, hi: float) -> Split:
    """The split of the window [lo, hi] (ns): ``spans`` are the host's
    spans (the window's own left out), ``chips`` each chip's operation
    intervals."""
    inside = sorted((sp for sp in spans if sp[1] > lo and sp[0] < hi),
                    key=lambda sp: (sp[0], -sp[1]))
    program = [sp for sp in inside if sp[2].startswith(PROGRAM)]
    harness = [sp for sp in inside if sp[2].startswith(HARNESS)]
    idle: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    for ops in chips:
        busy, merged = tr.union_seconds(ops, lo, hi)
        busy_total += busy
        holes = tr.gaps(merged, lo, hi)
        mids = [(s + e) / 2 for s, e in holes]
        for (s, e), p, h in zip(holes, innermost(program, mids),
                                innermost(harness, mids)):
            idle[p or h or "outside"] += e - s
    seconds: Dict[str, float] = defaultdict(float)
    nbytes: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    for s, e, name, stats in inside:
        seconds[name] += (min(e, hi) - max(s, lo)) * 1e-9
        nbytes[name] += stats.get("bytes", 0)
        count[name] += 1
    n = len(chips)
    return Split(window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
                 idle={k: v * 1e-9 / n for k, v in idle.items()},
                 seconds=dict(seconds), bytes=dict(nbytes),
                 count=dict(count))


@functools.lru_cache(maxsize=4)
def split_trace(path: str) -> Split:
    """:func:`split` of the ``.xplane.pb`` at ``path``, parsed once."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Span] = []
    chips: List[List[tr.Interval]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            chips.append([(s, e) for s, e, _ in
                          tr._events(lines["XLA Ops"])])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((HARNESS, *PROGRAM)):
                        s = float(ev.start_ns)
                        spans.append((s, s + float(ev.duration_ns), ev.name,
                                      dict(ev.stats)))
    windows = [(s, e) for s, e, name, _ in spans if name == tr.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {tr.WINDOW_SPAN!r} span in {path}")
    if not chips:
        raise ValueError(f"no TPU plane in {path}")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    return split([sp for sp in spans if sp[2] != tr.WINDOW_SPAN], chips,
                 lo, hi)


def newest_trace() -> str:
    files = glob.glob(os.path.join(str(common.TRACES), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {common.TRACES}")
    return max(files, key=os.path.getmtime)


def for_run(run) -> Optional[Split]:
    """The split of a traced run's own trace; ``None`` for an untraced
    run."""
    if run.reduced is None:
        return None
    path = newest_trace()
    got = split_trace(path)
    if abs(got.window_s - run.reduced.window_s) > SAME_RUN_S or \
            abs(got.busy_s - run.reduced.busy_s) > SAME_RUN_S:
        raise ValueError(
            f"{path} is not this run's trace: window {got.window_s} s and "
            f"busy {got.busy_s} s against the run's "
            f"{run.reduced.window_s} s and {run.reduced.busy_s} s")
    return got
