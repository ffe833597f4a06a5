"""From a profiler trace to device busy time, op times and idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``.  On a TPU the trace holds one plane per
chip (``/device:TPU:<i>``) with the lines ``XLA Modules`` (one event per
program run) and ``XLA Ops`` (one event per operation), and a host plane
(``/host:CPU``) whose ``python3`` line holds the harness's spans
(``bench.<name>``, from ``jax.profiler.TraceAnnotation``).  All events
are on one clock, in nanoseconds.

What it computes, over the window between the start of the first and the
end of the last ``bench.window`` span:

- ``busy_s``: the union of the intervals in which an operation ran, per
  chip, averaged over the chips;
- ``modules``: seconds and runs of each program, by its name without the
  hash (``jit__step``);
- ``ops``: self time of each operation by a stable name, the program's
  name and the operation's kind (``jit__step/fusion``,
  ``jit_f/custom-call:Cholesky``);
- ``idle``: the device's idle gaps, each given to the innermost harness
  span that covers its midpoint (``outside`` where none does), summed by
  span name.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
_HASH = re.compile(r"\(\d+\)$")
_OP = re.compile(r"^%?([A-Za-z_\-]+?)(?:\.\d+)?\s*=")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')

Interval = Tuple[float, float]


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    chips: int
    modules: Dict[str, Tuple[float, int]] = field(default_factory=dict)
    ops: Dict[str, float] = field(default_factory=dict)
    idle: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> Dict[str, List[list]]:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def union_seconds(intervals: Sequence[Interval], lo: float, hi: float
                  ) -> Tuple[float, List[Interval]]:
    """Length of the union of ``intervals`` clipped to [lo, hi], in the
    intervals' unit, and the merged intervals."""
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that ``merged`` (sorted, disjoint) leaves."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(event_name: str) -> str:
    """The kind of an XLA op event, without its number: ``fusion``,
    ``custom-call:Cholesky``."""
    m = _OP.match(event_name)
    kind = m.group(1) if m else event_name.split(" ")[0].lstrip("%")
    if kind == "custom-call":
        t = _TARGET.search(event_name)
        if t:
            kind += ":" + t.group(1)
    return kind


def self_times(events: Sequence[Tuple[float, float, str]]) -> List[float]:
    """Self time of each of ``events`` (sorted by start), which may nest:
    each event's duration less its children's, in the events' order."""
    own = [e - s for s, e, _ in events]
    stack: List[Tuple[float, int]] = []      # (end, index) of open events
    for i, (s, e, _) in enumerate(events):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= min(e, stack[-1][0]) - s
        stack.append((e, i))
    return own


def _events(line):
    for ev in line.events:
        yield float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns), \
            ev.name


def reduce_trace(path: str, window_span: str = WINDOW_SPAN) -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line)
                             if ev[2].startswith("bench."))
    windows = [(s, e) for s, e, n in spans if n == window_span]
    if not windows:
        raise ValueError(f"no {window_span!r} span in {path}")
    if not devices:
        raise ValueError(f"no TPU plane in {path}")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    spans = [sp for sp in spans if sp[2] != window_span]
    spans.sort()
    starts = [s for s, _, _ in spans]

    modules: Dict[str, list] = defaultdict(lambda: [0.0, 0])
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        mods = sorted(ev for ev in _events(lines["XLA Modules"])
                      if ev[1] > lo and ev[0] < hi)
        for s, e, name in mods:
            m = modules[_HASH.sub("", name)]
            m[0] += (min(e, hi) - max(s, lo)) * 1e-9
            m[1] += 1
        mod_starts = [s for s, _, _ in mods]
        op_events = sorted(ev for ev in _events(lines["XLA Ops"])
                           if ev[1] > lo and ev[0] < hi)
        busy, merged = union_seconds([(s, e) for s, e, _ in op_events],
                                     lo, hi)
        busy_total += busy
        clipped = [(max(s, lo), min(e, hi), n) for s, e, n in op_events]
        for (s, _, name), own in zip(clipped, self_times(clipped)):
            i = bisect.bisect_right(mod_starts, s) - 1
            module = _HASH.sub("", mods[i][2]) if i >= 0 and \
                mods[i][1] >= s else "?"
            ops[f"{module}/{op_name(name)}"] += own * 1e-9
        for gs, ge in gaps(merged, lo, hi):
            idle[_cover(spans, starts, (gs + ge) / 2)] += (ge - gs) * 1e-9
    chips = len(devices)
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total * 1e-9 / chips, chips=chips,
                   modules={k: (v[0], v[1]) for k, v in modules.items()},
                   ops=dict(ops),
                   idle={k: v / chips for k, v in idle.items()})


def _cover(spans, starts, t: float) -> str:
    """Name of the innermost span that covers ``t``: of those that do,
    the one that started last (spans nest)."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        s, e, name = spans[i]
        if e >= t:
            return name[len("bench."):]
    return "outside"
