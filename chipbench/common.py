"""What every cell of the benchmark shares: paths, seeds, spans, results.

Nothing here imports the program under test.
"""

from __future__ import annotations

import contextlib
import importlib.util
import shutil
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Sequence

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: the checkout's compile cache, model stores and traces: fixed paths
#: inside the checkout (listed in ``chipbench/.gitignore``)
STATE = BENCH / ".state"
JAX_CACHE = STATE / "jax_cache"
TRACES = STATE / "traces"


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: Optional[str] = None):
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = name or "chipbench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def reference_path(config: Dict[str, Any]) -> Path:
    """The plain reference beside a configuration's file of sizes."""
    return (ROOT / config["file"]).with_suffix(".ref.py")


def jax_seed(seed: int, purpose: str) -> int:
    """A 31-bit key for ``jax.random.PRNGKey`` from any ``--seed`` (which
    may need more than 32 bits) and what the key is for."""
    words = np.random.SeedSequence(
        [seed & 0xFFFFFFFF, seed >> 32,
         *purpose.encode()]).generate_state(1)
    return int(words[0] & 0x7FFFFFFF)


def host_rng(seed: int, purpose: str) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32,
                                  *purpose.encode()])


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) of every value, by linear
    interpolation between order statistics (numpy's default)."""
    if not values:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


class Spans:
    """Host spans of the harness, recorded on the profiler's clock too.

    Each span is a ``jax.profiler.TraceAnnotation`` while a trace is being
    recorded, so the trace reduction can say what the host was doing in
    each gap of the device.  The spans' own durations are kept in memory
    (``seconds[name]``), whether or not a trace is recorded.
    """

    def __init__(self) -> None:
        self.seconds: Dict[str, List[float]] = {}
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield
        self.seconds.setdefault(name, []).append(time.perf_counter() - t0)


class Check:
    """The numbers that decide ``correct``, each beside its limit."""

    def __init__(self) -> None:
        self.items: Dict[str, Dict[str, float]] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            math.isfinite(i["value"]) and i["value"] <= i["limit"]
            for i in self.items.values())


def device_info(count: int) -> Dict[str, Any]:
    import jax
    dev = jax.devices()[0]
    peak = 0
    for d in jax.devices()[:count]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


@dataclass
class Outcome:
    """What a kind's ``run`` hands back: end-to-end metrics, the counters
    and spans the per-layer readers read, and the check."""

    e2e: Dict[str, float]
    counters: Dict[str, Any]
    check: Check
    attempted: int
    failed: int
    device: Dict[str, Any]
    spans: Spans
    sizes: Dict[str, Any]
    reduced: Any = None                  # trace_reduce.Reduced, traced runs
    notes: Dict[str, Any] = field(default_factory=dict)
    held: Dict[str, Any] = field(default_factory=dict)   # for the control


class CompileCounter:
    """Counts the programs JAX compiles or loads from its persistent
    cache while ``active``: inside a measured window there should be
    none."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self) -> None:
        import jax
        self.count = 0
        self.active = False
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_: Any) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


@contextlib.contextmanager
def recorded_trace(spans: Spans, name: str) -> Iterator[Dict[str, Any]]:
    """Record a profiler trace of the block, framed by a ``bench.window``
    span; on exit, ``out["reduced"]`` holds its reduction."""
    import jax
    from trace_reduce import find_xplane, reduce_trace
    log_dir = TRACES / name
    shutil.rmtree(log_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    out: Dict[str, Any] = {}
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    spans.tracing = True
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield out
    finally:
        spans.tracing = False
        jax.profiler.stop_trace()
    out["reduced"] = reduce_trace(find_xplane(str(log_dir)))
