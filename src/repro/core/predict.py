"""Model-based prediction of blocked algorithms (paper §4.1, Eq. 4.1–4.6).

A blocked algorithm's execution is a deterministic sequence of kernel calls;
its predicted runtime is the sum of the per-call model estimates.  Summary
statistics propagate: min/med/max/mean add, standard deviations add in
quadrature (uncorrelated-estimate assumption, Eq. 4.3).  Performance and
efficiency predictions follow Eq. 4.4–4.6 including the second/first-order
Taylor corrections for the mean/std of the reciprocal.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .model import ModelSet
from .sampler import STATS, Stats

_STD = STATS.index("std")


def _span(name: str, **stats):
    """A ``jax.profiler`` span carrying ``stats``, recorded only while a
    trace is; jax is imported on first use, so this module loads without
    it."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, **stats)


@dataclass(frozen=True)
class KernelCall:
    """One kernel invocation inside an algorithm's call sequence."""

    kernel: str          # e.g. "gemm"
    case: Tuple          # flag/layout case, e.g. ("N", "T")
    sizes: Tuple[int, ...]

    def __repr__(self) -> str:  # compact trace printing
        c = ",".join(map(str, self.case))
        s = "x".join(map(str, self.sizes))
        return f"{self.kernel}[{c}]({s})"


def predict_runtime(calls: Iterable[KernelCall], models: ModelSet) -> Stats:
    """t_pred^s = sum over calls of t_est^s  (Eq. 4.2/4.3)."""
    acc = {s: 0.0 for s in STATS}
    var = 0.0
    for call in calls:
        est = models.estimate(call.kernel, call.case, call.sizes)
        for s in ("min", "med", "max", "mean"):
            acc[s] += est[s]
        var += est["std"] ** 2
    acc["std"] = var ** 0.5
    return Stats(**{"min": acc["min"], "med": acc["med"], "max": acc["max"],
                    "mean": acc["mean"], "std": acc["std"]})


# ----------------------------------------------------------------- batched --

@dataclass(frozen=True)
class CallGroup:
    """All calls to one (kernel, case) across a batch of call sequences."""

    kernel: str
    case: Tuple
    sizes: np.ndarray    # (K, d) float64 size arguments, one row per call
    config: np.ndarray   # (K,) intp — index of the originating call sequence


@dataclass(frozen=True)
class CompiledCalls:
    """A batch of call sequences compiled to per-(kernel, case) matrices.

    This is the "compiled" form of §4.1's deterministic call sequences: the
    per-call Python structure is gone, and prediction reduces to one
    stacked polynomial evaluation per group plus one scatter-add back onto
    configs.  ``flat_config`` is that scatter's index: the config of every
    call, concatenated in group order, derived once from the groups.
    """

    n_configs: int
    groups: Tuple[CallGroup, ...]
    flat_config: np.ndarray = field(init=False, repr=False, compare=False)

    def __post_init__(self):
        object.__setattr__(self, "flat_config", np.concatenate(
            [g.config for g in self.groups]) if self.groups
            else np.zeros(0, dtype=np.intp))

    @property
    def n_calls(self) -> int:
        return sum(g.sizes.shape[0] for g in self.groups)


def compile_calls(calls_per_config: Sequence[Iterable[KernelCall]]
                  ) -> CompiledCalls:
    """Group a batch of call sequences into per-(kernel, case) size matrices.

    The grouping is one ``predict.compile`` profiler span (stat
    ``configs``).
    """
    seqs = list(calls_per_config)
    with _span("predict.compile", configs=len(seqs)):
        buckets: Dict[Tuple[str, Tuple], Tuple[list, list]] = {}
        for i, calls in enumerate(seqs):
            for call in calls:
                szs, cfg = buckets.setdefault((call.kernel, call.case),
                                              ([], []))
                szs.append(call.sizes)
                cfg.append(i)
        groups = tuple(
            CallGroup(kernel=kernel, case=case,
                      sizes=np.asarray(szs, dtype=np.float64),
                      config=np.asarray(cfg, dtype=np.intp))
            for (kernel, case), (szs, cfg) in buckets.items()
        )
        return CompiledCalls(n_configs=len(seqs), groups=groups)


Tracer = Callable[[int, int], List[KernelCall]]


class TraceCache:
    """Memoizes tracer call sequences and compiled batches across sweeps.

    Tracing a blocked algorithm is a pure function of ``(n, b)`` — the call
    sequence is fully determined by the problem and block size (§4.1) — yet
    it is the one remaining Python loop on the prediction hot path.  The
    cache is keyed on ``(tracer identity, n, b)`` (the tracer object itself,
    which also keeps it alive, so ids are never recycled into stale hits)
    and additionally memoizes whole compiled sweep/grid/selection batches,
    so repeated sweeps over the same candidate set reuse one
    :class:`CompiledCalls` instead of re-tracing and re-grouping every
    point.  Neither depends on the models, so one cache serves any number
    of model sets.

    A cache a caller owns (``TraceCache()``, ``cache=``) never evicts its
    entries: hold ON to the tracer objects you sweep with (rebuilding a
    tracer closure per request defeats the cache and grows it unboundedly
    in a long-lived shared engine); call :meth:`clear` to reset.  The one
    the selection entry points share when given no engine is bounded
    (:data:`IMPLICIT_CACHE_ENTRIES`).
    """

    def __init__(self):
        self._calls: Dict[Tuple, Tuple[KernelCall, ...]] = {}
        self._compiled: Dict[Tuple, CompiledCalls] = {}
        self.hits = 0
        self.misses = 0

    def _lookup(self, table: Dict, key: Tuple):
        return table.get(key)

    def _store(self, table: Dict, key: Tuple, value) -> None:
        table[key] = value

    def calls(self, tracer: Tracer, n: int, b: int) -> Tuple[KernelCall, ...]:
        """The (cached) call sequence of one traced configuration; each
        miss traces it in one ``predict.trace`` profiler span (stats
        ``n``, ``b``)."""
        key = (tracer, n, b)
        out = self._lookup(self._calls, key)
        if out is None:
            self.misses += 1
            with _span("predict.trace", n=n, b=b):
                out = tuple(tracer(n, b))
            self._store(self._calls, key, out)
        else:
            self.hits += 1
        return out

    def _compiled_batch(self, key: Tuple,
                        points: Sequence[Tuple[Tracer, int, int]], *,
                        hits: int = 1) -> CompiledCalls:
        out = self._lookup(self._compiled, key)
        if out is None:
            out = compile_calls([self.calls(*p) for p in points])
            self._store(self._compiled, key, out)
        else:
            self.hits += hits   # whole-batch reuse: calls() is never consulted
        return out

    def compiled_selection(self, tracers: Sequence[Tracer], n: int,
                           candidates: Sequence[int]) -> CompiledCalls:
        """One reusable compiled batch for a tracer set x block-size grid
        at one ``n``, tracer-major: the batch a joint variant and
        block-size selection evaluates.  A hit traces and groups nothing,
        so it emits no ``predict.trace`` or ``predict.compile`` span; it
        counts one hit per configuration, as the per-configuration
        lookups it replaces did."""
        tracers, candidates = tuple(tracers), tuple(candidates)
        points = [(t, n, b) for t in tracers for b in candidates]
        return self._compiled_batch(("selection", tracers, n, candidates),
                                    points, hits=len(points))

    def compiled_sweep(self, tracer: Tracer, n: int,
                       candidates: Sequence[int]) -> CompiledCalls:
        """One reusable compiled batch for a whole block-size sweep."""
        return self._compiled_batch(
            ("sweep", tracer, n, tuple(candidates)),
            [(tracer, n, b) for b in candidates])

    def compiled_grid(self, tracer: Tracer, ns: Sequence[int],
                      bs: Sequence[int]) -> CompiledCalls:
        """One reusable compiled batch for a full (n, b) grid."""
        return self._compiled_batch(
            ("grid", tracer, tuple(ns), tuple(bs)),
            [(tracer, n, b) for n in ns for b in bs])

    def clear(self) -> None:
        self._calls.clear()
        self._compiled.clear()
        self.hits = self.misses = 0


#: entries each table (call sequences, compiled batches) of the implicit
#: engines' shared :class:`TraceCache` keeps before it evicts the least
#: recently used: a few hundred (n, b) points, and as many selections
IMPLICIT_CACHE_ENTRIES = 256


class _ImplicitTraceCache(TraceCache):
    """The process-wide cache of the engines :func:`resolve_engine` builds
    when a caller passes none.  No caller sees it, so it is bounded: past
    :data:`IMPLICIT_CACHE_ENTRIES` in a table the least recently used
    entry goes.  A lock keeps the recency order whole across threads."""

    def __init__(self):
        super().__init__()
        self._lock = threading.Lock()

    def _lookup(self, table: Dict, key: Tuple):
        with self._lock:
            out = table.pop(key, None)
            if out is not None:
                table[key] = out      # dicts keep insertion order: now last
            return out

    def _store(self, table: Dict, key: Tuple, value) -> None:
        with self._lock:
            table[key] = value
            while len(table) > IMPLICIT_CACHE_ENTRIES:
                del table[next(iter(table))]


_IMPLICIT_CACHE = _ImplicitTraceCache()


class PredictionEngine:
    """Vectorized batched prediction over configuration sweeps (§4.5/§4.6).

    Where :func:`predict_runtime` walks one call sequence through per-call
    dict lookups and per-stat polynomial evaluations, this engine compiles a
    whole batch of call sequences (one per candidate configuration) into
    per-(kernel, case) size matrices and predicts every configuration with a
    handful of stacked matrix products.  Statistics propagate exactly as in
    Eq. 4.2/4.3: min/med/max/mean sum per config, std adds in quadrature.
    The scalar path remains the reference oracle; both agree to ~1e-10.

    Every engine owns a :class:`TraceCache` (pass ``cache=`` to share one
    across engines; the engines :func:`resolve_engine` builds share one
    bounded process-wide cache): ``sweep``/``grid`` compile their whole
    candidate set once into a reusable :class:`CompiledCalls` artifact —
    also available directly via :meth:`compile_sweep`/:meth:`compile_grid`
    — so repeated sweeps skip both the Python tracing loop and the
    re-grouping, and ``predict_compiled`` consumes the artifact directly.
    """

    def __init__(self, models: ModelSet, *,
                 cache: Optional[TraceCache] = None):
        self.models = models
        self.cache = cache if cache is not None else TraceCache()

    def predict_compiled(self, compiled: CompiledCalls) -> np.ndarray:
        """(n_configs, len(STATS)) runtime statistics for a compiled batch.

        Each (kernel, case) group is evaluated batch-wise, and all groups
        are accumulated onto their configs with one ``np.add.at`` over the
        batch's ``flat_config`` index (std in quadrature).  The call is one
        ``predict.evaluate`` profiler span.
        """
        with _span("predict.evaluate"):
            if not compiled.groups:
                return np.zeros((compiled.n_configs, len(STATS)),
                                dtype=np.float64)
            est = np.concatenate(
                [np.asarray(self.models[g.kernel].estimate_batch(g.case,
                                                                 g.sizes))
                 for g in compiled.groups], axis=0)
            est[:, _STD] **= 2
            acc = np.zeros((compiled.n_configs, len(STATS)),
                           dtype=np.float64)
            np.add.at(acc, compiled.flat_config, est)
            acc[:, _STD] = np.sqrt(acc[:, _STD])
            return acc

    def predict_batch(self,
                      calls_per_config: Sequence[Iterable[KernelCall]],
                      ) -> np.ndarray:
        """Predict runtime stats for many call sequences at once: (N, 5)."""
        return self.predict_compiled(compile_calls(calls_per_config))

    def predict_stats(self,
                      calls_per_config: Sequence[Iterable[KernelCall]],
                      ) -> List[Stats]:
        return [Stats(*map(float, row))
                for row in self.predict_batch(calls_per_config)]

    # ------------------------------------------------- cached sweep/grid --
    def compile_sweep(self, tracer: Tracer, n: int,
                      candidates: Sequence[int]) -> CompiledCalls:
        """Trace + compile a block-size sweep once; cached across calls."""
        return self.cache.compiled_sweep(tracer, n, candidates)

    def compile_grid(self, tracer: Tracer, ns: Sequence[int],
                     bs: Sequence[int]) -> CompiledCalls:
        """Trace + compile a full (n, b) grid once; cached across calls."""
        return self.cache.compiled_grid(tracer, ns, bs)

    def sweep(self, tracer: Tracer, n: int,
              candidates: Sequence[int]) -> np.ndarray:
        """Predict one algorithm over a block-size grid: (len(candidates), 5)."""
        return self.predict_compiled(self.compile_sweep(tracer, n,
                                                        candidates))

    def grid(self, tracer: Tracer,
             ns: Sequence[int], bs: Sequence[int]) -> np.ndarray:
        """Predict a full (n, b) grid in one shot: (len(ns), len(bs), 5)."""
        flat = self.predict_compiled(self.compile_grid(tracer, ns, bs))
        return flat.reshape(len(ns), len(bs), len(STATS))


def resolve_engine(models: ModelSet,
                   engine: Optional[PredictionEngine]) -> PredictionEngine:
    """The engine a selection entry point predicts with.

    A supplied engine must have been built on ``models`` itself — a
    mismatch raises instead of handing back results from the wrong model
    set.  Without an engine the new one shares the bounded process-wide
    trace cache, so a caller that never keeps an engine still traces and
    compiles each candidate batch once.
    """
    if engine is not None:
        if engine.models is not models:
            raise ValueError(
                "the supplied engine was built on a different ModelSet than "
                "the models argument; predictions would silently come from "
                "the engine's set")
        return engine
    return PredictionEngine(models, cache=_IMPLICIT_CACHE)


def predict_performance(runtime: Stats, cost_flops: float) -> Dict[str, float]:
    """FLOP-rate prediction from a runtime prediction (Eq. 4.4/4.5)."""
    mu, sigma = runtime.mean, runtime.std
    out = {
        "min": cost_flops / runtime.max if runtime.max > 0 else float("inf"),
        "med": cost_flops / runtime.med if runtime.med > 0 else float("inf"),
        "max": cost_flops / runtime.min if runtime.min > 0 else float("inf"),
    }
    if mu > 0:
        out["mean"] = cost_flops / mu * (1.0 + sigma ** 2 / mu ** 2)
        out["std"] = cost_flops * sigma / mu ** 2
    else:
        out["mean"], out["std"] = float("inf"), 0.0
    return out


def predict_efficiency(performance: Dict[str, float],
                       peak_flops: float) -> Dict[str, float]:
    """Eq. 4.6: efficiency = performance / peak."""
    return {s: v / peak_flops for s, v in performance.items()}


# ------------------------------------------------------------------ errors --

def relative_error(pred: float, meas: float) -> float:
    """x_RE = (pred - meas) / meas  (§4.2).

    A zero measurement has no defined relative error; return ``nan`` so
    error sweeps over empty/degenerate measurements don't crash.
    """
    if meas == 0:
        return float("nan")
    return (pred - meas) / meas


def absolute_relative_error(pred: float, meas: float) -> float:
    """``|pred - meas| / meas`` — the magnitude of :func:`relative_error`
    (nan when the measurement is zero)."""
    return abs(relative_error(pred, meas))
