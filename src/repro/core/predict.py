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
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .fitting import monomials_jnp
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
class FusedBatch:
    """Padded size tensors + scatter indices for one-dispatch prediction.

    The per-(kernel, case) groups of a :class:`CompiledCalls` batch, padded
    to one rectangular ``(group, rows, dims)`` tensor so the whole batch
    evaluates as a single device program with no host round-trips:

    * ``sizes`` — ``(G, R, d_max)`` float64 size points.  Rows beyond a
      group's true call count are all-zero (the engine's degenerate-call
      mask turns them into exact-zero estimates), and dimensions beyond a
      group's true size rank are a benign ``1.0`` (every monomial carries
      exponent 0 there, so they contribute an exact factor of one);
    * ``segments`` — ``(G * R,)`` int32 config index per padded row, in
      row-major ``(group, row)`` order.  Padding rows map to the extra
      segment ``n_configs``, which the scatter-add drops — so padding can
      never leak into a real config's total;
    * ``flat_config`` — ``(n_calls,)`` intp config index per *real* call,
      concatenated in group order: the precomputed scatter indices the
      numpy backend accumulates all groups with in one ``np.add.at``;
    * ``dims`` / ``rows`` — each group's true size rank and call count
      (what the padding padded *from*).
    """

    sizes: np.ndarray
    segments: np.ndarray
    flat_config: np.ndarray
    dims: Tuple[int, ...]
    rows: Tuple[int, ...]


def _fuse_batch(groups: Tuple[CallGroup, ...], n_configs: int,
                pad_rows_to: Optional[int] = None) -> FusedBatch:
    """Pad per-group size matrices into one rectangular batch tensor."""
    if not groups:
        return FusedBatch(sizes=np.zeros((0, 0, 0), dtype=np.float64),
                          segments=np.zeros(0, dtype=np.int32),
                          flat_config=np.zeros(0, dtype=np.intp),
                          dims=(), rows=())
    rows = tuple(g.sizes.shape[0] for g in groups)
    dims = tuple(g.sizes.shape[1] for g in groups)
    n_rows = max(max(rows), pad_rows_to or 0)
    d_max = max(dims)
    sizes = np.zeros((len(groups), n_rows, d_max), dtype=np.float64)
    segments = np.full((len(groups), n_rows), n_configs, dtype=np.int32)
    for gi, g in enumerate(groups):
        k, d = g.sizes.shape
        sizes[gi, :k, :d] = g.sizes
        sizes[gi, :k, d:] = 1.0
        segments[gi, :k] = g.config
    return FusedBatch(sizes=sizes, segments=segments.reshape(-1),
                      flat_config=np.concatenate([g.config for g in groups]),
                      dims=dims, rows=rows)


@dataclass(frozen=True)
class CompiledCalls:
    """A batch of call sequences compiled to per-(kernel, case) matrices.

    This is the "compiled" form of §4.1's deterministic call sequences: the
    per-call Python structure is gone, and prediction reduces to one fused
    polynomial evaluation plus a scatter-add back onto configs.  Besides
    the per-group matrices (kept for the per-group reference path and
    introspection), the batch carries a :class:`FusedBatch` — the padded
    ``(group, rows, dims)`` size tensor and the segment/config scatter
    indices — emitted once by :func:`compile_calls` so no predict call
    ever re-derives them.
    """

    n_configs: int
    groups: Tuple[CallGroup, ...]
    fused: Optional[FusedBatch] = None

    @property
    def n_calls(self) -> int:
        return sum(g.sizes.shape[0] for g in self.groups)

    def fused_batch(self) -> FusedBatch:
        """The padded tensors + scatter indices (:class:`FusedBatch`).

        :func:`compile_calls` emits them eagerly; hand-built instances
        (``fused=None``) derive and memoize them on first use."""
        if self.fused is None:
            object.__setattr__(self, "fused",
                               _fuse_batch(self.groups, self.n_configs))
        return self.fused


def compile_calls(calls_per_config: Sequence[Iterable[KernelCall]], *,
                  pad_rows_to: Optional[int] = None) -> CompiledCalls:
    """Group a batch of call sequences into per-(kernel, case) size matrices.

    The returned :class:`CompiledCalls` also carries the padded
    :class:`FusedBatch` tensors the fused prediction path consumes.
    ``pad_rows_to`` forces the row axis to at least that width — results
    are bit-identical under any padding (padding rows scatter into a
    dropped segment), which the property tests pin.  The grouping is one
    ``predict.compile`` profiler span (stat ``configs``).
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
        return CompiledCalls(n_configs=len(seqs), groups=groups,
                             fused=_fuse_batch(groups, len(seqs),
                                               pad_rows_to))


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


#: evaluation backends a PredictionEngine can run its stacked polynomial
#: models on
BACKENDS = ("numpy", "jax")


# ------------------------------------------------------- fused evaluation --

def _zero_case_tensors(d: int):
    """An always-inside single piece evaluating to exactly zero — the
    stand-in for a (kernel, case) whose every call is degenerate and which
    therefore needs no model (Example 4.1 semantics)."""
    return (np.zeros((1, d)), np.full((1, d), np.inf),
            np.zeros((1, 1, d)), np.ones((1, 1, d)),
            np.zeros((1, 1, len(STATS))))


def _pad_model_tensors(per_case, fused: FusedBatch):
    """Pad per-case piece tensors to one (G, P, M, ·) batch.

    Padding *pieces* get ``lo=+inf, hi=-inf``: never inside, and at
    infinite clamp distance, so the piece lookup can never select them.
    Padding *monomials* are exact no-op rows (exponent 0, scale 1,
    coefficient 0), and padding *dims* of real pieces are always-inside
    (``lo=0, hi=+inf``) with exponent 0 — every pad contributes exactly
    nothing, which keeps the fused program bit-compatible with the
    per-group path's arithmetic.
    """
    d_max = fused.sizes.shape[2]
    tensors = [t if t is not None else _zero_case_tensors(d)
               for t, d in zip(per_case, fused.dims)]
    p_max = max(t[0].shape[0] for t in tensors)
    m_max = max(t[2].shape[1] for t in tensors)
    g = len(tensors)
    lo = np.full((g, p_max, d_max), np.inf)
    hi = np.full((g, p_max, d_max), -np.inf)
    exps = np.zeros((g, p_max, m_max, d_max))
    scl = np.ones((g, p_max, m_max, d_max))
    cof = np.zeros((g, p_max, m_max, len(STATS)))
    for gi, ((tlo, thi, te, ts, tc), d) in enumerate(zip(tensors,
                                                         fused.dims)):
        p, m = te.shape[0], te.shape[1]
        lo[gi, :p, :d] = tlo
        lo[gi, :p, d:] = 0.0
        hi[gi, :p, :d] = thi
        hi[gi, :p, d:] = np.inf
        exps[gi, :p, :m, :d] = te
        scl[gi, :p, :m, :d] = ts
        cof[gi, :p, :m, :] = tc
    return lo, hi, exps, scl, cof


_FUSED_JIT = None


def _fused_predict_impl(pts, lo, hi, exps, scl, cof, seg, *,
                        n_configs, std_col):
    """The whole compiled batch as ONE device program.

    ``pts (G, R, d)`` padded size points; ``lo/hi (G, P, d)`` piece
    domains; ``exps/scl (G, P, M, d)`` and ``cof (G, P, M, S)`` padded
    piece polynomials; ``seg (G*R,)`` config segment per row (padding
    rows map to the dropped segment ``n_configs``).  Fuses degenerate
    masking, piece lookup, design matrices, the stacked matmuls AND the
    config-wise scatter-add (std in quadrature) into a single dispatch;
    mirrors the per-group path exactly: first containing piece wins,
    out-of-domain rows clamp to the smallest squared distance, estimates
    clip at 0, degenerate rows are exact zeros.
    """
    import jax
    import jax.numpy as jnp

    live = jnp.all(pts > 0, axis=-1)                           # (G, R)
    safe = jnp.where(live[..., None], pts, 1.0)
    inside = jnp.all((safe[:, :, None, :] >= lo[:, None]) &
                     (safe[:, :, None, :] <= hi[:, None]), axis=-1)
    below = jnp.maximum(lo[:, None] - safe[:, :, None, :], 0.0)
    above = jnp.maximum(safe[:, :, None, :] - hi[:, None], 0.0)
    dist = (below ** 2).sum(-1) + (above ** 2).sum(-1)         # (G, R, P)
    pidx = jnp.where(inside.any(axis=-1), jnp.argmax(inside, axis=-1),
                     jnp.argmin(dist, axis=-1))                # (G, R)
    e = jnp.take_along_axis(exps, pidx[:, :, None, None], axis=1)
    s = jnp.take_along_axis(scl, pidx[:, :, None, None], axis=1)
    c = jnp.take_along_axis(cof, pidx[:, :, None, None], axis=1)
    # row-flatten (G, R) -> N so the shared design-matrix implementation
    # (monomials_jnp, also behind the per-group path) serves this one too
    flat_pts = safe.reshape(-1, safe.shape[-1])                # (N, d)
    x = monomials_jnp(flat_pts, e.reshape(-1, *e.shape[2:]),
                      s.reshape(-1, *s.shape[2:]))             # (N, M)
    out = jnp.maximum(
        jnp.einsum("nm,nms->ns", x, c.reshape(-1, *c.shape[2:])), 0.0)
    out = jnp.where(live.reshape(-1)[:, None], out, 0.0)       # (N, S)
    w = out.at[:, std_col].set(out[:, std_col] ** 2)
    tot = jax.ops.segment_sum(w, seg, num_segments=n_configs + 1)[:n_configs]
    return tot.at[:, std_col].set(jnp.sqrt(tot[:, std_col]))


def _fused_predict_jax(inputs, n_configs: int) -> np.ndarray:
    """Run the fused program jitted in float64 (one compile per batch
    shape signature, then cached by jax).  ``inputs`` is the device-
    resident ``(sizes, lo, hi, exps, scl, cof, segments)`` tuple, so a
    repeated sweep re-uploads nothing."""
    global _FUSED_JIT
    import jax

    if _FUSED_JIT is None:
        _FUSED_JIT = jax.jit(_fused_predict_impl,
                             static_argnames=("n_configs", "std_col"))
    with jax.enable_x64():
        return np.asarray(_FUSED_JIT(*inputs, n_configs=n_configs,
                                     std_col=_STD))


class PredictionEngine:
    """Vectorized batched prediction over configuration sweeps (§4.5/§4.6).

    Where :func:`predict_runtime` walks one call sequence through per-call
    dict lookups and per-stat polynomial evaluations, this engine compiles a
    whole batch of call sequences (one per candidate configuration) into
    per-(kernel, case) size matrices and predicts every configuration with a
    handful of stacked matrix products.  Statistics propagate exactly as in
    Eq. 4.2/4.3: min/med/max/mean sum per config, std adds in quadrature.
    The scalar path remains the reference oracle; both agree to ~1e-10.

    ``backend`` selects how the stacked polynomials are evaluated:
    ``"numpy"`` (the reference batched path — per-group evaluation, all
    groups accumulated with one precomputed scatter) or ``"jax"`` — piece
    lookup, design matrices, every group's stacked matmuls AND the
    config-wise scatter-add fused into ONE ``jax.jit``-compiled float64
    program over the batch's padded ``(group, rows, ...)`` tensors, so a
    whole compiled batch is a single dispatch with no host round-trips
    (agrees with numpy to ~1e-8; XLA compiles once per batch shape).  The
    per-group path survives as :meth:`predict_compiled_grouped`, the
    fused path's equivalence oracle.

    Every engine owns a :class:`TraceCache` (pass ``cache=`` to share one
    across engines; the engines :func:`resolve_engine` builds share one
    bounded process-wide cache): ``sweep``/``grid`` compile their whole
    candidate set once into a reusable :class:`CompiledCalls` artifact —
    also available directly via :meth:`compile_sweep`/:meth:`compile_grid`
    — so repeated sweeps skip both the Python tracing loop and the
    re-grouping, and ``predict_compiled`` consumes the artifact directly.
    """

    def __init__(self, models: ModelSet, *, backend: str = "numpy",
                 cache: Optional[TraceCache] = None):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"choose from {BACKENDS}")
        self.models = models
        self.backend = backend
        self.cache = cache if cache is not None else TraceCache()

    def predict_compiled(self, compiled: CompiledCalls) -> np.ndarray:
        """(n_configs, len(STATS)) runtime statistics for a compiled batch.

        The fused path: on ``backend="jax"`` the whole batch — every
        group's piece lookup, design matrices and matmuls plus the
        config scatter-add — runs as one jitted device program over the
        batch's :class:`FusedBatch` tensors; on ``"numpy"`` groups are
        evaluated batch-wise and accumulated with a single ``np.add.at``
        over the precomputed ``flat_config`` scatter indices.  Either
        way there is no per-group Python accumulation loop.  The call is
        one ``predict.evaluate`` profiler span (stat ``backend``).
        """
        with _span("predict.evaluate", backend=self.backend):
            if not compiled.groups:
                return np.zeros((compiled.n_configs, len(STATS)),
                                dtype=np.float64)
            fused = compiled.fused_batch()
            if self.backend == "jax":
                return _fused_predict_jax(
                    self._fused_device_inputs(compiled), compiled.n_configs)
            est = np.concatenate(
                [np.asarray(self.models[g.kernel].estimate_batch(g.case,
                                                                 g.sizes))
                 for g in compiled.groups], axis=0)
            est[:, _STD] **= 2
            acc = np.zeros((compiled.n_configs, len(STATS)),
                           dtype=np.float64)
            np.add.at(acc, fused.flat_config, est)
            acc[:, _STD] = np.sqrt(acc[:, _STD])
            return acc

    def predict_compiled_grouped(self, compiled: CompiledCalls) -> np.ndarray:
        """The per-group reference path (PR-2 semantics), kept as the
        fused path's equivalence oracle.

        One ``estimate_batch`` evaluation — and, on ``backend="jax"``,
        one jitted dispatch — per (kernel, case) group, accumulated
        host-side with per-stat ``np.bincount``; agrees with
        :meth:`predict_compiled` to ~1e-8 (the two paths associate the
        per-config additions differently, so agreement is to rounding,
        not bit-for-bit).
        """
        acc = np.zeros((compiled.n_configs, len(STATS)), dtype=np.float64)
        for g in compiled.groups:
            est = np.asarray(self.models[g.kernel].estimate_batch(
                g.case, g.sizes, backend=self.backend))
            for j in range(len(STATS)):
                w = est[:, j] ** 2 if j == _STD else est[:, j]
                acc[:, j] += np.bincount(g.config, weights=w,
                                         minlength=compiled.n_configs)
        acc[:, _STD] = np.sqrt(acc[:, _STD])
        return acc

    def _fused_model_tensors(self, compiled: CompiledCalls):
        """Padded (G, P, M, ·) model tensors for a compiled batch.

        Built from each case's :meth:`~repro.core.model.CaseModel.
        padded_tensors` and memoized ON the batch (a single entry,
        replaced whenever the model set or any per-case tensor identity
        changes — so a mutated model never serves stale tensors, and a
        long-lived batch never accumulates tensors for model sets it no
        longer predicts with).  A case that is missing but whose every
        call is degenerate gets an exact-zero stand-in — the same
        no-model-needed semantics as the scalar path; a live call to a
        missing case raises ``KeyError``.
        """
        per_case = []
        for g in compiled.groups:
            model = self.models[g.kernel]
            cm = model.cases.get(tuple(g.case))
            if cm is not None and cm.pieces:
                per_case.append(cm.padded_tensors())
                continue
            if np.any(np.all(g.sizes > 0, axis=1)):
                if cm is not None:
                    raise KeyError("empty case model")
                raise KeyError(f"{g.kernel}: no model for case {g.case!r} "
                               f"(have {list(model.cases)})")
            per_case.append(None)
        hit = compiled.__dict__.get("_fused_model_cache")
        if hit is not None and hit[0] is self.models \
                and len(hit[1]) == len(per_case) \
                and all(a is b for a, b in zip(hit[1], per_case)):
            return hit[2]
        tensors = _pad_model_tensors(per_case, compiled.fused_batch())
        object.__setattr__(compiled, "_fused_model_cache",
                           (self.models, tuple(per_case), tensors))
        return tensors

    def _fused_device_inputs(self, compiled: CompiledCalls):
        """Device-resident float64 inputs for the fused jax program.

        The padded size/model tensors are immutable once built, so their
        ``jnp`` copies are memoized on the batch (a single entry keyed
        by the model tensors' identity, which
        :meth:`_fused_model_tensors` already revalidates against
        mutation and model-set changes) — a repeated sweep is one
        dispatch with zero host-to-device transfers, and stale device
        buffers are dropped as soon as the model tensors change.
        """
        tensors = self._fused_model_tensors(compiled)
        hit = compiled.__dict__.get("_fused_device_cache")
        if hit is not None and hit[0] is tensors:
            return hit[1]
        import jax
        import jax.numpy as jnp

        fused = compiled.fused_batch()
        with jax.enable_x64():
            inputs = (jnp.asarray(fused.sizes),
                      *(jnp.asarray(t) for t in tensors),
                      jnp.asarray(fused.segments))
        object.__setattr__(compiled, "_fused_device_cache",
                           (tensors, inputs))
        return inputs

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


def resolve_engine(models: ModelSet, backend: Optional[str],
                   engine: Optional[PredictionEngine]) -> PredictionEngine:
    """Resolve the ``backend=``/``engine=`` pair a selection entry point got.

    An explicit ``backend`` (or the ``models`` argument itself) must not be
    silently overridden by a supplied engine — conflicting requests raise
    instead of handing back results from the wrong evaluation path or the
    wrong model set.  Without an engine the new one shares the bounded
    process-wide trace cache, so a caller that never keeps an engine still
    traces and compiles each candidate batch once.
    """
    if engine is not None:
        if backend is not None and backend != engine.backend:
            raise ValueError(
                f"backend={backend!r} conflicts with the supplied engine's "
                f"backend={engine.backend!r}; pass one or the other")
        if engine.models is not models:
            raise ValueError(
                "the supplied engine was built on a different ModelSet than "
                "the models argument; predictions would silently come from "
                "the engine's set")
        return engine
    return PredictionEngine(models, backend=backend or "numpy",
                            cache=_IMPLICIT_CACHE)


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
