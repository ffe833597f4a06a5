"""Algorithm selection and block-size optimization (paper §4.5 / §4.6).

Given a set of mathematically-equivalent blocked-algorithm variants — each
represented by a *tracer* producing its kernel-call sequence for a problem
size n and block size b — rank them by predicted runtime, entirely without
executing any of them.  Block-size optimization evaluates the prediction over
a candidate grid of b and returns the argmin plus the whole profile (used to
compute the paper's "performance yield" against empirical optima).

Every entry point runs on the vectorized :class:`PredictionEngine` (the
batch of candidate configurations is predicted with a handful of array
ops); the scalar per-call :func:`~repro.core.predict.predict_runtime` is
its equivalence oracle, which tests call directly.  Repeated selections
reuse a trace cache (traced call sequences and each compiled candidate batch)
instead of re-tracing: the ``engine=``'s own, or without one a bounded
cache shared by every engine built for a call that passed none.  Either
way each selection evaluates every candidate's models anew.

:func:`select_contraction_algorithm` extends the same selection interface
to tensor contractions (paper Ch. 6) via :mod:`repro.tc` — micro-benchmark
based candidate models ranked through the identical batched engine.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from .model import ModelSet
from .predict import PredictionEngine, Tracer, resolve_engine
from .sampler import STATS, Stats


@dataclass(frozen=True)
class RankedAlgorithm:
    """One ranked blocked-algorithm variant: its name, predicted runtime
    statistics, and the block size the prediction was made at."""

    name: str
    runtime: Stats
    block_size: int


def rank_algorithms(tracers: Mapping[str, Tracer], models: ModelSet,
                    n: int, b: int, *, stat: str = "med",
                    engine: Optional[PredictionEngine] = None,
                    ) -> List[RankedAlgorithm]:
    """Predict every variant's runtime and sort ascending (§4.5)."""
    names = list(tracers)
    eng = resolve_engine(models, engine)
    compiled = eng.cache.compiled_selection(
        [tracers[name] for name in names], n, [b])
    ranked = [RankedAlgorithm(name=name, runtime=Stats(*map(float, row)),
                              block_size=b)
              for name, row in zip(names, eng.predict_compiled(compiled))]
    ranked.sort(key=lambda r: getattr(r.runtime, stat))
    return ranked


def select_algorithm(tracers: Mapping[str, Tracer], models: ModelSet,
                     n: int, b: int, *, stat: str = "med",
                     engine: Optional[PredictionEngine] = None) -> str:
    """The name of the variant with the fastest predicted runtime —
    ``rank_algorithms(...)[0].name``, same keywords."""
    return rank_algorithms(tracers, models, n, b, stat=stat,
                           engine=engine)[0].name


def optimize_block_size(tracer: Tracer, models: ModelSet, n: int,
                        candidates: Sequence[int], *, stat: str = "med",
                        engine: Optional[PredictionEngine] = None,
                        ) -> Tuple[int, Dict[int, float]]:
    """b_pred = argmin_b t_pred(n, b) over the candidate grid (§4.6)."""
    eng = resolve_engine(models, engine)
    vals = eng.sweep(tracer, n, candidates)[:, STATS.index(stat)]
    profile = {b: float(v) for b, v in zip(candidates, vals)}
    b_pred = min(profile, key=profile.get)
    return b_pred, profile


def optimize_algorithm_and_block_size(
        tracers: Mapping[str, Tracer], models: ModelSet, n: int,
        candidates: Sequence[int], *, stat: str = "med",
        engine: Optional[PredictionEngine] = None,
) -> Tuple[str, int, float]:
    """Joint variant + block-size selection: the paper's two goals combined.

    One compiled batch over the whole variants x candidates grid; the
    first minimum wins ties, in tracer then candidate order."""
    eng = resolve_engine(models, engine)
    names = list(tracers)
    vals = eng.predict_compiled(eng.cache.compiled_selection(
        [tracers[name] for name in names], n, candidates))
    grid = vals[:, STATS.index(stat)].reshape(len(names), len(candidates))
    vi, bi = divmod(int(grid.argmin()), len(candidates))
    return names[vi], candidates[bi], float(grid[vi, bi])


# ------------------------------------------------- contractions (Ch. 6) --

def select_contraction_algorithm(spec, sizes: Mapping[str, int], *,
                                 stat: str = "med",
                                 repetitions: Optional[int] = None,
                                 predictor=None, session=None) -> str:
    """Ch. 6 counterpart of :func:`select_algorithm`: the contraction
    algorithm (traversal x kernel, batched kernels included) with the
    fastest predicted total runtime.

    Runs on :class:`repro.tc.ContractionPredictor` — deduplicated
    cache-aware micro-benchmarks compiled through the same batched
    :class:`PredictionEngine` the blocked-algorithm entry points use.
    Pass ``session=`` (a :class:`repro.tc.PredictorSession`) to share its
    suite measurements and compiled batches across calls; the per-call
    ``repetitions=``/``predictor=`` keywords are DEPRECATED in favor of
    the session (one release of shim support).
    """
    from ..tc.session import warn_deprecated_kwargs  # lazy: tc needs core
    from .contractions import ContractionSpec, _session_for
    if predictor is not None:
        if session is not None:
            raise ValueError("session= already owns the predictor "
                             "resources; pass one or the other")
        if repetitions is not None:
            raise ValueError("repetitions= applies to a newly built "
                             "predictor; the supplied predictor's suite "
                             "already fixes it")
        want = spec if isinstance(spec, ContractionSpec) else \
            ContractionSpec.parse(spec)
        if predictor.spec != want or predictor.sizes != dict(sizes):
            raise ValueError(
                f"the supplied predictor was built for "
                f"{predictor.spec.einsum_expr()} at {predictor.sizes}, not "
                f"{want.einsum_expr()} at {dict(sizes)}; the selection "
                f"would silently answer the wrong contraction")
        warn_deprecated_kwargs(
            "select_contraction_algorithm",
            "session.select_contraction_algorithm (the session memoizes "
            "the predictor)",
            {"predictor": predictor})
        return predictor.rank(stat=stat)[0].name
    sess = _session_for("select_contraction_algorithm", session,
                        repetitions=repetitions)
    return sess.select_contraction_algorithm(spec, sizes, stat=stat)


def _resolve_chain_predictor(chain, sizes, repetitions, predictor):
    """Build (or consistency-check a supplied) ChainPredictor."""
    from ..tc.chains import ChainPredictor, ChainSpec  # lazy: tc needs core
    if predictor is None:
        return ChainPredictor(chain, sizes, repetitions=repetitions)
    if repetitions is not None:
        raise ValueError("repetitions= applies to a newly built predictor; "
                         "the supplied predictor's suite already fixes it")
    want = ChainSpec.parse(chain)
    if predictor.chain != want or predictor.sizes != dict(sizes):
        raise ValueError(
            f"the supplied predictor was built for "
            f"{predictor.chain.einsum_expr()} at {predictor.sizes}, not "
            f"{want.einsum_expr()} at {dict(sizes)}; the selection would "
            f"silently answer the wrong einsum")
    return predictor


def rank_einsum_paths(chain, sizes: Optional[Mapping[str, int]] = None, *,
                      stat: str = "med",
                      repetitions: Optional[int] = None,
                      predictor=None,
                      sizes_grid: Optional[Sequence[
                          Mapping[str, int]]] = None,
                      suite=None, cache=None, session=None):
    """Rank every pairwise contraction path of an N-operand einsum.

    The chain counterpart of :func:`rank_algorithms`: all candidate paths
    (``chain`` is a :class:`repro.tc.ChainSpec` or an expression like
    ``"ij,jk,kl->il"``) are predicted through one shared deduplicated
    micro-benchmark suite and the batched engine and returned
    fastest-first as :class:`repro.tc.RankedChain` records — per-step
    winning algorithms included.  Pass ``predictor=`` (a :class:`repro.tc.ChainPredictor`)
    to reuse measurements and compiled batches across calls; the
    step-by-step per-algorithm oracle remains available on the predictor
    as :meth:`~repro.tc.ChainPredictor.rank_paths_oracle`.

    Size-sweep mode: pass ``sizes_grid=`` (a sequence of size mappings)
    instead of ``sizes`` to rank every path at every size point from ONE
    shared suite — returns one fastest-first ranking per size point; only
    the genuinely new micro-benchmark keys are measured.

    Pass ``session=`` (a :class:`repro.tc.PredictorSession`) to share its
    suite and trace cache across calls; the per-call ``repetitions=``/
    ``predictor=``/``suite=``/``cache=``/``sizes_grid=`` keywords are
    DEPRECATED in favor of the session and its
    :meth:`~repro.tc.PredictorSession.rank_einsum_paths` /
    :meth:`~repro.tc.PredictorSession.rank_einsum_sweep` methods (one
    release of shim support).
    """
    from ..tc.session import warn_deprecated_kwargs  # lazy: tc needs core
    from .contractions import _session_for
    if sizes_grid is not None:
        if sizes is not None or predictor is not None:
            raise ValueError("sizes_grid= replaces sizes= and builds its "
                             "own per-point predictors; pass one mode or "
                             "the other")
        sess = _session_for("rank_einsum_paths", session, suite=suite,
                            cache=cache, repetitions=repetitions,
                            extra_deprecated={"sizes_grid": sizes_grid})
        return list(sess.rank_einsum_sweep(chain, sizes_grid,
                                           stat=stat).rankings)
    if suite is not None or cache is not None:
        raise ValueError("suite=/cache= apply to the sizes_grid= sweep "
                         "mode; the single-size path shares state via "
                         "session= (or the deprecated predictor=)")
    if sizes is None:
        raise ValueError("sizes is required (or pass sizes_grid= for the "
                         "size-sweep mode)")
    if predictor is not None:
        if session is not None:
            raise ValueError("session= already owns the predictor "
                             "resources; pass one or the other")
        pred = _resolve_chain_predictor(chain, sizes, repetitions, predictor)
        warn_deprecated_kwargs(
            "rank_einsum_paths",
            "session.rank_einsum_paths (the session memoizes the "
            "predictor)",
            {"predictor": predictor})
        return pred.rank_paths(stat=stat)
    sess = _session_for("rank_einsum_paths", session,
                        repetitions=repetitions)
    return sess.rank_einsum_paths(chain, sizes, stat=stat)


def select_einsum_path(chain, sizes: Mapping[str, int], *,
                       stat: str = "med",
                       repetitions: Optional[int] = None,
                       predictor=None, session=None):
    """The fastest-predicted contraction path of an N-operand einsum.

    ``rank_einsum_paths(...)[0]``: one :class:`repro.tc.RankedChain`
    carrying the chosen path (``.name`` is its nested-parenthesis form,
    e.g. ``((0.1).(2.3))``), the selected algorithm per step and the
    composed total-runtime prediction.  Same keywords (and the same
    deprecations) as :func:`rank_einsum_paths`.
    """
    # shim plumbing: forwards the caller's own (possibly deprecated)
    # kwargs verbatim so the deprecation warning fires exactly once
    # reprolint: allow[deprecated-kwarg]
    return rank_einsum_paths(chain, sizes, stat=stat,
                             repetitions=repetitions,
                             predictor=predictor, session=session)[0]


def performance_yield(measured_runtime: Mapping[int, float], b_pred: int,
                      ) -> Tuple[int, float]:
    """§4.6: yield = t_meas(b_opt) / t_meas(b_pred) ∈ (0, 1].

    ``measured_runtime`` maps block size -> measured (median) runtime.
    Returns (b_opt, yield).
    """
    b_opt = min(measured_runtime, key=measured_runtime.get)
    y = measured_runtime[b_opt] / measured_runtime[b_pred]
    return b_opt, y
