"""Distributed-configuration predictor — the paper's algorithm selection
(§4.5) applied at cluster scale.

The paper ranks mathematically-equivalent blocked algorithms by summing
per-kernel model estimates, never executing the candidates.  Here the
"algorithms" are *sharding configurations* of one (arch x shape) cell —
e.g. Megatron-TP vs pure-FSDP vs hybrid axis splits — and the "model" is
the three-term roofline evaluated on each candidate's compiled dry-run:
lowering + compiling takes seconds, executing a candidate on 256 chips to
time it is what this avoids.  The predicted step time is
``max(compute_s, memory_s, collective_s)`` (bound model; terms overlap on
real hardware).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Mapping, Optional, Tuple

from ..core.model import ModelSet
from ..core.predict import (KernelCall, PredictionEngine,
                            resolve_engine)
from ..core.sampler import Stats
from .roofline import RooflineTerms


@dataclass(frozen=True)
class ConfigCandidate:
    """One sharding configuration: a name + a builder returning compiled."""

    name: str
    build: Callable[[], object]      # () -> (compiled, meta) or RooflineTerms
    note: str = ""


@dataclass
class RankedConfig:
    name: str
    terms: RooflineTerms
    note: str = ""

    @property
    def predicted_s(self) -> float:
        return self.terms.bound_s


def rank_configs(candidates: List[ConfigCandidate],
                 extract: Callable[[object], RooflineTerms],
                 ) -> List[RankedConfig]:
    """Compile every candidate and sort by predicted step time."""
    ranked = []
    for cand in candidates:
        built = cand.build()
        terms = built if isinstance(built, RooflineTerms) else extract(built)
        ranked.append(RankedConfig(cand.name, terms, cand.note))
    ranked.sort(key=lambda r: r.predicted_s)
    return ranked


# --------------------------------------------------------- batched ranking --

@dataclass(frozen=True)
class RankedTracedConfig:
    """A candidate ranked by the batched kernel-model prediction engine."""

    name: str
    runtime: Stats
    note: str = ""
    stat: str = "med"    # the statistic the ranking sorted by

    @property
    def predicted_s(self) -> float:
        return getattr(self.runtime, self.stat)


def rank_traced_configs(tracers: Mapping[str, Callable[..., List[KernelCall]]],
                        models: ModelSet,
                        *tracer_args,
                        stat: str = "med",
                        engine: Optional[PredictionEngine] = None,
                        ) -> List[RankedTracedConfig]:
    """Rank trace-producing candidates on the batched prediction engine.

    The roofline path above compiles each candidate to extract bound terms;
    this path never compiles anything: each candidate's kernel-call trace is
    batched through :class:`PredictionEngine`, so sweeping hundreds of
    configurations costs a handful of array ops — the §4.5 selection applied
    at config-sweep scale.  ``engine=`` exists for symmetry with the core
    selection entry points (these tracers take arbitrary
    ``*tracer_args``, so the per-(n, b) trace cache does not apply — a
    shared engine buys consistency checks, not reuse).
    """
    names = list(tracers)
    engine = resolve_engine(models, engine)
    runtimes = engine.predict_stats(
        [tracers[name](*tracer_args) for name in names])
    ranked = [RankedTracedConfig(name=name, runtime=rt, stat=stat)
              for name, rt in zip(names, runtimes)]
    ranked.sort(key=lambda r: r.predicted_s)
    return ranked
