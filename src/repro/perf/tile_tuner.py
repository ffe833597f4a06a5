"""Measured Pallas tile selection (the paper's block-size optimization
applied to BlockSpec tiles).

The paper tunes a blocked algorithm's block size b by predicting runtime
over candidate b and taking the argmin (§4.6).  The TPU analogue tunes
the matmul kernel's (bm, bn, bk): candidates are filtered by *legality*
(MXU alignment + VMEM capacity — the cache-line/cache-size constraints of
§3.1 transplanted to the TPU memory hierarchy) and ranked by **measured
per-grid-step tile models** served through a
:class:`~repro.tc.session.PredictorSession`'s device facet
(:mod:`repro.tc.device`): each surviving candidate's predicted total is
``T_h2d + per_step(bm, bn, bk) * grid_steps + T_d2h``, with the transfer
terms fitted from the memcpy micro-benchmark.  Measurements are
deduplicated and persisted in the platform
:class:`~repro.store.ModelStore` under its ``__device__`` name, so a warm
session selects tiles with zero fresh measurements.

The pre-device *analytic* three-term model survives two ways:

* ``analytic=True`` (or no session at all) ranks with it — CI and
  hardware-free environments keep a deterministic, measurement-free path;
* it is the equivalence/sanity **oracle** for the measured path: tests
  compare both rankings on CPU-interpret mode (reprolint's
  oracle-coverage gate pins ``select_tiles``/``rank_device_tiles`` to
  ``predict_tile_time`` / ``analytic=True``).

Analytic cost model per grid step (napkin math, EXPERIMENTS.md §Perf):

* compute:   bm*bn*bk MACs at MXU efficiency eff(bm,bn,bk) — tiles that
  are not multiples of 128 waste systolic-array occupancy;
* memory:    HBM->VMEM traffic: A tile + B tile per step; the output tile
  is resident.  Total traffic = m*k*(n/bn) + k*n*(m/bm) + m*n — small
  bm/bn re-stream the other operand;
* overhead:  per-step fixed grid cost.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from ..kernels.matmul import tile_legal
from .roofline import peaks

_GRID_STEP_OVERHEAD_S = 1e-6
_CANDIDATES = (128, 256, 512, 1024)


def _mxu_eff(b: int) -> float:
    """Systolic utilization of one tile dim.

    A dim occupies ``ceil(b / 128)`` full 128-wide passes of the array;
    utilization is the filled fraction of those passes: ``b / (128 *
    ceil(b / 128))``.  Multiples of 128 are full, b < 128 reduces to
    ``b / 128``, and a non-multiple above 128 (e.g. 192 -> 0.75) wastes
    its final pass — the case the old ``min(1, b / 128)`` missed.
    """
    return b / (128.0 * math.ceil(b / 128.0))


@dataclass(frozen=True)
class TileChoice:
    """One selected/ranked tile config.  ``predicted_s`` is the ranking
    total; the transfer/compute split and provenance are populated on the
    measured path (zeros and ``"analytic"`` on the analytic one)."""

    bm: int
    bn: int
    bk: int
    predicted_s: float
    t_h2d: float = 0.0
    t_compute: float = 0.0
    t_d2h: float = 0.0
    source: str = "analytic"     # "analytic" | "measured" | "model"


def predict_tile_time(m: int, n: int, k: int, bm: int, bn: int,
                      bk: int, itemsize: int = 2, *,
                      device_kind: str) -> float:
    """The analytic three-term estimate — the measured path's oracle —
    against ``device_kind``'s published peaks (an unknown kind raises)."""
    chip = peaks(device_kind)
    eff = _mxu_eff(bm) * _mxu_eff(bn) * _mxu_eff(bk)
    compute = 2.0 * m * n * k / (chip.flops * eff)
    traffic = itemsize * (m * k * (n / bn) + k * n * (m / bm) + m * n)
    memory = traffic / chip.hbm_bw
    steps = (m // bm) * (n // bn) * (k // bk)
    return max(compute, memory) + steps * _GRID_STEP_OVERHEAD_S


def _legal_candidates(m: int, n: int, k: int, candidates: Sequence[int],
                      vmem_limit: int) -> List[Tuple[int, int, int]]:
    """Clamped-to-dims, deduplicated, legality-filtered candidate tiles."""
    legal = []
    seen = set()
    for bm, bn, bk in itertools.product(candidates, repeat=3):
        cfg = (min(bm, m), min(bn, n), min(bk, k))
        if cfg in seen:
            continue
        seen.add(cfg)
        if tile_legal(m, n, k, *cfg, vmem_limit):
            legal.append(cfg)
    return legal


def rank_tiles(m: int, n: int, k: int, *,
               session=None, analytic: bool = False,
               vmem_limit: int = 16 * 2 ** 20,
               candidates: Sequence[int] = _CANDIDATES,
               stat: str = "med", transfer: bool = True,
               itemsize: int = 4,
               device_kind: Optional[str] = None) -> List[TileChoice]:
    """Every legal tile config ranked fastest-predicted first.

    With a ``session`` (a :class:`~repro.tc.PredictorSession`) and
    ``analytic=False``, rankings come from measured per-grid-step device
    models plus fitted H2D/D2H transfer terms
    (:meth:`~repro.tc.session.PredictorSession.rank_device_tiles`);
    measurements already in the session's suite — including ones
    warm-loaded from a :class:`~repro.store.ModelStore` — are never
    re-taken.  ``analytic=True`` (or ``session=None``) ranks with the
    deterministic three-term model instead — the hardware-free path and
    the measured path's sanity oracle — against the published peaks of
    ``device_kind``, by default the attached device's (a kind with no
    published peaks, such as a CPU, raises).
    """
    legal = _legal_candidates(m, n, k, candidates, vmem_limit)
    if not legal:
        raise ValueError(f"no legal tile for ({m},{n},{k}) "
                         f"within VMEM {vmem_limit}")
    if analytic or session is None:
        if device_kind is None:
            device_kind = jax.devices()[0].device_kind
        ranked = [TileChoice(bm, bn, bk,
                             predict_tile_time(m, n, k, bm, bn, bk,
                                               device_kind=device_kind))
                  for bm, bn, bk in legal]
        ranked.sort(key=lambda t: (t.predicted_s, (t.bm, t.bn, t.bk)))
        return ranked
    device = session.rank_device_tiles("pallas_matmul", (m, n, k), legal,
                                       stat=stat, transfer=transfer,
                                       itemsize=itemsize)
    return [TileChoice(r.config[0], r.config[1], r.config[2],
                       predicted_s=r.t_total, t_h2d=r.t_h2d,
                       t_compute=r.t_compute, t_d2h=r.t_d2h,
                       source=r.source)
            for r in device]


def select_tiles(m: int, n: int, k: int, *,
                 session=None, analytic: bool = False,
                 vmem_limit: int = 16 * 2 ** 20,
                 candidates: Sequence[int] = _CANDIDATES,
                 stat: str = "med", transfer: bool = True,
                 itemsize: int = 4,
                 device_kind: Optional[str] = None) -> TileChoice:
    """Pick (bm, bn, bk) without executing any candidate at problem size
    (the paper's prediction-not-execution principle): the argmin of
    :func:`rank_tiles` — measured models through the session's device
    facet by default, the analytic three-term model with
    ``analytic=True`` or no session (against ``device_kind``'s peaks)."""
    return rank_tiles(m, n, k, session=session, analytic=analytic,
                      vmem_limit=vmem_limit, candidates=candidates,
                      stat=stat, transfer=transfer, itemsize=itemsize,
                      device_kind=device_kind)[0]


def tile_table(shapes: Sequence[Tuple[int, int, int]],
               **kw) -> Dict[Tuple[int, int, int], TileChoice]:
    """``select_tiles`` over many shapes; one session's measurements are
    shared across the whole table (proxy-problem keys depend only on the
    tile config, not the problem size)."""
    return {s: select_tiles(*s, **kw) for s in shapes}
