"""Device-resident measurement of the repo's own Pallas kernels.

The suite's micro-benchmarks were einsum-only: every measured key came
from the §6.2 cache-aware protocol over numpy contractions, and the
Pallas tile tuner (:mod:`repro.perf.tile_tuner`) ranked tile candidates
with napkin constants instead of measurements.  This module extends the
:class:`~repro.tc.suite.MicroBenchmarkSuite` with a *device kernel
family*: the repo's own Pallas kernels (``kernels/matmul.py`` (bm, bn,
bk) tiles, ``flash_attention.py`` (bq, bkv) blocks, ``ssd.py`` chunk
lengths), keyed by (kernel name, tile config, VMEM class) via the key's
``config`` facet — deduplicated and cost-accounted exactly like einsum
keys.

**Measurement protocol** (see ``docs/device-measurement.md``): each tile
config is timed on its canonical *proxy problem* (a few grid steps per
grid dimension — :func:`repro.kernels.matmul.proxy_problem` and
friends), so the measured quantity is a per-grid-step kernel cost; a
full problem's compute term is that cost scaled by the problem's grid
step count — the paper's measure-the-kernel / predict-the-blocked-
algorithm split (§4.6) transplanted to BlockSpec tiles.  The sweep is
*device-resident*: per-config calls chain their device-scalar witnesses
through a donated accumulator token (a data dependency that both
serializes the configs on the device queue and prevents XLA from
eliding repeated work), no per-config host round-trips happen inside
the loop, and exactly ONE sanctioned ``block_until_ready`` drains the
queue at sweep end — enforced by reprolint's host-sync checker, whose
``HOT_PATHS`` table lists :meth:`DeviceSuite._sweep`.

**Transfer terms**: predictions decompose as ``T_total = T_h2d +
T_compute + T_d2h`` with per-direction bandwidth + fixed-overhead
models fitted by :mod:`repro.core.transfer` from a small memcpy
micro-benchmark (asymmetric directions, like the reference SUMMA WSE
decomposition's ~3x D2H penalty).

Fitted per-(kernel, VMEM class) config models and the transfer models
export to one :class:`~repro.core.model.ModelSet` that a
:class:`repro.store.ModelStore` persists under its reserved
``__device__`` name; a warm-started session ranks tile candidates with
zero fresh measurements.
"""

from __future__ import annotations

import time
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.fitting import Polynomial, fit_relative, monomial_basis
from ..core.grids import Domain
from ..core.model import (CaseModel, ModelSet, PerformanceModel, Piece)
from ..core.sampler import STATS, Stats
from ..core.transfer import (D2H, H2D, TransferModel, measure_transfers)
from ..kernels.flash_attention import (attn_grid_steps, attn_proxy_problem,
                                       attn_vmem_bytes, flash_attention)
from ..kernels.matmul import grid_steps as matmul_grid_steps
from ..kernels.matmul import matmul, proxy_problem
from ..kernels.matmul import vmem_bytes as matmul_vmem_bytes
from ..kernels.ops import require_tpu
from ..kernels.ssd import ssd, ssd_grid_steps, ssd_proxy_problem, ssd_vmem_bytes
from .suite import MicroBenchmark, MicroBenchmarkKey, MicroBenchmarkSuite

#: VMEM classes a device kernel key is measured under — the TPU-memory
#: analogue of the einsum keys' warm/cold cache classes.  A config whose
#: working set leaves double-buffering headroom (<= half of VMEM) is
#: RESIDENT; one that claims more is TIGHT, and its pipeline behaves
#: measurably differently — so the two must not share measurements.
VMEM_LIMIT = 16 * 2 ** 20
#: the two VMEM classes: double-buffering headroom vs a tight pipeline
RESIDENT, TIGHT = "vmem_resident", "vmem_tight"

#: model-set case tags (mirrors tc.parametric's percall/first split)
_PERCALL, _FIRST = "percall", "first"
_TRANSFER_CASE = ("transfer",)
_VALUE_FLOOR = 1e-12       # relative fits need strictly positive values


def vmem_class(working_set_bytes: int,
               vmem_limit: int = VMEM_LIMIT) -> str:
    """The VMEM class of one grid step's working set."""
    return RESIDENT if working_set_bytes <= vmem_limit // 2 else TIGHT


# --------------------------------------------------------------- registry --
class _MatmulDevice:
    """(bm, bn, bk) tiles of the Pallas matmul (``kernels/matmul.py``)."""

    name = "pallas_matmul"
    config_dims = ("bm", "bn", "bk")

    def vmem_bytes(self, cfg: Tuple[int, ...]) -> int:
        return matmul_vmem_bytes(*cfg)

    def proxy(self, cfg, steps_per_dim: int) -> Tuple[int, ...]:
        return proxy_problem(*cfg, steps_per_dim=steps_per_dim)

    def proxy_steps(self, cfg, steps_per_dim: int) -> int:
        return steps_per_dim ** 3

    def steps(self, problem, cfg) -> int:
        return matmul_grid_steps(*problem, *cfg)

    def operand_shapes(self, problem):
        m, n, k = problem
        return (m, k), (k, n), (m, n)

    def operands(self, problem, rng):
        a_sh, b_sh, _ = self.operand_shapes(problem)
        return (rng.standard_normal(a_sh).astype(np.float32),
                rng.standard_normal(b_sh).astype(np.float32))

    def bind(self, cfg, interpret: bool):
        bm, bn, bk = cfg
        return lambda x, y: matmul(x, y, bm=bm, bn=bn, bk=bk,
                                   interpret=interpret)

    def transfer_bytes(self, problem, itemsize: int = 4):
        m, n, k = problem
        return itemsize * (m * k + k * n), itemsize * m * n


class _FlashAttentionDevice:
    """(bq, bkv, d) blocks of the flash-attention kernel.  The head dim
    rides in the config: it is a static shape parameter of every block,
    so two head dims are two distinct kernel configurations."""

    name = "flash_attention"
    config_dims = ("bq", "bkv", "d")

    def vmem_bytes(self, cfg) -> int:
        return attn_vmem_bytes(*cfg)

    def proxy(self, cfg, steps_per_dim: int):
        return attn_proxy_problem(*cfg, steps_per_dim=steps_per_dim)

    def proxy_steps(self, cfg, steps_per_dim: int) -> int:
        return steps_per_dim ** 2

    def steps(self, problem, cfg) -> int:
        b, h, sq, skv, d = problem
        assert d == cfg[2], (d, cfg)
        return attn_grid_steps(b, h, sq, skv, cfg[0], cfg[1])

    def operand_shapes(self, problem):
        b, h, sq, skv, d = problem
        return (b, h, sq, d), (b, h, skv, d), (b, h, sq, d)

    def operands(self, problem, rng):
        q_sh, kv_sh, _ = self.operand_shapes(problem)
        q = rng.standard_normal(q_sh).astype(np.float32)
        k = rng.standard_normal(kv_sh).astype(np.float32)
        v = rng.standard_normal(kv_sh).astype(np.float32)
        return q, k, v

    def bind(self, cfg, interpret: bool):
        bq, bkv, _ = cfg
        return lambda q, k, v: flash_attention(q, k, v, bq=bq, bkv=bkv,
                                               interpret=interpret)

    def transfer_bytes(self, problem, itemsize: int = 4):
        q_sh, kv_sh, o_sh = self.operand_shapes(problem)
        nin = int(np.prod(q_sh)) + 2 * int(np.prod(kv_sh))
        return itemsize * nin, itemsize * int(np.prod(o_sh))


class _SsdDevice:
    """(chunk, P, N) configs of the Mamba-2 SSD chunked kernel."""

    name = "pallas_ssd"
    config_dims = ("chunk", "p", "n")

    def vmem_bytes(self, cfg) -> int:
        return ssd_vmem_bytes(*cfg)

    def proxy(self, cfg, steps_per_dim: int):
        return ssd_proxy_problem(*cfg, steps_per_dim=steps_per_dim)

    def proxy_steps(self, cfg, steps_per_dim: int) -> int:
        return steps_per_dim

    def steps(self, problem, cfg) -> int:
        b, l, h, p, g, n = problem
        assert (p, n) == (cfg[1], cfg[2]), (problem, cfg)
        return ssd_grid_steps(b, l, h, cfg[0])

    def operand_shapes(self, problem):
        b, l, h, p, g, n = problem
        return (b, l, h, p), (b, l, g, n), (b, l, h, p)

    def operands(self, problem, rng):
        b, l, h, p, g, n = problem
        x = rng.standard_normal((b, l, h, p)).astype(np.float32)
        dt = np.full((b, l, h), 1e-3, dtype=np.float32)
        a_log = np.zeros((h,), dtype=np.float32)
        bb = rng.standard_normal((b, l, g, n)).astype(np.float32)
        cc = rng.standard_normal((b, l, g, n)).astype(np.float32)
        return x, dt, a_log, bb, cc

    def bind(self, cfg, interpret: bool):
        chunk = cfg[0]
        return lambda x, dt, a_log, b, c: ssd(x, dt, a_log, b, c,
                                              chunk=chunk,
                                              interpret=interpret)

    def transfer_bytes(self, problem, itemsize: int = 4):
        b, l, h, p, g, n = problem
        nin = b * l * h * p + b * l * h + h + 2 * b * l * g * n
        return itemsize * nin, itemsize * b * l * h * p


#: the device kernel registry: name -> adapter
DEVICE_KERNELS = {k.name: k for k in (_MatmulDevice(),
                                      _FlashAttentionDevice(),
                                      _SsdDevice())}


def device_key(kernel_name: str, config: Sequence[int], *,
               steps_per_dim: int = 2,
               vmem_limit: int = VMEM_LIMIT) -> MicroBenchmarkKey:
    """The suite key of one (kernel, tile config, VMEM class) benchmark.

    The operand shapes are the config's canonical *proxy problem*
    operands, so the key — like every einsum key — fully reconstructs
    its measurement; two problems tuned at the same config share one
    key, which is what makes warm-store tile ranking measurement-free
    across problem sizes.
    """
    kernel = DEVICE_KERNELS[kernel_name]
    config = tuple(int(c) for c in config)
    problem = kernel.proxy(config, steps_per_dim)
    a_sh, b_sh, o_sh = kernel.operand_shapes(problem)
    cls = vmem_class(kernel.vmem_bytes(config), vmem_limit)
    return MicroBenchmarkKey(equation=kernel_name, a_shape=tuple(a_sh),
                             b_shape=tuple(b_sh), out_shape=tuple(o_sh),
                             classes=(cls, cls), config=config)


@dataclass(frozen=True)
class DeviceRanked:
    """One ranked tile config with its transfer/compute decomposition."""

    config: Tuple[int, ...]
    t_total: float             # T_h2d + T_compute + T_d2h (seconds)
    t_h2d: float
    t_compute: float
    t_d2h: float
    per_step_s: float          # measured/modeled per-grid-step kernel cost
    source: str                # "measured" | "model"


class DeviceSuite:
    """Device-resident sweeps + measured tile models over one shared suite.

    Wraps a :class:`~repro.tc.suite.MicroBenchmarkSuite`: device kernel
    measurements land in ``suite.results`` with ordinary "measured"
    provenance and wall-clock cost accounting, so store persistence,
    warm starts and the ``measured == 0`` zero-fresh-measurement proof
    work unchanged.  Measurement runs the kernels on the TPU and refuses
    any other backend unless ``interpret=True`` is passed (the tests and
    the CI smoke lane do: interpret timings measure the interpreter, not
    a device).  ``passes`` defaults to the suite's repetition protocol;
    ``transfer_measure_fn`` injects a synthetic memcpy probe (tests fit
    against known constants).
    """

    def __init__(self, suite: MicroBenchmarkSuite, *,
                 interpret: bool = False,
                 vmem_limit: int = VMEM_LIMIT,
                 steps_per_dim: int = 2,
                 passes: Optional[int] = None,
                 transfer_measure_fn=None,
                 transfer_repetitions: int = 5,
                 sweep_fn=None):
        self.suite = suite
        self.interpret = bool(interpret)
        self.vmem_limit = vmem_limit
        self.steps_per_dim = steps_per_dim
        self.passes = suite.repetitions if passes is None else passes
        self.transfer_measure_fn = transfer_measure_fn
        self.transfer_repetitions = transfer_repetitions
        #: injectable sweep backend: (kernel_name, configs) -> {config:
        #: (Stats, first, seconds)}.  Tests inject a deterministic one;
        #: the default is the real device-resident loop.
        self.sweep_fn = sweep_fn or self._sweep
        self._transfer: Optional[Tuple[TransferModel, TransferModel]] = None
        #: (kernel, classes) -> {"percall": CaseModel, "first": Polynomial}
        #: loaded from a store's ``__device__`` model set
        self._loaded: Dict[Tuple[str, Tuple[str, str]], Dict] = {}

    # -------------------------------------------------------------- keys --
    def key(self, kernel_name: str,
            config: Sequence[int]) -> MicroBenchmarkKey:
        return device_key(kernel_name, config,
                          steps_per_dim=self.steps_per_dim,
                          vmem_limit=self.vmem_limit)

    # ------------------------------------------------------- measurement --
    def measure_grid(self, kernel_name: str,
                     configs: Sequence[Sequence[int]],
                     ) -> Dict[Tuple[int, ...], MicroBenchmark]:
        """Measured benchmarks for every config, deduplicated.

        Only configs whose key the suite does not already hold enter the
        device-resident sweep; the rest are served from ``results`` like
        any shared einsum key.
        """
        configs = [tuple(int(c) for c in cfg) for cfg in configs]
        missing = []
        seen = set()
        for cfg in configs:
            if cfg in seen:
                continue
            seen.add(cfg)
            if self.key(kernel_name, cfg) not in self.suite.results:
                missing.append(cfg)
        if missing:
            for cfg, (stats, first, seconds) in self.sweep_fn(
                    kernel_name, missing).items():
                self.suite.record_measurement(self.key(kernel_name, cfg),
                                              stats, first, seconds)
        return {cfg: self.suite.results[self.key(kernel_name, cfg)]
                for cfg in configs}

    def _sweep(self, kernel_name: str,
               configs: Sequence[Tuple[int, ...]]) -> Dict:
        """The device-resident measurement loop (reprolint hot path).

        Per config: jit-compile the kernel on its proxy problem with the
        accumulator token donated, run one untimed-for-stats warmup
        dispatch (its wall-clock — compile-dominated — is the first-call
        overhead), then ``passes`` timed dispatches.  Configs chain
        through the token (each call adds a witness scalar of the
        previous output), so the device executes them serially and no
        repetition can be elided; the host only *enqueues* inside the
        loop.  Exactly one sanctioned sync drains the queue at sweep
        end; the drained tail is redistributed over the samples
        proportionally, keeping totals exact on asynchronous backends
        (on the CPU/interpret CI platform dispatch is effectively
        synchronous and the tail is ~0).
        """
        import jax
        import jax.numpy as jnp

        require_tpu(self.interpret)
        kernel = DEVICE_KERNELS[kernel_name]
        t_start = time.perf_counter()
        rng = np.random.default_rng(self.suite.seed)
        runners = []
        for cfg in configs:
            problem = kernel.proxy(cfg, self.steps_per_dim)
            ops = tuple(jnp.asarray(o)
                        for o in kernel.operands(problem, rng))
            call = kernel.bind(cfg, self.interpret)

            def chain(token, *operands, _call=call):
                out = _call(*operands)
                return token + out.ravel()[0].astype(jnp.float32)

            runners.append((cfg, jax.jit(chain, donate_argnums=(0,)), ops))

        with warnings.catch_warnings():
            # interpret mode warns that donated buffers went unused —
            # expected off-accelerator, not actionable here
            warnings.filterwarnings("ignore", message=".*[Dd]onat")
            token = jnp.float32(0.0)
            firsts = {}
            for cfg, run, ops in runners:
                t0 = time.perf_counter()
                token = run(token, *ops)
                firsts[cfg] = time.perf_counter() - t0
            samples = {cfg: [] for cfg in configs}
            for _ in range(self.passes):
                for cfg, run, ops in runners:
                    t0 = time.perf_counter()
                    token = run(token, *ops)
                    samples[cfg].append(time.perf_counter() - t0)
            # the single sanctioned sweep-end sync: every chained dispatch
            # above is async; draining the queue once here is what makes
            # the per-config enqueue deltas a complete timing of the sweep
            jax.block_until_ready(token)  # reprolint: allow[host-sync]
        tail = time.perf_counter() - t_start - sum(firsts.values()) \
            - sum(s for v in samples.values() for s in v)
        sampled_total = sum(s for v in samples.values() for s in v)
        scale = 1.0 + max(tail, 0.0) / sampled_total \
            if sampled_total > 0 else 1.0
        wall = time.perf_counter() - t_start
        out = {}
        weights = {cfg: firsts[cfg] + sum(samples[cfg]) for cfg in configs}
        wtotal = sum(weights.values()) or 1.0
        for cfg in configs:
            per_call = [s * scale for s in samples[cfg]]
            out[cfg] = (Stats.from_samples(per_call), firsts[cfg],
                        wall * weights[cfg] / wtotal)
        return out

    # ---------------------------------------------------------- transfer --
    def transfer_models(self) -> Tuple[TransferModel, TransferModel]:
        """The (H2D, D2H) transfer models — measured once per suite (the
        memcpy probe's wall-clock lands in ``suite.cost_seconds``), or
        loaded from a store's ``__device__`` model set."""
        if self._transfer is None:
            if self.transfer_measure_fn is None:
                require_tpu(self.interpret)
            h2d, d2h, cost = measure_transfers(
                measure_fn=self.transfer_measure_fn,
                repetitions=self.transfer_repetitions)
            self.suite.cost_seconds += cost
            self._transfer = (h2d, d2h)
        return self._transfer

    # ------------------------------------------------------------ ranking --
    def rank(self, kernel_name: str, problem: Sequence[int],
             configs: Sequence[Sequence[int]], *, stat: str = "med",
             transfer: bool = True, itemsize: int = 4,
             ) -> List[DeviceRanked]:
        """Rank tile configs for ``problem``, fastest-predicted first.

        Per config the total decomposes as ``T_h2d + T_compute +
        T_d2h``: per-grid-step kernel cost (measured, or predicted by a
        loaded ``__device__`` model — zero fresh measurements on a warm
        store) scaled to the problem's step count, plus one H2D
        transfer of the input operands and one D2H of the output.
        """
        kernel = DEVICE_KERNELS[kernel_name]
        problem = tuple(int(p) for p in problem)
        configs = [tuple(int(c) for c in cfg) for cfg in configs]
        est: Dict[Tuple[int, ...], Tuple[float, str]] = {}
        need = []
        for cfg in configs:
            key = self.key(kernel_name, cfg)
            mb = self.suite.results.get(key)
            if mb is not None:
                est[cfg] = (getattr(mb.stats, stat), "measured")
                continue
            pred = self._model_predict(kernel_name, key.classes, cfg, stat)
            if pred is not None:
                est[cfg] = (pred, "model")
            else:
                need.append(cfg)
        for cfg, mb in (self.measure_grid(kernel_name, need).items()
                        if need else ()):
            est[cfg] = (getattr(mb.stats, stat), "measured")
        t_h2d = t_d2h = 0.0
        if transfer:
            h2d, d2h = self.transfer_models()
            in_bytes, out_bytes = kernel.transfer_bytes(problem, itemsize)
            t_h2d, t_d2h = h2d.time(in_bytes), d2h.time(out_bytes)
        ranked = []
        for cfg in configs:
            per_call, source = est[cfg]
            per_step = per_call / kernel.proxy_steps(cfg,
                                                     self.steps_per_dim)
            t_compute = per_step * kernel.steps(problem, cfg)
            ranked.append(DeviceRanked(
                config=cfg, t_total=t_h2d + t_compute + t_d2h,
                t_h2d=t_h2d, t_compute=t_compute, t_d2h=t_d2h,
                per_step_s=per_step, source=source))
        ranked.sort(key=lambda r: (r.t_total, r.config))
        return ranked

    def _model_predict(self, kernel_name: str, classes: Tuple[str, str],
                       cfg: Tuple[int, ...],
                       stat: str) -> Optional[float]:
        entry = self._loaded.get((kernel_name, classes))
        if entry is None:
            return None
        piece = entry[_PERCALL].find_piece(cfg)
        if piece is None:
            return None               # outside the fitted config domain
        return piece.estimate(cfg)[stat]

    # -------------------------------------------------------- persistence --
    def to_model_set(self) -> ModelSet:
        """Measured device kernels + transfer models as one
        :class:`ModelSet` — the payload of the store's ``__device__``
        name.  Per (kernel, VMEM classes): per-call-stat polynomials
        fitted over the measured config points (relative LS on the
        cost-bounded basis, §3.2.4) under case ``(classes, "percall")``,
        and a constant first-call fit under ``(classes, "first")`` whose
        piece domain records the fitted config bounding box.  Transfer
        models ride as ``memcpy_h2d`` / ``memcpy_d2h`` kernels.
        """
        groups: Dict[Tuple[str, Tuple[str, str]], List] = {}
        for key, mb in self.suite.results.items():
            if key.config is not None and key.equation in DEVICE_KERNELS:
                groups.setdefault((key.equation, key.classes),
                                  []).append((key.config, mb))
        ms = ModelSet()
        for (name, classes) in sorted(groups):
            entries = sorted(groups[(name, classes)], key=lambda e: e[0])
            points = np.asarray([cfg for cfg, _ in entries], float)
            ndim = points.shape[1]
            lo = tuple(float(v) for v in points.min(axis=0))
            hi = tuple(float(v) for v in points.max(axis=0))
            basis = monomial_basis(((1,) * ndim,))
            polys = {}
            for s in STATS:
                vals = np.maximum([getattr(mb.stats, s)
                                   for _, mb in entries], _VALUE_FLOOR)
                polys[s] = fit_relative(points, vals, basis)
            first_vals = np.maximum([mb.first for _, mb in entries],
                                    _VALUE_FLOOR)
            first_poly = fit_relative(points, first_vals, ((0,) * ndim,))
            if name not in ms:
                ms.add(PerformanceModel(kernel=name, setup="tc-device"))
            pm = ms[name]
            pm.add_piece((classes, _PERCALL),
                         Piece(domain=Domain(lo, hi), polys=polys))
            pm.add_piece((classes, _FIRST),
                         Piece(domain=Domain(lo, hi),
                               polys={s: first_poly for s in STATS}))
        if self._transfer is not None:
            for model in self._transfer:
                pm = PerformanceModel(kernel=f"memcpy_{model.direction}",
                                      setup="tc-device")
                pm.add_piece(_TRANSFER_CASE, model.to_piece())
                ms.add(pm)
        return ms

    def load_model_set(self, ms: ModelSet) -> int:
        """Restore :meth:`to_model_set` output (a store warm start);
        returns how many (kernel, classes) config models were loaded.
        In-memory models win over loaded ones."""
        loaded = 0
        transfer: Dict[str, TransferModel] = {}
        for name, pm in ms.models.items():
            if name.startswith("memcpy_"):
                direction = name[len("memcpy_"):]
                piece = pm.cases[_TRANSFER_CASE].pieces[0]
                transfer[direction] = TransferModel.from_piece(direction,
                                                              piece)
                continue
            percall: Dict[Tuple[str, str], CaseModel] = {}
            first: Dict[Tuple[str, str], Polynomial] = {}
            for case, cm in pm.cases.items():
                classes, kind = case
                if kind == _PERCALL:
                    percall[tuple(classes)] = cm
                elif kind == _FIRST:
                    first[tuple(classes)] = cm.pieces[0].polys["med"]
            for classes, cm in percall.items():
                slot = (name, classes)
                if slot in self._loaded or classes not in first:
                    continue
                self._loaded[slot] = {_PERCALL: cm,
                                      _FIRST: first[classes]}
                loaded += 1
        if self._transfer is None and H2D in transfer and D2H in transfer:
            self._transfer = (transfer[H2D], transfer[D2H])
        return loaded
