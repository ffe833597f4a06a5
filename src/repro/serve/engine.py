"""Batched serving engine: continuous-batching prefill + decode loop.

A minimal production-shaped server: requests join a batch slot, prefill
populates their KV cache region, decode steps advance every active slot
one token per step, finished sequences free their slot for waiting
requests.  Runs on CPU for the examples/tests; the same step functions are
what the dry-run lowers for the 256/512-chip meshes.

Since the scheduler refactor the engine exposes its per-tick mechanics as
*step hooks* — :meth:`ServeEngine.add_request` (blocking prefill),
:meth:`ServeEngine.begin_prefill` (interleaved prefill lane),
:meth:`ServeEngine.advance` (ONE fused step over every decode and prefill
lane) and :meth:`ServeEngine.free_slots` — and delegates the tick loop to
a pluggable scheduler (:mod:`repro.serve.scheduler`).  ``run()`` with the
default :class:`~repro.serve.scheduler.FifoScheduler` reproduces the
pre-refactor behavior action-for-action (the equivalence oracle pinned by
``tests/test_serve_scheduler.py``); a
:class:`~repro.serve.scheduler.ModelGuidedScheduler` instead drives
admission, slot packing and prefill interleaving from measured step-cost
predictions.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..models import decode_step, init_decode_state


@dataclass
class Request:
    """One generation request.

    ``arrival_s`` is the request's open-loop arrival offset on the
    ``run()`` clock (0 = available immediately — the closed-loop default);
    ``submitted_s``/``finished_s`` are stamped by the serve loop, and
    :attr:`latency_s` is the submit→finish span the load generator
    reports percentiles over.
    """

    uid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    out_tokens: List[int] = field(default_factory=list)
    #: keep every output token's step logits in :attr:`out_logits`.  Off
    #: by default: each kept entry pins a whole (slots, 1, V) step output
    #: on the device for as long as the request lives.
    keep_logits: bool = False
    #: with :attr:`keep_logits`, per output token, the step logits it was
    #: chosen from and the slot row: ``(logits (slots, 1, V) device array,
    #: slot)`` — a reference to the step's own output, no copy and no sync
    out_logits: List[Tuple[jax.Array, int]] = field(default_factory=list)
    done: bool = False
    arrival_s: float = 0.0       # open-loop arrival time (run() clock)
    submitted_s: Optional[float] = None   # entered the waiting queue
    finished_s: Optional[float] = None    # last token produced

    @property
    def latency_s(self) -> Optional[float]:
        """Submit→finish latency (seconds), once finished."""
        if self.submitted_s is None or self.finished_s is None:
            return None
        return self.finished_s - self.submitted_s


@dataclass
class EngineStats:
    """Aggregated serving counters (one engine run).

    ``prefill_s``/``decode_s`` time the jitted step calls *synchronized*
    (``block_until_ready``) — under async dispatch an unsynchronized
    wall-clock stop under-reports by whatever was still in flight.
    ``latencies_s`` collects per-request submit→finish spans;
    ``tick_overhead_s``/``ticks`` account the scheduler's own planning
    cost per tick.
    """

    prefill_s: float = 0.0
    decode_steps: int = 0
    decode_s: float = 0.0
    tokens_out: int = 0
    ticks: int = 0
    tick_overhead_s: float = 0.0
    latencies_s: List[float] = field(default_factory=list)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_out / self.decode_s if self.decode_s else 0.0

    @property
    def tick_overhead_ms(self) -> float:
        """Mean scheduler planning overhead per tick, milliseconds."""
        return 1e3 * self.tick_overhead_s / self.ticks if self.ticks else 0.0

    def latency_ms(self, percentile: float) -> float:
        """A submit→finish latency percentile (milliseconds)."""
        if not self.latencies_s:
            return 0.0
        return 1e3 * float(np.percentile(np.asarray(self.latencies_s),
                                         percentile))


def _step(cfg: ArchConfig, precision: Optional[str], params, caches, token,
          index):
    """The engine's decode step, its matmuls at ``precision`` (``None``:
    the backend's default)."""
    if precision is None:
        return decode_step(cfg, params, caches, token, index)
    with jax.default_matmul_precision(precision):
        return decode_step(cfg, params, caches, token, index)


def _reset_slot(caches, slot):
    """Zero one batch row (axis 1 of every cache leaf: (periods, batch,
    ...)) of the decode state."""
    return jax.tree_util.tree_map(lambda c: c.at[:, slot].set(0), caches)


class ServeEngine:
    """Static-batch serving engine (batch slots, per-slot position).

    Slot states: *free* (neither active nor prefilling), *prefilling*
    (an interleaved-prefill lane consuming one prompt token per fused
    step) and *active* (decoding one output token per step).  The legacy
    blocking path (:meth:`add_request`) prefills a slot to completion in
    one call; the interleaved path (:meth:`begin_prefill` +
    :meth:`advance`) folds prefill tokens into the same fused steps that
    advance decode lanes — prompt processing then costs no dedicated
    engine steps while decode work exists.

    ``matmul_precision`` sets the decode step's matmul precision (a
    ``jax.default_matmul_precision`` name; ``None`` keeps the backend's
    default).  On a TPU the default computes a float32 matmul as one
    bfloat16 pass, and XLA then converts every layer's float32 weights to
    bfloat16 ahead of the layer scan: mamba2-2.7b's float32 decode step
    needs 17.35 GB that way, and 13.6 GB at ``"highest"``.
    """

    def __init__(self, cfg: ArchConfig, params, *, batch_slots: int = 4,
                 ctx_len: int = 512, dtype=jnp.float32, scheduler=None,
                 matmul_precision: Optional[str] = None):
        assert cfg.causal, "decoder-only architectures serve"
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.ctx = ctx_len
        self.caches = init_decode_state(cfg, batch_slots, ctx_len,
                                        dtype=dtype)
        self.positions = np.zeros(batch_slots, dtype=np.int64)
        self.active: Dict[int, Request] = {}
        self.prefilling: Dict[int, Request] = {}
        self.prefill_done: Dict[int, int] = {}   # prompt tokens consumed
        self.stats = EngineStats()
        self.scheduler = scheduler
        # caches are donated: the step updates them in place instead of
        # holding two copies of every slot's state on the device
        self._decode = jax.jit(functools.partial(_step, cfg,
                                                 matmul_precision),
                               donate_argnums=(1,))
        self._reset = jax.jit(_reset_slot, donate_argnums=(0,))

    def _admit(self, slot: int) -> None:
        """Zero ``slot``'s caches: a new request must not start from the
        recurrent state or KV entries its slot's previous tenant left."""
        self.caches = self._reset(self.caches,
                                  jnp.asarray(slot, dtype=jnp.int32))

    # -------------------------------------------------------------- slots --
    def free_slots(self) -> List[int]:
        """Slots neither decoding nor mid-prefill, lowest first."""
        return [s for s in range(self.slots)
                if s not in self.active and s not in self.prefilling]

    # ------------------------------------------------------------ prefill --
    def add_request(self, req: Request) -> bool:
        """Admit a request into a free slot; prefill via decode replay.

        The *blocking* prefill hook: every prompt token but the last is
        replayed through the fused step before this returns, so every
        other lane stalls for ``len(prompt) - 1`` steps — the FIFO
        baseline's behavior.  The last prompt token is the slot's first
        decode input: its logits choose the first output token.  The
        replay feeds token 0 to the other lanes, so it is correct for
        one request at a time only.
        """
        free = self.free_slots()
        if not free:
            return False
        slot = free[0]
        t0 = time.perf_counter()
        self._admit(slot)
        # single-slot prefill: replay prompt tokens through decode_step
        # (keeps one compiled step; a bulk prefill kernel is lowered for the
        # dry-run separately)
        for i, tok in enumerate(req.prompt[:-1]):
            token = jnp.zeros((self.slots, 1), dtype=jnp.int32
                              ).at[slot, 0].set(int(tok))
            _, self.caches = self._decode(self.params, self.caches, token,
                                          jnp.asarray(i, dtype=jnp.int32))
        self.positions[slot] = len(req.prompt) - 1
        self.active[slot] = req
        # prefill_s is a wall-clock bill: the request is not admitted until
        # its cache writes land, so the clock must stop on a drained queue
        # reprolint: allow[host-sync]
        jax.block_until_ready(self.caches)
        self.stats.prefill_s += time.perf_counter() - t0
        return True

    def begin_prefill(self, req: Request, slot: Optional[int] = None) -> int:
        """Open an *interleaved* prefill lane for ``req``.

        The lane consumes one prompt token per :meth:`advance` call,
        riding along with the decode lanes in the same fused step; when
        every prompt token but the last is consumed the slot transitions
        to decode, whose first step feeds the last prompt token.  The
        slot's caches are reset on admission.  Returns the slot used.
        """
        free = self.free_slots()
        if slot is None:
            if not free:
                raise ValueError("no free slot for prefill")
            slot = free[0]
        elif slot not in free:
            raise ValueError(f"slot {slot} is not free")
        self._admit(slot)
        if len(req.prompt) > 1:
            self.prefilling[slot] = req
            self.prefill_done[slot] = 0
        else:
            self.positions[slot] = 0
            self.active[slot] = req
        return slot

    # ------------------------------------------------------------- decode --
    def advance(self) -> List[Request]:
        """ONE fused engine step: advance every decode and prefill lane.

        Decode lanes are fed their last token and append the argmax
        output (and, for a request with :attr:`Request.keep_logits`, a
        reference to the step's logits); prefill lanes consume their next
        prompt token (the slot flips to decode once only the last prompt
        token is left, after which it behaves exactly like a
        blocking-prefilled slot).  Returns the requests that finished on
        this step.
        """
        if not self.active and not self.prefilling:
            return []
        t0 = time.perf_counter()
        token = np.zeros((self.slots, 1), dtype=np.int32)
        for slot, req in self.active.items():
            last = req.out_tokens[-1] if req.out_tokens else \
                int(req.prompt[-1])
            token[slot, 0] = last
        for slot, req in self.prefilling.items():
            token[slot, 0] = int(req.prompt[self.prefill_done[slot]])
        index = int(max(
            [int(self.positions[s]) for s in self.active] +
            [self.prefill_done[s] for s in self.prefilling]))
        logits, self.caches = self._decode(
            self.params, self.caches, jnp.asarray(token),
            jnp.asarray(index, dtype=jnp.int32))
        had_decode = bool(self.active)
        finished: List[Request] = []
        if had_decode:
            # the engine's one designed D2H point per step: the argmax
            # tokens must reach the host to extend request state
            # reprolint: allow[host-sync]
            nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1))
            for slot, req in list(self.active.items()):
                req.out_tokens.append(int(nxt[slot]))
                if req.keep_logits:
                    req.out_logits.append((logits, slot))
                self.positions[slot] += 1
                self.stats.tokens_out += 1
                if len(req.out_tokens) >= req.max_new_tokens:
                    req.done = True
                    finished.append(req)
                    del self.active[slot]
        for slot in list(self.prefilling):
            self.prefill_done[slot] += 1
            req = self.prefilling[slot]
            if self.prefill_done[slot] >= len(req.prompt) - 1:
                self.positions[slot] = len(req.prompt) - 1
                del self.prefilling[slot]
                del self.prefill_done[slot]
                self.active[slot] = req
        # decode_s/prefill_s time one fused step end-to-end; StepCostModel
        # calibrates against these, so the step must be complete here
        # reprolint: allow[host-sync]
        jax.block_until_ready(self.caches)
        dt = time.perf_counter() - t0
        if had_decode:
            self.stats.decode_steps += 1
            self.stats.decode_s += dt
        else:
            self.stats.prefill_s += dt
        return finished

    # ---------------------------------------------------------------- run --
    def run(self, requests: List[Request], *,
            scheduler=None) -> EngineStats:
        """Serve ``requests`` to completion under a scheduling policy.

        ``scheduler`` (or the engine's constructor-time one) decides
        per-tick admissions; the default
        :class:`~repro.serve.scheduler.FifoScheduler` preserves the
        pre-refactor first-come-first-served blocking-prefill behavior.
        Open-loop traces (``Request.arrival_s > 0``) are released onto
        the waiting queue as the run clock passes their arrival time.
        """
        from .scheduler import FifoScheduler, serve_loop
        sched = scheduler if scheduler is not None else \
            (self.scheduler if self.scheduler is not None
             else FifoScheduler())
        return serve_loop(self, list(requests), sched)
