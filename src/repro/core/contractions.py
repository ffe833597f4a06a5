"""BLAS-based tensor-contraction algorithms + micro-benchmark prediction
(paper Ch. 6).

A contraction like ``C[abc] = A[ai] * B[ibc]`` can be computed by many
alternative algorithms, each consisting of nested **for**-loops around a
single fixed-size compute kernel (gemm / gemv / ger / dot / axpy analogues —
here: jitted einsums over the kernel dimensions).  §6.1's generator
enumerates them systematically: choose which indices become loop indices,
check the remainder matches a kernel pattern, and permute the loop order.

Since each algorithm performs its *entire* computation in repeated calls to
ONE kernel with FIXED operand sizes, a micro-benchmark of a handful of calls
predicts the whole algorithm (§6.2).  The benchmark is *cache-aware*: for
each operand the *access distance* (bytes touched between consecutive uses
of the same operand slice, §6.2.3) decides whether the timed calls reuse a
warm buffer or cycle through fresh buffers, recreating the cache state of
the real loop nest.  First-iteration overhead (§6.2.6) is measured
separately and added once.
"""

from __future__ import annotations

import functools
import itertools
import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .sampler import Stats, measure_calls

_DTYPE = np.float32
_ITEM = 4


# ------------------------------------------------------------------- spec --

@dataclass(frozen=True)
class ContractionSpec:
    """``C[out] = A[a_idx] * B[b_idx]`` in Einstein notation."""

    a_idx: str
    b_idx: str
    out_idx: str

    @staticmethod
    def parse(expr: str) -> "ContractionSpec":
        """Parse e.g. ``"abc=ai,ibc"`` or einsum-style ``"ai,ibc->abc"``."""
        if "->" in expr:
            ins, out = expr.split("->")
            a, b = ins.split(",")
        else:
            out, ins = expr.split("=")
            a, b = ins.split(",")
        return ContractionSpec(a.strip(), b.strip(), out.strip())

    @property
    def contracted(self) -> Tuple[str, ...]:
        """Indices summed over: shared by A and B but absent from C.

        An index shared by A and B that *also* appears in the output is a
        batch index (e.g. ``b`` in ``bij,bjk->bik``), not a contraction —
        treating it as contracted would let the generator build kernels that
        sum over it.
        """
        return tuple(i for i in self.a_idx
                     if i in self.b_idx and i not in self.out_idx)

    @property
    def batch(self) -> Tuple[str, ...]:
        """Indices shared by A, B and C (batch dimensions).

        The §6.1 kernels are plain BLAS calls without batching, so batch
        indices can only ever be loop indices.
        """
        return tuple(i for i in self.a_idx
                     if i in self.b_idx and i in self.out_idx)

    @property
    def all_indices(self) -> Tuple[str, ...]:
        seen = []
        for i in self.a_idx + self.b_idx:
            if i not in seen:
                seen.append(i)
        return tuple(seen)

    def flops(self, sizes: Mapping[str, int]) -> float:
        return 2.0 * math.prod(sizes[i] for i in self.all_indices)

    def einsum_expr(self) -> str:
        return f"{self.a_idx},{self.b_idx}->{self.out_idx}"


# -------------------------------------------------------------- algorithms --

#: kernel patterns: (#free-A kernel dims, #free-B kernel dims, #contracted)
_KERNEL_PATTERNS = {
    "gemm": (1, 1, 1),
    "gemv": (1, 0, 1),   # A matrix, B vector
    "gevm": (0, 1, 1),   # row-vector x matrix
    "ger": (1, 1, 0),    # outer-product update
    "dot": (0, 0, 1),
    "axpy_a": (1, 0, 0),  # scaled copy of an A fiber
    "axpy_b": (0, 1, 0),
}


@dataclass(frozen=True)
class ContractionAlgorithm:
    """One loop-nest + kernel decomposition of a contraction (§6.1)."""

    spec: ContractionSpec
    kernel: str
    kernel_dims: Tuple[str, ...]   # indices handled inside the kernel call
    loop_order: Tuple[str, ...]    # outer-to-inner loop indices

    @property
    def name(self) -> str:
        loops = "".join(self.loop_order) or "-"
        return f"loops[{loops}]_{self.kernel}[{''.join(self.kernel_dims)}]"

    def kernel_equation(self) -> str:
        """Einsum equation of one kernel invocation."""
        a = "".join(i for i in self.spec.a_idx if i in self.kernel_dims)
        b = "".join(i for i in self.spec.b_idx if i in self.kernel_dims)
        o = "".join(i for i in self.spec.out_idx if i in self.kernel_dims)
        return f"{a},{b}->{o}"

    def n_iterations(self, sizes: Mapping[str, int]) -> int:
        return math.prod(sizes[i] for i in self.loop_order) if \
            self.loop_order else 1

    def kernel_shapes(self, sizes: Mapping[str, int]):
        a = tuple(sizes[i] for i in self.spec.a_idx if i in self.kernel_dims)
        b = tuple(sizes[i] for i in self.spec.b_idx if i in self.kernel_dims)
        o = tuple(sizes[i] for i in self.spec.out_idx
                  if i in self.kernel_dims)
        return a, b, o

    def kernel_flops(self, sizes: Mapping[str, int]) -> float:
        return 2.0 * math.prod(sizes[i] for i in self.kernel_dims)


def generate_algorithms(spec: ContractionSpec,
                        max_loop_perms: int = 24) -> List[ContractionAlgorithm]:
    """Enumerate all loop/kernel decompositions (§6.1).

    For every kernel pattern, choose kernel indices (free-A, free-B,
    contracted) consistent with the pattern, make the rest loop indices, and
    emit one algorithm per loop-order permutation.
    """
    contracted = set(spec.contracted)
    batch = set(spec.batch)
    # batch indices are neither free nor contracted: the BLAS-style kernel
    # patterns cannot absorb them, so they may only become loop indices
    free_a = [i for i in spec.a_idx if i not in contracted and i not in batch]
    free_b = [i for i in spec.b_idx if i not in contracted and i not in batch]
    algs: List[ContractionAlgorithm] = []
    seen = set()
    for kernel, (nfa, nfb, nc) in _KERNEL_PATTERNS.items():
        for ka in itertools.combinations(free_a, nfa):
            for kb in itertools.combinations(free_b, nfb):
                for kc in itertools.combinations(sorted(contracted), nc):
                    kdims = tuple(ka) + tuple(kb) + tuple(kc)
                    loops = [i for i in spec.all_indices if i not in kdims]
                    perms = list(itertools.permutations(loops))
                    if len(perms) > max_loop_perms:
                        perms = perms[:max_loop_perms]
                    for order in perms:
                        key = (kernel, kdims, order)
                        if key in seen:
                            continue
                        seen.add(key)
                        algs.append(ContractionAlgorithm(
                            spec, kernel, kdims, order))
    return algs


# --------------------------------------------------------------- execution --

_ALPHABET = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"


def canonical_equation(equation: str) -> str:
    """Relabel an einsum equation by order of first appearance.

    ``ik,kl->il`` and ``ij,jk->ik`` both become ``ab,bc->ac``: einsum is
    invariant under index renaming (operand *shapes* are positional), so
    one jitted kernel — and one micro-benchmark — serves every renaming.
    Execution (:func:`execute`) and the ``repro.tc`` suite both key on
    the canonical form, which keeps "first-call overhead once per
    distinct signature" true in practice: a chain step renamed from an
    earlier one reuses its compiled kernel instead of recompiling.
    """
    ins, out = equation.split("->")
    a, b = ins.split(",")
    mapping: Dict[str, str] = {}
    for ch in a + b + out:
        if ch not in mapping:
            mapping[ch] = _ALPHABET[len(mapping)]
    rename = lambda s: "".join(mapping[c] for c in s)  # noqa: E731
    return f"{rename(a)},{rename(b)}->{rename(out)}"


@functools.lru_cache(maxsize=None)
def _canonical_kernel_fn(equation: str):
    return jax.jit(lambda a, b: jnp.einsum(equation, a, b))


def _kernel_fn(equation: str):
    # one jit object per CANONICAL equation: renamed-identical kernels
    # share one compiled program (per shape), matching the suite's dedup
    # keys — canonicalize BEFORE the cache lookup, or every raw spelling
    # would get its own jit object and recompile
    return _canonical_kernel_fn(canonical_equation(equation))


def _slicer(idx: str, kernel_dims, assignment):
    return tuple(
        slice(None) if i in kernel_dims else assignment[i] for i in idx)


def execute(alg: ContractionAlgorithm, A: np.ndarray, B: np.ndarray,
            sizes: Mapping[str, int]) -> np.ndarray:
    """Run the loop nest, calling the jitted kernel per iteration."""
    spec = alg.spec
    out_shape = tuple(sizes[i] for i in spec.out_idx)
    C = np.zeros(out_shape, dtype=_DTYPE)
    fn = _kernel_fn(alg.kernel_equation())
    ranges = [range(sizes[i]) for i in alg.loop_order]
    accumulate = any(i in spec.contracted for i in alg.loop_order)
    for combo in itertools.product(*ranges):
        assign = dict(zip(alg.loop_order, combo))
        a = A[_slicer(spec.a_idx, alg.kernel_dims, assign)]
        b = B[_slicer(spec.b_idx, alg.kernel_dims, assign)]
        r = np.asarray(fn(jnp.asarray(a), jnp.asarray(b)))
        csl = _slicer(spec.out_idx, alg.kernel_dims, assign)
        if accumulate:
            C[csl] += r
        else:
            C[csl] = r
    return C


def execute_reference(spec: ContractionSpec, A: np.ndarray,
                      B: np.ndarray) -> np.ndarray:
    return np.einsum(spec.einsum_expr(), A, B)


# ----------------------------------------------------- cache-aware predict --

#: effective cache capacity used for access-distance decisions (bytes).
CACHE_BYTES = 32 * 2 ** 20


def access_distance(alg: ContractionAlgorithm,
                    sizes: Mapping[str, int]) -> Dict[str, float]:
    """Bytes touched between consecutive uses of the same slice (§6.2.3).

    For each operand, count the iterations of the innermost loops that do
    NOT index it: the same slice is reused once those loops cycle, and the
    data touched in between — that many calls' working sets — is the access
    distance.  Operands indexed by the innermost loop change slice every
    iteration, and operands not indexed by any loop are touched on *every*
    iteration; in both cases one call's working set separates consecutive
    uses (distance = ``call_bytes``, §6.2.3 — never 0: even an
    always-touched operand is evicted between uses if a single call's
    operands overflow the cache).
    """
    spec = alg.spec
    a_sh, b_sh, o_sh = alg.kernel_shapes(sizes)
    call_bytes = _ITEM * (math.prod(a_sh) + math.prod(b_sh) +
                          math.prod(o_sh))
    out = {}
    for name, idx in (("A", spec.a_idx), ("B", spec.b_idx),
                      ("C", spec.out_idx)):
        # walk loops inner -> outer; accumulate iteration space not touching
        # this operand, up to the innermost loop that does index it
        reuse_span = 1
        indexed = False
        for loop in reversed(alg.loop_order):
            if loop in idx:
                indexed = True
                break
            reuse_span *= sizes[loop]
        # not indexed by any loop (including loop-less algorithms): the
        # operand is touched on every call, one working set apart
        out[name] = call_bytes * (reuse_span if indexed else 1)
    return out


def _make_buffers(shape, n_copies, rng):
    return [jnp.asarray(rng.standard_normal(shape), dtype=jnp.float32)
            for _ in range(n_copies)]


def cold_pool_size(repetitions: int, call_bytes: int,
                   cache_bytes: int = CACHE_BYTES) -> int:
    """Buffers needed to keep a cold operand cold across the benchmark.

    A cold operand (access distance beyond the cache) must not re-enter the
    cache between timed calls.  Cycling through ``n`` buffers re-uses each
    one every ``n`` calls — i.e. with ``n * call_bytes`` of kernel traffic in
    between — so ``n`` must span the cache capacity; alternatively
    ``repetitions + 1`` buffers (one per timed call plus the overhead call)
    suffice outright because no buffer is ever re-used.  A fixed cap (the
    old ``min(8, repetitions)``) silently turned cold measurements warm
    whenever ``repetitions > 8`` and eight calls' traffic fit in cache.
    """
    span = math.ceil(cache_bytes / max(call_bytes, 1)) + 1
    return max(2, min(repetitions + 1, span))


def run_kernel_benchmark(equation: str, a_shape: Sequence[int],
                         b_shape: Sequence[int], out_shape: Sequence[int], *,
                         cold_a: bool, cold_b: bool, repetitions: int,
                         cache_bytes: int = CACHE_BYTES,
                         rng: Optional[np.random.Generator] = None,
                         ) -> Tuple[Stats, float]:
    """The §6.2 measurement protocol for one kernel signature.

    Returns (per-call stats, first-call overhead in seconds).  Cold input
    operands cycle through a pool of distinct buffers between timed calls —
    sized by :func:`cold_pool_size` from the repetition count and cache
    capacity — while warm ones reuse one buffer.  The kernel is a
    functional jitted einsum that allocates its output, so no output-cache
    precondition can (or need) be established.  Shared by the per-algorithm
    :func:`microbenchmark` and the deduplicated ``repro.tc`` suite, so the
    two paths can never desynchronize.
    """
    rng = rng or np.random.default_rng(0)
    fn = _kernel_fn(equation)
    call_bytes = _ITEM * (math.prod(a_shape) + math.prod(b_shape) +
                          math.prod(out_shape))
    n_cyc = cold_pool_size(repetitions, call_bytes, cache_bytes)
    a_bufs = _make_buffers(tuple(a_shape), n_cyc if cold_a else 1, rng)
    b_bufs = _make_buffers(tuple(b_shape), n_cyc if cold_b else 1, rng)

    counter = [0]

    def call():
        i = counter[0]
        counter[0] += 1
        # the sync IS the measurement: the §2.1.2 protocol brackets exactly
        # one kernel execution, so the timed call must drain the device
        # reprolint: allow[host-sync]
        fn(a_bufs[i % len(a_bufs)],
           b_bufs[i % len(b_bufs)]).block_until_ready()

    # first-call overhead (compile + cold libraries), measured separately
    t0 = time.perf_counter()
    call()
    first = time.perf_counter() - t0
    stats = measure_calls({"k": call}, repetitions=repetitions,
                          warm_pairs=False, warmup=False)["k"]
    return stats, first


def microbenchmark(alg: ContractionAlgorithm, sizes: Mapping[str, int], *,
                   repetitions: int = 5, cache_bytes: int = CACHE_BYTES,
                   rng: Optional[np.random.Generator] = None,
                   ) -> Tuple[Stats, float]:
    """Cache-aware micro-benchmark of ONE kernel invocation (§6.2).

    Classifies each input operand warm/cold by its access distance versus
    the cache capacity and delegates the measurement to
    :func:`run_kernel_benchmark`.
    """
    a_sh, b_sh, o_sh = alg.kernel_shapes(sizes)
    dists = access_distance(alg, sizes)
    return run_kernel_benchmark(alg.kernel_equation(), a_sh, b_sh, o_sh,
                                cold_a=dists["A"] > cache_bytes,
                                cold_b=dists["B"] > cache_bytes,
                                repetitions=repetitions,
                                cache_bytes=cache_bytes, rng=rng)


def predict_contraction(alg: ContractionAlgorithm,
                        sizes: Mapping[str, int], *,
                        repetitions: int = 5,
                        stat: str = "med",
                        breakdown: bool = False):
    """Predicted total runtime: first-call overhead + n_iterations x per-call.

    The measured first-call overhead (§6.2.6: library/compile setup paid
    once per contraction) is included once in the total; ``breakdown=True``
    returns the components instead of the single total.
    """
    stats, first = microbenchmark(alg, sizes, repetitions=repetitions)
    n = alg.n_iterations(sizes)
    per_call = getattr(stats, stat)
    total = first + per_call * n
    if breakdown:
        return {"total_s": total, "first_call_s": first,
                "loop_s": per_call * n, "per_call_s": per_call,
                "n_iterations": n}
    return total


def _session_for(fn: str, session, *, suite=None, cache=None,
                 repetitions=None, extra_deprecated=None):
    """The one shim implementation behind every legacy entry point.

    A supplied ``session=`` conflicts with the legacy resource kwargs it
    replaced (silently preferring one would hide a caller bug); legacy
    kwargs construct a session internally under a single
    :class:`DeprecationWarning`; a bare call gets a fresh default session
    — exactly the resources it would have built before the redesign.
    """
    from ..tc.session import (PredictorSession,  # lazy: tc builds on core
                              warn_deprecated_kwargs)
    legacy = {"suite": suite, "cache": cache, "repetitions": repetitions,
              **(extra_deprecated or {})}
    if session is not None:
        used = [k for k, v in legacy.items() if v is not None]
        if used:
            raise ValueError(
                f"{fn}: session= already owns the "
                f"{', '.join(k + '=' for k in used)} resource(s); pass "
                f"one or the other")
        return session
    warn_deprecated_kwargs(fn, "the session's methods", legacy,
                           stacklevel=4)
    return PredictorSession(suite=suite, cache=cache,
                            repetitions=repetitions)


def rank_contraction_algorithms(spec: ContractionSpec,
                                sizes: Optional[Mapping[str, int]] = None, *,
                                algorithms: Optional[Sequence[
                                    ContractionAlgorithm]] = None,
                                repetitions: Optional[int] = None,
                                stat: str = "med",
                                batched: bool = True,
                                suite=None,
                                cache=None,
                                sizes_grid: Optional[Sequence[
                                    Mapping[str, int]]] = None,
                                session=None,
                                ) -> Union[
                                    List[Tuple[ContractionAlgorithm, float]],
                                    List[List[Tuple[ContractionAlgorithm,
                                                    float]]]]:
    """Predict every algorithm and sort ascending by predicted runtime.

    By default this runs on :class:`repro.tc.ContractionPredictor`: the
    candidate set (including batched-kernel algorithms when ``algorithms``
    is not given) shares one deduplicated micro-benchmark suite and is
    predicted through the batched :class:`PredictionEngine`.  Pass
    ``session=`` (a :class:`repro.tc.PredictorSession`) to share its
    suite and trace cache across calls — the sprawl of per-call
    ``suite=``/``cache=``/``repetitions=``/``sizes_grid=`` keywords is
    DEPRECATED in favor of the session and its methods (one release of
    shim support: they still work, warning, by constructing a session
    internally).  ``batched=False`` keeps the
    original per-algorithm path — one independent micro-benchmark per
    candidate — as the equivalence oracle.

    Size-sweep mode: pass ``sizes_grid=`` (a sequence of size mappings)
    instead of ``sizes`` to rank the candidate set at every size point
    from ONE shared suite — returns one ranked list per size point, and
    only the genuinely new (equation, shapes, cache-class) keys are
    measured (deprecated alias of
    :meth:`repro.tc.PredictorSession.rank_contraction_sweep`, which also
    exposes the shared suite and per-point predictors).
    """
    algorithms = list(algorithms) if algorithms is not None else None
    if sizes_grid is not None:
        if sizes is not None:
            raise ValueError("pass sizes= or sizes_grid=, not both")
        if not batched:
            raise ValueError("sizes_grid= runs on the batched predictor; "
                             "the scalar oracle (batched=False) has no "
                             "size-sweep mode")
        sess = _session_for("rank_contraction_algorithms", session,
                            suite=suite, cache=cache,
                            repetitions=repetitions,
                            extra_deprecated={"sizes_grid": sizes_grid})
        sweep = sess.rank_contraction_sweep(spec, sizes_grid, stat=stat,
                                            algorithms=algorithms)
        return [[(r.algorithm, getattr(r.runtime, stat)) for r in ranking]
                for ranking in sweep.rankings]
    if sizes is None:
        raise ValueError("sizes is required (or pass sizes_grid= for the "
                         "size-sweep mode)")
    if batched:
        sess = _session_for("rank_contraction_algorithms", session,
                            suite=suite, cache=cache,
                            repetitions=repetitions)
        ranked = sess.rank_contraction_algorithms(spec, sizes, stat=stat,
                                                  algorithms=algorithms)
        return [(r.algorithm, getattr(r.runtime, stat)) for r in ranked]
    if suite is not None or cache is not None:
        raise ValueError("suite=/cache= apply to the batched "
                         "predictor; the scalar oracle (batched=False) has "
                         "none of them")
    if session is not None:
        raise ValueError("session= applies to the batched predictor; the "
                         "scalar oracle (batched=False) runs without one")
    algs = algorithms if algorithms is not None else \
        generate_algorithms(spec)
    reps = 5 if repetitions is None else repetitions
    ranked = [(a, predict_contraction(a, sizes, repetitions=reps,
                                      stat=stat)) for a in algs]
    ranked.sort(key=lambda t: t[1])
    return ranked


def measure_contraction(alg: ContractionAlgorithm, A: np.ndarray,
                        B: np.ndarray, sizes: Mapping[str, int],
                        repetitions: int = 3) -> Stats:
    """Time full algorithm executions (the expensive reference, §6.3)."""
    execute(alg, A, B, sizes)  # warm-up/compile
    samples = []
    for _ in range(repetitions):
        t0 = time.perf_counter()
        execute(alg, A, B, sizes)
        samples.append(time.perf_counter() - t0)
    return Stats.from_samples(samples)
