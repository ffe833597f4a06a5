"""One pass over the system's main path on one TPU, every result checked.

Run from the repository root on a host with a TPU::

    python3 chip_smoke.py

It runs four phases in one process, in this order, and prints each
phase's findings and seconds on lines of their own:

1. ``device``: the platform JAX reports.  Anything but a TPU stops here.
2. ``blocked`` (paper §3, §4.5/§4.6): measure and fit models of every
   kernel case the Cholesky tracers emit at n=2048 and block sizes
   64-512, at most 64 fresh points per case, rank the three ``potrf`` variants and pick a block size
   without executing, then execute every variant at every candidate block
   size and report the pick's regret.  Every factor is checked against
   ``numpy.linalg.cholesky``, and the fused float64 predictor on the chip
   against the numpy backend.
3. ``tiles``: measure the Pallas matmul, flash-attention and SSD kernels
   on the chip at real widths, rank the matmul tiles of one real shape,
   execute every legal tile and report the pick's regret.  Every kernel
   is checked against ``repro.kernels.ref``.
4. ``serve``: serve mamba2-2.7b whole, at its published widths and in
   float32, under the model-guided scheduler, and compare every
   request's logits with the reference forward pass.  Beside that error
   it prints the reference's own spread over scan chunks and a control,
   the reference with bfloat16-rounded weights, that the check must
   fail.

The last line is ``{"ok": true, "device": {...}}``.  A failed phase or
check exits non-zero before it.  All data and weights come from seeds.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import sys
import time
from pathlib import Path
from typing import Dict, Sequence, Tuple

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (STATS, GeneratorConfig, KernelBenchmark,  # noqa: E402
                        ModelSet, PredictionEngine, compile_calls,
                        generate_model, optimize_algorithm_and_block_size)
from repro.core.grids import Domain  # noqa: E402
from repro.dla import ExecEngine, blocked  # noqa: E402
from repro.dla.kernels import KERNELS  # noqa: E402
from repro.dla.tracers import CHOLESKY_TRACERS  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import forward, init_params  # noqa: E402
from repro.perf.tile_tuner import rank_tiles  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402
from repro.tc import PredictorSession  # noqa: E402

SEED = 0
_MED = STATS.index("med")

# ---------------------------------------------------------- tolerances --
#: a pick may be at most this much slower than the fastest candidate
#: measured.  The paper's block-size selection reaches yields of 0.9 and
#: more on its CPUs (§4.6); 0.8 is allowed here, on a chip where every
#: kernel call of the blocked algorithms also pays host transfers.
REGRET_TOL = 0.25
#: factor error, max |L - L_numpy| / max |L_numpy|.  The chip's default
#: matmul precision rounds float32 operands to bfloat16 (unit roundoff
#: 2^-9) in the gemm/syrk updates.  On the seeded SPD matrix (a a^T +
#: n I) a host emulation of exactly that rounding gives 4.7e-5 at n=2048
#: for block sizes 64 and 512; 5e-4 leaves a factor of ten.
FACTOR_TOL = 5e-4
#: fused float64 predictor on the chip vs the numpy backend, max relative
#: difference of any statistic.  Both evaluate the same float64
#: polynomials and agree to ~1e-8 on the CPU; the chip emulates float64
#: in software, so 1e-6 is allowed.
PREDICTOR_TOL = 1e-6
#: kernel output vs its jnp oracle, max |out - ref| / max |ref|, the
#: oracle at HIGHEST precision.  A kernel whose float32 dots run as one
#: bfloat16 pass has a per-product error of at most 2^-8 relative; summed
#: with random signs, the error stays near 2^-8 of the output's scale
#: (4e-3).  1e-2 holds that and fails any wrong tile, mask or carry.
KERNEL_TOL = 1e-2
#: served logits vs the reference forward pass, max over every request
#: and output position of max |served - ref| / max |ref| in that row.
#: The engine's decode step runs its float32 matmuls at HIGHEST, not at
#: the chip's default (one bfloat16 pass per matmul): at the default XLA
#: converts every layer's weights to bfloat16 ahead of the layer loop,
#: and mamba2-2.7b's step then needs 17.35 GB of a 16 GB v5e.  The
#: reference runs at HIGHEST too.  What differs is float32 rounding and
#: the algorithm, the server's per-token recurrence against the
#: reference's chunked scan, amplified over 64 layers of random weights:
#: the chip gave 2.652e-3 (chip run, PR 11).  The run prints the two
#: bounds this limit sits between: the reference's own spread, between
#: scan chunks of 32 and of the config's 256 (6.5e-4 at the served
#: positions, 5.3e-3 over all of them), and a control, the reference with
#: its weights rounded to bfloat16 (what a server at the default
#: precision would round at the least), which must exceed the limit.
LOGIT_TOL = 1e-2


# ------------------------------------------------------------------ sizes --
# blocked: the paper's Cholesky at n=2048 over block sizes 64-512
N = 2048
BLOCK_SIZES = (64, 128, 256, 512)
#: fresh measurement points per kernel case: refinement samples no more
#: (a root grid larger than this would be sampled whole; none here is)
MAX_POINTS = 64
#: the benchmarks' generator settings (benchmarks.common), with the budget
GEN_CONFIG = GeneratorConfig(overfit=0, oversampling=2, repetitions=5,
                             error_bound=0.04, min_width=64, max_pieces=6,
                             max_points=MAX_POINTS)
POTRF_REPETITIONS = 3
# tiles: 2048 tokens through mamba2-2.7b's 2560 -> 2 x 5120 in-projection
MM_SHAPE = (2048, 10240, 2560)                # (m, n, k)
TILE_REPETITIONS = 5
ATTN_SHAPE = (1, 8, 2048, 128)                # (b, h, s, d)
ATTN_BLOCKS = (128, 256, 512)                 # bq and bkv candidates
# mamba2-2.7b: 80 heads of 64, one group, state 128
SSD_SHAPE = (1, 1024, 80, 64, 1, 128)         # (b, l, h, p, g, n)
SSD_CHUNKS = (64, 128, 256)
# serve
MODEL = "mamba2-2.7b"
SLOTS = 8
PROMPT_LENS = (32, 96)                        # inclusive range
NEW_TOKENS = 16
REF_LEN = 128                                 # padded reference length
SPREAD_CHUNK = 32                             # second reference's scan chunk


def check(ok: bool, what: str) -> None:
    """Stop the run, exit code 1, if a check fails."""
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def say(phase: str, line: str) -> None:
    print(f"[{phase}] {line}", flush=True)


def _norm_err(got, want) -> float:
    """max |got - want| / max |want|."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _regret(measured: Dict, pick) -> Tuple[float, object]:
    best = min(measured, key=measured.get)
    return measured[pick] / measured[best] - 1.0, best


# ---------------------------------------------------------------- device --
def phase_device() -> Dict[str, object]:
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    say("device", f"platform={info['platform']} kind={info['kind']} "
                  f"count={info['count']}")
    check(info["platform"] == "tpu",
          f"JAX finds no TPU (platform {info['platform']!r})")
    return info


# --------------------------------------------------------------- blocked --
def cholesky_cases(n: int, block_sizes: Sequence[int]) -> Dict:
    """{(kernel, case): (lo, hi)}: the box of non-degenerate sizes each
    kernel case of the Cholesky tracers takes at ``n`` over
    ``block_sizes``."""
    box: Dict = {}
    for tracer in CHOLESKY_TRACERS.values():
        for b in block_sizes:
            for call in tracer(n, b):
                if min(call.sizes) == 0:
                    continue          # degenerate: predicted as 0 s
                lo, hi = box.get((call.kernel, call.case),
                                 (call.sizes, call.sizes))
                box[(call.kernel, call.case)] = (
                    tuple(map(min, lo, call.sizes)),
                    tuple(map(max, hi, call.sizes)))
    return box


def measure_models() -> ModelSet:
    ms = ModelSet()
    t0 = time.perf_counter()
    points = 0
    for (kernel, case), (lo, hi) in sorted(
            cholesky_cases(N, BLOCK_SIZES).items()):
        kd = KERNELS[kernel]
        bench = KernelBenchmark(name=kernel, cases=(case,),
                                domain=Domain(lo, hi),
                                cost_exponents=kd.cost_exponents,
                                make_call=kd.make_call)
        model, report = generate_model(bench, GEN_CONFIG)
        ms.add(model)
        points += report.measured_points
        say("blocked", f"model {kernel}{list(case)} over {lo}..{hi}: "
                       f"{report.measured_points} points, "
                       f"{sum(report.pieces_per_case.values())} pieces, "
                       f"{report.seconds:.1f} s")
        check(report.measured_points <= MAX_POINTS,
              f"{kernel} measured {report.measured_points} points")
    say("blocked", f"measured {points} points in "
                   f"{time.perf_counter() - t0:.1f} s (at most "
                   f"{MAX_POINTS} fresh points per case)")
    return ms


def run_potrf(a: np.ndarray, variant: int, b: int) -> Tuple[float, np.ndarray]:
    """Seconds of one blocked Cholesky execution, and its factor."""
    eng = ExecEngine()
    mat = eng.bind("A", a)
    t0 = time.perf_counter()
    blocked.potrf(eng, mat, a.shape[0], b, variant)
    factor = eng.mats["A"]
    seconds = time.perf_counter() - t0
    return seconds, np.tril(factor)


def phase_blocked() -> None:
    ms = measure_models()
    configs = [(name, b) for name in CHOLESKY_TRACERS
               for b in BLOCK_SIZES]
    numpy_engine = PredictionEngine(ms)
    compiled = compile_calls([
        numpy_engine.cache.calls(CHOLESKY_TRACERS[name], N, b)
        for name, b in configs])
    predicted_stats = numpy_engine.predict_compiled(compiled)
    predicted = {c: float(t) for c, t in zip(configs,
                                             predicted_stats[:, _MED])}
    name, b, _ = optimize_algorithm_and_block_size(
        CHOLESKY_TRACERS, ms, N, list(BLOCK_SIZES))
    pick = (name, b)
    check(pick == min(predicted, key=predicted.get),
          f"selection {pick} is not the predicted minimum")
    say("blocked", "predicted order: " + ", ".join(
        f"{v}/b={bb} {predicted[(v, bb)]:.4f}s"
        for v, bb in sorted(predicted, key=predicted.get)))

    rng = np.random.default_rng(SEED)
    g = rng.standard_normal((N, N))
    a = g @ g.T + N * np.eye(N)
    want = np.linalg.cholesky(a)
    measured = {}
    worst = 0.0
    for v, bb in configs:
        variant = int(v[len("potrf"):])
        _, factor = run_potrf(a, variant, bb)   # compiles every shape
        worst = max(worst, _norm_err(factor, want))
        measured[(v, bb)] = float(np.median(
            [run_potrf(a, variant, bb)[0]
             for _ in range(POTRF_REPETITIONS)]))
    say("blocked", "measured order: " + ", ".join(
        f"{v}/b={bb} {measured[(v, bb)]:.4f}s"
        for v, bb in sorted(measured, key=measured.get)))
    regret, best = _regret(measured, pick)
    say("blocked", f"pick {pick[0]}/b={pick[1]} measured "
                   f"{measured[pick]:.4f}s; fastest {best[0]}/b={best[1]} "
                   f"{measured[best]:.4f}s; regret {regret:.4f} "
                   f"(tolerance {REGRET_TOL})")
    check(regret <= REGRET_TOL, f"potrf regret {regret:.4f}")
    say("blocked", f"factor error {worst:.3e} over {len(configs)} "
                   f"executions (tolerance {FACTOR_TOL})")
    check(worst <= FACTOR_TOL, f"Cholesky factor error {worst:.3e}")

    fused = PredictionEngine(ms, backend="jax", cache=numpy_engine.cache)
    got = fused.predict_compiled(compiled)
    rel = float(np.max(np.abs(got - predicted_stats)
                       / np.maximum(np.abs(predicted_stats), 1e-300)))
    say("blocked", f"fused jax predictor ({jax.default_backend()}, "
                   f"float64) vs numpy: max relative difference "
                   f"{rel:.3e} (tolerance {PREDICTOR_TOL})")
    check(rel <= PREDICTOR_TOL, f"fused predictor difference {rel:.3e}")


# ----------------------------------------------------------------- tiles --
def _median_seconds(fn, repetitions: int) -> float:
    jax.block_until_ready(fn())                 # compile + warm
    samples = []
    for _ in range(repetitions):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples))


def phase_tiles() -> None:
    session = PredictorSession()
    device = session.device_suite(interpret=False)
    key = jax.random.PRNGKey(SEED)
    k_x, k_y, k_q, k_k, k_v, k_s = jax.random.split(key, 6)
    highest = jax.default_matmul_precision("highest")

    # matmul: rank every legal tile from proxy measurements, then run all
    m, n, k = MM_SHAPE
    ranked = rank_tiles(m, n, k, session=session)
    pick = (ranked[0].bm, ranked[0].bn, ranked[0].bk)
    x = jax.random.normal(k_x, (m, k), jnp.float32)
    y = jax.random.normal(k_y, (k, n), jnp.float32)
    measured = {}
    for t in ranked:
        tile = (t.bm, t.bn, t.bk)
        call = functools.partial(ops.matmul, x, y, bm=t.bm, bn=t.bn,
                                 bk=t.bk, interpret=False)
        measured[tile] = _median_seconds(call, TILE_REPETITIONS)
    regret, best = _regret(measured, pick)
    pred_rank = {(t.bm, t.bn, t.bk): i for i, t in enumerate(ranked)}
    meas_rank = sorted(measured, key=measured.get)
    say("tiles", f"matmul {MM_SHAPE}: {len(ranked)} legal tiles; "
                 f"predicted top 5 {[tuple(c) for c in list(pred_rank)[:5]]}"
                 f", measured top 5 {meas_rank[:5]}")
    # the ranking total adds host transfers of every operand; the timed
    # calls find their operands on the device, so compare the compute term
    say("tiles", f"pick {pick} measured {measured[pick] * 1e3:.3f} ms "
                 f"(predicted compute {ranked[0].t_compute * 1e3:.3f} ms "
                 f"+ transfers {(ranked[0].t_h2d + ranked[0].t_d2h) * 1e3:.3f}"
                 f" ms); "
                 f"fastest {best} {measured[best] * 1e3:.3f} ms, predicted "
                 f"rank {pred_rank[best] + 1}; regret {regret:.4f} "
                 f"(tolerance {REGRET_TOL})")
    check(regret <= REGRET_TOL, f"tile regret {regret:.4f}")
    with highest:
        want = ref.matmul_ref(x, y)
    err = _norm_err(ops.matmul(x, y, bm=pick[0], bn=pick[1], bk=pick[2],
                               interpret=False), want)
    say("tiles", f"matmul {pick} vs ref: {err:.3e} (tolerance "
                 f"{KERNEL_TOL})")
    check(err <= KERNEL_TOL, f"matmul error {err:.3e}")

    # flash attention: measure every (bq, bkv) block, check each
    b, h, s, d = ATTN_SHAPE
    blocks = [(bq, bkv, d) for bq in ATTN_BLOCKS
              for bkv in ATTN_BLOCKS]
    bench = device.measure_grid("flash_attention", blocks)
    q, kk, v = (jax.random.normal(kx, (b, h, s, d), jnp.float32)
                for kx in (k_q, k_k, k_v))
    with highest:
        want = ref.attention_ref(q, kk, v)
    errs = {cfg: _norm_err(ops.attention(q, kk, v, bq=cfg[0], bkv=cfg[1],
                                         interpret=False), want)
            for cfg in blocks}
    say("tiles", "flash_attention per-call (proxy) "
        + ", ".join(f"{c[:2]} {bench[c].stats.med * 1e6:.1f}us"
                    for c in blocks)
        + f"; max error vs ref at {ATTN_SHAPE}: "
          f"{max(errs.values()):.3e} (tolerance {KERNEL_TOL})")
    check(max(errs.values()) <= KERNEL_TOL,
          f"flash attention errors {errs}")

    # SSD at mamba2 widths: measure every chunk, check each
    b, l, h, p, g, nst = SSD_SHAPE
    chunks = [(c, p, nst) for c in SSD_CHUNKS]
    bench = device.measure_grid("pallas_ssd", chunks)
    ks = jax.random.split(k_s, 5)
    xs = jax.random.normal(ks[0], (b, l, h, p), jnp.float32)
    dt = jax.random.uniform(ks[1], (b, l, h), jnp.float32, 1e-3, 1e-1)
    a_log = jnp.log(jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0))
    bb = jax.random.normal(ks[3], (b, l, g, nst), jnp.float32)
    cc = jax.random.normal(ks[4], (b, l, g, nst), jnp.float32)
    with highest:
        want = ref.ssd_ref(xs, dt, a_log, bb, cc)
    errs = {c: _norm_err(ops.ssd(xs, dt, a_log, bb, cc, chunk=c[0],
                                 interpret=False), want)
            for c in chunks}
    say("tiles", "pallas_ssd per-call (proxy) "
        + ", ".join(f"chunk {c[0]} {bench[c].stats.med * 1e6:.1f}us"
                    for c in chunks)
        + f"; max error vs ref at {SSD_SHAPE}: "
          f"{max(errs.values()):.3e} (tolerance {KERNEL_TOL})")
    check(max(errs.values()) <= KERNEL_TOL, f"SSD errors {errs}")


# ----------------------------------------------------------------- serve --
def _reference(cfg, params, seqs: np.ndarray) -> jax.Array:
    """``models.forward`` over ``seqs``, float32 at HIGHEST precision."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(functools.partial(forward, cfg))(params,
                                                        jnp.asarray(seqs))


def _row_err(got: np.ndarray, want: np.ndarray) -> float:
    """max over rows (last axis) of max |got - want| / max |want|."""
    return float(np.max(np.max(np.abs(got - want), axis=-1)
                        / np.max(np.abs(want), axis=-1)))


def _round_to_bf16(x: jax.Array) -> jax.Array:
    """float32 ``x`` rounded to the nearest bfloat16, ties to even, kept in
    float32.  Integer arithmetic on the bits: XLA may fold a float32 ->
    bfloat16 -> float32 convert pair away (excess precision), and on a
    TPU it does."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def phase_serve() -> None:
    cfg = get_config(MODEL)
    t0 = time.perf_counter()
    params = init_params(cfg, jax.random.PRNGKey(SEED), dtype=jnp.float32)
    jax.block_until_ready(params)
    weights = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    say("serve", f"{cfg.name}: {cfg.n_layers} layers, d_model "
                 f"{cfg.d_model}, float32 weights {weights / 1e9:.2f} GB, "
                 f"built in {time.perf_counter() - t0:.1f} s")

    session = PredictorSession()
    sched = session.guided_scheduler(cfg, slots=SLOTS)
    say("serve", f"step-cost model: {sched.model.n_benchmarks} "
                 f"micro-benchmarks in {sched.model.build_seconds:.1f} s, "
                 f"predicted tick {sched.model.tick_cost(SLOTS) * 1e3:.3f}"
                 f" ms")

    rng = np.random.default_rng(SEED)
    lo, hi = PROMPT_LENS
    requests = [Request(uid=i, max_new_tokens=NEW_TOKENS, keep_logits=True,
                        prompt=rng.integers(0, cfg.vocab,
                                            int(rng.integers(lo, hi + 1)),
                                            dtype=np.int32))
                for i in range(SLOTS)]
    # HIGHEST: at the chip's default precision the step does not fit (see
    # LOGIT_TOL)
    engine = ServeEngine(cfg, params, batch_slots=SLOTS, ctx_len=REF_LEN,
                         matmul_precision="highest")
    t0 = time.perf_counter()
    stats = engine.run(requests, scheduler=sched)
    say("serve", f"{len(requests)} requests, prompts "
                 f"{sorted(len(r.prompt) for r in requests)}: "
                 f"{stats.tokens_out} tokens, {stats.ticks} ticks, "
                 f"{stats.decode_steps} decode steps in "
                 f"{time.perf_counter() - t0:.1f} s (first step compiles)")
    check(all(r.done and len(r.out_tokens) == NEW_TOKENS
              for r in requests), "a request was not served in full")

    # references: forward over prompt + output, padded at the end (the
    # model is causal, so the padding changes no earlier logit)
    seqs = np.zeros((len(requests), REF_LEN), np.int32)
    for i, r in enumerate(requests):
        s = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1],
                                                  np.int32)])
        check(len(s) <= REF_LEN, f"request {r.uid} exceeds REF_LEN")
        seqs[i, :len(s)] = s

    def at_served(logits: jax.Array) -> np.ndarray:
        """(requests, NEW_TOKENS, V): the rows the server produced."""
        return np.stack([np.asarray(logits[i, len(r.prompt) - 1:
                                           len(r.prompt) - 1 + NEW_TOKENS])
                         for i, r in enumerate(requests)])

    served = np.stack([np.stack([np.asarray(lg[slot, 0])
                                 for lg, slot in r.out_logits])
                       for r in requests])
    want_all = _reference(cfg, params, seqs)
    want = at_served(want_all)
    worst = _row_err(served, want)
    say("serve", f"logits vs forward (float32, both at HIGHEST) over "
                 f"{len(requests)} x {NEW_TOKENS} positions: max "
                 f"error {worst:.3e} (tolerance {LOGIT_TOL})")
    check(worst <= LOGIT_TOL, f"logit error {worst:.3e}")

    # what the limit sits between: the reference's own spread over scan
    # chunks, and a control the check must fail
    other_all = _reference(dataclasses.replace(cfg, ssm_chunk=SPREAD_CHUNK),
                           params, seqs)
    spread = _row_err(at_served(other_all), want)
    spread_all = _row_err(np.asarray(other_all), np.asarray(want_all))
    del other_all, want_all
    say("serve", f"reference spread, scan chunk {SPREAD_CHUNK} vs "
                 f"{min(cfg.ssm_chunk, REF_LEN)}: {spread:.3e} at the "
                 f"served positions, {spread_all:.3e} over all {REF_LEN}")
    # round the weights in place (donated): nothing needs the float32
    # ones again, and two copies do not fit
    to_bf16 = jax.jit(lambda tree: jax.tree_util.tree_map(_round_to_bf16,
                                                          tree),
                      donate_argnums=(0,))
    control = _row_err(at_served(_reference(cfg, to_bf16(params), seqs)),
                       want)
    say("serve", f"control, forward with bfloat16-rounded weights vs "
                 f"forward: {control:.3e} (must exceed {LOGIT_TOL})")
    check(control > LOGIT_TOL,
          f"the logit check cannot tell bfloat16 weights apart "
          f"({control:.3e})")
    stats_mem = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats_mem:
        say("serve", f"peak device memory "
                     f"{stats_mem['peak_bytes_in_use'] / 1e9:.2f} GB")


def main() -> None:
    t_all = time.perf_counter()
    info = phase_device()
    say("device", f"compile cache {enable_compile_cache()}")
    for name, phase in (("blocked", phase_blocked), ("tiles", phase_tiles),
                        ("serve", phase_serve)):
        t0 = time.perf_counter()
        phase()
        say(name, f"phase done in {time.perf_counter() - t0:.1f} s")
    say("device", f"all phases in {time.perf_counter() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
