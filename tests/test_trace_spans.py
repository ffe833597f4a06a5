"""The program's profiler spans: ``ExecEngine``'s kernel-call phases, the
predictor's tracing, compiling and evaluating, and the names of the
level-3 kernel programs.

Each test records a ``jax.profiler`` trace around the work and reads the
spans, with their stats, back from the ``.xplane.pb``."""

from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

from benchmarks.common import catalog_synthetic_model_set
from repro.core import PredictionEngine, optimize_algorithm_and_block_size
from repro.core.predict import TraceCache
from repro.dla import ExecEngine, Matrix, blocked, kernels as K, trace_calls
from repro.dla.tracers import CHOLESKY_TRACERS, potrf_tracer

# the profiler's event stats warn on first access under Python 3.12+
pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats:DeprecationWarning")

PHASES = ["dla.exec.upload", "dla.exec.kernel", "dla.exec.download",
          "dla.exec.writeback"]


def recorded_spans(log_dir, work):
    """Run ``work`` under the profiler; its ``dla.*`` and ``predict.*``
    spans as ``(start_ns, name, stats)``, in time order."""
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("dla.", "predict.")):
                    spans.append((ev.start_ns, ev.name, dict(ev.stats)))
    return sorted(spans, key=lambda sp: sp[0])


def operand_bytes(call):
    """(uploaded, downloaded) float32 bytes of one ``ExecEngine`` call of
    the Cholesky kernels: every operand's full view up, the result down."""
    s = call.sizes
    if call.kernel == "gemm":
        m, n, k = s
        return 4 * (m * k + k * n + m * n), 4 * m * n
    if call.kernel == "syrk":
        n, k = s
        return 4 * (n * k + n * n), 4 * n * n
    if call.kernel == "trsm":
        m, n = s
        t = n if call.case[0] == "R" else m
        return 4 * (t * t + m * n), 4 * m * n
    if call.kernel == "potf2":
        (n,) = s
        return 4 * n * n, 4 * n * n
    raise KeyError(call.kernel)


@pytest.mark.parametrize("variant", [1, 2, 3])
def test_exec_engine_spans_each_kernel_call(tmp_path, variant):
    n, b = 256, 64
    rng = np.random.default_rng(variant)
    g = rng.standard_normal((n, n))
    a = g @ g.T + n * np.eye(n)
    eng = ExecEngine()

    def work():
        blocked.potrf(eng, eng.bind("A", a), n, b, variant)

    spans = recorded_spans(tmp_path, work)
    calls = [c for c in trace_calls(lambda e: blocked.potrf(
        e, Matrix("A", n, n), n, b, variant)) if min(c.sizes) > 0]
    assert [name for _, name, _ in spans] == \
        ["dla.exec.bind"] + PHASES * len(calls)
    assert spans[0][2]["bytes"] == 4 * n * n
    for phase in PHASES:
        assert [st["kernel"] for _, name, st in spans if name == phase] == \
            [c.kernel for c in calls]
    up, down = (sum(x) for x in zip(*map(operand_bytes, calls)))
    assert sum(st["bytes"] for _, name, st in spans
               if name == "dla.exec.upload") == up
    assert sum(st["bytes"] for _, name, st in spans
               if name == "dla.exec.download") == down
    np.testing.assert_allclose(np.tril(eng.mats["A"]),
                               np.linalg.cholesky(a), rtol=1e-3, atol=1e-3)


def test_trace_cache_spans_each_miss_and_no_hit(tmp_path):
    cache = TraceCache()
    tracer = potrf_tracer(3)

    def work():
        cache.calls(tracer, 128, 32)
        cache.calls(tracer, 128, 32)
        cache.calls(tracer, 128, 64)

    spans = recorded_spans(tmp_path, work)
    assert [(name, st) for _, name, st in spans] == [
        ("predict.trace", {"n": 128, "b": 32}),
        ("predict.trace", {"n": 128, "b": 64})]
    assert (cache.misses, cache.hits) == (2, 1)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_selection_traces_compiles_and_evaluates_in_spans(tmp_path,
                                                          backend):
    models = catalog_synthetic_model_set()
    engine = PredictionEngine(models, backend=backend)
    candidates = [32, 64]
    out = {}

    def work():
        out["pick"] = optimize_algorithm_and_block_size(
            CHOLESKY_TRACERS, models, 256, candidates, engine=engine)

    spans = recorded_spans(tmp_path, work)
    configs = len(CHOLESKY_TRACERS) * len(candidates)
    assert [name for _, name, _ in spans] == \
        ["predict.trace"] * configs + ["predict.compile", "predict.evaluate"]
    assert [st["b"] for _, _, st in spans[:configs]] == \
        candidates * len(CHOLESKY_TRACERS)
    assert spans[-2][2] == {"configs": configs}
    assert spans[-1][2] == {"backend": backend}
    assert out["pick"][0] in CHOLESKY_TRACERS


LEVEL3 = {"gemm": K._gemm_fn, "syrk": K._syrk_fn, "syr2k": K._syr2k_fn,
          "symm": K._symm_fn, "trsm": K._trsm_fn, "trmm": K._trmm_fn}


@pytest.mark.parametrize("name", sorted(LEVEL3))
def test_level3_programs_carry_the_kernel_name(name):
    kd = K.KERNELS[name]
    case = kd.cases[0]
    sizes = (8, 8, 8)[:len(kd.cost_exponents(case)[0])]
    ops = kd.make_operands(case, sizes, np.random.default_rng(0))
    flags = tuple(float(c) if isinstance(c, (int, float)) else c
                  for c in case)
    text = LEVEL3[name](*flags).lower(*ops).as_text()
    assert f"@jit_{name} " in text and "jit_f " not in text
