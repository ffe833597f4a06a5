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


def potrf_calls(n, b, variant):
    """The non-degenerate kernel calls of one blocked Cholesky."""
    return [c for c in trace_calls(lambda e: blocked.potrf(
        e, Matrix("A", n, n), n, b, variant)) if min(c.sizes) > 0]


def spd(n, seed):
    g = np.random.default_rng(seed).standard_normal((n, n))
    return g @ g.T + n * np.eye(n)


@pytest.mark.parametrize("variant", [1, 2, 3])
def test_exec_engine_spans_each_kernel_call(tmp_path, variant):
    """bind uploads once, each kernel call is one dispatch with no
    transfer, and the fetch downloads once: 4 n^2 bytes each way."""
    n, b = 256, 64
    a = spd(n, variant)
    eng = ExecEngine()
    out = {}

    def work():
        blocked.potrf(eng, eng.bind("A", a), n, b, variant)
        out["L"] = eng.mats["A"]

    spans = recorded_spans(tmp_path, work)
    calls = potrf_calls(n, b, variant)
    assert [name for _, name, _ in spans] == \
        ["dla.exec.bind", "dla.exec.upload"] + \
        ["dla.exec.kernel"] * len(calls) + ["dla.exec.download"]
    assert spans[0][2]["bytes"] == 4 * n * n
    assert spans[1][2] == {"kernel": "bind", "bytes": 4 * n * n}
    assert [st["kernel"] for _, name, st in spans
            if name == "dla.exec.kernel"] == [c.kernel for c in calls]
    assert spans[-1][2] == {"kernel": "fetch", "bytes": 4 * n * n}
    np.testing.assert_allclose(np.tril(out["L"]), np.linalg.cholesky(a),
                               rtol=1e-3, atol=1e-3)


def _lower(n, seed):
    a = np.tril(np.random.default_rng(seed).standard_normal((n, n)))
    np.fill_diagonal(a, np.abs(a.diagonal()) + n)
    return a


#: algorithm -> (matrices it binds, how it runs on them), n = 96, b = 32
PRODUCT_PATHS = {
    "potrf": (lambda: {"A": spd(96, 0)},
              lambda e, m: blocked.potrf(e, m["A"], 96, 32, 3)),
    "trtri": (lambda: {"A": _lower(96, 1)},
              lambda e, m: blocked.trtri(e, m["A"], 96, 32, 5)),
    "lauum": (lambda: {"A": _lower(96, 2)},
              lambda e, m: blocked.lauum(e, m["A"], 96, 32)),
    "getrf": (lambda: {"A": spd(96, 3)},
              lambda e, m: blocked.getrf(e, m["A"], 96, 32)),
    "sygst": (lambda: {"A": spd(96, 4), "L": _lower(96, 4)},
              lambda e, m: blocked.sygst(e, m["A"], m["L"], 96, 32)),
}


@pytest.mark.parametrize("alg", sorted(PRODUCT_PATHS))
def test_product_path_moves_each_matrix_once(tmp_path, alg):
    """bind -> kernel calls -> one read of ``mats``: 4 n^2 bytes up per
    bound matrix, 4 n^2 down for the one fetched, nothing per call."""
    make, run = PRODUCT_PATHS[alg]
    inputs = make()
    eng = ExecEngine()

    def work():
        run(eng, {k: eng.bind(k, v) for k, v in inputs.items()})
        eng.mats["A"]

    spans = recorded_spans(tmp_path, work)
    moved = [(name, st["kernel"], st["bytes"]) for _, name, st in spans
             if name in ("dla.exec.upload", "dla.exec.download")]
    assert moved == [("dla.exec.upload", "bind", 4 * 96 * 96)] * \
        len(inputs) + [("dla.exec.download", "fetch", 4 * 96 * 96)]
    assert sum(name == "dla.exec.kernel" for _, name, _ in spans) > 0


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_bind_never_writes_the_callers_array(dtype):
    """The stored matrix is donated to every kernel call; the caller's
    array, read once by ``bind``, stays as it was."""
    n, b = 128, 32
    a = spd(n, 5).astype(dtype)
    before = a.copy()
    eng = ExecEngine()
    blocked.potrf(eng, eng.bind("A", a), n, b, 3)
    np.testing.assert_allclose(np.tril(eng.mats["A"]),
                               np.linalg.cholesky(before), rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_array_equal(a, before)
    assert a.dtype == dtype


def _scale_first_entry(host, b):
    host[0, 0] *= 1.01            # fault injection into a finished block


def _scale_panel(host, b):
    host[b:, :b] = 2 * host[b:, :b]   # host panel work, as geqrf_exec


@pytest.mark.parametrize("write", [_scale_first_entry, _scale_panel])
def test_host_write_through_mats_reaches_the_next_kernel_call(tmp_path,
                                                               write):
    """Host code that writes ``mats[key]`` between two kernel calls hands
    the next call the written values, through one upload."""
    n, b = 128, 64
    a = spd(n, 6)
    eng = ExecEngine()
    A = eng.bind("A", a)
    L11 = A.v(0, b, 0, b)

    def work():
        eng.potf2("L", L11)
        write(eng.mats["A"], b)
        eng.trsm("R", "L", "T", "N", 1, L11, A.v(b, n, 0, b))

    spans = recorded_spans(tmp_path, work)
    assert [(name, st.get("kernel")) for _, name, st in spans] == [
        ("dla.exec.kernel", "potf2"), ("dla.exec.download", "fetch"),
        ("dla.exec.upload", "trsm"), ("dla.exec.kernel", "trsm")]
    want = np.array(a)
    want[:b, :b] = np.linalg.cholesky(a[:b, :b])
    write(want, b)
    want[b:, :b] = np.linalg.solve(np.tril(want[:b, :b]), want[b:, :b].T).T
    got = eng.mats["A"]
    np.testing.assert_allclose(np.tril(got[:, :b]), np.tril(want[:, :b]),
                               rtol=1e-4, atol=1e-4)


def test_engine_is_freed_without_the_cycle_collector():
    """An engine and the matrices it holds are freed as soon as the
    caller drops them, so a stream of problems reuses their memory."""
    import gc
    import weakref
    n = 64
    gc.disable()
    try:
        eng = ExecEngine()
        blocked.potrf(eng, eng.bind("A", spd(n, 8)), n, 32, 3)
        host = eng.mats["A"]
        gone = weakref.ref(eng)
        del eng
        assert gone() is None
        assert host.flags.writeable
    finally:
        gc.enable()


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


@pytest.mark.parametrize("n", [256, 320, 384])
def test_default_engine_selection_evaluates_a_cached_batch(tmp_path, n):
    """Without ``engine=`` a second selection at the same n neither traces
    nor compiles: one ``predict.evaluate``, and the answer a fresh engine
    and the scalar oracle give."""
    models = catalog_synthetic_model_set()
    candidates = [32, 64, 128]
    optimize_algorithm_and_block_size(CHOLESKY_TRACERS, models, n,
                                      candidates)
    out = {}

    def work():
        out["pick"] = optimize_algorithm_and_block_size(
            CHOLESKY_TRACERS, models, n, candidates)

    spans = recorded_spans(tmp_path, work)
    assert [(name, st) for _, name, st in spans] == \
        [("predict.evaluate", {"backend": "numpy"})]
    fresh = optimize_algorithm_and_block_size(
        CHOLESKY_TRACERS, models, n, candidates,
        engine=PredictionEngine(models))
    assert out["pick"] == fresh
    oracle = optimize_algorithm_and_block_size(
        CHOLESKY_TRACERS, models, n, candidates, batched=False)
    assert out["pick"][:2] == oracle[:2]
    assert out["pick"][2] == pytest.approx(oracle[2], rel=1e-10)


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
