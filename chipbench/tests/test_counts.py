"""The benchmark's FLOP and byte counts against the shapes they count."""

import pytest

from common import BENCH, load_json, load_module

blas = load_module(BENCH / "counts" / "blas.py")
mamba2 = load_module(BENCH / "counts" / "mamba2.py")


def test_gemm_counts():
    flops, nbytes = blas.call_counts("gemm", ("N", "T", -1, 1), (6, 5, 4))
    assert flops == 2 * 6 * 5 * 4
    assert nbytes == 4 * (6 * 4 + 4 * 5 + 2 * 6 * 5)
    _, nbytes = blas.call_counts("gemm", ("N", "N", 1, 0), (6, 5, 4))
    assert nbytes == 4 * (6 * 4 + 4 * 5 + 6 * 5)       # C is not read


def test_syrk_trsm_potf2_count_triangles():
    assert blas.call_counts("syrk", ("L", "N", -1, 1), (4, 3)) == \
        (10 * 2 * 3, 4 * (4 * 3 + 2 * 10))
    # B (m, n) := B A^-T with A (n, n) lower triangular
    assert blas.call_counts("trsm", ("R", "L", "T", "N", 1), (5, 3)) == \
        (5 * 3 * 3, 4 * (6 + 2 * 15))
    flops, nbytes = blas.call_counts("potf2", ("L",), (3,))
    assert flops == pytest.approx(9.0) and nbytes == 4 * 2 * 6


def test_zero_size_calls_do_nothing():
    assert blas.call_counts("gemm", ("N", "T", -1, 1), (0, 5, 4)) == (0, 0)
    assert blas.least_seconds("trsm", ("R", "L", "T", "N", 1), (0, 3),
                              1e12, 1e9) == 0


def test_least_seconds_is_the_larger_bound():
    f, b = blas.call_counts("gemm", ("N", "N", 1, 1), (512, 512, 512))
    assert blas.least_seconds("gemm", ("N", "N", 1, 1), (512, 512, 512),
                              1e12, 1e9) == max(f / 1e12, b / 1e9)


def test_every_potrf_call_has_counts():
    from repro.dla.tracers import CHOLESKY_TRACERS
    for tracer in CHOLESKY_TRACERS.values():
        for c in tracer(256, 64):
            blas.call_counts(c.kernel, c.case, c.sizes)


def test_mamba2_weights_match_the_program_parameters():
    import jax
    import jax.numpy as jnp
    program_config = load_module(BENCH / "kinds" / "serve.py").program_config
    from repro.models import init_params
    sizes = load_json(BENCH / "configs" / "mamba2-2.7b.json")
    cfg = program_config(sizes)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0),
                                                dtype=jnp.float32))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert mamba2.weight_bytes(sizes) == 4 * n
    assert mamba2.weight_bytes(sizes) == pytest.approx(10.80e9, rel=2e-3)
    # two FLOPs per weight a token multiplies: all but the norms, the
    # per-head scalars and the embedding gather (the head reads it once)
    _, width, state = mamba2.widths(sizes)
    assert width == 2 * 5120 + 2 * 128 + 80 and state == 80 * 64 * 128
    assert mamba2.matmul_params(sizes) == 64 * (2560 * width + 5120 * 2560) \
        + 50288 * 2560
    # one step at 8 slots: every weight once, the state read and written
    assert mamba2.step_bytes(sizes, 8) == pytest.approx(
        4 * n + 2 * 4 * 64 * 8 * state)
