"""Per-kernel Pallas tests: shape/dtype sweeps vs the ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, matmul, ssd, tile_legal
from repro.kernels.ref import attention_ref, matmul_ref, ssd_ref

RNG = np.random.default_rng(7)


def _arr(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (128, 128, 128, 64, 64, 64),
    (256, 128, 192, 128, 64, 64),
    (64, 64, 64, 64, 64, 64),
    (256, 256, 256, 128, 128, 128),
    (384, 128, 256, 128, 128, 64),
])
def test_matmul_sweep(m, n, k, bm, bn, bk, dtype):
    x, y = _arr((m, k), dtype), _arr((k, n), dtype)
    out = matmul(x, y, bm=bm, bn=bn, bk=bk, interpret=True)
    ref = matmul_ref(x, y)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hkv,s,d,kw", [
    (4, 4, 64, 32, dict(causal=True)),                 # MHA causal
    (4, 2, 128, 32, dict(causal=True)),                # GQA
    (4, 1, 64, 16, dict(causal=False)),                # MQA encoder
    (4, 2, 128, 32, dict(causal=True, window=32)),     # sliding window
    (4, 2, 64, 32, dict(causal=True, softcap=20.0)),   # gemma2 softcap
])
def test_flash_attention_sweep(hq, hkv, s, d, kw, dtype):
    b = 2
    q = _arr((b, hq, s, d), dtype)
    k = _arr((b, hkv, s, d), dtype)
    v = _arr((b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, bq=32, bkv=32, interpret=True, **kw)
    ref = attention_ref(q, k, v, **kw)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("l,h,p,g,n,chunk", [
    (64, 4, 16, 2, 8, 16),
    (128, 4, 16, 1, 16, 32),
    (64, 8, 8, 4, 8, 64),     # chunk == L
])
def test_ssd_sweep(l, h, p, g, n, chunk):
    b = 2
    x = _arr((b, l, h, p), jnp.float32)
    dt = jnp.abs(_arr((b, l, h), jnp.float32)) * 0.1
    a_log = _arr((h,), jnp.float32) * 0.5
    bb = _arr((b, l, g, n), jnp.float32)
    cc = _arr((b, l, g, n), jnp.float32)
    out = ssd(x, dt, a_log, bb, cc, chunk=chunk, interpret=True)
    ref = ssd_ref(x, dt, a_log, bb, cc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_tile_legality():
    assert tile_legal(1024, 1024, 1024, 128, 128, 128)
    assert not tile_legal(1024, 1024, 1024, 100, 128, 128)  # misaligned
    assert not tile_legal(1024, 1024, 1024, 1024, 1024, 1024,
                          vmem_limit=2 ** 20)               # VMEM blow-up
    assert tile_legal(64, 64, 64, 64, 64, 64)               # small dims ok


def test_xla_fallbacks_match():
    from repro.kernels import ops
    x, y = _arr((128, 64), jnp.float32), _arr((64, 128), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.matmul(x, y, bm=64, bn=64, bk=64, interpret=True)),
        np.asarray(ops.matmul(x, y, use_pallas=False)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("call", ["matmul", "attention", "ssd"])
def test_ops_refuse_to_run_off_tpu_without_interpret(call):
    """Off the TPU a Pallas kernel runs only in interpret mode, and only
    when asked for: no silent fallback."""
    from repro.kernels import ops
    if jax.default_backend() == "tpu":
        pytest.skip("the refusal is for backends without a TPU")
    x = _arr((8, 8), jnp.float32)
    args = {"matmul": (x, x), "attention": (x[None, None],) * 3,
            "ssd": (x[None, :, None], x[None, :, :1], x[0, :1], x[None, :, None],
                    x[None, :, None])}[call]
    with pytest.raises(RuntimeError, match="interpret=True"):
        getattr(ops, call)(*args)
