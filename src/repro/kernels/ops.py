"""Public jit'd wrappers for the Pallas kernels.

The kernels compile for the TPU.  On any other backend they refuse to run
unless the caller asks for ``interpret=True``, which runs the kernel body
in Python per grid step — the tests do, to validate correctness against
the ``ref.py`` oracles.  ``use_pallas=False`` runs the XLA reference
implementations instead — this is also what the distributed model code
uses under ``shard_map``/``pjit`` so that dry-run lowering works for
every mesh.
"""

from __future__ import annotations

import jax

from . import ref
from .flash_attention import flash_attention
from .matmul import matmul as _pallas_matmul
from .matmul import tile_legal, vmem_bytes
from .ssd import ssd as _pallas_ssd


def require_tpu(interpret: bool) -> None:
    """Refuse to run a Pallas kernel off the TPU unless interpret mode was
    asked for: a silent interpret fallback would time the interpreter."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"Pallas kernels need a TPU, but the JAX backend is "
            f"{jax.default_backend()!r}; pass interpret=True to run them "
            f"in interpret mode")


def matmul(x, y, *, bm=128, bn=128, bk=128, use_pallas=True,
           interpret=False):
    if not use_pallas:
        return ref.matmul_ref(x, y)
    require_tpu(interpret)
    return _pallas_matmul(x, y, bm=bm, bn=bn, bk=bk, interpret=interpret)


def attention(q, k, v, *, causal=True, window=0, softcap=0.0,
              bq=128, bkv=128, use_pallas=True, interpret=False):
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap)
    require_tpu(interpret)
    return flash_attention(q, k, v, bq=bq, bkv=bkv, causal=causal,
                           window=window, softcap=softcap,
                           interpret=interpret)


def ssd(x, dt, a_log, b, c, *, chunk=128, use_pallas=True,
        interpret=False):
    if not use_pallas:
        return ref.ssd_ref(x, dt, a_log, b, c)
    require_tpu(interpret)
    return _pallas_ssd(x, dt, a_log, b, c, chunk=chunk, interpret=interpret)


__all__ = ["matmul", "attention", "ssd", "tile_legal", "vmem_bytes",
           "require_tpu"]
