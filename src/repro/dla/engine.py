"""Execution and tracing engines for blocked algorithms.

A blocked algorithm is written ONCE against the :class:`Engine` interface and
then either

* **executed** (:class:`ExecEngine`) — the matrices stay on the device,
  and each kernel call runs a jitted JAX kernel of ``repro.dla.kernels``
  on sub-matrix views of them and updates the stored matrix in place (the
  "LAPACK calling BLAS" structure; the model generator times kernels the
  same way, operands on the device and no transfer in the call, so
  predictions and executions see the same per-call cost), or
* **traced** (:class:`TraceEngine`) — only the ``(kernel, case, sizes)``
  sequence is recorded, *without any execution*.  This is what the paper's
  predictions consume (§4.1): the call sequence is fully determined by the
  problem size and block size.
"""

from __future__ import annotations

import functools
from collections.abc import Mapping
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from ..core.predict import CompiledCalls, KernelCall, compile_calls
from . import kernels as K


@dataclass(frozen=True)
class View:
    """A rectangular sub-matrix view: (matrix key, row range, col range)."""

    mat: str
    r0: int
    r1: int
    c0: int
    c1: int

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.r1 - self.r0, self.c1 - self.c0)

    @property
    def rows(self) -> int:
        return self.r1 - self.r0

    @property
    def cols(self) -> int:
        return self.c1 - self.c0


class Matrix:
    """Handle for a matrix participating in a blocked algorithm."""

    def __init__(self, key: str, n_rows: int, n_cols: int):
        self.key = key
        self.n_rows = n_rows
        self.n_cols = n_cols

    def v(self, r0: int, r1: int, c0: int, c1: int) -> View:
        assert 0 <= r0 <= r1 <= self.n_rows, (r0, r1, self.n_rows)
        assert 0 <= c0 <= c1 <= self.n_cols, (c0, c1, self.n_cols)
        return View(self.key, r0, r1, c0, c1)

    def full(self) -> View:
        return self.v(0, self.n_rows, 0, self.n_cols)


class Engine:
    """Kernel-call interface shared by execution and tracing."""

    # level 3
    def gemm(self, transA, transB, alpha, beta, A: View, B: View, C: View):
        raise NotImplementedError

    def syrk(self, uplo, trans, alpha, beta, A: View, C: View):
        raise NotImplementedError

    def syr2k(self, uplo, trans, alpha, beta, A: View, B: View, C: View):
        raise NotImplementedError

    def symm(self, side, uplo, alpha, beta, A: View, B: View, C: View):
        raise NotImplementedError

    def trsm(self, side, uplo, transA, diag, alpha, A: View, B: View):
        raise NotImplementedError

    def trmm(self, side, uplo, transA, diag, alpha, A: View, B: View):
        raise NotImplementedError

    # unblocked LAPACK
    def potf2(self, uplo, A: View):
        raise NotImplementedError

    def trti2(self, uplo, diag, A: View):
        raise NotImplementedError

    def lauu2(self, uplo, A: View):
        raise NotImplementedError

    def sygs2(self, itype, uplo, A: View, L: View):
        raise NotImplementedError

    def getf2(self, A: View):
        raise NotImplementedError

    def geqr2(self, A: View):
        raise NotImplementedError

    def trsyl(self, transA, transB, sgn, A: View, B: View, C: View):
        raise NotImplementedError


class TraceEngine(Engine):
    """Records the kernel-call sequence without executing (paper §4.1)."""

    def __init__(self):
        self.calls: List[KernelCall] = []

    def _rec(self, kernel: str, case: Tuple, sizes: Tuple[int, ...]):
        # degenerate (zero-size) calls are kept: the models estimate them as
        # 0 s, mirroring Example 4.1's zero-width panels
        self.calls.append(KernelCall(kernel, case, sizes))

    def gemm(self, tA, tB, a, b, A, B, C):
        m, n = C.shape
        k = A.cols if tA == "N" else A.rows
        self._rec("gemm", (tA, tB, a, b), (m, n, k))

    def syrk(self, uplo, trans, a, b, A, C):
        n = C.rows
        k = A.cols if trans == "N" else A.rows
        self._rec("syrk", (uplo, trans, a, b), (n, k))

    def syr2k(self, uplo, trans, a, b, A, B, C):
        n = C.rows
        k = A.cols if trans == "N" else A.rows
        self._rec("syr2k", (uplo, trans, a, b), (n, k))

    def symm(self, side, uplo, a, b, A, B, C):
        self._rec("symm", (side, uplo, a, b), C.shape)

    def trsm(self, side, uplo, tA, diag, a, A, B):
        self._rec("trsm", (side, uplo, tA, diag, a), B.shape)

    def trmm(self, side, uplo, tA, diag, a, A, B):
        self._rec("trmm", (side, uplo, tA, diag, a), B.shape)

    def potf2(self, uplo, A):
        self._rec("potf2", (uplo,), (A.rows,))

    def trti2(self, uplo, diag, A):
        self._rec("trti2", (uplo, diag), (A.rows,))

    def lauu2(self, uplo, A):
        self._rec("lauu2", (uplo,), (A.rows,))

    def sygs2(self, itype, uplo, A, L):
        self._rec("sygs2", (itype, uplo), (A.rows,))

    def getf2(self, A):
        self._rec("getf2", ("NP",), A.shape)

    def geqr2(self, A):
        self._rec("geqr2", ("N",), A.shape)

    def trsyl(self, tA, tB, sgn, A, B, C):
        self._rec("trsyl", (tA, tB, sgn), C.shape)

    def compile(self) -> CompiledCalls:
        """Compile the recorded sequence into per-(kernel, case) size
        matrices — the form the batched :class:`PredictionEngine` consumes."""
        return compile_calls([self.calls])


def trace_calls(fn: Callable[["Engine"], None]) -> List[KernelCall]:
    """Trace one blocked-algorithm execution into its kernel-call sequence."""
    eng = TraceEngine()
    fn(eng)
    return eng.calls


def compile_traces(fns: Sequence[Callable[["Engine"], None]],
                   ) -> CompiledCalls:
    """Trace a whole batch of algorithm builders and compile them into one
    reusable per-(kernel, case) batch — the artifact
    :meth:`repro.core.predict.PredictionEngine.predict_compiled` consumes."""
    return compile_calls([trace_calls(fn) for fn in fns])


@functools.lru_cache(maxsize=None)
def _program(name: str):
    """Kernel ``name`` as one jitted program over stored matrices.

    ``program(case, views, out, written, read, offsets)`` slices operand
    ``i`` of shape ``views[i][1]`` out of matrix ``views[i][0]`` of
    ``(written,) + read`` at ``offsets[2i:2i+2]``, runs the kernel, and
    writes its result into ``written`` at operand ``out``'s offsets.
    ``written`` is donated, so XLA updates it in place.  Returns the
    updated matrix and the kernel's extra outputs (geqr2's ``q``).
    """
    kernel = K.KERNELS[name]

    def program(case, views, out, written, read, offsets):
        mats = (written,) + read
        ops = tuple(lax.dynamic_slice(mats[m], (offsets[2 * i],
                                                offsets[2 * i + 1]), shape)
                    for i, (m, shape) in enumerate(views))
        result, extra = kernel.run(case, ops), ()
        if name == "geqr2":     # store R over the panel, hand Q back
            q, r = result
            result, extra = jnp.zeros_like(q).at[:r.shape[0]].set(
                jnp.triu(r)), (q,)
        at = (offsets[2 * out], offsets[2 * out + 1])
        return lax.dynamic_update_slice(written, result, at), extra

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, static_argnames=("case", "views", "out"),
                   donate_argnames=("written",))


@functools.lru_cache(maxsize=1 << 14)
def _device_offsets(offsets: Tuple[int, ...]):
    """A kernel call's view offsets as an int32 device array, uploaded
    once per distinct tuple: a blocked algorithm repeats its offsets from
    problem to problem, so a call moves nothing to the device."""
    return jax.device_put(np.array(offsets, np.int32))


def _download(value, kernel: str) -> np.ndarray:
    """Device array ``value`` downloaded into a writable host array.

    The array JAX downloads into is its own and only marked read-only
    because JAX keeps it as the device array's cached value; the callers
    drop the device array, so it is handed on writable instead of copied
    (a copy into fresh pages costs more than the transfer).  Where the
    download aliases memory numpy cannot write (JAX's CPU backend), it is
    copied."""
    with TraceAnnotation("dla.exec.download", kernel=kernel,
                         bytes=value.nbytes):
        host = np.asarray(value)
        if not host.flags.writeable:
            try:
                host.flags.writeable = True
            except ValueError:
                host = host.copy()
        return host


def _upload(host: np.ndarray, kernel: str):
    with TraceAnnotation("dla.exec.upload", kernel=kernel,
                         bytes=host.nbytes):
        return jax.device_put(host)


class HostMatrices(Mapping):
    """``ExecEngine.mats``: the stored matrices, each read as a host numpy
    array.

    A matrix lives on the device (``device``) until host code reads it:
    ``mats[key]`` then waits for the device, downloads the matrix once and
    keeps the host array (``host``).  From then on that array is the
    matrix, so host code may read and write it in place; the next kernel
    call that touches the matrix uploads it again (:meth:`on_device`) and
    drops the host array."""

    def __init__(self):
        self.device: Dict[str, jax.Array] = {}
        self.host: Dict[str, np.ndarray] = {}

    def __getitem__(self, key: str) -> np.ndarray:
        if key not in self.host:
            self.host[key] = _download(self.device.pop(key), "fetch")
        return self.host[key]

    def __iter__(self) -> Iterator[str]:
        return iter(list(self.device) + list(self.host))

    def __len__(self) -> int:
        return len(self.device) + len(self.host)

    def on_device(self, key: str, kernel: str):
        """Matrix ``key`` on the device, uploaded again for ``kernel``'s
        call if host code holds it."""
        host = self.host.pop(key, None)
        if host is not None:
            self.device[key] = _upload(host, kernel)
        return self.device[key]


class ExecEngine(Engine):
    """Executes blocked algorithms via JAX kernels on matrices it keeps on
    the device.

    ``bind`` uploads a matrix once.  Each kernel call is one jitted
    program (:func:`_program`) that slices its operands out of the stored
    matrices, runs the kernel and writes the result into the matrix it
    updates, in place: nothing moves between host and device between
    calls.  ``mats`` (:class:`HostMatrices`) hands a matrix to the host
    with one download, after the device has finished with it.

    Profiler spans: ``dla.exec.bind`` around ``bind``, with a
    ``dla.exec.upload`` (``kernel="bind"``) inside; a ``dla.exec.upload``
    for each upload of a matrix that host code held; one
    ``dla.exec.kernel`` (the host's dispatch) for each kernel call; a
    ``dla.exec.download`` (``kernel="fetch"``) for each read of ``mats``
    that downloads.  Uploads and downloads carry their ``bytes``.  The
    spans are recorded only while a ``jax.profiler`` trace is, and add no
    synchronization.
    """

    def __init__(self):
        self.mats = HostMatrices()
        # QR panels store reflector blocks out-of-place
        self.q_panels: Dict[Tuple, np.ndarray] = {}

    # -------------------------------------------------------------- store --
    def bind(self, key: str, array: np.ndarray) -> Matrix:
        """Store ``array`` (as float32) under ``key``, on the device.  The
        caller's array is read once and never written."""
        with TraceAnnotation("dla.exec.bind", bytes=4 * np.size(array)):
            self.mats.host.pop(key, None)
            stored = _upload(np.asarray(array, dtype=np.float32), "bind")
        self.mats.device[key] = stored
        return Matrix(key, *stored.shape)

    @staticmethod
    def _skip(*views: View) -> bool:
        return any(0 in v.shape for v in views)

    def _call(self, name: str, case: Tuple, out: View, *ops: View) -> Tuple:
        """Run kernel ``name`` on the views ``ops`` and store its result in
        ``out`` (one of ``ops``); returns the kernel's extra outputs."""
        keys = list(dict.fromkeys([out.mat] + [v.mat for v in ops]))
        mats = [self.mats.on_device(k, name) for k in keys]
        views = tuple((keys.index(v.mat), v.shape) for v in ops)
        offsets = _device_offsets(tuple(x for v in ops for x in (v.r0, v.c0)))
        with TraceAnnotation("dla.exec.kernel", kernel=name):
            self.mats.device[out.mat], extra = _program(name)(
                case, views, ops.index(out), mats[0],
                tuple(mats[1:]), offsets)
        return extra

    # ------------------------------------------------------------ level 3 --
    def gemm(self, tA, tB, a, b, A, B, C):
        if self._skip(C) or (A.cols if tA == "N" else A.rows) == 0:
            return
        self._call("gemm", (tA, tB, a, b), C, A, B, C)

    def syrk(self, uplo, trans, a, b, A, C):
        if self._skip(C) or (A.cols if trans == "N" else A.rows) == 0:
            return
        self._call("syrk", (uplo, trans, a, b), C, A, C)

    def syr2k(self, uplo, trans, a, b, A, B, C):
        if self._skip(C) or (A.cols if trans == "N" else A.rows) == 0:
            return
        self._call("syr2k", (uplo, trans, a, b), C, A, B, C)

    def symm(self, side, uplo, a, b, A, B, C):
        if self._skip(C, A):
            return
        self._call("symm", (side, uplo, a, b), C, A, B, C)

    def trsm(self, side, uplo, tA, diag, a, A, B):
        if self._skip(B):
            return
        self._call("trsm", (side, uplo, tA, diag, a), B, A, B)

    def trmm(self, side, uplo, tA, diag, a, A, B):
        if self._skip(B):
            return
        self._call("trmm", (side, uplo, tA, diag, a), B, A, B)

    # -------------------------------------------------- unblocked kernels --
    def potf2(self, uplo, A):
        if self._skip(A):
            return
        self._call("potf2", (uplo,), A, A)

    def trti2(self, uplo, diag, A):
        if self._skip(A):
            return
        self._call("trti2", (uplo, diag), A, A)

    def lauu2(self, uplo, A):
        if self._skip(A):
            return
        self._call("lauu2", (uplo,), A, A)

    def sygs2(self, itype, uplo, A, L):
        if self._skip(A):
            return
        self._call("sygs2", (itype, uplo), A, A, L)

    def getf2(self, A):
        if self._skip(A):
            return
        self._call("getf2", ("NP",), A, A)

    def geqr2(self, A):
        if self._skip(A):
            return
        (q,) = self._call("geqr2", ("N",), A, A)
        self.q_panels[(A.mat, A.r0, A.c0)] = _download(q, "geqr2")

    def trsyl(self, tA, tB, sgn, A, B, C):
        if self._skip(C):
            return
        self._call("trsyl", (tA, tB, sgn), C, A, B, C)
