"""Platform fingerprints: what a stored model is a model *of*.

The paper's amortization argument — models are "generated automatically
once per platform" (Ch. 4) — only holds while *platform* means the same
thing across processes.  A :class:`PlatformFingerprint` pins down the
identity a :class:`~repro.store.modelstore.ModelStore` file is valid
for: the CPU, the core count, the jax backend and device kind the
kernels dispatch to, the library versions the measurements went
through, the measurement dtype, and the repro version that produced the
artifact.  Loading a store whose fingerprint differs from the running
platform refuses by default (``allow_mismatch=True`` opts into reuse,
e.g. for cross-machine tournaments) — a silently wrong platform model
is worse than a re-measured one.

The file-format *schema* version is deliberately not a fingerprint
field: it is checked first and separately by the store loader (see
``SCHEMA_VERSION`` in :mod:`repro.store.modelstore`), because a schema
bump means "this code cannot read that payload", not "that platform is
not this platform".
"""

from __future__ import annotations

import os
import platform
from dataclasses import asdict, dataclass, fields
from typing import Dict, List, Tuple

#: how the stored kernel timings call a kernel
#: (``repro.dla.kernels.KernelDef.make_call``): operands already on the
#: device, timed up to the end of the kernel's device work, nothing
#: downloaded — the call ``repro.dla.engine.ExecEngine`` makes.  A store
#: without it timed another call (a host round trip per kernel call) and
#: refuses to load.
KERNEL_CALL = "device-resident"

#: fallback package version when importlib metadata is unavailable
#: (running from a source tree via PYTHONPATH, not an installed wheel)
_FALLBACK_VERSION = "0.1.0"


def repro_version() -> str:
    """The repro package version stamped into every store artifact."""
    from importlib.metadata import PackageNotFoundError, version
    try:
        return version("repro")
    except PackageNotFoundError:
        return _FALLBACK_VERSION


def _cpu_model() -> str:
    """A best-effort CPU model string (portable across linux/mac CI)."""
    model = platform.processor() or platform.machine() or "unknown"
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return model


def _library_versions() -> str:
    """The measurement-relevant library stack, one canonical string."""
    import jax
    import numpy as np
    return f"numpy={np.__version__},jax={jax.__version__}"


def _jax_device() -> Tuple[str, str]:
    """(backend, device kind of device 0) the kernels dispatch to.  A
    failed device query raises: a store keyed to a placeholder would
    match any platform whose query failed the same way."""
    import jax
    return jax.default_backend(), jax.devices()[0].device_kind


@dataclass(frozen=True)
class PlatformFingerprint:
    """The platform identity a stored suite/model is valid for."""

    cpu: str              # CPU model string
    cores: int            # logical core count
    backend: str          # jax default backend ("cpu"/"gpu"/"tpu")
    device_kind: str      # jax device kind of device 0
    libraries: str        # "numpy=...,jax=..." measurement library stack
    dtype: str            # operand dtype the micro-benchmarks run in
    repro_version: str    # repro package version that wrote the store
    kernel_call: str = KERNEL_CALL  # how the kernel timings call a kernel

    def as_dict(self) -> Dict[str, object]:
        return asdict(self)

    @staticmethod
    def from_dict(d: Dict[str, object]) -> "PlatformFingerprint":
        names = [f.name for f in fields(PlatformFingerprint)]
        return PlatformFingerprint(**{n: d.get(n, "missing") for n in names})

    def mismatches(self, other: "PlatformFingerprint") -> List[str]:
        """Field names on which the two fingerprints disagree."""
        return [f.name for f in fields(self)
                if getattr(self, f.name) != getattr(other, f.name)]


def current_fingerprint(*, dtype: str = "float32",
                        ) -> PlatformFingerprint:
    """The running process's platform fingerprint.

    ``dtype`` names the operand dtype of the stored measurements — the
    contraction micro-benchmarks run in float32
    (:data:`repro.core.contractions._ITEM` is 4 bytes), so that is the
    default; a store of float64 Pallas-kernel measurements would carry
    its own.
    """
    backend, device_kind = _jax_device()
    return PlatformFingerprint(
        cpu=_cpu_model(),
        cores=os.cpu_count() or 1,
        backend=backend,
        device_kind=device_kind,
        libraries=_library_versions(),
        dtype=dtype,
        repro_version=repro_version(),
    )
