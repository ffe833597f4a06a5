"""repro.store — the persistent platform model store.

Three layers (see ``docs/model-store.md``):

* :mod:`repro.store.modelstore` — versioned on-disk persistence of
  micro-benchmark measurements and model sets under a
  :mod:`platform fingerprint <repro.store.fingerprint>`;
* :mod:`repro.store.drift` — deterministic re-measurement probes that
  detect when a stored model has drifted off the platform;
* :mod:`repro.store.tournament` — named predictor snapshots scored
  against a measured oracle on frozen workloads.

``repro.tc`` never imports this package at module level (the session
lazy-imports it), so the dependency arrow stays ``store -> tc -> core``.
"""

from .drift import DriftProbe, DriftReading
from .fingerprint import PlatformFingerprint, current_fingerprint
from .modelstore import (DEVICE_MODEL_SET, PARAMETRIC_MODEL_SET,
                         SCHEMA_VERSION, ModelStore, StoreMismatchError)
from .tournament import (Snapshot, SnapshotScore, TournamentResult,
                         Workload, frozen_workloads, kendall_tau,
                         run_tournament, workload)

__all__ = [
    "DEVICE_MODEL_SET", "PARAMETRIC_MODEL_SET", "SCHEMA_VERSION",
    "ModelStore",
    "StoreMismatchError",
    "PlatformFingerprint", "current_fingerprint",
    "DriftProbe", "DriftReading",
    "Snapshot", "SnapshotScore", "TournamentResult", "Workload",
    "frozen_workloads", "kendall_tau", "run_tournament", "workload",
]
