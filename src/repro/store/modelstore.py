"""The persistent platform model store (versioned, fingerprint-keyed).

A :class:`ModelStore` makes the paper's "once per platform" amortization
real across processes: it serializes a
:class:`~repro.tc.suite.MicroBenchmarkSuite`'s measurements (keyed by the
canonical :class:`~repro.tc.suite.MicroBenchmarkKey` — equation, kernel
shapes, per-operand cache classes), plus any
:class:`~repro.core.model.ModelSet` artifacts, under a
:class:`~repro.store.fingerprint.PlatformFingerprint`.  A serve process
or CI run warm-starts by loading the store into a fresh suite: every
ranking drawn from it re-predicts from the stored measurements with
*zero* new micro-benchmarks, and — because the measurements round-trip
bit-exactly through JSON (``float.__repr__`` is shortest-round-trip) —
the predictions are bit-identical to the in-memory session the store was
saved from.

Two guards protect the load path:

* **schema**: a payload whose ``schema_version`` differs from this
  module's :data:`SCHEMA_VERSION` cannot be interpreted by this code and
  refuses outright (``allow_mismatch`` does not override a schema gap);
* **fingerprint**: a payload written on a different platform (CPU,
  cores, jax backend/device, library stack, dtype, repro version)
  refuses unless ``allow_mismatch=True`` — measurements are facts about
  a platform, not about the code.

Reprolint's ``store-schema`` checker statically forbids writing store
payloads anywhere in this package without the ``SCHEMA_VERSION``
constant in the payload, so a format change can never ship silently.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Mapping, Optional, Union

from ..core.contractions import CACHE_BYTES
from ..core.model import ModelSet
from ..core.sampler import Stats
from ..tc.suite import (MicroBenchmark, MicroBenchmarkKey,
                        MicroBenchmarkSuite)
from .fingerprint import PlatformFingerprint, current_fingerprint

#: store file-format version.  Bump on any payload layout change; the
#: loader refuses mismatched schemas even under ``allow_mismatch=True``.
SCHEMA_VERSION = 1

#: reserved ``model_sets`` name holding fitted size-parametric models
#: (:meth:`repro.tc.parametric.ParametricModels.to_model_set`).  Riding
#: inside the existing schema-versioned ``model_sets`` mapping keeps the
#: payload layout — and therefore :data:`SCHEMA_VERSION` — unchanged:
#: stores written before parametric models existed load exactly as
#: before, and old readers see just another named model set.
PARAMETRIC_MODEL_SET = "__parametric__"

#: reserved ``model_sets`` name holding fitted *device kernel* models —
#: per-(Pallas kernel, VMEM class) tile-config polynomials plus the
#: memcpy H2D/D2H transfer models
#: (:meth:`repro.tc.device.DeviceSuite.to_model_set`).  Same schema
#: trick as :data:`PARAMETRIC_MODEL_SET`.  Device measurements are even
#: more platform-bound than einsum ones (they time the accelerator
#: itself), so loading them under a mismatched fingerprint is refused by
#: the standard gate — :meth:`ModelStore.device_model_set` is only
#: reachable after :meth:`ModelStore.load` has already verified the
#: fingerprint.
DEVICE_MODEL_SET = "__device__"


class StoreMismatchError(ValueError):
    """A store file refusing to load: wrong schema or wrong platform."""


def _key_to_dict(key: MicroBenchmarkKey) -> dict:
    d = {"equation": key.equation,
         "a_shape": list(key.a_shape),
         "b_shape": list(key.b_shape),
         "out_shape": list(key.out_shape),
         "classes": list(key.classes)}
    if key.config is not None:
        # device kernel keys only: einsum keys keep the pre-device
        # payload entry byte-for-byte, so old stores load unchanged
        d["config"] = list(key.config)
    return d


def _key_from_dict(d: Mapping) -> MicroBenchmarkKey:
    config = d.get("config")
    return MicroBenchmarkKey(equation=d["equation"],
                             a_shape=tuple(d["a_shape"]),
                             b_shape=tuple(d["b_shape"]),
                             out_shape=tuple(d["out_shape"]),
                             classes=tuple(d["classes"]),
                             config=None if config is None
                             else tuple(config))


def sort_key(key: MicroBenchmarkKey) -> tuple:
    """The canonical deterministic ordering of benchmark keys — used for
    stable payload layout and for the drift probe's subset selection.
    The config facet sorts as ``()`` when absent: ``None`` would not
    compare against device keys' tuples."""
    return (key.equation, key.a_shape, key.b_shape, key.out_shape,
            key.classes, key.config or ())


def _finite(value: float, what: str) -> float:
    """Stored measurements must be finite: NaN/inf would round-trip into
    silently poisoned rankings."""
    value = float(value)
    if not math.isfinite(value):
        raise ValueError(f"non-finite {what} ({value!r}) cannot be stored")
    return value


class ModelStore:
    """Measurements + model artifacts under one fingerprint.

    Build one with :meth:`from_suite` (capture a measured suite), extend
    it with :meth:`add_model_set` (per-signature or generated-model
    artifacts), persist with :meth:`save`, and reconstruct with
    :meth:`load`.
    """

    def __init__(self, *,
                 fingerprint: Optional[PlatformFingerprint] = None):
        self.fingerprint = fingerprint if fingerprint is not None \
            else current_fingerprint()
        self.measurements: Dict[MicroBenchmarkKey, MicroBenchmark] = {}
        self.model_sets: Dict[str, ModelSet] = {}
        #: the captured suite's measurement protocol + accumulated cost
        self.suite_meta: Dict[str, float] = {
            "repetitions": 5, "cache_bytes": CACHE_BYTES, "seed": 0,
            "cost_seconds": 0.0}

    # ------------------------------------------------------------ capture --
    @classmethod
    def from_suite(cls, suite: MicroBenchmarkSuite, *,
                   fingerprint: Optional[PlatformFingerprint] = None,
                   ) -> "ModelStore":
        """Capture a suite's measurements (and protocol) into a store."""
        store = cls(fingerprint=fingerprint)
        store.add_suite(suite)
        return store

    def add_suite(self, suite: MicroBenchmarkSuite) -> None:
        """Merge a suite's measurements into the store.

        The suite's measurement protocol (repetitions, cache capacity,
        seed) becomes the store's — merging suites with conflicting
        protocols raises, since their measurements are not comparable.
        """
        meta = {"repetitions": suite.repetitions,
                "cache_bytes": suite.cache_bytes, "seed": suite.seed}
        for name, value in meta.items():
            if self.measurements and self.suite_meta[name] != value:
                raise ValueError(
                    f"suite {name}={value} conflicts with the store's "
                    f"{name}={self.suite_meta[name]}; one store holds one "
                    f"measurement protocol")
        self.suite_meta.update(meta)
        self.measurements.update(suite.results)
        # total wall-clock behind the stored measurements: what a warm
        # start amortizes (fresh + any loaded-from-elsewhere cost)
        self.suite_meta["cost_seconds"] = float(
            sum(mb.seconds for mb in self.measurements.values()))

    def add_model_set(self, name: str, models: ModelSet) -> None:
        """Attach a :class:`ModelSet` artifact under ``name``."""
        self.model_sets[name] = models

    def model_set(self, name: str) -> ModelSet:
        return self.model_sets[name]

    def add_parametric_models(self, models) -> None:
        """Attach fitted size-parametric models under the reserved name
        (:data:`PARAMETRIC_MODEL_SET`).

        Accepts a :class:`repro.tc.parametric.ParametricModels` registry
        (exported via its ``to_model_set``) or an already-exported
        :class:`ModelSet`.  The models round-trip bit-exactly: a session
        warm-started from this store predicts unmeasured shapes
        bit-identically to the session that fitted them.
        """
        ms = models.to_model_set() if hasattr(models, "to_model_set") \
            else models
        self.model_sets[PARAMETRIC_MODEL_SET] = ms

    def parametric_model_set(self) -> Optional[ModelSet]:
        """The stored size-parametric models, or ``None`` if this store
        holds none (e.g. written before they existed)."""
        return self.model_sets.get(PARAMETRIC_MODEL_SET)

    def add_device_models(self, models) -> None:
        """Attach fitted device kernel models under the reserved name
        (:data:`DEVICE_MODEL_SET`).

        Accepts a :class:`repro.tc.device.DeviceSuite` (exported via its
        ``to_model_set``) or an already-exported :class:`ModelSet`.
        With these stored, a warm-started session ranks Pallas tile
        configs with zero fresh device sweeps and no memcpy probe.
        """
        ms = models.to_model_set() if hasattr(models, "to_model_set") \
            else models
        self.model_sets[DEVICE_MODEL_SET] = ms

    def device_model_set(self) -> Optional[ModelSet]:
        """The stored device kernel + transfer models, or ``None`` if
        this store holds none (e.g. written before they existed)."""
        return self.model_sets.get(DEVICE_MODEL_SET)

    # ---------------------------------------------------------- warm start --
    def load_into(self, suite: MicroBenchmarkSuite) -> int:
        """Inject every stored measurement into ``suite`` (warm start).

        Keys the suite already measured keep their fresh result.  Loaded
        keys are counted under the suite's ``loaded`` counter and their
        original wall-clock cost under ``loaded_cost_seconds`` — so a
        warm-started cost fraction can state its amortized cost instead
        of silently claiming the measurements were free.
        """
        n = 0
        for key, mb in self.measurements.items():
            if key not in suite.results:
                suite.load_measurement(mb)
                n += 1
        return n

    def build_suite(self, *, repetitions: Optional[int] = None,
                    measure_fn=None) -> MicroBenchmarkSuite:
        """A fresh suite under the store's measurement protocol, with
        every stored measurement pre-loaded.

        ``repetitions`` may restate the stored value but not contradict
        it (the stored measurements were taken under that protocol);
        ``measure_fn`` backs any *new* keys and the drift probe.
        """
        stored = int(self.suite_meta["repetitions"])
        if repetitions is not None and repetitions != stored:
            raise ValueError(
                f"repetitions={repetitions} conflicts with the store's "
                f"measurement protocol (repetitions={stored})")
        suite = MicroBenchmarkSuite(
            repetitions=stored,
            cache_bytes=int(self.suite_meta["cache_bytes"]),
            seed=int(self.suite_meta["seed"]),
            measure_fn=measure_fn)
        self.load_into(suite)
        return suite

    # ------------------------------------------------------------------ io --
    def to_payload(self) -> dict:
        """The JSON payload: schema version first, fingerprint second —
        the two gates the loader checks before touching measurements."""
        return {
            "schema_version": SCHEMA_VERSION,
            "fingerprint": self.fingerprint.as_dict(),
            "suite": dict(self.suite_meta),
            "measurements": [
                {"key": _key_to_dict(key),
                 "stats": {s: _finite(v, f"stat {s}")
                           for s, v in mb.stats.as_dict().items()},
                 "first": _finite(mb.first, "first-call overhead"),
                 "seconds": _finite(mb.seconds, "benchmark cost")}
                for key, mb in sorted(self.measurements.items(),
                                      key=lambda kv: sort_key(kv[0]))],
            "model_sets": {name: ms.to_dict()
                           for name, ms in sorted(self.model_sets.items())},
        }

    def save(self, path: Union[str, Path]) -> None:
        """Write the store to ``path`` (atomic enough for CI artifacts:
        one ``json.dump`` into a freshly truncated file)."""
        with open(path, "w") as f:
            json.dump(self.to_payload(), f, indent=1)

    @classmethod
    def load(cls, path: Union[str, Path], *, allow_mismatch: bool = False,
             fingerprint: Optional[PlatformFingerprint] = None,
             ) -> "ModelStore":
        """Load a store, refusing schema and fingerprint mismatches.

        ``fingerprint`` overrides the running platform's (tests pin it);
        ``allow_mismatch=True`` downgrades a *fingerprint* mismatch to
        acceptance — a *schema* mismatch always refuses, since this code
        cannot interpret another schema's payload at all.
        """
        with open(path) as f:
            payload = json.load(f)
        schema = payload.get("schema_version")
        if schema != SCHEMA_VERSION:
            raise StoreMismatchError(
                f"{path}: store schema_version={schema!r} but this code "
                f"reads schema_version={SCHEMA_VERSION}; re-generate the "
                f"store (allow_mismatch cannot bridge a schema gap)")
        stored_fp = PlatformFingerprint.from_dict(
            payload.get("fingerprint", {}))
        current = fingerprint if fingerprint is not None \
            else current_fingerprint()
        mismatched = stored_fp.mismatches(current)
        if mismatched and not allow_mismatch:
            detail = ", ".join(
                f"{name}: stored={getattr(stored_fp, name)!r} != "
                f"current={getattr(current, name)!r}" for name in mismatched)
            raise StoreMismatchError(
                f"{path}: platform fingerprint mismatch ({detail}); pass "
                f"allow_mismatch=True to load another platform's "
                f"measurements anyway")
        store = cls(fingerprint=stored_fp)
        store.suite_meta.update(payload.get("suite", {}))
        for entry in payload.get("measurements", []):
            key = _key_from_dict(entry["key"])
            store.measurements[key] = MicroBenchmark(
                key=key, stats=Stats(**entry["stats"]),
                first=entry["first"], seconds=entry["seconds"])
        for name, ms in payload.get("model_sets", {}).items():
            store.model_sets[name] = ModelSet.from_dict(ms)
        return store

    # ------------------------------------------------------------- summary --
    @property
    def n_keys(self) -> int:
        """Distinct stored micro-benchmark measurements."""
        return len(self.measurements)

    @property
    def cost_seconds(self) -> float:
        """Wall-clock the stored measurements originally cost — what a
        warm start amortizes instead of re-spending."""
        return float(self.suite_meta.get("cost_seconds", 0.0))

    def describe(self) -> str:
        fp = self.fingerprint
        return (f"ModelStore(schema={SCHEMA_VERSION}, keys={self.n_keys}, "
                f"model_sets={len(self.model_sets)}, "
                f"cost={self.cost_seconds:.2f}s, platform={fp.backend}/"
                f"{fp.device_kind}, {fp.cores} cores)")
