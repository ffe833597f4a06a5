"""Checker registry: importing this package registers every checker.

One module per checker; each encodes one standing ROADMAP invariant:

* :mod:`.host_sync` — hot paths stay dispatch-free (the serve engine's
  fused step + sub-ms scheduler ticks);
* :mod:`.retrace` — jit call sites declare Python-config params static
  (one compile per platform, not one per config value);
* :mod:`.deprecated_kwargs` — every ranking entry point goes through
  :class:`repro.tc.PredictorSession` (PR 6 API redesign);
* :mod:`.oracle_coverage` — every prediction fast path is pinned to its
  equivalence oracle by a test (the docs/architecture.md convention);
* :mod:`.metric_tracking` — every smoke metric is tracked or explicitly
  allowlisted in ``benchmarks/compare_smoke.py``;
* :mod:`.store_schema` — model-store writers stamp the
  ``SCHEMA_VERSION`` constant into every payload (PR 8 persistence
  layer), and ``schema_version`` keys are never hard-coded numbers.
"""

from . import (deprecated_kwargs, host_sync, metric_tracking,  # noqa: F401
               oracle_coverage, retrace, store_schema)

__all__ = ["deprecated_kwargs", "host_sync", "metric_tracking",
           "oracle_coverage", "retrace", "store_schema"]
