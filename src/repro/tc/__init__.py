"""repro.tc — cache-aware tensor-contraction prediction (paper Ch. 6).

Promotes the Ch. 6 scenario to a first-class subsystem on the batched
prediction engine: a §6.1 generator extended with batched-kernel patterns
(:mod:`~repro.tc.kernels`), a deduplicated cache-aware micro-benchmark
suite that reports its own cost (:mod:`~repro.tc.suite`), a
:class:`ContractionPredictor` that compiles the whole candidate set
through the PR-1/2 :class:`~repro.core.predict.PredictionEngine`
(:mod:`~repro.tc.predictor`), and an einsum-path layer that composes
per-step predictors into multi-contraction chain rankings with
cache-state propagation between steps (:mod:`~repro.tc.chains`).

Since the session redesign, :class:`~repro.tc.session.PredictorSession`
is the single entry point: one object owning the shared suite and trace
cache, fronting every ranking/selection mode and the serving
scheduler's step-cost models.  The legacy module-level call forms remain
as one-release deprecation shims.

See ``docs/contraction-prediction.md`` for the full walkthrough.
"""

from .chains import (MAX_OPERANDS, ChainPath, ChainPredictor, ChainSizeSweep,
                     ChainSpec, ChainStep, RankedChain, compose_chain_runtime,
                     execute_chain, execute_chain_reference,
                     execute_path_reference, rank_einsum_sweep,
                     validate_paths)
from .kernels import (BATCH_SUFFIX, BATCHABLE_KERNELS, base_kernel,
                      generate_algorithms, generate_batched_algorithms,
                      is_batched_kernel, kernel_batch_dims, slice_call_bytes,
                      validate_algorithms)
from .parametric import (ParametricModel, ParametricModels, SignatureKey,
                         cost_exponents, key_at, signature_dims,
                         signature_of, size_point)
from .predictor import (ContractionPredictor, ContractionSizeSweep,
                        RankedContraction, rank_contraction_sweep)
from .session import PredictorSession, warn_deprecated_kwargs
from .suite import (COLD, WARM, MicroBenchmark, MicroBenchmarkKey,
                    MicroBenchmarkSuite, benchmark_key, canonical_equation)

__all__ = [
    "BATCH_SUFFIX", "BATCHABLE_KERNELS", "base_kernel",
    "generate_algorithms", "generate_batched_algorithms",
    "is_batched_kernel", "kernel_batch_dims", "slice_call_bytes",
    "validate_algorithms",
    "ContractionPredictor", "ContractionSizeSweep", "RankedContraction",
    "rank_contraction_sweep",
    "COLD", "WARM", "MicroBenchmark", "MicroBenchmarkKey",
    "MicroBenchmarkSuite", "benchmark_key", "canonical_equation",
    "MAX_OPERANDS", "ChainPath", "ChainPredictor", "ChainSizeSweep",
    "ChainSpec", "ChainStep", "RankedChain", "compose_chain_runtime",
    "execute_chain", "execute_chain_reference", "execute_path_reference",
    "rank_einsum_sweep", "validate_paths",
    "PredictorSession", "warn_deprecated_kwargs",
    "ParametricModel", "ParametricModels", "SignatureKey",
    "cost_exponents", "key_at", "signature_dims", "signature_of",
    "size_point",
    "DEVICE_KERNELS", "DeviceRanked", "DeviceSuite", "device_key",
    "vmem_class", "RESIDENT", "TIGHT",
]

#: the device measurement facet (:mod:`repro.tc.device`) imports the
#: Pallas kernels — and therefore jax — at module load, so its names are
#: re-exported lazily: ``import repro.tc`` stays numpy-light and only a
#: first device-facet access pays the jax import.
_DEVICE_EXPORTS = frozenset({
    "DEVICE_KERNELS", "DeviceRanked", "DeviceSuite", "device_key",
    "vmem_class", "RESIDENT", "TIGHT"})


def __getattr__(name):
    if name in _DEVICE_EXPORTS:
        from . import device
        return getattr(device, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
