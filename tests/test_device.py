"""Tests for repro.tc.device: device-resident Pallas kernel measurement,
H2D/D2H transfer terms, and measured tile ranking.

Three contracts anchor this file:

* the **analytic oracle**: the measured tile ranking
  (``rank_device_tiles`` / ``select_tiles``) operates over exactly the
  candidate set the pre-device analytic model (``predict_tile_time``,
  kept alive behind ``analytic=True``) ranks — reprolint's
  oracle-coverage gate pins the pairing to this module;
* **transfer fits recover their constants**: fitting the memcpy model
  against an injected synthetic probe reproduces the injected bandwidth
  and overhead, asymmetrically per direction;
* **warm stores rank with zero fresh measurements**: device models ride
  the ``ModelStore`` under its reserved ``__device__`` name, round-trip
  bit-exactly, and refuse to load across platform fingerprints.

Real sweeps run the actual Pallas kernels in interpret mode on tiny
configs; everything asserting exact values injects a deterministic
``sweep_fn`` / ``transfer_measure_fn`` instead.
"""

import numpy as np
import pytest

from repro.core.model import ModelSet
from repro.core.sampler import Stats
from repro.core.transfer import (D2H, H2D, fit_transfer, measure_transfers)
from repro.perf.roofline import TARGET_KIND
from repro.perf.tile_tuner import (TileChoice, _mxu_eff, predict_tile_time,
                                   rank_tiles, select_tiles)
from repro.store import (DEVICE_MODEL_SET, ModelStore, PlatformFingerprint,
                         StoreMismatchError)
from repro.store.drift import DriftProbe
from repro.tc import PredictorSession
from repro.tc.device import (DEVICE_KERNELS, RESIDENT, TIGHT, DeviceSuite,
                             device_key, vmem_class)
from repro.tc.suite import MicroBenchmarkSuite

CONFIGS = [(8, 8, 8), (16, 16, 16), (8, 16, 8)]


def synthetic_sweep(kernel_name, configs):
    """Deterministic pure function of (kernel, config): exact checks."""
    kernel = DEVICE_KERNELS[kernel_name]
    out = {}
    for cfg in configs:
        t = 1e-9 * kernel.vmem_bytes(cfg) + 1e-6
        out[cfg] = (Stats(0.9 * t, t, 1.2 * t, 1.02 * t, 0.05 * t),
                    1e-3, 10.0 * t)
    return out


def synthetic_xfer(direction, nbytes, repetitions):
    """Affine probe with known constants; D2H 3x slower than H2D."""
    bw = 3e9 if direction == H2D else 1e9
    return [2e-6 + nbytes / bw] * repetitions


def device_suite(suite=None, **kw):
    kw.setdefault("sweep_fn", synthetic_sweep)
    kw.setdefault("transfer_measure_fn", synthetic_xfer)
    return DeviceSuite(suite or MicroBenchmarkSuite(repetitions=2), **kw)


def fake_measure(key, repetitions):
    t = 1e-9 * key.call_bytes + 2e-6
    return Stats(0.95 * t, t, 1.1 * t, 1.01 * t, 0.02 * t), 1e-3


# ------------------------------------------------------------------ keys --

def test_device_key_carries_config_and_vmem_class():
    key = device_key("pallas_matmul", (8, 8, 8))
    assert key.config == (8, 8, 8)
    assert key.equation == "pallas_matmul"
    # proxy-problem operand shapes: 2 grid steps per dim
    assert (key.a_shape, key.b_shape, key.out_shape) == \
        ((16, 16), (16, 16), (16, 16))
    assert key.classes == (RESIDENT, RESIDENT)
    # a config whose working set exceeds half of VMEM classifies tight
    big = device_key("pallas_matmul", (1024, 1024, 1024))
    assert big.classes == (TIGHT, TIGHT)
    assert vmem_class(0) == RESIDENT


def test_einsum_protocol_refuses_device_keys():
    suite = MicroBenchmarkSuite(measure_fn=fake_measure, repetitions=2)
    key = device_key("pallas_matmul", (8, 8, 8))
    with pytest.raises(ValueError, match="device"):
        suite._measure(key, suite.repetitions)
    ds = device_suite(suite)
    ds.measure_grid("pallas_matmul", [(8, 8, 8)])
    # drift repair goes through the einsum protocol -> same refusal
    with pytest.raises(ValueError, match="device"):
        suite.refresh(key)


def test_sweep_dedup_and_cost_accounting():
    ds = device_suite()
    suite = ds.suite
    res = ds.measure_grid("pallas_matmul", CONFIGS + [CONFIGS[0]])
    assert set(res) == set(CONFIGS)
    assert suite.measured == len(CONFIGS)
    assert suite.cost_seconds > 0
    # every key deduplicates: nothing is ever measured twice
    before = suite.measured
    ds.measure_grid("pallas_matmul", CONFIGS)
    assert suite.measured == before
    counters = suite.counters()
    assert counters["measured"] == len(CONFIGS)


def test_real_interpret_sweep_measures_all_registered_kernels():
    """The actual device-resident loop, interpret mode, tiny configs."""
    suite = MicroBenchmarkSuite(repetitions=2)
    ds = DeviceSuite(suite, interpret=True, passes=2,
                     transfer_measure_fn=synthetic_xfer)
    assert ds.interpret            # interpret mode is asked for, not inferred
    for name, cfg in [("pallas_matmul", (8, 8, 8)),
                      ("flash_attention", (8, 8, 16)),
                      ("pallas_ssd", (8, 4, 4))]:
        mb = ds.measure_grid(name, [cfg])[cfg]
        assert mb.stats.med > 0 and mb.first > 0 and mb.seconds > 0
        assert mb.key.config == cfg
    assert suite.measured == 3


def test_measurement_refuses_to_run_off_tpu_without_interpret():
    """Off the chip neither the kernel sweep nor the memcpy probe falls
    back to the CPU: timing the interpreter is not measuring a device."""
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("the refusal is for backends without a TPU")
    ds = DeviceSuite(MicroBenchmarkSuite(repetitions=2))
    assert not ds.interpret
    with pytest.raises(RuntimeError, match="interpret=True"):
        ds.measure_grid("pallas_matmul", [(8, 8, 8)])
    with pytest.raises(RuntimeError, match="interpret=True"):
        ds.transfer_models()
    assert ds.suite.measured == 0


# -------------------------------------------------------------- ranking --

def test_rank_decomposes_transfer_and_compute():
    ds = device_suite()
    ranked = ds.rank("pallas_matmul", (64, 64, 64), CONFIGS)
    assert [r.config for r in ranked] == \
        sorted((r.config for r in ranked),
               key=lambda c: next(x.t_total for x in ranked
                                  if x.config == c))
    for r in ranked:
        assert r.t_total == pytest.approx(r.t_h2d + r.t_compute + r.t_d2h)
        assert r.t_h2d > 0 and r.t_d2h > 0
        assert r.source == "measured"
        kernel = DEVICE_KERNELS["pallas_matmul"]
        assert r.t_compute == pytest.approx(
            r.per_step_s * kernel.steps((64, 64, 64), r.config))
    # D2H is modeled 3x slower per byte but moves m*n vs m*k + k*n bytes
    h2d, d2h = ds.transfer_models()
    assert d2h.time(1 << 20) > h2d.time(1 << 20)


def test_select_tiles_measured_path_matches_analytic_candidates():
    """The measured ranking and its analytic oracle agree on the legal
    candidate set and both pick from it (CPU-interpret equivalence)."""
    sess = PredictorSession(repetitions=2)
    sess.device_suite(sweep_fn=synthetic_sweep,
                      transfer_measure_fn=synthetic_xfer)
    measured = rank_tiles(64, 64, 64, session=sess, candidates=(8, 16))
    analytic = rank_tiles(64, 64, 64, analytic=True, candidates=(8, 16),
                          device_kind=TARGET_KIND)
    assert {(t.bm, t.bn, t.bk) for t in measured} == \
        {(t.bm, t.bn, t.bk) for t in analytic}
    choice = select_tiles(64, 64, 64, session=sess, candidates=(8, 16))
    assert choice == measured[0]
    assert choice.source in ("measured", "model")
    assert choice.t_compute > 0
    # the analytic oracle also backs select_tiles when no session exists
    fallback = select_tiles(64, 64, 64, candidates=(8, 16),
                            device_kind=TARGET_KIND)
    assert fallback.source == "analytic"
    assert fallback.predicted_s == pytest.approx(predict_tile_time(
        64, 64, 64, fallback.bm, fallback.bn, fallback.bk,
        device_kind=TARGET_KIND))
    # session front-end reaches the same device ranking
    direct = sess.rank_device_tiles("pallas_matmul", (64, 64, 64),
                                    [(8, 8, 8), (16, 16, 16)])
    assert [r.config for r in direct] == \
        [r.config for r in sess.device_suite().rank(
            "pallas_matmul", (64, 64, 64), [(8, 8, 8), (16, 16, 16)])]


def test_mxu_eff_models_partial_passes():
    # the old min(b, 128) double-clamp scored every b >= 128 as full
    assert _mxu_eff(64) == pytest.approx(0.5)
    assert _mxu_eff(128) == pytest.approx(1.0)
    assert _mxu_eff(192) == pytest.approx(0.75)   # 192 = 1.5 passes
    assert _mxu_eff(256) == pytest.approx(1.0)


# ------------------------------------------------------------- transfer --

def test_transfer_fit_recovers_synthetic_constants():
    h2d, d2h, cost = measure_transfers(measure_fn=synthetic_xfer)
    assert cost >= 0
    for model, bw in ((h2d, 3e9), (d2h, 1e9)):
        assert model.overhead_s == pytest.approx(2e-6, rel=1e-6)
        assert model.bytes_per_s == pytest.approx(bw, rel=1e-6)
    # the directions are asymmetric, as fitted
    assert d2h.time(1 << 20) > 2.5 * h2d.time(1 << 20)


def test_transfer_models_round_trip_bit_exactly(tmp_path):
    h2d, d2h, _ = measure_transfers(measure_fn=synthetic_xfer)
    suite = MicroBenchmarkSuite(repetitions=2)
    ds = device_suite(suite)
    ds._transfer = (h2d, d2h)
    ds.measure_grid("pallas_matmul", CONFIGS)
    store = ModelStore.from_suite(suite)
    store.add_device_models(ds)
    path = tmp_path / "store.json"
    store.save(path)
    loaded = ModelStore.load(path, fingerprint=store.fingerprint)
    ds2 = device_suite(MicroBenchmarkSuite(repetitions=2))
    ds2.load_model_set(loaded.device_model_set())
    h2d2, d2h2 = ds2.transfer_models()
    for n in (0, 1 << 10, 1 << 20, 1 << 28):
        # json floats round-trip via repr: bit-exact, not approximate
        assert h2d2.time(n) == h2d.time(n)
        assert d2h2.time(n) == d2h.time(n)
    assert (h2d2.direction, d2h2.direction) == (H2D, D2H)


def test_fit_transfer_is_relative_affine():
    sizes = (1024, 4096, 16384)
    model = fit_transfer(H2D, sizes, [1e-6 + n / 2e9 for n in sizes])
    assert model.overhead_s == pytest.approx(1e-6, rel=1e-6)
    assert model.bytes_per_s == pytest.approx(2e9, rel=1e-6)


# ------------------------------------------------------ store warm start --

def test_warm_store_ranks_with_zero_fresh_measurements(tmp_path):
    cold = PredictorSession(repetitions=2)
    cold.device_suite(sweep_fn=synthetic_sweep,
                      transfer_measure_fn=synthetic_xfer)
    ranked = cold.rank_device_tiles("pallas_matmul", (64, 64, 64), CONFIGS)
    assert cold.suite.measured == len(CONFIGS)
    path = tmp_path / "store.json"
    cold.save_store(path)

    warm = PredictorSession(store=path)
    warm.device_suite(transfer_measure_fn=synthetic_xfer)
    again = warm.rank_device_tiles("pallas_matmul", (64, 64, 64), CONFIGS)
    assert warm.suite.measured == 0          # zero fresh measurements
    assert [(r.config, r.t_total, r.t_h2d, r.t_compute, r.t_d2h)
            for r in again] == \
        [(r.config, r.t_total, r.t_h2d, r.t_compute, r.t_d2h)
         for r in ranked]                    # bit-identical ranking
    # an unmeasured config inside the fitted domain predicts from the
    # loaded __device__ models — still zero fresh measurements
    extra = warm.rank_device_tiles("pallas_matmul", (64, 64, 64),
                                   [(8, 8, 16)])
    assert extra[0].source == "model"
    assert warm.suite.measured == 0


def test_device_model_set_refuses_foreign_fingerprint(tmp_path):
    """Regression: the reserved ``__device__`` set is fingerprint-gated
    like every payload — device timings must not cross platforms."""
    sess = PredictorSession(repetitions=2)
    sess.device_suite(sweep_fn=synthetic_sweep,
                      transfer_measure_fn=synthetic_xfer)
    sess.rank_device_tiles("pallas_matmul", (64, 64, 64), CONFIGS)
    path = tmp_path / "store.json"
    store = sess.save_store(path)
    assert DEVICE_MODEL_SET in store.model_sets
    other = PlatformFingerprint(
        cpu="other-cpu", cores=1, backend="tpu", device_kind="TPU v9",
        libraries="other", dtype="float32", repro_version="0.0.0")
    with pytest.raises(StoreMismatchError):
        ModelStore.load(path, fingerprint=other)
    # the explicit escape hatch still works and carries the device set
    loaded = ModelStore.load(path, fingerprint=other, allow_mismatch=True)
    assert loaded.device_model_set() is not None
    assert "pallas_matmul" in loaded.device_model_set()


def test_device_models_export_import_round_trip():
    ds = device_suite()
    ds.measure_grid("pallas_matmul", CONFIGS)
    ds.rank("pallas_matmul", (32, 32, 32), CONFIGS)   # fits transfer too
    ms = ds.to_model_set()
    assert sorted(ms.models) == ["memcpy_d2h", "memcpy_h2d",
                                 "pallas_matmul"]
    ms2 = ModelSet.from_dict(ms.to_dict())
    ds2 = device_suite(MicroBenchmarkSuite(repetitions=2))
    assert ds2.load_model_set(ms2) == 1
    # model predictions agree with the fit source at the fitted points
    for cfg in CONFIGS:
        pred = ds2._model_predict("pallas_matmul", (RESIDENT, RESIDENT),
                                  cfg, "med")
        measured = ds.suite.results[ds.key("pallas_matmul", cfg)].stats.med
        assert pred == pytest.approx(measured, rel=0.2)


def test_drift_probe_skips_device_keys():
    suite = MicroBenchmarkSuite(measure_fn=fake_measure, repetitions=2)
    ds = device_suite(suite)
    ds.measure_grid("pallas_matmul", CONFIGS)
    from repro.tc.suite import MicroBenchmarkKey
    einsum_key = MicroBenchmarkKey(
        equation="ab,bc->ac", a_shape=(8, 8), b_shape=(8, 8),
        out_shape=(8, 8), classes=("warm", "warm"))
    suite.measure_key(einsum_key)
    probe = DriftProbe(suite, max_keys=8)
    keys = probe.keys()
    assert keys and all(k.config is None for k in keys)
    readings = probe.probe()                 # refusal-free: einsum only
    assert len(readings) == len(keys)
