"""The chip smoke's phases, rehearsed on the CPU at small sizes.

``chip_smoke.py`` runs the main path on one TPU at full size.  Here each
of its phases runs with the module's sizes cut down, the Pallas kernels
in interpret mode, and the regret limit lifted (CPU timings at these
sizes rank nothing); every correctness check of the phase still holds.
"""

import importlib.util
from pathlib import Path

import pytest

from repro.configs import get_config, reduced
from repro.kernels import ops
from repro.tc import PredictorSession

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _interpreted(fn):
    def call(*args, interpret=False, **kwargs):
        return fn(*args, interpret=True, **kwargs)
    return call


class _InterpretSession(PredictorSession):
    def device_suite(self, **kwargs):
        if self._device is None:
            kwargs["interpret"] = True
        return super().device_suite(**kwargs)


@pytest.fixture
def smoke(monkeypatch):
    cs = _load_smoke()
    small = dict(
        N=256, BLOCK_SIZES=(64, 128), MAX_POINTS=24, POTRF_REPETITIONS=1,
        GEN_CONFIG=cs.GeneratorConfig(overfit=0, oversampling=2,
                                      repetitions=2, error_bound=0.04,
                                      min_width=64, max_pieces=6,
                                      max_points=24),
        MM_SHAPE=(256, 256, 256), TILE_REPETITIONS=1,
        ATTN_SHAPE=(1, 2, 256, 128), ATTN_BLOCKS=(128,),
        SSD_SHAPE=(1, 128, 2, 64, 1, 128), SSD_CHUNKS=(64,),
        SLOTS=3, NEW_TOKENS=4, PROMPT_LENS=(4, 8), REF_LEN=32,
        SPREAD_CHUNK=8, REGRET_TOL=float("inf"),
        get_config=lambda name: reduced(get_config(name)),
        PredictorSession=_InterpretSession)
    for name, value in small.items():
        monkeypatch.setattr(cs, name, value)
    monkeypatch.setattr(cs, "ops", type("ops", (), {
        k: staticmethod(_interpreted(getattr(ops, k)))
        for k in ("matmul", "attention", "ssd")}))
    return cs


@pytest.mark.parametrize("phase", ["blocked", "tiles", "serve"])
def test_smoke_phase_runs_and_checks_on_cpu(smoke, phase, capsys):
    getattr(smoke, f"phase_{phase}")()
    out = capsys.readouterr().out
    assert f"[{phase}]" in out and "FAILED" not in out


def test_smoke_stops_at_the_device_phase_on_cpu():
    cs = _load_smoke()
    with pytest.raises(SystemExit, match="JAX finds no TPU"):
        cs.phase_device()
