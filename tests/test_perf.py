"""Perf-layer tests: HLO collective parsing, trip-count scaling, roofline
terms, tile tuner, analytic cost model."""

import jax
import pytest

from repro.perf.analytic import cell_cost, forward_flops
from repro.perf.hlo_scale import scaled_collective_bytes, split_computations
from repro.perf.roofline import (TARGET_KIND, RooflineTerms, collective_bytes,
                                 model_flops_for)
from repro.perf.tile_tuner import predict_tile_time, select_tiles
from repro.configs import SHAPES, get_config

_HLO = """\
HloModule test

%body.1 (p: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[512,256]{1,0} all-gather(%y), replica_groups=[4,4]<=[16], dimensions={0}
  ROOT %t = tuple(%i, %ar)
}

%cond.1 (p: (s32[], f32[128,256])) -> pred[] {
  %c = s32[] constant(10)
  ROOT %cmp = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[128,256]) -> f32[128,256] {
  %rs = f32[32,256]{1,0} reduce-scatter(%a), replica_groups={{0,1,2,3}}, dimensions={0}
  %w = (s32[], f32[128,256]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[128,256] get-tuple-element(%w), index=1
}
"""


def test_collective_bytes_flat():
    cb = collective_bytes(_HLO)
    assert cb["all-reduce"] == 128 * 256 * 4
    # all-gather result / group size (groups of 4)
    assert cb["all-gather"] == 512 * 256 * 4 // 4
    # reduce-scatter result * group size
    assert cb["reduce-scatter"] == 32 * 256 * 4 * 4


def test_scaled_collectives_multiply_by_trip_count():
    comps = split_computations(_HLO)
    assert set(comps) >= {"body.1", "cond.1", "main"}
    cb = scaled_collective_bytes(_HLO)
    assert cb["all-reduce"] == 10 * 128 * 256 * 4
    assert cb["reduce-scatter"] == 32 * 256 * 4 * 4   # outside the loop


def test_roofline_terms_dominance():
    t = RooflineTerms(flops=197e12, bytes_accessed=0.0,
                      coll_bytes={"all-reduce": 0}, n_devices=1,
                      model_flops=197e12, device_kind=TARGET_KIND)
    assert t.dominant == "compute"
    assert t.compute_s == pytest.approx(1.0)
    assert t.roofline_fraction == pytest.approx(1.0)
    t2 = RooflineTerms(flops=1.0, bytes_accessed=819e9,
                       coll_bytes={"all-reduce": 0}, n_devices=1,
                       device_kind=TARGET_KIND)
    assert t2.dominant == "memory"
    assert t2.memory_s == pytest.approx(1.0)


def test_peaks_table_refuses_unknown_device_kind():
    from repro.perf.roofline import PEAKS, peaks
    assert peaks("TPU v5 lite").flops == 197e12
    assert all(p.source for p in PEAKS.values())
    t = RooflineTerms(flops=1.0, bytes_accessed=1.0, coll_bytes={},
                      n_devices=1, device_kind="cpu")
    with pytest.raises(KeyError, match="no published peaks"):
        t.compute_s
    with pytest.raises(KeyError, match="no published peaks"):
        predict_tile_time(256, 256, 256, 128, 128, 128, device_kind="cpu")
    # the analytic ranking defaults to the attached device: a CPU here
    assert jax.devices()[0].device_kind not in PEAKS
    with pytest.raises(KeyError, match="no published peaks"):
        select_tiles(256, 256, 256)


def test_model_flops_factors():
    cfg = get_config("deepseek-7b")
    train = model_flops_for(cfg, SHAPES["train_4k"])
    prefill = model_flops_for(cfg, SHAPES["prefill_32k"])
    n = cfg.param_count(active_only=True)
    assert train == pytest.approx(6 * n * 256 * 4096)
    assert prefill == pytest.approx(2 * n * 32 * 32768)


def test_moe_uses_active_params():
    cfg = get_config("arctic-480b")
    mf = model_flops_for(cfg, SHAPES["train_4k"])
    assert mf < 6 * cfg.param_count() * 256 * 4096 / 10  # 128e top-2


def test_analytic_flops_close_to_model_flops():
    """Analytic forward FLOPs must be within ~2x of 2*N*D for dense LMs."""
    for arch in ("deepseek-7b", "gemma2-27b", "phi3-mini-3.8b"):
        cfg = get_config(arch)
        fwd = forward_flops(cfg, 1, 4096)
        ref = 2 * cfg.param_count(active_only=True) * 4096
        assert 0.8 < fwd / ref < 2.2, (arch, fwd / ref)


def test_cell_cost_kinds():
    cfg = get_config("mamba2-2.7b")
    tr = cell_cost(cfg, SHAPES["train_4k"])
    de = cell_cost(cfg, SHAPES["long_500k"])
    assert tr.flops > de.flops             # decode is one token
    assert de.hbm_bytes > 0


def test_tile_tuner_selects_legal_aligned():
    c = select_tiles(4096, 4096, 4096, device_kind=TARGET_KIND)
    assert c.bm % 128 == 0 and c.bn % 128 == 0 and c.bk % 128 == 0
    # small matrices: clamped tiles
    c2 = select_tiles(64, 64, 64, candidates=(64, 128),
                      device_kind=TARGET_KIND)
    assert (c2.bm, c2.bn, c2.bk) == (64, 64, 64)


def test_tile_tuner_selection_is_argmin_and_vmem_safe():
    from repro.kernels.matmul import vmem_bytes

    choice = select_tiles(4096, 4096, 4096, device_kind=TARGET_KIND)
    # selected tile fits VMEM and beats (or ties) other legal candidates
    assert vmem_bytes(choice.bm, choice.bn, choice.bk) <= 16 * 2 ** 20
    for cand in ((128, 128, 128), (256, 256, 128), (512, 128, 128)):
        t = predict_tile_time(4096, 4096, 4096, *cand,
                              device_kind=TARGET_KIND)
        assert choice.predicted_s <= t * (1 + 1e-9)


def test_dryrun_artifacts_exist_and_complete():
    """The committed dry-run sweep must cover every (arch x shape x mesh)."""
    import json
    from pathlib import Path

    from repro.configs import all_configs

    d = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    if not d.exists():
        pytest.skip("dry-run artifacts not generated yet")
    for arch, cfg in all_configs().items():
        for shape in cfg.shapes:
            for mesh in ("16x16", "2x16x16"):
                f = d / f"{arch}__{shape}__{mesh}.json"
                assert f.exists(), f.name
                meta = json.loads(f.read_text())
                assert meta["compute_s"] > 0
                assert meta["memory"]["temp_size_in_bytes"] >= 0
