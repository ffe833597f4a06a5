"""The trace reduction on a trace recorded on one TPU v5e by a traced run
of ``cholesky.solve``: two rounds of the five sizes (n = 2048 ... 4096),
each problem's selection in a ``bench.select`` span and its execution in
a ``bench.execute`` span, all in one ``bench.window`` span.  Every pick
had block size 512."""

from pathlib import Path

import pytest

import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "cholesky_solve.xplane.pb"


def test_union_and_gaps():
    busy, merged = tr.union_seconds([(0, 2), (1, 3), (5, 6), (8, 12)], 0, 10)
    assert busy == 6 and merged == [(0, 3), (5, 6), (8, 10)]
    assert tr.gaps(merged, 0, 10) == [(3, 5), (6, 8)]


def test_self_times_subtract_nested_children():
    events = [(0, 10, "while"), (1, 3, "dot"), (4, 5, "add"), (12, 13, "x")]
    assert tr.self_times(events) == [7, 2, 1, 1]


def test_op_names_drop_numbers_and_keep_custom_call_targets():
    assert tr.op_name("%fusion.40 = s32[8] fusion(...)") == "fusion"
    assert tr.op_name('%custom-call.10 = f32[128,128] custom-call(%s), '
                      'custom_call_target="Cholesky"') == \
        "custom-call:Cholesky"
    assert tr.op_name("%copy-start = (f32[512]) copy-start(%A.1)") == \
        "copy-start"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(str(TRACE))


def test_recorded_trace_programs(reduced):
    # at b=512 a round factors 4 + 5 + 6 + 7 + 8 = 30 diagonal blocks
    # (potf2) and updates the 25 blocks below a first one twice (trsm and
    # syrk: both programs are named ``f``)
    assert reduced.modules["jit__potf2"][1] == 2 * 30
    assert reduced.modules["jit_f"][1] == 2 * 2 * 25
    assert reduced.chips == 1


def test_recorded_trace_busy_and_idle(reduced):
    assert 0.92 < reduced.window_s < 0.93
    assert 0 < reduced.busy_s < reduced.window_s
    # the device is idle between the calls: the host copies every operand
    assert reduced.idle_share > 0.9
    assert sum(reduced.idle.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s, rel=1e-9)
    module_s = sum(s for s, _ in reduced.modules.values())
    assert reduced.busy_s <= module_s * (1 + 1e-9)


def test_recorded_trace_ops_account_for_busy_time(reduced):
    assert sum(reduced.ops.values()) == pytest.approx(reduced.busy_s,
                                                      rel=1e-6)
    top = reduced.breakdown()["device_ops"][0][0]
    assert top.startswith(("jit_f/", "jit__potf2/"))


def test_idle_gaps_go_to_the_span_around_them(reduced):
    assert set(reduced.idle) == {"execute", "select"}
    assert reduced.idle["execute"] > 10 * reduced.idle["select"]
