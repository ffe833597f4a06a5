"""The split of a traced run's idle time by the program's spans, and the
three readers of it."""

import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import program_trace as pt
import trace_reduce as tr
from common import BENCH, load_module

TRACE = Path(__file__).parent / "data" / "cholesky_solve.xplane.pb"
READERS = ("blocked.exec_transfer_idle", "blocked.exec_copy_mb",
           "blocked.predict_trace_share")

pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats:DeprecationWarning")


def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py")


def span(s, e, name, **stats):
    return (s, e, name, stats)


# one problem on one chip, in ns: a selection that traces, then a kernel
# call inside the execution
SPANS = [
    span(0, 40, "bench.select"),
    span(5, 25, "predict.trace", n=8, b=4),
    span(26, 38, "predict.evaluate", backend="numpy"),
    span(50, 100, "bench.execute"),
    span(52, 60, "dla.exec.upload", kernel="potf2", bytes=256),
    span(60, 61, "dla.exec.kernel", kernel="potf2"),
    span(61, 80, "dla.exec.download", kernel="potf2", bytes=192),
    span(80, 84, "dla.exec.writeback", kernel="potf2"),
]
OPS = [(30, 45), (64, 70), (76, 86), (92, 96)]
LO, HI = 0, 110


def test_idle_goes_to_the_innermost_program_span_else_harness_else_outside():
    got = pt.split(SPANS, [OPS], LO, HI)
    assert got.idle == pytest.approx({
        "predict.trace": 30e-9,          # gap 0-30, inside the selection
        "dla.exec.upload": 19e-9,        # gap 45-64
        "dla.exec.download": 6e-9,       # gap 70-76
        "bench.execute": 6e-9,           # gap 86-92: no program span
        "outside": 14e-9})               # gap 96-110: no span at all
    assert got.busy_s == pytest.approx(35e-9)
    assert sum(got.idle.values()) == pytest.approx(got.window_s - got.busy_s)


def test_spans_are_summed_by_name_and_chips_averaged():
    got = pt.split(SPANS, [OPS, OPS], LO, HI)
    assert got.idle["dla.exec.upload"] == pytest.approx(19e-9)
    assert got.busy_s == pytest.approx(35e-9)
    assert got.seconds["predict.trace"] == pytest.approx(20e-9)
    assert got.seconds["bench.select"] == pytest.approx(40e-9)
    assert got.bytes["dla.exec.upload"] == 256
    assert got.bytes["dla.exec.download"] == 192
    assert got.count["dla.exec.kernel"] == 1


def test_innermost_prefers_the_span_that_started_last():
    spans = sorted([span(0, 10, "a"), span(2, 4, "b"), span(5, 9, "c"),
                    span(6, 7, "d")], key=lambda sp: (sp[0], -sp[1]))
    assert pt.innermost(spans, [1, 3, 4.5, 6.5, 8, 11]) == \
        ["a", "b", "a", "d", "c", None]


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_on_an_untraced_run(name):
    run = SimpleNamespace(reduced=None, counters={"problems": 3})
    assert reader(name).read(run) is None


@pytest.mark.parametrize("name,value", [
    ("blocked.exec_transfer_idle", 100.0 * 25 / 110),
    ("blocked.exec_copy_mb", (256 + 192) / 1e6 / 2),
    ("blocked.predict_trace_share", 100.0 * 20 / 40)])
def test_readers_on_a_split(name, value, monkeypatch):
    got = pt.split(SPANS, [OPS], LO, HI)
    monkeypatch.setattr(pt, "newest_trace", lambda: "trace")
    monkeypatch.setattr(pt, "split_trace", lambda path: got)
    run = SimpleNamespace(
        reduced=SimpleNamespace(window_s=got.window_s, busy_s=got.busy_s),
        counters={"traced_calls": [(2048, "potrf1", 512)] * 2})
    assert reader(name).read(run) == pytest.approx(value)


@pytest.fixture
def recorded(state):
    """The recorded chip trace as the newest of the run's traces: a
    program without spans of its own, inside the harness's."""
    where = state / "traces" / "cholesky.solve" / "plugins"
    where.mkdir(parents=True)
    shutil.copy(TRACE, where / TRACE.name)
    return tr.reduce_trace(str(where / TRACE.name))


def test_a_trace_without_program_spans_gives_its_idle_to_the_harness(
        recorded):
    run = SimpleNamespace(reduced=recorded, counters={
        "traced_calls": [(2048, "potrf1", 512)] * 10})
    got = pt.for_run(run)
    assert got.window_s == pytest.approx(recorded.window_s, abs=1e-6)
    assert {k[len("bench."):]: v for k, v in got.idle.items()} == \
        pytest.approx(recorded.idle)
    for name in READERS:
        assert reader(name).read(run) is None


def test_another_runs_trace_is_refused(recorded):
    other = SimpleNamespace(window_s=recorded.window_s + 1e-3,
                            busy_s=recorded.busy_s)
    with pytest.raises(ValueError, match="not this run's trace"):
        pt.for_run(SimpleNamespace(reduced=other, counters={}))
    other = SimpleNamespace(window_s=recorded.window_s,
                            busy_s=recorded.busy_s * 1.01)
    with pytest.raises(ValueError, match="not this run's trace"):
        pt.for_run(SimpleNamespace(reduced=other, counters={}))
