"""Put the benchmark's modules and the program on the path, and keep
every test's model store, traces and compile cache out of the checkout's."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


@pytest.fixture
def state(tmp_path, monkeypatch):
    import common
    monkeypatch.setattr(common, "STATE", tmp_path)
    monkeypatch.setattr(common, "TRACES", tmp_path / "traces")
    return tmp_path
