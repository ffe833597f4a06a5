"""The fused step's share of the chip's peak, in percent: the model FLOPs
of every live lane of every step in the window (two per weight each
token multiplies, and the state update, from the benchmark's own
counts) over the window, over the chip's bfloat16 peak.  float32 at
precision highest has no published peak, so the bfloat16 one is used."""

from common import BENCH, load_module
from peaks import peaks


def read(run):
    lanes = run.counters.get("lane_steps")
    if not lanes or not run.counters.get("window_s"):
        return None
    counts = load_module(BENCH / "counts" / f"{run.sizes['family']}.py")
    flops = counts.token_flops(run.sizes) * sum(lanes.values())
    return 100.0 * flops / run.counters["window_s"] / \
        peaks(run.device["kind"]).flops
