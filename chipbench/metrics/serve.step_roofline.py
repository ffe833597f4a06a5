"""The fused step's share of its roofline, in percent, over the traced
steps: the least time of a step (the larger of its bytes, every weight
and twice every slot's recurrent state, over the memory bandwidth, and
its FLOPs on every slot over the bfloat16 peak) times the steps, over
the device time of every program that ran in the traced window."""

from common import BENCH, load_module
from peaks import peaks


def read(run):
    steps = run.counters.get("traced_steps")
    if not steps or run.reduced is None:
        return None
    counts = load_module(BENCH / "counts" / f"{run.sizes['family']}.py")
    p = peaks(run.device["kind"])
    slots = run.sizes["slots"]
    least = max(counts.step_bytes(run.sizes, slots) / p.hbm_bw,
                counts.token_flops(run.sizes) * slots / p.flops)
    device_s = sum(s for s, _ in run.reduced.modules.values())
    return 100.0 * least * steps / device_s if device_s else None
