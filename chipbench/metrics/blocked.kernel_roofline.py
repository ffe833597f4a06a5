"""Share of the BLAS/LAPACK calls' roofline, in percent, over the traced
problems: the least time of every call (the larger of its FLOPs over the
chip's peak and its bytes over the memory bandwidth, from the
benchmark's own counts) over the device time of every program that ran
in the traced window."""

from common import BENCH, load_module
from peaks import peaks


def read(run):
    calls = run.counters.get("traced_calls")
    if not calls or run.reduced is None:
        return None
    from repro.dla import tracers
    blas = load_module(BENCH / "counts" / "blas.py")
    family = getattr(tracers, run.sizes["tracers"])
    p = peaks(run.device["kind"])
    least = 0.0
    for n, name, b in calls:
        for c in family[name](n, b):
            least += blas.least_seconds(c.kernel, c.case, c.sizes,
                                        p.flops, p.hbm_bw)
    device_s = sum(s for s, _ in run.reduced.modules.values())
    return 100.0 * least / device_s if device_s else None
