"""Milliseconds per fused engine step: the window over the steps taken
in it (``ServeEngine.advance`` calls), host time between steps included."""


def read(run):
    steps = run.counters.get("steps")
    return 1e3 * run.counters["window_s"] / steps if steps else None
