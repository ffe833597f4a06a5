"""Milliseconds a request of the window waited for a slot, at the 90th
percentile: from its scheduled arrival to the tick that admitted it (the
scheduler's queue, which the median time to first token does not see)."""


def read(run):
    wait = run.counters.get("wait_p90_s")
    return None if wait is None else 1e3 * wait
