"""Share of lane-steps in the window that consumed a prompt token, in
percent: prefill lanes over prefill and decode lanes, summed over every
fused step (a count)."""


def read(run):
    lanes = run.counters.get("lane_steps")
    if not lanes or not sum(lanes.values()):
        return None
    return 100.0 * lanes["prefill"] / (lanes["prefill"] + lanes["decode"])
