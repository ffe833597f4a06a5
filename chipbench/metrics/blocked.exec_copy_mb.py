"""Megabytes (10^6 bytes) that ``ExecEngine`` moved between host and
device per traced problem: the ``bytes`` of the program's
``dla.exec.upload`` and ``dla.exec.download`` spans in the traced window,
over the problems solved there."""

from program_trace import TRANSFERS, for_run


def read(run):
    split = for_run(run)
    calls = run.counters.get("traced_calls")
    if split is None or not calls or \
            not any(n in split.count for n in TRANSFERS):
        return None
    return sum(split.bytes.get(n, 0) for n in TRANSFERS) / 1e6 / len(calls)
