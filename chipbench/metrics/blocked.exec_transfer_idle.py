"""Share of the traced window in which the device was idle while
``ExecEngine`` moved a kernel call's operands to the device or its result
back to the host, in percent: the idle gaps inside the program's
``dla.exec.upload`` and ``dla.exec.download`` spans."""

from program_trace import TRANSFERS, for_run


def read(run):
    split = for_run(run)
    if split is None or not any(n in split.count for n in TRANSFERS):
        return None
    idle = sum(split.idle.get(n, 0.0) for n in TRANSFERS)
    return 100.0 * idle / split.window_s
