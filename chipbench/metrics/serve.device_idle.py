"""Share of the traced window in which no operation ran on the device,
in percent, while the engine served."""


def read(run):
    return None if run.reduced is None else 100.0 * run.reduced.idle_share
