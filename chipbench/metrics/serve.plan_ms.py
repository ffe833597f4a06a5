"""Mean milliseconds the scheduler spent planning one tick in the window
(``ModelGuidedScheduler.plan``, timed as ``serve_loop`` times it into
``EngineStats.tick_overhead_s``)."""


def read(run):
    ticks = run.counters.get("ticks")
    if not ticks:
        return None
    return 1e3 * run.counters["tick_overhead_s"] / ticks
