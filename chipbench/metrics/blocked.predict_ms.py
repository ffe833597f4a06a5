"""Mean milliseconds of one predict-and-select call in the window: the
harness's clock around ``core.selection.optimize_algorithm_and_block_size``
for each problem."""


def read(run):
    select = run.counters.get("select_s")
    if not select:
        return None
    return 1e3 * sum(select) / len(select)
