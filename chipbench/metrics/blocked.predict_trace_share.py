"""Share of the selection's time spent tracing the candidates' call
sequences, in percent: the program's ``predict.trace`` spans (one per
trace-cache miss) over the harness's ``bench.select`` spans, in which
every prediction of the traced window runs."""

from program_trace import for_run


def read(run):
    split = for_run(run)
    if split is None or "predict.evaluate" not in split.count or \
            not split.seconds.get("bench.select"):
        return None
    return 100.0 * split.seconds.get("predict.trace", 0.0) / \
        split.seconds["bench.select"]
