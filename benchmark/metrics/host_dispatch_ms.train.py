"""The host's busy time a step: the mean ``train.step`` annotation
(``engine.run_step``: the batch's check and placement and the step's dispatch)
in the profiler's slice."""

from benchmark import spans


def read(run):
    return spans.mean_ms(run, "train.step", "train.step")
