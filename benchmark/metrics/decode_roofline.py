"""The decode step against the memory roofline: the bytes one step has to read
(every weight once, and the K/V that the rows' real lengths hold) over the
chip's bandwidth, against the mean device time of the decode program in the
traced slice. Bandwidth bounds it: a step does 2 operations a weight byte a
row, far under the ridge. The rows' lengths are the window's means (rows in
the batch a step, and a finished request's prompt plus half its output)."""

import re

from benchmark import flops

PROGRAM = re.compile(r"fn_greedy|decode")


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    times = [d for n, d in trace["busiest"]["modules"] if PROGRAM.search(n)]
    done = run["window"]["done"]
    c = run["counters"]
    steps = c["after"]["steps"] - c["before"]["steps"]
    if not times or not done or not steps:
        return None
    rows = (c["after"]["occupancy_sum"] - c["before"]["occupancy_sum"]) / steps
    mean_len = sum(lp + new / 2.0 for lp, new in done) / len(done)
    least_s = flops.decode_step_bytes(run["model"], [rows * mean_len]) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(times) / len(times) / 1e9)
