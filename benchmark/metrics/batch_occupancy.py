"""Rows decoding in a step, the window's mean: ``occupancy_sum / steps`` of
``GenerationEngine.stats()``, taken at the window's two ends."""


def read(run):
    c = run["counters"]
    steps = c["after"]["steps"] - c["before"]["steps"]
    if not steps:
        return None
    return (c["after"]["occupancy_sum"] - c["before"]["occupancy_sum"]) / steps
