"""Share of the traced slice in which no operation ran, on the busiest chip."""

from benchmark import xplane


def read(run):
    return xplane.idle_pct(run["trace"]) if run.get("trace") else None
