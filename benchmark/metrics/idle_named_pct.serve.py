"""Share of the busiest chip's idle time in the profiler's slice (gaps of a
microsecond and more, the slice's two ends counted) that lies under one of
the engine loop's ``serve.*`` spans; the seconds by innermost span go to
stderr. In no cell yet."""

from benchmark import spans


def read(run):
    return spans.idle_named_pct(run)
