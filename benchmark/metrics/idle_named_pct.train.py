"""Share of the busiest chip's idle time in the profiler's slice (gaps of a
microsecond and more, the slice's two ends counted) that lies under one of
the program's ``train.*`` spans; the seconds by innermost span go to stderr."""

from benchmark import spans


def read(run):
    return spans.idle_named_pct(run)
