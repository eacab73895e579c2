"""Seconds of Python tracing and lowering in set-up: the run log's
``jax.trace`` and ``jax.lower`` entries (JAX's own duration events, the
outermost trace of each program) that ended before ``train.epoch`` 1 began,
the benchmark's own programs among them. No compile cache saves this. Traces
under a millisecond are not in the log; the program counts them, over the whole
process, and the count goes to stderr."""

import sys

from benchmark import spans


def read(run):
    found = spans.setup_entries(run)
    if found is None:
        return None
    from distkeras_tpu.observability import trace

    counts = trace.jax_counts()
    print(f"traces under a millisecond, counted and not kept: {counts['short_traces']} "
          f"({counts['short_trace_ns'] / 1e9:.4f} s over the process)", file=sys.stderr)
    return spans.union_s([e for e in found if e["name"] in ("jax.trace", "jax.lower")])
