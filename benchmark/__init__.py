"""The benchmark: one cell per run, found by name in ``BENCHMARK.json``.

Everything that decides a number lives here and nowhere else: traffic
generation, weights, the plain reference, FLOP and byte counts, the table of
peaks, the trace reduction and the comparison behind ``correct``. From the
program (``distkeras_tpu``) it takes the system under test and nothing more.
"""
