"""The time a step waits for data: all ``train.input`` annotations in the
profiler's slice (``ds.batches`` with the prefetcher's start, and every
``next()`` of the batch iterator) over the slice's ``train.step`` count."""

from benchmark import spans


def read(run):
    return spans.mean_ms(run, "train.input", "train.step")
