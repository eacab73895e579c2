"""Share of the rows that chunked-prefill steps ran in the window that were
padding (a step's rows are padded to a power of two): ``chunk_rows`` against
``chunk_rows_padded`` of ``GenerationEngine.stats()``, taken at the window's
two ends. In no cell yet."""


def read(run):
    c = run["counters"]
    if "chunk_rows_padded" not in c["after"]:
        return None
    padded = c["after"]["chunk_rows_padded"] - c["before"]["chunk_rows_padded"]
    if not padded:
        return None
    return 100.0 * (1.0 - (c["after"]["chunk_rows"] - c["before"]["chunk_rows"]) / padded)
