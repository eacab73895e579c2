"""How much of ``setup_s`` the program accounts for: the union of every run-log
span that ended before ``train.epoch`` 1 began (``train.epoch`` 0, the warm-up,
is one of them), over the set-up the benchmark clocked. Imports and the
benchmark's own work between its programs lie outside."""

from benchmark import spans


def read(run):
    found = spans.setup_entries(run)
    if found is None:
        return None
    by: dict = {}
    for e in found:
        rec = by.setdefault(e["name"], [0.0, 0])
        rec[0] += e["dur_ns"] / 1e9
        rec[1] += 1
    spans.say(f"{len(found)} run-log entries before the window; seconds by name "
              "(children count again under their parents):",
              {f"{name} x {n}": s for name, (s, n) in by.items()})
    return 100.0 * spans.union_s(found) / run["end_to_end"]["setup_s"]
