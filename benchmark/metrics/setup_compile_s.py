"""Seconds in the compiler, or loading from its cache (a load is one
``jax.compile`` entry, ``args["cache"]`` "hit"), before ``train.epoch`` 1
began. The longest programs by name go to stderr."""

from benchmark import spans


def read(run):
    found = spans.setup_entries(run)
    if found is None:
        return None
    compiles = [e for e in found if e["name"] == "jax.compile"]
    by: dict = {}
    for e in compiles:
        key = f"{e['args']['fun']} (cache {e['args'].get('cache')})"
        by[key] = by.get(key, 0.0) + e["dur_ns"] / 1e9
    spans.say(f"{len(compiles)} compiles or cache loads before the window:", by)
    return spans.union_s(compiles)
