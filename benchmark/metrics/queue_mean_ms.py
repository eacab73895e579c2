"""Mean time from ``submit`` to admission (``queue_ms``) of the requests the
engine retired in the window, as ``GenerationEngine.latency_stats`` gives it
for the trailing ``window`` seconds at the window's close; the mean over its
classes by their counts."""


def read(run):
    classes = [c for c in run["counters"]["latency"].values() if "queue_ms" in c]
    n = sum(c["count"] for c in classes)
    if not n:
        return None
    return sum(c["queue_ms"] * c["count"] for c in classes) / n
