"""The program's own spans, read for the per-layer metrics that name set-up
and the device's idle gaps.

Two sources, both written by ``distkeras_tpu.observability.trace``:

- the profiler sink: ``train.*`` and ``serve.*`` annotations in the trace's
  ``/host:CPU`` plane, on the clock of the device's lines, with their keywords
  (``step_num``, ``epoch``, ``rows`` ...);
- the run log: set-up phases, epoch ends and every trace, lower and compile of
  the process, on the host's ``perf_counter_ns``, kept with tracing off.

A program that has neither (the commit before these spans) gives ``None``
everywhere here, and a reader built on it leaves its metric out.

``python3 benchmark/spans.py <trace dir>`` prints what a trace holds of them:
the programs and named kernels on the chip, every step's annotation beside its
program on the device (one clock: the host dispatches before the chip starts,
and a drain returns after it ends), and the idle time by span.
"""

from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import xplane

HOST_PLANE = "/host:CPU"
PROGRAM = ("train.", "serve.")
MIN_GAP_NS = 1000.0        # as xplane.reduce: less between two operations is no gap
UNNAMED = "(no span)"

_read: dict = {}


# -- the run log ---------------------------------------------------------------


def run_log():
    """The program's run log, oldest first, or ``None`` where it keeps none."""
    try:
        from distkeras_tpu.observability import trace
        return trace.run_log()
    except (ImportError, AttributeError):
        return None


def end_ns(e) -> int:
    return e["t0_ns"] + e["dur_ns"]


def setup_entries(run):
    """The run log's entries that lie in the run's set-up: from the process's
    start (``setup_s`` before the window opened) to the instant ``train.epoch``
    1 of the last ``train()`` call began. ``None`` where the program keeps no
    run log, or the log holds no such call."""
    log = run_log()
    calls = [e for e in log or () if e["name"] == "train.build_engine"]
    if not calls:
        return None
    first = [e for e in log if e["name"] == "train.epoch"
             and e["t0_ns"] >= calls[-1]["t0_ns"] and (e["args"] or {}).get("epoch") == 1]
    if not first:
        return None
    t1 = first[0]["t0_ns"]
    t0 = t1 - run["end_to_end"]["setup_s"] * 1e9
    return [e for e in log if t0 <= e["t0_ns"] and end_ns(e) <= t1]


def of_last_call(entries, names):
    """Those of ``names`` that belong to the last ``train()`` call."""
    t0 = [e for e in entries if e["name"] == "train.build_engine"][-1]["t0_ns"]
    return [e for e in entries if e["name"] in names and e["t0_ns"] >= t0]


def union_s(entries) -> float:
    """Seconds covered by the entries: a child inside its phase counts once."""
    return xplane.union_ns([(e["t0_ns"], end_ns(e)) for e in entries]) / 1e9


def say(title, rows):
    """Seconds by name on stderr, largest first: the look behind a metric."""
    print(title, file=sys.stderr)
    for name, s in sorted(rows.items(), key=lambda kv: -kv[1])[:12]:
        print(f"  {s:12.6f} s  {name}", file=sys.stderr)


# -- the profiler sink ---------------------------------------------------------


def read_annotations(path: str) -> list:
    """``(name, start_ns, duration_ns, keywords)`` of the program's annotations
    in the host plane, by start."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM):
                    out.append((e.name, float(e.start_ns), float(e.duration_ns),
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda a: (a[1], -a[2]))


def busiest_ops(planes: dict, chips: int) -> list:
    """``(start, end)`` of every operation on the chip that was busy longest."""
    devices = xplane.device_planes(planes)
    spans = [[(s, s + d) for _, s, d in devices[i][xplane.OPS_LINE]]
             for i in sorted(devices)[:chips]]
    return max(spans, key=xplane.union_ns) if spans else []


def of_run(run):
    """``(annotations, operations of the busiest chip)`` of a traced run, read
    once; ``None`` without a trace or where the program annotates nothing."""
    if not run.get("trace_dir"):
        return None
    path = xplane.trace_file(run["trace_dir"])
    if path not in _read:
        anns = read_annotations(path)
        _read[path] = (anns, busiest_ops(xplane.read_planes(path), run["chips"])
                       if anns else [])
    anns, ops = _read[path]
    return (anns, ops) if anns else None


def mean_ms(run, name, per):
    """Total time of the ``name`` annotations in the slice over the number of
    ``per`` annotations there, in milliseconds."""
    found = of_run(run)
    if found is None:
        return None
    total = sum(d for n, _, d, _ in found[0] if n == name)
    count = sum(1 for n, _, _, _ in found[0] if n == per)
    return total / count / 1e6 if count else None


def innermost(anns, lo, hi) -> dict:
    """``{name: ns}`` of ``[lo, hi]`` by the innermost annotation that covers
    each instant (the one that began last), ``UNNAMED`` where none does."""
    over = [(s, s + d, n) for n, s, d, _ in anns if s < hi and s + d > lo]
    cuts = sorted({lo, hi} | {t for s, e, _ in over for t in (s, e) if lo < t < hi})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        cover = [(s, -e, n) for s, e, n in over if s <= a and e >= b]
        name = max(cover)[2] if cover else UNNAMED
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def idle_by_span(anns, ops, slice_s=None) -> dict:
    """The busiest chip's idle time in the slice, by innermost program span.

    Gaps of a microsecond and more between the first operation and the last,
    and the slice's two ends: ``slice_s`` (the profiler's run by the host's
    clock) less the stretch from the first operation to the last, as
    ``xplane.reduce`` counts them. An annotation is itself an event of the
    trace, so what of the two ends lies under a span lies between the first
    annotation's start and the first operation, or between the last operation
    and the last annotation's end; the rest of the ends is under none."""
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    out: dict = {}

    def add(part):
        for name, ns in part.items():
            out[name] = out.get(name, 0.0) + ns

    for a, b in xplane.gaps(ops, lo, hi):
        if b - a >= MIN_GAP_NS:
            add(innermost(anns, a, b))
    first = min(lo, min(s for _, s, _, _ in anns))
    last = max(hi, max(s + d for _, s, d, _ in anns))
    ends = (lo - first) + (last - hi)
    if slice_s is not None:
        ends = max(ends, slice_s * 1e9 - (hi - lo))
    if ends >= MIN_GAP_NS:
        add(innermost(anns, first, lo))
        add(innermost(anns, hi, last))
        add({UNNAMED: ends - (lo - first) - (last - hi)})
    return out


def idle_named_pct(run):
    """Share of the busiest chip's idle time in the slice that lies under a
    program span; the seconds by span go to stderr."""
    found = of_run(run)
    if found is None or not found[1]:
        return None
    by = idle_by_span(*found, run.get("trace_slice_s"))
    total = sum(by.values())
    if not total:
        return None
    say("idle seconds of the busiest chip by innermost program span:",
        {n: ns / 1e9 for n, ns in by.items()})
    return 100.0 * (total - by.get(UNNAMED, 0.0)) / total


def _dump(directory: str) -> None:
    path = xplane.trace_file(directory)
    anns = read_annotations(path)
    planes = xplane.read_planes(path)
    devices = xplane.device_planes(planes)
    if not devices:
        sys.exit(f"{path}: no TPU plane; {len(anns)} program annotations in {HOST_PLANE}")
    chip = devices[sorted(devices)[0]]
    modules = sorted((s, s + d, n) for n, s, d in chip[xplane.MODULES_LINE])
    stems: dict = {}
    for _, _, n in modules:
        stems[n.split("(")[0]] = stems.get(n.split("(")[0], 0) + 1
    print("programs (XLA Modules):", stems)
    kernels: dict = {}
    for n, _, d in chip[xplane.OPS_LINE]:
        if xplane.KERNEL in n:
            rec = kernels.setdefault(re.sub(r"[.\d]+$", "", xplane.short_name(n)), [0, 0.0])
            rec[0] += 1
            rec[1] += d / 1e9
    print("kernels (XLA Ops, by name stem):",
          {k: f"{c} x, {s:.4f} s" for k, (c, s) in kernels.items()})
    steps = [a for a in anns if a[0].endswith(".step")]
    for a in steps:
        after = [m for m in modules if m[0] >= a[1]]
        first = f"next program begins {(after[0][0] - a[1]) / 1e6:9.3f} ms later" if after else ""
        print(f"  {a[0]} {a[3].get('step_num')}: {a[1] / 1e6:12.3f} ms, lasts {a[2] / 1e6:8.3f} ms; {first}")
    for a in anns:
        if a[0].endswith(".drain"):
            done = [m[1] for m in modules if m[1] <= a[1] + a[2]]
            print(f"  {a[0]} ends {a[1] + a[2]:.0f} ns, "
                  f"{(a[1] + a[2] - max(done)) / 1e6:.3f} ms after the last program's end" if done
                  else f"  {a[0]}: no program ended before it")
    ops = busiest_ops(planes, 1)
    if anns and ops:
        say("idle seconds by innermost program span (the trace's own extent):",
            {n: ns / 1e9 for n, ns in idle_by_span(anns, ops).items()})


if __name__ == "__main__":
    _dump(sys.argv[1])
