"""From a profiler trace (``.xplane.pb``) to busy time, kernel time and gaps.

Read with ``jax.profiler.ProfileData`` and nothing else. A TPU's plane is named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event for every operation
the chip ran, nested where an operation (a ``while``, a fusion's call) contains
others, and ``XLA Modules`` one for every program. Everything here works on
plain ``(name, start_ns, duration_ns)`` triples so that the tests can check it
on a recorded trace.

``python3 benchmark/xplane.py <trace dir>`` prints what a trace holds, for the
look by hand that comes before any code is written against it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|send|recv")


def trace_file(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


KERNEL = 'custom_call_target="tpu_custom_call"'


def short_name(event_name: str) -> str:
    """An operation's event is named by its whole HLO line; its name is what
    stands before `` = `` (``%fusion.12``, ``%blocks_3._attn_full.4``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def group_name(event_name: str) -> str:
    """The breakdown's name for an operation: its short name, with a block's
    own operations (``blocks_7.x.3``) gathered over layers and calls, so that 96
    kernel calls a step show as one line."""
    name = short_name(event_name)
    if re.search(r"blocks_\d+", name):
        name = re.sub(r"\.\d+$", "", re.sub(r"blocks_\d+", "blocks_*", name))
        if KERNEL in event_name:
            name += " (kernel)"
    return name


def kernel_calls(trace: dict) -> list:
    """``(outputs, operands, own_ns, events)`` of every Pallas kernel in the
    slice: the shapes its HLO line declares, as ``dtype[dims]`` strings."""
    out = []
    for name, (ns, n) in trace["ops"].items():
        if KERNEL not in name or " custom-call(" not in name:
            continue
        outs, rest = name.split(" = ", 1)[1].split(" custom-call(", 1)
        shape = re.compile(r"([a-z]+[0-9]+\[[0-9,]*\])")
        out.append((shape.findall(outs), shape.findall(rest.split("),", 1)[0]), ns, n))
    return out


def read_planes(path: str) -> dict:
    """``{plane name: {line name: [(event name, start_ns, duration_ns), ...]}}``."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events)
    return out


def device_planes(planes: dict) -> dict:
    """The chips' planes, by device number."""
    out = {}
    for name, lines in planes.items():
        hit = re.fullmatch(r"/device:TPU:(\d+)", name)
        if hit and lines.get(OPS_LINE):
            out[int(hit.group(1))] = lines
    return out


def union_ns(intervals) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_times(events) -> list:
    """``(name, start, end, self_ns)`` for every event of one line: its duration
    less what the events nested wholly inside it cover. An event that only
    overlaps another's tail is its neighbour, not its child."""
    out, stack = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and (stack[-1][2] <= start or stack[-1][2] < end):
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= dur
        stack.append([name, start, end, dur])
    out.extend(tuple(s) for s in stack)
    return out


def gaps(intervals, lo, hi):
    """The idle stretches ``(start, end)`` of ``[lo, hi]`` that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return out


def reduce_device(lines: dict) -> dict:
    """One chip: the traced window (first operation's start to the last's end),
    the time in which an operation ran, each operation's own time, and the time
    in which a collective ran and nothing else did."""
    ops = self_times(lines[OPS_LINE])
    lo = min(s for _, s, _, _ in ops)
    hi = max(e for _, _, e, _ in ops)
    spans = [(s, e) for _, s, e, _ in ops]
    busy = union_ns(spans)
    by_name: dict = {}
    for name, _, _, own in ops:
        rec = by_name.setdefault(name, [0.0, 0])
        rec[0] += own
        rec[1] += 1
    # leaves only: an enclosing while or call is busy through its children
    leaf = [(n, s, e) for n, s, e, own in ops if own >= 0.999 * (e - s)]
    coll = [(s, e) for n, s, e in leaf if COLLECTIVE.search(n)]
    other = [(s, e) for n, s, e in leaf if not COLLECTIVE.search(n)]
    exposed = union_ns(coll) - (union_ns(coll) + union_ns(other) - union_ns(coll + other))
    idle = []
    starts = sorted((s, n) for n, s, _, _ in ops)
    for s, e in gaps(spans, lo, hi):
        i = bisect.bisect_left(starts, (e, ""))
        nxt = starts[i][1] if i < len(starts) else "end of trace"
        idle.append((f"before {short_name(nxt)}", e - s))
    return {"window_ns": hi - lo, "busy_ns": busy, "ops": by_name,
            "collective_ns": union_ns(coll), "collective_exposed_ns": exposed,
            "idle_gaps": idle,
            "modules": [(n, d) for n, _, d in lines.get(MODULES_LINE, [])]}


def reduce(planes: dict, chips: int, slice_s: float | None = None) -> dict:
    """What the metrics read: per-chip reductions, and busy and window seconds
    averaged over the chips used. ``slice_s``: how long the profiler ran by the
    host's clock, from ``start_trace``'s return to the call of ``stop_trace``;
    that is the window then, so that the time before the slice's first
    operation and after its last counts as idle. Without it the window runs
    from the first operation to the last."""
    devices = device_planes(planes)
    if len(devices) < chips:
        raise ValueError(f"the trace holds {len(devices)} TPU planes with operations; "
                         f"the cell uses {chips}")
    per = {d: reduce_device(devices[d]) for d in sorted(devices)[:chips]}
    busy = sum(r["busy_ns"] for r in per.values()) / chips / 1e9
    window = (float(slice_s) if slice_s is not None
              else sum(r["window_ns"] for r in per.values()) / chips / 1e9)
    busiest = max(per.values(), key=lambda r: r["busy_ns"])
    ops: dict = {}
    for r in per.values():
        for name, (ns, n) in r["ops"].items():
            rec = ops.setdefault(name, [0.0, 0])
            rec[0] += ns / chips
            rec[1] += n
    grouped: dict = {}
    for name, (ns, n) in ops.items():
        rec = grouped.setdefault(group_name(name), [0.0, 0])
        rec[0] += ns
        rec[1] += n
    gap_total: dict = {}
    for name, ns in busiest["idle_gaps"]:
        if ns >= 1000.0:          # a few nanoseconds between two operations is no gap
            gap_total[name] = gap_total.get(name, 0.0) + ns
    ends = window * 1e9 - busiest["window_ns"]
    if ends >= 1000.0:
        gap_total["the slice's two ends (before its first operation, after its last)"] = ends
    top = sorted(grouped.items(), key=lambda kv: -kv[1][0])[:10]
    top_gaps = sorted(gap_total.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy, "window_s": window, "devices": per, "busiest": busiest,
            "ops": ops,
            "breakdown": {"device_ops": [[n, v[0] / 1e9] for n, v in top],
                          "idle_gaps": [[n, ns / 1e9] for n, ns in top_gaps]}}


def reduce_dir(directory: str, chips: int, slice_s: float | None = None) -> dict:
    return reduce(read_planes(trace_file(directory)), chips, slice_s)


def idle_pct(trace: dict) -> float:
    """Share of the traced slice in which no operation ran, on the busiest chip."""
    return 100.0 * (1.0 - trace["busiest"]["busy_ns"] / 1e9 / trace["window_s"])


def op_seconds(trace: dict, pattern: str) -> tuple[float, int]:
    """Own device seconds (a chip's mean) and the number of events, over every
    chip used, of the operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    hit = [v for n, v in trace["ops"].items() if rx.search(n)]
    return sum(v[0] for v in hit) / 1e9, sum(v[1] for v in hit)


def _dump(directory: str) -> None:
    from jax.profiler import ProfileData

    path = trace_file(directory)
    shown = set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                key = (plane.name, line.name, re.sub(r"[.\d]+$", "", e.name))
                if key not in shown and len(shown) < 400 and plane.name.startswith("/device"):
                    shown.add(key)
                    print("stats", key, {k: str(v)[:120] for k, v in e.stats})
    planes = read_planes(path)
    for pname, lines in planes.items():
        print(f"plane {pname!r}")
        for lname, events in lines.items():
            total = sum(d for _, _, d in events)
            print(f"  line {lname!r}: {len(events)} events, {total / 1e9:.4f} s")
            by: dict = {}
            for n, _, d in events:
                rec = by.setdefault(n, [0.0, 0])
                rec[0] += d
                rec[1] += 1
            for n, (d, c) in sorted(by.items(), key=lambda kv: -kv[1][0])[:25]:
                print(f"    {d / 1e9:10.5f} s {c:6d} x {n[:140]}")


if __name__ == "__main__":
    _dump(sys.argv[1])
