"""A traced slice's device time by the program's own scopes, and by pass.

An event of a chip's ``XLA Ops`` line is named by its HLO line, which says
nothing of the module or ``jax.named_scope`` it came from. The program that
compiled the step can say: ``distkeras_tpu.observability.programs.op_scopes(
"train_step")`` is ``{HLO instruction: (scope path, pass)}`` of the very step it
ran (pass: ``forward``, ``remat`` for remat's second forward, ``backward``;
``""`` where the compiler made the operation and kept no scope). Here
``xplane.reduce``'s own time by event name is laid on that table: seconds by
``(path, pass)``, over the slice's ``train.step`` annotations.

``reduce`` sums own time by name over every program in the slice, so a probe's
or a counter's small program can lend a ``fusion.<n>`` of its own to the step's
name; what the slice's other programs took is said on stderr, which bounds it.
A program without the table (the commit before it) gives ``None`` everywhere
here, and a reader built on it leaves its metric out. So does a table that
names under :data:`FOUND_MIN_PCT` of the slice's busy time: it is then not the
table of the program the trace shows.

``python3 benchmark/parts.py <trace dir or .xplane.pb[.gz]> <op_scopes json>``
prints the whole table for a saved pair (``MeshTrainer(profile_dir=)`` leaves
both in the profile directory).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import spans, xplane

PROGRAM = "train_step"
MODULE = "jit_" + PROGRAM
STEP = "train.step"
FOUND_MIN_PCT = 99.5
PASSES = ("forward", "remat", "backward", "")
NOT_IN_TABLE = (("(not in the table)",), "")
#: the attention sublayer under ``blocks_*``: a module in the three sparse
#: blocks, a method of the block in the dense one
ATTENTION = ("attn", "cca", "blocks_*._attn_full")
FLASH = ("flash_fwd", "flash_dq", "flash_dkv")

_made: dict = {}


def program_table():
    """The running program's table of its train step, or ``None`` where the
    program keeps none (or will not vouch for it)."""
    try:
        from distkeras_tpu.observability import programs
    except (ImportError, AttributeError):
        return None
    try:
        return programs.op_scopes(PROGRAM)
    except Exception:       # a second compile that fails costs five metrics, not the run
        import traceback

        traceback.print_exc()
        return None


def load_table(path: str) -> dict:
    """A table as ``programs.save`` wrote it."""
    with open(path) as f:
        doc = json.load(f)
    parts = [(tuple(p), w) for p, w in doc["parts"]]
    return {op: parts[i] for op, i in doc["ops"].items()}


def lay(trace: dict, table: dict, steps: int) -> dict:
    """``trace["ops"]`` (``{event name: [own ns, events]}``) by the table's
    ``(path, pass)``; an event the table lacks goes to :data:`NOT_IN_TABLE`
    (``missing``: ns by operation), one it holds under no path also to
    ``unscoped`` (seconds by operation)."""
    parts: dict = {}
    missing: dict = {}
    unscoped: dict = {}
    for event, (ns, n) in trace["ops"].items():
        name = xplane.short_name(event)
        part = table.get(name)
        if part is None:
            part = NOT_IN_TABLE
            missing[name] = missing.get(name, 0.0) + ns
        elif not part[0]:
            unscoped[f"{name} [{part[1] or 'no op_name'}]"] = ns / 1e9
        rec = parts.setdefault(part, [0.0, 0])
        rec[0] += ns
        rec[1] += n
    busy = trace["busy_s"] * 1e9
    absent = sum(missing.values())
    return {"parts": parts, "missing": missing, "unscoped": unscoped, "steps": steps,
            "busy_ns": busy, "sum_ns": sum(ns for ns, _ in parts.values()),
            "found_pct": 100.0 * (1.0 - absent / busy),
            "scoped_pct": 100.0 * (1.0 - (absent + sum(unscoped.values()) * 1e9) / busy)}


def show(laid: dict, out=None) -> None:
    """The whole table: part x pass, ms a step, share, events (to stderr
    unless ``out`` is given)."""
    out = out or sys.stderr
    steps, busy = laid["steps"], laid["busy_ns"]
    rows: dict = {}
    for (path, which), (ns, n) in laid["parts"].items():
        row = rows.setdefault(path, {w: 0.0 for w in PASSES} | {"events": 0})
        row[which] += ns
        row["events"] += n
    print(f"device time by part and pass, ms a step over {steps} steps: busy "
          f"{busy / 1e9:.6f} s, the parts sum to {laid['sum_ns'] / 1e9:.6f} s; in the "
          f"table by name {laid['found_pct']:.3f} %, under a path {laid['scoped_pct']:.3f} %",
          file=out)
    print(f"  {'forward':>9} {'remat':>9} {'backward':>9} {'no pass':>9} {'total':>9} "
          f"{'share':>7} {'events':>7}  part", file=out)
    for path, row in sorted(rows.items(), key=lambda kv: -sum(kv[1][w] for w in PASSES)):
        total = sum(row[w] for w in PASSES)
        cells = " ".join(f"{row[w] / steps / 1e6:9.3f}" for w in PASSES)
        print(f"  {cells} {total / steps / 1e6:9.3f} {100.0 * total / busy:6.2f}% "
              f"{row['events']:7d}  {'/'.join(path) or '(no path)'}", file=out)


def other_programs(trace: dict) -> None:
    """Stderr gets what the busiest chip's programs took, the step's apart."""
    by: dict = {}
    for name, ns in trace["busiest"]["modules"]:
        stem = name.split("(")[0]
        by[stem] = by.get(stem, 0.0) + ns / 1e9
    step = sum(s for n, s in by.items() if n.startswith(MODULE))
    spans.say(f"programs in the slice (XLA Modules, busiest chip): {MODULE} {step:.6f} s, "
              f"others {sum(by.values()) - step:.6f} s, which bounds what their "
              f"operations can lend the step's names:", by)


def of_run(run):
    """The traced run's slice laid on the program's table, made and shown once;
    ``None`` without a trace, a table, a ``train.step`` annotation, or where
    the table names too little of the slice."""
    if not run.get("trace") or not run.get("trace_dir"):
        return None
    key = run["trace_dir"]
    if key not in _made:
        _made[key] = None
        table, found = program_table(), spans.of_run(run)
        for e in spans.run_log() or ():
            if e["name"] == "program.op_scopes":
                print(f"program.op_scopes took {e['dur_ns'] / 1e9:.3f} s: {e['args']}",
                      file=sys.stderr)
        steps = sum(1 for a in found[0] if a[0] == STEP) if found else 0
        if table is not None and steps:
            laid = lay(run["trace"], table, steps)
            other_programs(run["trace"])
            show(laid)
            if laid["found_pct"] < FOUND_MIN_PCT:
                spans.say(f"only {laid['found_pct']:.3f} % of the slice's busy time is in "
                          f"the table by name (under {FOUND_MIN_PCT}): not this trace's "
                          f"program, no metric is read from it; missing:",
                          {n: ns / 1e9 for n, ns in laid["missing"].items()})
            else:
                _made[key] = laid
    return _made[key]


def ms_a_step(run, keep, by=None, what=""):
    """Milliseconds a step of the parts ``keep(path, pass)`` admits; with
    ``by(path, pass) -> name`` their seconds a step by that name on stderr."""
    laid = of_run(run)
    if laid is None:
        return None
    kept = {part: ns for part, (ns, _) in laid["parts"].items() if keep(*part)}
    if by is not None:
        rows: dict = {}
        for part, ns in kept.items():
            rows[by(*part)] = rows.get(by(*part), 0.0) + ns / laid["steps"] / 1e9
        spans.say(f"{what}, seconds a step:", rows)
    return sum(kept.values()) / laid["steps"] / 1e6


def scoped_pct(run):
    """Share of the slice's busy time whose operation is in the table and
    under a path; what is under none goes to stderr by operation."""
    laid = of_run(run)
    if laid is None:
        return None
    spans.say("operations under no path, seconds in the slice:", laid["unscoped"])
    return laid["scoped_pct"]


def in_attention(path) -> bool:
    return any(c in ATTENTION for c in path)


def after_attention(path, depth: int = 2) -> str:
    """The ``depth`` components under the attention sublayer's own."""
    at = next(i for i, c in enumerate(path) if c in ATTENTION)
    return "/".join(path[at + 1:at + 1 + depth]) or "(the sublayer's own)"


def _main(trace_path: str, table_path: str) -> None:
    path = xplane.trace_file(trace_path) if os.path.isdir(trace_path) else trace_path
    planes = xplane.read_planes(path)
    trace = xplane.reduce(planes, len(xplane.device_planes(planes)))
    steps = sum(1 for a in spans.read_annotations(path) if a[0] == STEP)
    if not steps:
        sys.exit(f"{path}: no {STEP} annotation, so no step to divide by")
    other_programs(trace)
    show(lay(trace, load_table(table_path), steps), out=sys.stdout)


if __name__ == "__main__":
    _main(sys.argv[1], sys.argv[2])
