"""The gated delta rule against its roofline, over the traced slice. A call's
least time is the larger of the recurrence's own operations over the bf16
peak (``flops_next.gated_delta_call_flops``: 6 dk dv a token and value head
forward, 14 backward; what chunking adds is not counted, so any
implementation is judged on the same work) and its bytes over the memory
bandwidth (``gated_delta_call_bytes``: q, k, v, g, beta and o once each at
their dtype; dO, dq, dk, dv, dg, dbeta in the backward). The calls are those
the trace MADE: a Gated DeltaNet layer's forward in each pass whose
operations the program's table puts under the scope (the first forward, and
remat's where it runs one) and its backward (the operations of pass
``backward``, and those under ``gated_delta_bwd``, the written-out backward,
whose operations carry the pass of the forward that made its residuals).
The time spent is the device time of every operation under scope
``gated_delta``, or of a kernel named ``gated_delta*`` that took their place
(``benchmark/parts.py``)."""

import sys

from benchmark import flops_next, parts


def _pass(path, which):
    """The pass an operation of the rule belongs to, None for another's."""
    if not any(c.startswith("gated_delta") for c in path):
        return None
    return "backward" if "gated_delta_bwd" in path else which


def read(run):
    m = run["model"]
    if not run.get("trace") or m.get("block") != "next":
        return None
    by_pass = {p: parts.ms_a_step(run, lambda path, which, p=p: _pass(path, which) == p)
               for p in parts.PASSES}
    if any(ms is None for ms in by_pass.values()):
        return None
    spent = sum(by_pass.values())
    job, peaks = run["traffic"], run["peaks"]
    rows, length = job["batch_size"] // run["chips"], job["seq_len"]
    n = flops_next.layers(m, "gdn")
    calls = {"fwd": n * sum(by_pass[p] > 0 for p in ("forward", "remat")),
             "bwd": n * (by_pass["backward"] > 0)}
    least = sum(count * max(
        flops_next.gated_delta_call_flops(m, kind, rows, length) / peaks["bf16_flops_per_s"],
        flops_next.gated_delta_call_bytes(m, kind, rows, length) / peaks["hbm_bytes_per_s"])
        for kind, count in calls.items()) * 1e3
    print(f"gated_delta_roofline: ms a step by pass {by_pass}, calls a step {calls}, "
          f"least {least:.4f} ms of {spent:.4f}", file=sys.stderr)
    return 100.0 * least / spent if spent else None
