"""The flash-attention kernels at latent attention's two widths (q and k 192,
v 128) against their roofline, over the traced slice. A call's least time comes
from the causal (query, key) pairs (``flops_kanana.flash_call_flops``: 2 x 320
a pair forward, 2 x 512 in dq, 2 x 640 in dk/dv) and from each operand read
and each result written once at its own width (``flash_call_bytes``), whatever
tiles compute them; the time spent is the device time of every kernel named
``flash_fwd`` / ``flash_dq`` / ``flash_dkv`` in the slice. How many least
times a step holds follows from the step, not from the trace: under remat a
layer's forward runs twice and its two backward kernels once."""

import re
import sys

from benchmark import flops_kanana, xplane

KERNELS = re.compile(r"^%?flash_(fwd|dq|dkv)")
CALLS_A_LAYER = {"fwd": 2, "dq": 1, "dkv": 1}


def read(run):
    trace, m = run.get("trace"), run["model"]
    if not trace or m.get("block") != "mla":
        return None
    job, peaks = run["traffic"], run["peaks"]
    rows, length = job["batch_size"] // run["chips"], job["seq_len"]
    steps = job["trace_epochs"] * job["steps_per_epoch"]
    least = sum(
        calls * m["depth"] * steps * max(
            flops_kanana.flash_call_flops(m, kind, rows, length) / peaks["bf16_flops_per_s"],
            flops_kanana.flash_call_bytes(m, kind, rows, length) / peaks["hbm_bytes_per_s"])
        for kind, calls in CALLS_A_LAYER.items())
    spent, events = xplane.op_seconds(trace, KERNELS.pattern)
    print(f"flash_roofline.mla: {events} kernel events, {spent:.4f} s on the device, least "
          f"{least:.4f} s", file=sys.stderr)
    return 100.0 * least / spent if spent else None
