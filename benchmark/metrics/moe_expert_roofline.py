"""The grouped expert products against their roofline, over the traced slice.

The work is counted from the program's counters, whatever implements it: each
layer's tokens routed to held experts in the slice's steps go through the
gate-and-up and the down projection four times a step (forward, remat's second
forward, the gradient to the rows, the gradient to the weights). A call's least
time is the larger of its operations over the bf16 peak and its bytes over the
memory bandwidth (``flops_zaya.grouped_call``). The time spent is the device
time of the kernels that do it: ``jax.lax.ragged_dot``'s on a TPU are named
``ragged-dot-*`` (the products and the kernel that lays out their groups); a
Pallas kernel that took their place would be named ``moe_gmm_*``.
"""

import re
import sys

from benchmark import flops_zaya, xplane

KERNELS = re.compile(r"^%?(ragged-dot|moe_gmm)")
CALLS_A_STEP = 4          # forward, remat's forward, d rows, d weights


def read(run):
    trace, moe = run.get("trace"), run.get("moe") or {}
    if not trace or moe.get("slice_tokens") is None:
        return None
    m, peaks, steps = run["model"], run["peaks"], moe["slice_steps"]
    least = 0.0
    for rows in flops_zaya.held_tokens(m, moe["slice_tokens"]):
        for wide in (True, False):
            ops, moved = flops_zaya.grouped_call(m, rows / steps, wide)
            least += CALLS_A_STEP * steps * max(ops / peaks["bf16_flops_per_s"],
                                                moved / peaks["hbm_bytes_per_s"])
    spent, events = xplane.op_seconds(trace, KERNELS.pattern)
    print(f"moe_expert_roofline: {events} grouped-product events, {spent:.4f} s "
          f"on the device, least {least / run['chips']:.4f} s", file=sys.stderr)
    return 100.0 * least / run["chips"] / spent if spent else None
