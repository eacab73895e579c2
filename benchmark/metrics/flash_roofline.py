"""The flash-attention kernels against their roofline, over the traced slice.

A kernel's event carries its HLO line; the three kernels are told apart by what
they return over ``[rows x heads, length, head]`` operands: forward a bf16
output and a float32 log-sum-exp, dK/dV two bf16 outputs, dQ one. Each call's
least time is the larger of its operations over the bf16 peak and its bytes
over the memory bandwidth (``benchmark/flops.py``, causal); the share is the sum
of least times over the sum of device times. Compute bounds all three at these
shapes. Under remat the forward runs twice a step, and both runs count: a
roofline share is of the calls made.
"""

from benchmark import flops, xplane


def _kind(outputs):
    if len(outputs) == 2 and outputs[1].startswith("f32"):
        return "fwd"
    return "dkv" if len(outputs) == 2 else "dq"


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    m, job, peaks = run["model"], run["traffic"], run["peaks"]
    rows = job["batch_size"] // run["chips"]
    dh, length = m["dim"] // m["heads"], job["seq_len"]
    q_shape = f"bf16[{rows * m['heads']},{length},{dh}]"
    least = spent = 0.0
    for outputs, operands, ns, events in xplane.kernel_calls(trace):
        if not operands or operands[0] != q_shape:
            continue
        kind = _kind(outputs)
        need = max(
            flops.flash_call_flops(kind, rows, m["heads"], length, dh, m["attn_window"])
            / peaks["bf16_flops_per_s"],
            flops.flash_call_bytes(kind, rows, m["heads"], m["kv_heads"] or m["heads"],
                                   length, dh) / peaks["hbm_bytes_per_s"])
        least += need * events / run["chips"]
        spent += ns / 1e9
    return 100.0 * least / spent if spent else None
