"""The flash-attention kernels under CCA against their roofline, over the
traced slice: ``flash_roofline``'s reading at a grouped-head shape, whose head
size is the configuration's ``head_dim`` and not ``dim // heads``. The kernels
are found by their query operand ``[rows x heads, length, head_dim]`` and told
apart by what they return (``benchmark/metrics/flash_roofline.py``)."""

from benchmark import flops, xplane


def _kind(outputs):
    if len(outputs) == 2 and outputs[1].startswith("f32"):
        return "fwd"
    return "dkv" if len(outputs) == 2 else "dq"


def read(run):
    trace = run.get("trace")
    if not trace or "head_dim" not in run["model"]:
        return None
    m, job, peaks = run["model"], run["traffic"], run["peaks"]
    rows = job["batch_size"] // run["chips"]
    dh, length = m["head_dim"], job["seq_len"]
    q_shape = f"bf16[{rows * m['heads']},{length},{dh}]"
    least = spent = 0.0
    for outputs, operands, ns, events in xplane.kernel_calls(trace):
        if not operands or operands[0] != q_shape:
            continue
        kind = _kind(outputs)
        need = max(
            flops.flash_call_flops(kind, rows, m["heads"], length, dh) / peaks["bf16_flops_per_s"],
            flops.flash_call_bytes(kind, rows, m["heads"], m["kv_heads"], length, dh)
            / peaks["hbm_bytes_per_s"])
        least += need * events / run["chips"]
        spent += ns / 1e9
    return 100.0 * least / spent if spent else None
