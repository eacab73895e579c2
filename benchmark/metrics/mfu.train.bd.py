"""A block-diffusion step against the chips' bf16 peak, over the whole window:
the operations forward and backward REQUIRE (``benchmark/flops_sdar.py``: both
copies of every row through every layer but what the last layer's clean copy
feeds nothing with; attention over the pairs the mask admits, not the tiles
computed; an expert's for each (position, expert) pair the program's counters
say went to a held expert; the head at the positions ``bd_masked_tokens`` says
were masked; remat not counted) over the window's seconds, chips and peak."""

from benchmark import flops_sdar


def read(run):
    moe, bd = run.get("moe") or {}, run.get("bd") or {}
    if moe.get("window_tokens") is None or bd.get("window_masked") is None:
        return None
    m, job, steps = run["model"], run["traffic"], run["window"]["steps"]
    # the window's counts spread evenly over its steps: the count is linear
    need = steps * sum(flops_sdar.step_flops(
        m, job["batch_size"], job["seq_len"],
        [pairs / steps for pairs in flops_sdar.held_pairs(m, moe["window_tokens"])],
        bd["window_masked"] / steps).values())
    return 100.0 * need / (run["window"]["seconds"] * run["chips"]
                           * run["peaks"]["bf16_flops_per_s"])
