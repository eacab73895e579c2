"""A latent-attention expert step against the chips' bf16 peak, over the whole
window: the operations forward and backward REQUIRE (``benchmark/flops_kanana.py``:
what every token passes: the latent projections, the shared expert, the
router, the dense layer, the head; causal attention at 2 x (192 + 128) a pair
forward; an expert's for each (token, expert) pair the program's counters say
went to a held expert; remat not counted) over the window's seconds, chips and
peak."""

from benchmark import flops_kanana


def read(run):
    moe, m = run.get("moe") or {}, run["model"]
    if moe.get("window_tokens") is None or m.get("block") != "mla":
        return None
    job, steps = run["traffic"], run["window"]["steps"]
    # the window's counts spread evenly over its steps: the count is linear
    need = steps * sum(flops_kanana.step_flops(
        m, job["batch_size"], job["seq_len"],
        [pairs / steps for pairs in flops_kanana.held_pairs(m, moe["window_tokens"])]).values())
    return 100.0 * need / (run["window"]["seconds"] * run["chips"]
                           * run["peaks"]["bf16_flops_per_s"])
