"""A sparse model's step against the chips' bf16 peak, over the whole window:
the operations forward and backward require a token (``benchmark/flops_zaya.py``:
what every token passes, plus an expert's for each token the program's counters
say was routed to a held expert; remat not counted) times tokens a second, over
chips times peak."""

from benchmark import flops_zaya


def read(run):
    tokens = (run.get("moe") or {}).get("window_tokens")
    if tokens is None:
        return None
    m = run["model"]
    visits = sum(flops_zaya.held_tokens(m, tokens)) / run["window"]["tokens"]
    need = flops_zaya.train_flops_per_token(m, run["traffic"]["seq_len"], visits)
    rate = run["window"]["tokens"] / run["window"]["seconds"]
    return 100.0 * need * rate / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
