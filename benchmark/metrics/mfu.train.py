"""The whole step's share of the chips' bf16 peak, over the whole window: the
operations the forward and backward passes require a token (recomputation under
remat not counted) times tokens a second, over chips times peak."""

from benchmark import flops


def read(run):
    rate = run["window"]["tokens"] / run["window"]["seconds"]
    need = flops.train_flops_per_token(run["model"], run["traffic"]["seq_len"])
    return 100.0 * need * rate / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
