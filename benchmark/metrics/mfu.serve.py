"""The whole serving step's share of the chip's bf16 peak, over the whole
window: the forward operations of every request finished in the window (each
prompt and output position through the blocks, the head once a token put out)
over window times peak. Requests cut by the window's two edges balance."""

from benchmark import flops


def read(run):
    need = sum(flops.serve_flops(run["model"], lp, new)
               for lp, new in run["window"]["done"])
    return 100.0 * need / (run["window"]["seconds"] * run["chips"]
                           * run["peaks"]["bf16_flops_per_s"])
