"""How unevenly the router loads the held experts: the busiest held expert's
tokens over the held experts' mean, over the window, in the worst layer. From
the program's counters. Standard error gets the tokens by layer and expert
and the share routed to experts that are not held."""

import sys

import numpy as np


def read(run):
    tokens = (run.get("moe") or {}).get("window_tokens")
    if tokens is None:
        return None
    tokens = np.asarray(tokens, np.float64)
    first, count = run["model"]["experts_held"]
    held = tokens[:, first:first + count]
    for layer, row in enumerate(tokens):
        print(f"moe tokens, layer {layer}: {row.astype(np.int64).tolist()}", file=sys.stderr)
    print(f"moe: {100.0 * (1.0 - held.sum() / tokens.sum()):.2f} % of (token, layer) pairs "
          f"went to experts not held", file=sys.stderr)
    return float(np.max(held.max(axis=1) / held.mean(axis=1)))
