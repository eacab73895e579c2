"""The fused cross-entropy's device time a step in the traced slice: every
operation whose scope path holds ``fused_ce_fwd`` or ``fused_ce_bwd``
(``benchmark/parts.py``); the two scopes and the passes apart on stderr."""

from benchmark import parts

SCOPES = ("fused_ce_fwd", "fused_ce_bwd")


def read(run):
    return parts.ms_a_step(
        run, lambda path, which: any(s in path for s in SCOPES),
        by=lambda path, which: f"{next(s for s in SCOPES if s in path)} [{which}]",
        what="the fused loss by scope and pass")
