"""Remat's second forward, a step, in the traced slice: every operation the
program's table gives the pass ``remat`` (``rematted_computation`` in its
``op_name``), by part on stderr. An operation the compiler made in place of
the program's (the TPU's ``ragged-dot`` kernels) has no pass and is not here."""

from benchmark import parts


def read(run):
    return parts.ms_a_step(run, lambda path, which: which == "remat",
                           by=lambda path, which: "/".join(path[:3]) or "(no path)",
                           what="remat's forward by part")
