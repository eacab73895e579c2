"""What moves rows around the expert products, a step, in the traced slice:
every operation whose scope path holds ``moe_route`` (the sort, the gathers,
the masks, the unsort; under top-k a chunk's gather and scatter-add), by pass
on stderr (``benchmark/parts.py``)."""

from benchmark import parts


def read(run):
    return parts.ms_a_step(run, lambda path, which: "moe_route" in path,
                           by=lambda path, which: f"moe_route [{which}]",
                           what="moe_route by pass")
