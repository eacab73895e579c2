"""The Gated DeltaNet sublayers' device time a step, in the traced slice:
every operation whose scope path holds the sublayer's component ``gdn``
(its norm, the fused projections ``qkvz`` and ``ba``, the convolution
``gdn_conv``, the delta rule ``gated_delta``, the gated norm ``gdn_norm``,
the output projection ``out``), by the component under it on stderr
(``benchmark/parts.py``)."""

from benchmark import parts


def _under(path):
    at = path.index("gdn")
    return path[at + 1] if len(path) > at + 1 else "(the sublayer's own)"


def read(run):
    return parts.ms_a_step(run, lambda path, which: "gdn" in path,
                           by=lambda path, which: _under(path),
                           what="the Gated DeltaNet sublayers by component")
