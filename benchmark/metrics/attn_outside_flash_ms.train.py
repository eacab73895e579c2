"""The attention sublayer's device time a step OUTSIDE the flash kernels, in
the traced slice: every operation whose scope path holds the sublayer
(``parts.ATTENTION``) less those under ``flash_fwd`` / ``flash_dq`` /
``flash_dkv``: the projections, the norms, rotary, what stands between the
projections and the kernels, and the launchers' copies. By the components
under the sublayer on stderr."""

from benchmark import parts


def read(run):
    return parts.ms_a_step(
        run, lambda path, which: parts.in_attention(path) and not any(
            k in path for k in parts.FLASH),
        by=lambda path, which: parts.after_attention(path),
        what="attention outside the flash kernels by what stands under the sublayer")
