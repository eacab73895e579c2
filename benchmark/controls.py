"""The serve cells' controls: a lower precision put where the program stood.

A served model's control need not decode: at every served position of the
sampled requests' own prompts and tokens it reads the token that the lower
precision puts first, and the reference says how far below its best that
token lies. Two controls:

- ``int8``: the program's own path, ``quantize_lm`` (int8 matrices with one
  scale an output channel, served by ``q_matmul``), in one dense forward;
- ``fp8``: the reference with both operands of every matrix product in
  float8 e4m3.

The benchmark's runs never call this. ``benchmark/tests`` plants both at a
tiny size, and on the chip, at a cell's own size and load,

    python3 benchmark/controls.py <workload> <seconds> <seed> [<seed> ...]

prints for each seed one line: the program's readings (the lower ones) and
each control's over the same sample. PERF.md section 6 holds what it read.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference, weights
from benchmark.drivers import serve
from benchmark.harness import program_lm


def _served_positions(first, r):
    lp, k = r["prompt_len"], len(r["tokens"])
    return np.asarray(first[lp - 1:lp - 1 + k])


def int8_first(m, mix, seed, sample) -> list:
    """What the program's int8 path puts first at each served position."""
    from distkeras_tpu.models import quantize_lm

    key = weights.seed_key(seed)
    params = jax.jit(lambda k: weights.program_tree(m, k, mix["served_dtype"]))(key)
    spec, params = quantize_lm(program_lm(m, attn_impl=mix["attn_impl"]), params)
    first = jax.jit(lambda p, t: jnp.argmax(spec.apply(p, {}, t[None], False)[0][0], -1))
    return [_served_positions(first(params, jnp.asarray(serve.padded(m, r))), r)
            for r in sample]


def fp8_first(m, mix, seed, sample) -> list:
    """What the reference in float8 puts first at each served position."""
    w = jax.jit(lambda k: weights.stacked(m, k, mix["served_dtype"]))(weights.seed_key(seed))
    first = jax.jit(lambda w, t: jnp.argmax(reference.next_logits(m, w, t, "fp8"), -1))
    return [_served_positions(first(w, jnp.asarray(serve.padded(m, r))), r)
            for r in sample]


CONTROLS = {"int8": int8_first, "fp8": fp8_first}


def control_checks(m, mix, seed, sample, control: str) -> dict:
    """The cell's two numbers with the control's tokens in the served ones' place."""
    chosen = CONTROLS[control](m, mix, seed, sample)
    return serve.gap_checks(serve.reference_gaps(m, mix, seed, sample, chosen),
                            mix["limits"])


def main(argv) -> int:
    from benchmark import harness, loader

    loaded = loader.load_cell(argv[0])
    seconds, seeds = float(argv[1]), [int(s) for s in argv[2:]]
    devices, _ = harness.find_chips(loaded["cell"]["chips"])
    import distkeras_tpu.utils

    distkeras_tpu.utils.enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    m, mix = loaded["config"]["model"], loaded["traffic"]
    for seed in seeds:
        t = time.perf_counter()
        facts = serve.drive(loaded, seed, seconds, False, devices, t0=t)
        out = {"seed": seed, "drive_s": time.perf_counter() - t,
               "program": {k: c["value"] for k, c in facts["checks"].items()},
               "tokens": facts["checks"]["served_token_gap"]["tokens"],
               "end_to_end": facts["end_to_end"], "failed": facts["failed"],
               "attempted": facts["attempted"],
               "compiles_in_window": facts["compiles_in_window"],
               "memory_peak_bytes": facts["memory_peak_bytes"]}
        for control in CONTROLS:
            t = time.perf_counter()
            c = control_checks(m, mix, seed, facts["sample"], control)
            out[control] = {k: v["value"] for k, v in c.items()}
            out[control]["tokens_off"] = c["served_token_gap"]["tokens_not_the_references_first"]
            out[control]["s"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
