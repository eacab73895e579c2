"""The controls behind a block-diffusion train cell's limits: the reference
with a fault planted, put where the program stood.

- ``fp8``: both operands of every matrix product in float8 e4m3;
- ``half_batch``: every step drops the second half of its rows;
- ``other_experts``: the next share of the router's experts held, with their
  own weights;
- ``causal_in_block``: a noised query sees only the keys up to itself inside
  its own block (the mask of an autoregressive model, which diffusion over
  blocks is not; ``own_block_gap`` is the number made for it);
- ``unweighted``: the ``1 / t`` left out of the loss.

The benchmark's runs never call this. ``benchmark/tests/test_sdar.py`` plants
all five at a tiny size, and on the chip, at the cell's own size,

    python3 benchmark/limits_bd.py <workload> <seed> <control,control,...> [<seed> ...]

prints for each seed and control one line: ``drivers/train_bd.py``'s numbers
of the control against the sound reference, and the leaf that read worst. PERF.md section 6 holds what it read and the limits chosen from it.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import loader, reference_sdar
from benchmark.drivers import train, train_bd

CONTROLS = ("fp8", "half_batch", "other_experts", "causal_in_block", "unweighted")


def planted(m, control: str) -> dict:
    """``reference_sdar.train_steps``'s arguments that plant ``control``."""
    first, count = m["experts_held"]
    return {"fp8": dict(precision="fp8"), "half_batch": dict(half_batch=True),
            "other_experts": dict(held=((first + count) % m["experts"], count)),
            "causal_in_block": dict(fault="causal_in_block"),
            "unweighted": dict(fault="unweighted")}[control]


def main(argv, benchmark_file=None) -> int:
    loaded = loader.load_cell(argv[0], benchmark_file)
    m, job = loaded["config"]["model"], loaded["traffic"]
    b = job["batch_size"]
    steps = dict(learning_rate=job["learning_rate"],
                 rows_per_block=job["reference_rows_per_block"],
                 queries_per_block=job["reference_queries_per_block"])
    for seed, controls in zip(argv[1::2], argv[2::2]):
        seed = int(seed)
        x, y = train_bd.token_pool(m, job, seed)
        first = [(x[i * b:(i + 1) * b], y[i * b:(i + 1) * b])
                 for i in range(train.PROBE_STEPS)]
        ref = reference_sdar.train_steps(m, seed, first, **steps)
        for control in controls.split(","):
            t = time.perf_counter()
            got = reference_sdar.train_steps(m, seed, first, **steps, **planted(m, control))
            read = train_bd.bd_checks(m, got, ref, job["limits"])
            print(json.dumps({
                "seed": seed, "control": control, "seconds": round(time.perf_counter() - t, 1),
                **{name: c["value"] for name, c in read.items()},
                "grad_leaf": read["grad_norm_gap"]["leaf"],
                "delta_leaf": read["delta_norm_gap"]["leaf"]}), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) < 4 or len(sys.argv) % 2:
        sys.exit(__doc__)
    sys.exit(main(sys.argv[1:]))
