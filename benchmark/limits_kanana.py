"""The controls behind a latent-attention train cell's limits: the reference
with a fault planted, put where the program stood.

- ``fp8``: both operands of every matrix product in float8 e4m3;
- ``no_rope``: the rotary part of the score (``q_rope . k_rope``) left out;
- ``scale_128``: the scale ``1 / sqrt(qk_nope_dim)`` where the model has ``1 /
  sqrt(qk_nope_dim + qk_rope_dim)``;
- ``biased_weights``: a chosen expert's weight taken from ``s + b`` (the bias
  is for choosing only);
- ``unscaled``: ``route_scale`` left out of the weights;
- ``no_shared``: the shared expert left out;
- ``other_experts``: the next share of the router's experts held, with their
  own weights.

The benchmark's runs never call this. ``tests/test_kanana.py`` plants all
seven at a tiny size, and on the chip, at the cell's own size,

    python3 benchmark/limits_kanana.py <workload> <seed> <control,control,...> [<seed> ...]

prints for each seed and control one line: ``drivers/train_kanana.py``'s
numbers of the control against the sound reference, and the leaf that read
worst. PERF.md section 6 holds what it read and the limits chosen from it.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import loader, reference_kanana
from benchmark.drivers import train, train_kanana

CONTROLS = ("fp8", "other_experts") + reference_kanana.FAULTS


def planted(m, control: str) -> dict:
    """``reference_kanana.train_steps``'s arguments that plant ``control``."""
    first, count = m["experts_held"]
    if control in reference_kanana.FAULTS:
        return dict(fault=control)
    return {"fp8": dict(precision="fp8"),
            "other_experts": dict(held=((first + count) % m["experts"], count))}[control]


def main(argv, benchmark_file=None) -> int:
    loaded = loader.load_cell(argv[0], benchmark_file)
    m, job = loaded["config"]["model"], loaded["traffic"]
    b = job["batch_size"]
    steps = dict(learning_rate=job["learning_rate"],
                 rows_per_block=job["reference_rows_per_block"],
                 queries_per_block=job["reference_queries_per_block"])
    for seed, controls in zip(argv[1::2], argv[2::2]):
        seed = int(seed)
        x, y = train.token_pool(m, job, seed)
        first = [(x[i * b:(i + 1) * b], y[i * b:(i + 1) * b])
                 for i in range(train.PROBE_STEPS)]
        ref = reference_kanana.train_steps(m, seed, first, **steps)
        for control in controls.split(","):
            t = time.perf_counter()
            got = reference_kanana.train_steps(m, seed, first, **steps, **planted(m, control))
            read = train_kanana.mla_checks(m, got, ref, job["limits"])
            print(json.dumps({
                "seed": seed, "control": control, "seconds": round(time.perf_counter() - t, 1),
                **{name: c["value"] for name, c in read.items()},
                "later_losses": read["loss1_gap"]["later_steps"],
                "grad_leaf": read["grad_norm_gap"]["leaf"],
                "delta_leaf": read["delta_norm_gap"]["leaf"],
                "fails": sorted(n for n, c in read.items() if not c["value"] <= c["limit"])}),
                flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) < 4 or len(sys.argv) % 2:
        sys.exit(__doc__)
    sys.exit(main(sys.argv[1:]))
