"""The controls behind a hybrid linear-attention train cell's limits: the
reference with a fault planted, put where the program stood.

- ``fp8``: both operands of every matrix product in float8 e4m3, the delta
  rule's products among them;
- ``bf16_state``: the delta rule's state rounded to bfloat16 after every token;
- ``no_output_gate``: attention's ``sigmoid(gate)`` left out;
- ``no_l2``: q and k not L2-normed;
- ``decay_after_write``: ``S = exp(g) (S + beta k (v - Sᵀ k)ᵀ)``;
- ``shared_ungated``: the shared expert added without its sigmoid gate.

The benchmark's runs never call this. ``tests/test_qwen3_next.py`` plants them
at a tiny size, and on the chip, at the cell's own size,

    python3 benchmark/limits_next.py <workload> <seed> <control,control,...> [<seed> ...]

prints for each seed and control one line: ``drivers/train_next.py``'s
numbers of the control against the sound reference, and the leaf that read
worst. PERF.md section 6 holds what it read and the limits chosen from it.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import loader, reference_next
from benchmark.drivers import train, train_next

CONTROLS = ("fp8",) + reference_next.FAULTS


def planted(control: str) -> dict:
    """``reference_next.train_steps``'s arguments that plant ``control``."""
    return dict(precision="fp8") if control == "fp8" else dict(fault=control)


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
        ref = reference_next.train_steps(m, seed, first, **steps)
        for control in controls.split(","):
            t = time.perf_counter()
            got = reference_next.train_steps(m, seed, first, **steps, **planted(control))
            read = train_next.next_checks(m, got, ref, job["limits"])
            print(json.dumps({
                "seed": seed, "control": control, "seconds": round(time.perf_counter() - t, 1),
                **{name: c["value"] for name, c in read.items()},
                "later_losses": read["loss1_gap"]["later_steps"],
                "grad_leaf": read["grad_norm_gap"]["leaf"],
                "grad_worst_of_all": read["grad_norm_gap"]["worst_of_all_leaves"],
                "delta_leaf": read["delta_norm_gap"]["leaf"],
                "fails": sorted(n for n, c in read.items() if not c["value"] <= c["limit"])}),
                flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) < 4 or len(sys.argv) % 2:
        sys.exit(__doc__)
    sys.exit(main(sys.argv[1:]))
