"""Run one cell once: ``python3 benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``. The last line of standard output is the result."""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # process start, as near as Python can note it

import argparse
import importlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import checks, harness, loader

    loaded = loader.load_cell(args.workload)
    import distkeras_tpu.utils          # a bare checkout without the program stops here

    devices, peaks = harness.find_chips(loaded["cell"]["chips"])
    distkeras_tpu.utils.enable_compilation_cache()
    import jax

    # every program, however quick to compile, is found again by the next run,
    # and none is thrown out to make room: one cell's programs may not push
    # another's out of the directory (PERF.md section 6, set-up)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)

    driver = importlib.import_module("benchmark.drivers." + loaded["traffic"]["driver"])
    facts = driver.drive(loaded, args.seed, args.seconds, bool(args.trace), devices,
                         t0=_T0)
    facts.update(model=loaded["config"]["model"], traffic=loaded["traffic"],
                 peaks=peaks, chips=loaded["cell"]["chips"])
    first = devices[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": loaded["cell"]["chips"],
              "memory_peak_bytes": facts["memory_peak_bytes"]}
    breakdown = None
    if args.trace:
        from benchmark import xplane

        if not os.path.isdir(facts["trace_dir"]):
            raise RuntimeError("--trace 1, and the window closed before the slice began: "
                               "--seconds is shorter than the mix's trace start")
        facts["trace"] = xplane.reduce_dir(facts["trace_dir"], loaded["cell"]["chips"],
                                           facts["trace_slice_s"])
        device["busy_s"] = facts["trace"]["busy_s"]
        device["window_s"] = facts["trace"]["window_s"]
        breakdown = facts["trace"]["breakdown"]
        wanted = loaded["per_layer"]
        values = {m["name"]: loader.load_reader(m["reader"])(facts) for m in wanted}
    else:
        wanted = loaded["end_to_end"]
        values = {m["name"]: facts["end_to_end"].get(m["name"]) for m in wanted}
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values[m["name"]] is not None}
    correct = checks.holds(facts["checks"]) and facts["failed"] == 0
    for name, c in facts["checks"].items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})"
              f"{'' if c['value'] <= c['limit'] else '  <-- FAILS'}", file=sys.stderr)
    print(harness.result_line(correct, facts["attempted"], facts["failed"], metrics,
                              device, facts["checks"], breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
