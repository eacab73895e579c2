"""The train driver for a hybrid linear-attention expert configuration
(``"block": "next"``: Gated DeltaNet layers beside gated full attention,
softmax-routed experts beside a gated shared expert).

``drivers/train.py``'s window, pool of batches and probes on the one
``MeshTrainer.train`` call, with what differs for this model: its constructor,
``weights_next``, ``reference_next`` (its delta rule token by token), the
per-expert pair counters of every layer, and a comparison in which EVERY
number is read from the timed call's own steps, where ``engine.run_step``
returns their state: the first step's loss (``loss1_gap``; the second and
third are read beside it with no limit, as in ``drivers/train_kanana.py``);
the first gradient (Adam's first moment after step one) by the leaves no route
decides (``grad_norm_gap``: the Gated DeltaNet projection's q, k, v, z, b and
a columns, the attention's query and gate columns and the shared expert's
gate, up, down and sigmoid gate are leaves of their own) and by the median of
the held experts' and the routers' matrices (``expert_grad_gap``); the
parameters' change after step three (``delta_norm_gap``); and from the
model's state as step one left it the layers' pair counters against the
reference's routes, counted (``route_count_gap``). Nothing is run after the
window but the reference.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks, reference_next, weights_next
from benchmark.drivers.train import (MIN_STEP_S, PROBE_STEPS, StateProbes, WindowedRows,
                                     token_pool)
from benchmark.drivers.train_bd import ProbedMeshTrainer, route_counts
from benchmark.drivers.train_moe import epoch_tokens, print_epochs
from benchmark.harness import CompileCounter, Tracer, memory_peak_bytes
from benchmark.reference import ADAM_B1


def program_lm(m: dict, **options):
    """The program's ``transformer_lm`` with hybrid linear-attention expert
    blocks at a configuration's sizes (``m`` is the file's ``model`` group)."""
    from distkeras_tpu.models import NextDims, transformer_lm

    if m.get("block") != "next":
        raise ValueError(f"drivers/train_next.py drives hybrid linear-attention expert "
                         f"blocks; this configuration's block is {m.get('block')!r}")
    dims = NextDims(
        head_dim=m["head_dim"], rotary_dim=m["rotary_dim"], rope_base=m["rope_base"],
        full_attention_interval=m["full_attention_interval"],
        linear_key_heads=m["linear_key_heads"], linear_value_heads=m["linear_value_heads"],
        linear_key_dim=m["linear_key_dim"], linear_value_dim=m["linear_value_dim"],
        conv_kernel=m["conv_kernel"], experts=m["experts"],
        experts_per_token=m["experts_per_token"], experts_held=tuple(m["experts_held"]),
        expert_dim=m["expert_dim"], shared_dim=m["shared_dim"], norm_eps=m["norm_eps"])
    return transformer_lm(
        vocab=m["vocab"], maxlen=m["maxlen"], dim=m["dim"], heads=m["heads"],
        kv_heads=m["kv_heads"], depth=m["depth"], pos_embedding="rope",
        tie_embeddings=m["tie_embeddings"], dtype=jnp.dtype(m["dtype"]), next=dims, **options)


class NextStateProbes(StateProbes):
    """``StateProbes`` over ``weights_next``'s leaves, with the layers' pair
    counters ``[depth, experts]`` read from the model's state as step one
    left it."""

    def __init__(self, m, key):
        self.n = 0
        self.grad_norms = self.delta_norms = self.counts = None
        self._key = key

        def norms(tree):
            return weights_next.leaf_norms(m, weights_next.from_program_tree(m, tree))

        self._grad = jax.jit(lambda mu: norms(jax.tree.map(lambda a: a / (1.0 - ADAM_B1), mu)))
        self._delta = jax.jit(lambda p, key: norms(jax.tree.map(
            jnp.subtract, p, weights_next.program_tree(m, key))))
        self._counts = jax.jit(lambda counters: jnp.stack(
            [counters[f"blocks_{i}"]["moe"]["moe_tokens"] for i in range(m["depth"])]))

    def after_step(self, params, opt_state, nt):
        super().after_step(params, opt_state)
        if self.n == 1:
            self.counts = self._counts(nt["counters"])

    def readings(self):
        grad_norms, delta_norms = super().readings()
        return {"grad_norms": grad_norms, "delta_norms": delta_norms,
                "counts": jax.device_get(self.counts)}


def build_spec(m, job, key):
    """The program's model, initialised with the benchmark's weights and its
    counters at nought."""
    spec = program_lm(m, attn_impl=job["attn_impl"], fused_ce=job["fused_ce"],
                      ce_chunk=job["ce_chunk"], remat=job["remat"])
    make = jax.jit(lambda k: (weights_next.program_tree(m, k), weights_next.counters_tree(m)))
    return dataclasses.replace(spec, init=lambda _rng: make(key))


def print_readings(m, program, ref):
    """Standard error gets one line of JSON with the readings
    :func:`next_checks` compares by leaf and by expert, the program's beside
    the reference's: what a limit is chosen from."""
    lists = lambda norms: {k: np.asarray(v, np.float64).round(9).tolist() for k, v in norms.items()}
    side = lambda a, counts: {
        "losses": a["losses"], "grad_norms": lists(a["grad_norms"]),
        "delta_norms": lists(a["delta_norms"]), "counts": np.asarray(counts).tolist()}
    print("next readings " + json.dumps({
        "program": side(program, program["counts"]),
        "reference": side(ref, route_counts(m, ref["routes"]))}), file=sys.stderr)


def drive(loaded, seed: int, seconds: float, trace: bool, devices, t0: float) -> dict:
    """Run the cell's window; returns the facts the metrics and checks read."""
    m, job = loaded["config"]["model"], loaded["traffic"]
    chips = loaded["cell"]["chips"]
    # first of all, and before anything is made or compiled: a program without
    # the hybrid linear-attention block stops here, at once
    program_lm(m)
    key = weights_next.seed_key(seed)
    x, y = token_pool(m, job, seed)
    tracer = Tracer(trace, epochs=(job["trace_from_epoch"], job["trace_epochs"]))
    compiles = CompileCounter()
    ds = WindowedRows(x, y, job, seconds, tracer)
    probes = NextStateProbes(m, key)
    spec = build_spec(m, job, key)
    trainer = ProbedMeshTrainer(
        spec, loss="sparse_softmax_cross_entropy",
        worker_optimizer=job["optimizer"], learning_rate=job["learning_rate"],
        mesh_shape=dict(job["mesh_shape"]),
        parameter_sharding=job["parameter_sharding"],
        batch_size=job["batch_size"], input_mode="stream", log_metrics=True,
        num_epoch=2 + int(seconds / (job["steps_per_epoch"] * MIN_STEP_S)) + 1,
        seed=int(seed) & 0x7FFFFFFF)
    trainer.probes = probes
    try:
        trainer.train(ds)
    finally:
        tracer.stop()
        compiles.close()
    if ds.t_close is None:
        raise RuntimeError("the trainer ran out of epochs before the window's "
                           "seconds were up: a step took under MIN_STEP_S")
    history = trainer.get_history()
    losses = [float(v) for v in history.losses()]
    program = {"losses": losses[:PROBE_STEPS], **probes.readings()}
    print_epochs(m, history.records)
    sliced = job["trace_from_epoch"], job["trace_epochs"]
    window_steps = ds.window_steps
    window_s = ds.t_close - ds.t_open - ds.paused
    peak = memory_peak_bytes(devices[:chips])
    moe = {"window_tokens": epoch_tokens(history.records, 1),
           "slice_tokens": epoch_tokens(history.records, *sliced),
           "slice_steps": job["trace_epochs"] * job["steps_per_epoch"]}
    del trainer, probes
    gc.collect()
    batch = job["batch_size"]
    first = [(x[i * batch:(i + 1) * batch], y[i * batch:(i + 1) * batch])
             for i in range(PROBE_STEPS)]
    t_ref = time.perf_counter()
    stats = devices[0].memory_stats() or {}
    print(f"memory before the reference: {stats.get('bytes_in_use', 0)} bytes in use of "
          f"{stats.get('bytes_limit', 0)}, peak {peak}", file=sys.stderr)
    ref = reference_next.train_steps(m, seed, first, job["learning_rate"],
                                     rows_per_block=job["reference_rows_per_block"],
                                     queries_per_block=job["reference_queries_per_block"])
    print(f"the reference after the window took {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    print_readings(m, program, ref)
    finite = all(np.isfinite(v) for v in losses)
    tokens = window_steps * batch * job["seq_len"]
    return {
        "checks": next_checks(m, program, ref, job["limits"]),
        "attempted": len(losses), "failed": 0 if finite else len(losses),
        "window": {"seconds": window_s, "steps": window_steps, "tokens": tokens,
                   "paused_for_profiler_s": ds.paused},
        "end_to_end": {"train_tokens_per_s": tokens / window_s, "setup_s": ds.t_open - t0},
        "memory_peak_bytes": peak,
        "compiles_in_window": compiles.between(ds.t_open, ds.t_close),
        "trace_dir": tracer.directory if trace else None,
        "trace_slice_s": tracer.slice_s,
        "moe": moe,
    }


def next_checks(m, program: dict, ref: dict, limits: dict) -> dict:
    """``loss1_gap`` (the first step's loss, before any update; the later
    steps' gaps ride on it as readings with no limit) and ``delta_norm_gap``
    as ``checks.train`` has them; ``grad_norm_gap`` over the leaves no route
    decides (every leaf but the held experts' matrices and the routers');
    ``expert_grad_gap``, the MEDIAN gap of those; ``route_count_gap``, half
    the distance between the program's pair counters after step one and the
    reference's routes counted, over a layer's pairs, worst layer.
    ``program`` holds the probes' readings and the losses, ``ref`` is
    ``reference_next.train_steps``'s."""
    out = checks.train(program, ref, limits)
    out["loss1_gap"]["later_steps"] = [out.pop(f"loss{i}_gap")["value"]
                                       for i in range(2, len(ref["losses"]) + 1)]
    routed = lambda name: name.startswith("ex_") or name == "wr"
    split = lambda norms, keep: {k: v for k, v in norms.items() if routed(k) == keep}
    g, at = checks.worst_leaf_gap(split(program["grad_norms"], False),
                                  split(ref["grad_norms"], False))
    out["grad_norm_gap"] = {"value": g, "limit": limits["grad_norm_gap"], "leaf": at,
                            "worst_of_all_leaves": out["grad_norm_gap"]["value"],
                            "worst_leaf_of_all": out["grad_norm_gap"]["leaf"]}
    p, r = (checks._flat(split(a["grad_norms"], True))[1] for a in (program, ref))
    gaps = np.abs(p - r) / np.maximum(r, np.median(r))
    out["expert_grad_gap"] = {"value": float(np.median(gaps)), "limit": limits["expert_grad_gap"],
                              "leaves": int(gaps.size)}
    # a control is the reference again: its counters are its routes, counted
    got = np.asarray(program["counts"], np.int64) if "counts" in program \
        else route_counts(m, program["routes"])
    want = route_counts(m, ref["routes"])
    apart = np.abs(got - want).sum(1) / (2.0 * want.sum(1))
    out["route_count_gap"] = {"value": float(apart.max()), "limit": limits["route_count_gap"],
                              "pairs_a_layer": int(want[0].sum()), "by_layer": apart.tolist()}
    return out
