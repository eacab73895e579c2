"""The train driver for a latent-attention expert configuration (``"block":
"mla"``: multi-head latent attention, a leading dense layer, sigmoid-routed
experts beside a shared expert).

``drivers/train.py``'s window, pool of batches and probes on the one
``MeshTrainer.train`` call, with what differs for this model: the builder,
``weights_kanana``, ``reference_kanana``, the routers' balancing bias in the
model's state (the seed's; every training step moves it by the sign rule, in
the reference too), the per-expert pair counters of the EXPERT layers (layer 0
is dense and has none), and a comparison in which EVERY number is read from
the timed call's own steps, where ``engine.run_step`` returns their state:
the first step's loss (``loss1_gap``; the second and third are read beside
it with no limit: at Adam's 1e-4 an update is under one bf16 step of most
weights, so the program's rounded weights carry a larger second-order part
than the float32 reference's and its loss rises a tenth faster, 5e-5 to 1.6e-4
of the loss by step three on the chip, PERF.md section 6); the first gradient (Adam's first moment after step one) by the
leaves no route decides (``grad_norm_gap``: the attention's, with the query
projection's rotary columns, the latent's shared rotary key and the second
projection's keys and values leaves of their own, the shared expert's, the
dense layer's, the table's and the head's) and by the median of the held
experts' and the routers' matrices (``expert_grad_gap``); the parameters'
change after step three (``delta_norm_gap``); from the model's state as step
one left it the expert layers' pair counters against the reference's routes,
counted (``route_count_gap``); and as step three left it the routers' bias
against the reference's (``bias_gap``: the mean distance over what three
steps of the rule move an entry by, so that a bias left unchanged reads 1).
Nothing is run after the window but the reference.
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

from benchmark import checks, reference_kanana, weights_kanana
from benchmark.drivers.train import (MIN_STEP_S, PROBE_STEPS, StateProbes, WindowedRows,
                                     token_pool)
from benchmark.drivers.train_bd import ProbedMeshTrainer, route_counts
from benchmark.drivers.train_moe import epoch_tokens, print_epochs
from benchmark.harness import CompileCounter, Tracer, memory_peak_bytes
from benchmark.reference import ADAM_B1


def program_lm(m: dict, **options):
    """The program's ``transformer_lm`` with latent-attention expert blocks at
    a configuration's sizes (``m`` is the file's ``model`` group)."""
    from distkeras_tpu.models import MlaDims, transformer_lm

    if m.get("block") != "mla":
        raise ValueError(f"drivers/train_kanana.py drives latent-attention expert blocks; "
                         f"this configuration's block is {m.get('block')!r}")
    dims = MlaDims(
        qk_nope_dim=m["qk_nope_dim"], qk_rope_dim=m["qk_rope_dim"], v_dim=m["v_dim"],
        kv_rank=m["kv_rank"], rope_base=m["rope_base"], experts=m["experts"],
        experts_per_token=m["experts_per_token"], experts_held=tuple(m["experts_held"]),
        expert_dim=m["expert_dim"], shared_experts=m["shared_experts"],
        dense_layers=m["dense_layers"], dense_dim=m["dense_dim"],
        route_scale=m["route_scale"], bias_rate=m["bias_rate"], norm_eps=m["norm_eps"])
    return transformer_lm(
        vocab=m["vocab"], maxlen=m["maxlen"], dim=m["dim"], heads=m["heads"],
        depth=m["depth"], pos_embedding="rope", tie_embeddings=m["tie_embeddings"],
        dtype=jnp.dtype(m["dtype"]), mla=dims, **options)


class MlaStateProbes(StateProbes):
    """``StateProbes`` over ``weights_kanana``'s leaves, with two readings of
    the model's state where a step left it: the expert layers' pair counters
    ``[expert layers, experts]`` after step one, and the routers' bias after
    step ``PROBE_STEPS``."""

    def __init__(self, m, key):
        self.n = 0
        self.grad_norms = self.delta_norms = self.counts = self.bias = None
        self._key = key
        layers = [f"blocks_{i}" for i in weights_kanana.layers_of(m, "expert")]

        def norms(tree):
            return weights_kanana.leaf_norms(m, weights_kanana.from_program_tree(m, tree))

        self._grad = jax.jit(lambda mu: norms(jax.tree.map(lambda a: a / (1.0 - ADAM_B1), mu)))
        self._delta = jax.jit(lambda p, key: norms(jax.tree.map(
            jnp.subtract, p, weights_kanana.program_tree(m, key))))
        self._counts, self._bias = (jax.jit(lambda counters, name=name: jnp.stack(
            [counters[layer]["moe"][name] for layer in layers]) + 0)
            for name in ("moe_tokens", "router_bias"))

    def after_step(self, params, opt_state, nt):
        super().after_step(params, opt_state)
        if self.n == 1:
            self.counts = self._counts(nt["counters"])
        if self.n == PROBE_STEPS:
            self.bias = self._bias(nt["counters"])

    def readings(self):
        grad_norms, delta_norms = super().readings()
        counts, bias = jax.device_get((self.counts, self.bias))
        return {"grad_norms": grad_norms, "delta_norms": delta_norms, "counts": counts,
                "bias": bias}


def build_spec(m, job, key):
    """The program's model, initialised with the benchmark's weights, its
    counters at nought and its routers' balancing bias the seed's."""
    spec = program_lm(m, attn_impl=job["attn_impl"], fused_ce=job["fused_ce"],
                      ce_chunk=job["ce_chunk"], remat=job["remat"])
    make = jax.jit(lambda k: (weights_kanana.program_tree(m, k),
                              weights_kanana.counters_tree(m, k)))
    return dataclasses.replace(spec, init=lambda _rng: make(key))


def print_readings(m, program, ref):
    """Standard error gets one line of JSON with the readings
    :func:`mla_checks` compares by leaf and by expert, the program's beside
    the reference's: what a limit is chosen from."""
    lists = lambda norms: {k: np.asarray(v, np.float64).round(9).tolist() for k, v in norms.items()}
    side = lambda a, counts: {
        "losses": a["losses"], "grad_norms": lists(a["grad_norms"]),
        "delta_norms": lists(a["delta_norms"]), "counts": np.asarray(counts).tolist(),
        "bias": np.asarray(a["bias"], np.float64).round(6).tolist()}
    print("mla readings " + json.dumps({
        "program": side(program, program["counts"]),
        "reference": side(ref, route_counts(m, ref["routes"]))}), file=sys.stderr)


def drive(loaded, seed: int, seconds: float, trace: bool, devices, t0: float) -> dict:
    """Run the cell's window; returns the facts the metrics and checks read."""
    m, job = loaded["config"]["model"], loaded["traffic"]
    chips = loaded["cell"]["chips"]
    # first of all, and before anything is made or compiled: a program without
    # the latent-attention block stops here, at once
    program_lm(m)
    key = weights_kanana.seed_key(seed)
    x, y = token_pool(m, job, seed)
    tracer = Tracer(trace, epochs=(job["trace_from_epoch"], job["trace_epochs"]))
    compiles = CompileCounter()
    ds = WindowedRows(x, y, job, seconds, tracer)
    probes = MlaStateProbes(m, key)
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
    ref = reference_kanana.train_steps(m, seed, first, job["learning_rate"],
                                       rows_per_block=job["reference_rows_per_block"],
                                       queries_per_block=job["reference_queries_per_block"])
    print(f"the reference after the window took {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    print_readings(m, program, ref)
    finite = all(np.isfinite(v) for v in losses)
    tokens = window_steps * batch * job["seq_len"]
    return {
        "checks": mla_checks(m, program, ref, job["limits"]),
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


def mla_checks(m, program: dict, ref: dict, limits: dict) -> dict:
    """``loss1_gap`` (the first step's loss, before any update; the later
    steps' gaps ride on it as readings with no limit) and ``delta_norm_gap``
    as ``checks.train`` has them; ``grad_norm_gap`` over the leaves no route decides (every leaf but the held
    experts' matrices and the routers'); ``expert_grad_gap``, the MEDIAN gap of
    those; ``route_count_gap``, half the distance between the program's pair
    counters after step one and the reference's routes counted, over a layer's
    pairs, worst layer (the share of pairs counted at another expert);
    ``bias_gap``, the mean distance between the program's routers' bias after
    the probed steps and the reference's, over ``bias_rate`` times the steps
    (what the rule moves an entry by: a bias the steps left alone reads 1, a
    rule with the sign turned 2). ``program`` holds the probes' readings and
    the losses, ``ref`` is ``reference_kanana.train_steps``'s."""
    out = checks.train(program, ref, limits)
    out["loss1_gap"]["later_steps"] = [out.pop(f"loss{i}_gap")["value"]
                                       for i in range(2, len(ref["losses"]) + 1)]
    # its grad_norm_gap is the worst of ALL leaves: kept as a reading, and the
    # number is taken again without the leaves a route decides
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
    moved = m["bias_rate"] * len(ref["losses"])
    apart = np.abs(np.asarray(program["bias"], np.float64) - np.asarray(ref["bias"], np.float64))
    out["bias_gap"] = {"value": float(apart.mean() / moved), "limit": limits["bias_gap"],
                       "entries_apart": int((apart > 0.5 * m["bias_rate"]).sum()),
                       "entries": int(apart.size)}
    return out
