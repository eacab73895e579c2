"""The train driver for a ZAYA1 configuration (``"block": "zaya"``).

``drivers/train.py``'s window, pool of batches and probes on the one
``MeshTrainer.train`` call, with what differs for a model that is not the dense
block: the model's builder, ``weights_zaya``, ``reference_zaya``, per-leaf norms
in which every held expert's three matrices are leaves of their own, the
routers' balancing bias in the model's state (the seed's; every training step
balances it on its own tokens, in the reference too), the per-expert token
counters the trainer fetches beside the loss, and one more check,
``route_gap``: the share of (token, layer) pairs of the first batch that the
program's router and the reference's send to different experts. The program's
choices are read after the window from one training-mode forward of the same
model on the seed's weights and state (``mutable=["intermediates",
"counters"]``): a program of its own, since the engine's step returns nothing
of a batch's shape and, with every step's bias balanced, the per-expert
counters it does return are equal by construction (PERF.md section 7).
Everything else that is compared comes from the timed call, as in
``drivers/train.py``.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks, reference_zaya, weights_zaya
from benchmark.drivers.train import (MIN_STEP_S, PROBE_STEPS, ProbedMeshTrainer, StateProbes,
                                     WindowedRows, token_pool)
from benchmark.harness import CompileCounter, Tracer, memory_peak_bytes
from benchmark.reference import ADAM_B1


def program_lm(m: dict, **options):
    """The program's ``transformer_lm`` with ZAYA1 blocks at a configuration's
    sizes (``m`` is the file's ``model`` group)."""
    from distkeras_tpu.models import ZayaDims, transformer_lm

    if m.get("block") != "zaya":
        raise ValueError(f"drivers/train_moe.py drives ZAYA1 blocks; this configuration's "
                         f"block is {m.get('block')!r}")
    dims = ZayaDims(
        head_dim=m["head_dim"], conv_kernels=tuple(m["conv_kernels"]),
        rotary_fraction=m["rotary_fraction"], rope_base=m["rope_base"],
        router_dim=m["router_dim"], experts=m["experts"],
        experts_held=tuple(m["experts_held"]), expert_dim=m["expert_dim"],
        norm_eps=m["norm_eps"])
    return transformer_lm(
        vocab=m["vocab"], maxlen=m["maxlen"], dim=m["dim"], heads=m["heads"],
        depth=m["depth"], kv_heads=m["kv_heads"], pos_embedding="rope",
        tie_embeddings=m["tie_embeddings"], dtype=jnp.dtype(m["dtype"]), zaya=dims,
        **options)


class MoEStateProbes(StateProbes):
    """``StateProbes`` over ``weights_zaya``'s leaves."""

    def __init__(self, m, key):
        self.n = 0
        self.grad_norms = self.delta_norms = None
        self._key = key

        def norms(tree):
            return weights_zaya.leaf_norms(m, weights_zaya.from_program_tree(m, tree))

        self._grad = jax.jit(lambda mu: norms(jax.tree.map(lambda a: a / (1.0 - ADAM_B1), mu)))
        self._delta = jax.jit(lambda p, key: norms(jax.tree.map(
            jnp.subtract, p, weights_zaya.program_tree(m, key))))


def build_spec(m, job, key):
    """The program's model, initialised with the benchmark's weights, its
    counters at nought and its routers' balancing bias the seed's."""
    spec = program_lm(m, attn_impl=job["attn_impl"], fused_ce=job["fused_ce"],
                      ce_chunk=job["ce_chunk"], remat=job["remat"])
    make = jax.jit(lambda k: (weights_zaya.program_tree(m, k),
                              weights_zaya.counters_tree(m, k)))
    return dataclasses.replace(spec, init=lambda _rng: make(key))


def program_routes(spec, state, tokens):
    """The expert the program's router chooses for every token of ``tokens
    [B, S]`` in every layer of a training step, ``[depth, B, S]``; ``state``
    is ``spec.init``'s ``(params, counters)``."""
    def chosen(state, tokens):
        params, counters = state
        _, seen = spec.module.apply({"params": params, **counters}, tokens, training=True,
                                    method="hidden", mutable=["intermediates", "counters"])
        blocks = seen["intermediates"]
        return jnp.stack([blocks[f"blocks_{i}"]["moe"]["moe_chosen"][0] for i in range(len(blocks))])

    return jax.device_get(jax.jit(chosen)(state, jnp.asarray(tokens)))


def epoch_tokens(history, first: int, count: int | None = None):
    """The tokens routed to each expert, ``[layers, experts]``, summed over the
    epochs ``first .. first + count - 1`` (to the last one without ``count``)."""
    from distkeras_tpu.models.lm import moe_tokens

    rows = [moe_tokens(r["counters"]) for r in history
            if "counters" in r and r["epoch"] >= first
            and (count is None or r["epoch"] < first + count)]
    return np.sum(rows, axis=0) if rows else None


def print_epochs(m, records):
    """Standard error gets, an epoch, its seconds and each layer's share of
    tokens routed to held experts: a step's time follows that share."""
    first, count = weights_zaya.held(m)
    wall = {r["epoch"]: r["wall_time"] for r in records if "wall_time" in r}
    for r in records:
        if "counters" in r:
            t = epoch_tokens([r], r["epoch"], 1).astype(np.float64)
            held = t[:, first:first + count].sum(1) / np.maximum(t.sum(1), 1.0)
            print(f"moe epoch {r['epoch']}: {wall.get(r['epoch'], float('nan')):.4f} s, held "
                  f"share by layer {[round(float(v), 4) for v in held]}, mean "
                  f"{float(held.mean()):.4f}", file=sys.stderr)


def drive(loaded, seed: int, seconds: float, trace: bool, devices, t0: float) -> dict:
    """Run the cell's window; returns the facts the metrics and checks read."""
    m, job = loaded["config"]["model"], loaded["traffic"]
    chips = loaded["cell"]["chips"]
    # first of all, and before anything is made or compiled: a program without
    # the ZAYA1 block stops here, at once
    program_lm(m)
    key = weights_zaya.seed_key(seed)
    x, y = token_pool(m, job, seed)
    tracer = Tracer(trace, epochs=(job["trace_from_epoch"], job["trace_epochs"]))
    compiles = CompileCounter()
    ds = WindowedRows(x, y, job, seconds, tracer)
    probes = MoEStateProbes(m, key)
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
    grad_norms, delta_norms = probes.readings()
    window_tokens = epoch_tokens(history.records, 1)
    print_epochs(m, history.records)
    slice_tokens = epoch_tokens(history.records, job["trace_from_epoch"], job["trace_epochs"])
    window_steps = ds.window_steps
    window_s = ds.t_close - ds.t_open - ds.paused
    peak = memory_peak_bytes(devices[:chips])
    del trainer, probes
    gc.collect()
    batch = job["batch_size"]
    first = [(x[i * batch:(i + 1) * batch], y[i * batch:(i + 1) * batch])
             for i in range(PROBE_STEPS)]
    t_ref = time.perf_counter()
    routes = program_routes(spec, spec.init(None), first[0][0])
    stats = devices[0].memory_stats() or {}
    print(f"memory before the reference: {stats.get('bytes_in_use', 0)} bytes in use of "
          f"{stats.get('bytes_limit', 0)}, peak {peak}", file=sys.stderr)
    ref = reference_zaya.train_steps(m, seed, first, job["learning_rate"],
                                     rows_per_block=job["reference_rows_per_block"])
    print(f"the routes and the reference after the window took "
          f"{time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    program = {"losses": losses[:PROBE_STEPS], "grad_norms": grad_norms,
               "delta_norms": delta_norms, "routes": routes}
    finite = all(np.isfinite(v) for v in losses)
    tokens = window_steps * batch * job["seq_len"]
    return {
        "checks": moe_checks(program, ref, job["limits"]),
        "attempted": len(losses), "failed": 0 if finite else len(losses),
        "window": {"seconds": window_s, "steps": window_steps, "tokens": tokens,
                   "paused_for_profiler_s": ds.paused},
        "end_to_end": {"train_tokens_per_s": tokens / window_s, "setup_s": ds.t_open - t0},
        "memory_peak_bytes": peak,
        "compiles_in_window": compiles.between(ds.t_open, ds.t_close),
        "trace_dir": tracer.directory if trace else None,
        "trace_slice_s": tracer.slice_s,
        "moe": {"window_tokens": window_tokens, "slice_tokens": slice_tokens,
                "slice_steps": job["trace_epochs"] * job["steps_per_epoch"]},
    }


def moe_checks(program: dict, ref: dict, limits: dict) -> dict:
    """``checks.train``'s five numbers and ``route_gap``."""
    out = checks.train(program, ref, limits)
    apart = np.asarray(program["routes"]) != np.asarray(ref["routes"])
    out["route_gap"] = {"value": float(np.mean(apart)), "limit": limits["route_gap"],
                        "pairs": int(apart.size), "apart_by_layer": apart.mean((1, 2)).tolist()}
    return out
