"""The train driver for a configuration trained by diffusion over blocks
(``"block": "sdar"``).

``drivers/train.py``'s window, pool of batches and probes on the one
``MeshTrainer.train`` call, with what differs for this model: the builder,
``weights_sdar``, ``reference_sdar``, a batch whose label is its own clean rows
(the step noises them on the device from the state's key and step count, and
the reference draws the same noise again), the counter of masked positions
beside the per-expert pair counters, and a comparison in which EVERY number is
read from the timed call's own steps, where ``engine.run_step`` returns their
state: the three losses; the first gradient (Adam's first moment after step
one) by the leaves no route decides (``grad_norm_gap``) and by the median of
the held experts' and the routers' matrices (``expert_grad_gap``: one position
weighted ``1 / t`` near 1000 and routed otherwise at bf16 changes ONE expert's
gradient severalfold, so the worst of them says nothing); the parameters'
change after step three (``delta_norm_gap``); and from the model's state as
step one left it the layers' pair counters against the reference's routes,
counted (``route_count_gap``), and the first layer's attention sublayer at
the first row's first noised block (``own_block_gap``: its queries see that
block and nothing else, so a wrong mask INSIDE a block shows there at full
size where, over the step's other numbers, it is lost among a query's two
thousand clean keys). Nothing is run after the window but the reference.
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

from distkeras_tpu.trainers import MeshTrainer

from benchmark import checks, reference_sdar, weights_sdar
from benchmark.drivers.train import (MIN_STEP_S, POOL_BATCHES, PROBE_STEPS, StateProbes,
                                     WindowedRows)
from benchmark.drivers.train_moe import epoch_tokens
from benchmark.harness import CompileCounter, Tracer, memory_peak_bytes
from benchmark.reference import ADAM_B1


def program_lm(m: dict, **options):
    """The program's ``transformer_lm`` with block-diffusion expert blocks at a
    configuration's sizes (``m`` is the file's ``model`` group)."""
    from distkeras_tpu.models import SdarDims, transformer_lm

    if m.get("block") != "sdar":
        raise ValueError(f"drivers/train_bd.py drives block-diffusion expert blocks; this "
                         f"configuration's block is {m.get('block')!r}")
    dims = SdarDims(
        head_dim=m["head_dim"], rope_base=m["rope_base"], experts=m["experts"],
        experts_per_token=m["experts_per_token"], experts_held=tuple(m["experts_held"]),
        expert_dim=m["expert_dim"], norm_eps=m["norm_eps"], block_length=m["block_length"],
        noise_floor=m["noise_floor"])
    return transformer_lm(
        vocab=m["vocab"], maxlen=m["maxlen"], dim=m["dim"], heads=m["heads"],
        depth=m["depth"], kv_heads=m["kv_heads"], pos_embedding="rope",
        tie_embeddings=m["tie_embeddings"], dtype=jnp.dtype(m["dtype"]), sdar=dims,
        **options)


def token_pool(m, job, seed: int):
    """``POOL_BATCHES`` batches of distinct seeded clean rows, ids uniform over
    every row of the table but the last (MASK); the label is the row itself."""
    rng = np.random.default_rng([int(seed), 0x7261696E])
    rows = POOL_BATCHES * job["batch_size"]
    toks = rng.integers(0, m["vocab"] - 1, size=(rows, job["seq_len"]), dtype=np.int32)
    return toks, toks


class BDStateProbes(StateProbes):
    """``StateProbes`` over ``weights_sdar``'s leaves, with two more readings
    of step one, from the model's state as the step left it: the layers' pair
    counters ``[depth, experts]`` and the first layer's ``first_block``."""

    def __init__(self, m, key):
        self.n = 0
        self.grad_norms = self.delta_norms = self.state = None
        self._key = key

        def norms(tree):
            return weights_sdar.leaf_norms(m, weights_sdar.from_program_tree(m, tree))

        self._grad = jax.jit(lambda mu: norms(jax.tree.map(lambda a: a / (1.0 - ADAM_B1), mu)))
        self._delta = jax.jit(lambda p, key: norms(jax.tree.map(
            jnp.subtract, p, weights_sdar.program_tree(m, key))))
        self._state = jax.jit(lambda counters: (
            jnp.stack([counters[f"blocks_{i}"]["moe"]["moe_tokens"] for i in range(m["depth"])]),
            counters["blocks_0"]["attn"]["first_block"] + 0.0))

    def after_step(self, params, opt_state, nt):
        super().after_step(params, opt_state)
        if self.n == 1:
            self.state = self._state(nt["counters"])

    def readings(self):
        grad_norms, delta_norms = super().readings()
        counts, first_block = jax.device_get(self.state)
        return {"grad_norms": grad_norms, "delta_norms": delta_norms, "counts": counts,
                "first_block": first_block}


class ProbedMeshTrainer(MeshTrainer):
    """``drivers/train.py``'s, whose probes see the model's state as well:
    what ``engine.run_step`` returns of the counters is the step's own."""

    probes = None

    def _build_engine(self):
        engine, to_engine, from_engine = super()._build_engine()
        inner, seen = engine.run_step, self.probes

        def run_step(params, nt, opt_state, batch):
            out = inner(params, nt, opt_state, batch)
            seen.after_step(out[0], out[2], out[1])
            return out

        engine.run_step = run_step
        return engine, to_engine, from_engine


def build_spec(m, job, key):
    """The program's model, initialised with the benchmark's weights, its
    counters at nought and its noise's key the seed's."""
    spec = program_lm(m, attn_impl=job["attn_impl"], fused_ce=job["fused_ce"],
                      ce_chunk=job["ce_chunk"], remat=job["remat"])
    make = jax.jit(lambda k: (weights_sdar.program_tree(m, k),
                              weights_sdar.counters_tree(m, k)))
    return dataclasses.replace(spec, init=lambda _rng: make(key))


def epoch_masked(history, first: int, count: int | None = None):
    """Positions masked in the epochs ``first .. first + count - 1`` (to the
    last one without ``count``), from the ``bd_masked_tokens`` counter."""
    got = [r["counters"]["bd_masked_tokens"] for r in history
           if "bd_masked_tokens" in r.get("counters", {}) and r["epoch"] >= first
           and (count is None or r["epoch"] < first + count)]
    return int(np.sum(got)) if got else None


def print_epochs(m, records):
    """Standard error gets, an epoch, its seconds, each layer's share of
    (position, expert) pairs sent to held experts and the positions masked: a
    step's time follows both."""
    first, count = weights_sdar.held(m)
    wall = {r["epoch"]: r["wall_time"] for r in records if "wall_time" in r}
    for r in records:
        if "counters" in r:
            t = epoch_tokens([r], r["epoch"], 1).astype(np.float64)
            held = t[:, first:first + count].sum(1) / np.maximum(t.sum(1), 1.0)
            print(f"bd epoch {r['epoch']}: {wall.get(r['epoch'], float('nan')):.4f} s, held "
                  f"share by layer {[round(float(v), 4) for v in held]}, mean "
                  f"{float(held.mean()):.4f}, masked {epoch_masked([r], r['epoch'], 1)}",
                  file=sys.stderr)


def print_readings(m, program, ref):
    """Standard error gets one line of JSON with the readings ``bd_checks``
    compares by leaf and by expert, the program's beside the reference's: the
    first gradient's norms, the change's, the pair counters: what a limit is
    chosen from."""
    lists = lambda norms: {k: np.asarray(v, np.float64).round(9).tolist() for k, v in norms.items()}
    side = lambda a, counts: {
        "losses": a["losses"], "grad_norms": lists(a["grad_norms"]),
        "delta_norms": lists(a["delta_norms"]), "counts": np.asarray(counts).tolist()}
    print("bd readings " + json.dumps({
        "program": side(program, program["counts"]),
        "reference": side(ref, route_counts(m, ref["routes"]))}), file=sys.stderr)


def drive(loaded, seed: int, seconds: float, trace: bool, devices, t0: float) -> dict:
    """Run the cell's window; returns the facts the metrics and checks read."""
    m, job = loaded["config"]["model"], loaded["traffic"]
    chips = loaded["cell"]["chips"]
    # first of all, and before anything is made or compiled: a program without
    # the block-diffusion block stops here, at once
    program_lm(m, fused_ce=True)
    key = weights_sdar.seed_key(seed)
    x, y = token_pool(m, job, seed)
    tracer = Tracer(trace, epochs=(job["trace_from_epoch"], job["trace_epochs"]))
    compiles = CompileCounter()
    ds = WindowedRows(x, y, job, seconds, tracer)
    probes = BDStateProbes(m, key)
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
    facts = {
        "moe": {"window_tokens": epoch_tokens(history.records, 1),
                "slice_tokens": epoch_tokens(history.records, *sliced),
                "slice_steps": job["trace_epochs"] * job["steps_per_epoch"]},
        "bd": {"window_masked": epoch_masked(history.records, 1),
               "slice_masked": epoch_masked(history.records, *sliced),
               "warmup_masked": epoch_masked(history.records, 0, 1)},
    }
    del trainer, probes
    gc.collect()
    batch = job["batch_size"]
    first = [(x[i * batch:(i + 1) * batch], y[i * batch:(i + 1) * batch])
             for i in range(PROBE_STEPS)]
    t_ref = time.perf_counter()
    stats = devices[0].memory_stats() or {}
    print(f"memory before the reference: {stats.get('bytes_in_use', 0)} bytes in use of "
          f"{stats.get('bytes_limit', 0)}, peak {peak}", file=sys.stderr)
    ref = reference_sdar.train_steps(m, seed, first, job["learning_rate"],
                                     rows_per_block=job["reference_rows_per_block"],
                                     queries_per_block=job["reference_queries_per_block"])
    print(f"the reference after the window took {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    print_readings(m, program, ref)
    finite = all(np.isfinite(v) for v in losses)
    tokens = window_steps * batch * job["seq_len"]
    return {
        "checks": bd_checks(m, program, ref, job["limits"]),
        "attempted": len(losses), "failed": 0 if finite else len(losses),
        "window": {"seconds": window_s, "steps": window_steps, "tokens": tokens,
                   "paused_for_profiler_s": ds.paused},
        "end_to_end": {"train_tokens_per_s": tokens / window_s, "setup_s": ds.t_open - t0},
        "memory_peak_bytes": peak,
        "compiles_in_window": compiles.between(ds.t_open, ds.t_close),
        "trace_dir": tracer.directory if trace else None,
        "trace_slice_s": tracer.slice_s,
        **facts,
    }


def route_counts(m, routes):
    """(position, expert) pairs by layer and expert ``[depth, experts]`` of
    chosen experts ``[depth, rows, 2 L, k]``: what the program's counters hold
    after the step that chose them."""
    return np.stack([np.bincount(np.asarray(layer).ravel(), minlength=m["experts"])
                     for layer in routes])


def bd_checks(m, program: dict, ref: dict, limits: dict) -> dict:
    """The three losses and ``delta_norm_gap`` as ``checks.train`` has them;
    ``grad_norm_gap`` over the leaves no route decides (every leaf but the
    held experts' matrices and the routers'); ``expert_grad_gap``, the MEDIAN
    gap of those; ``route_count_gap``, half the distance between the
    program's pair counters after step one and the reference's routes
    counted, over a layer's pairs, worst layer (the share of pairs counted at
    another expert); ``own_block_gap``, the largest difference at the first
    row's first noised block over the reference's largest value there.
    ``program`` holds the probes' readings and the losses, ``ref`` is
    ``reference_sdar.train_steps``'s."""
    out = checks.train(program, ref, limits)
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
    got, want = (np.asarray(a["first_block"], np.float64) for a in (program, ref))
    out["own_block_gap"] = {"value": float(np.abs(got - want).max() / np.abs(want).max()),
                            "limit": limits["own_block_gap"]}
    return out
