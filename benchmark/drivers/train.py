"""The train driver: one ``MeshTrainer.train`` call, its first epoch warm-up.

The window is the run of epochs after the first, all inside the one
``train()`` call on the one compiled step and its state. The benchmark's
``Dataset`` makes the rows from the seed, notes the clock whenever the trainer
asks for an epoch's batches (the trainer has just fetched the last loss, so
the device is drained), and hands out none once the window's seconds are up.

What ``correct`` compares comes from that same call: the losses of the first
three steps from the trainer's history, and two readings of the state those
steps leave, taken where ``engine.run_step`` returns it: per-leaf norms of the
first gradient (Adam's first moment after step one, over 1 - b1) and of the
parameters' change after step three (what step four is given).
"""

from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.data import Dataset
from distkeras_tpu.trainers import MeshTrainer

from benchmark import checks, reference, weights
from benchmark.harness import CompileCounter, Tracer, memory_peak_bytes, program_lm

PROBE_STEPS = 3
POOL_BATCHES = 16      # distinct seeded batches; the window cycles through them
MIN_STEP_S = 0.005     # no step is shorter: bounds the epochs train() is asked for


def token_pool(m, job, seed: int):
    """``POOL_BATCHES`` batches of distinct seeded rows, features and labels
    shifted by one. The window cycles through the pool."""
    rng = np.random.default_rng([int(seed), 0x7261696E])
    rows = POOL_BATCHES * job["batch_size"]
    toks = rng.integers(0, m["vocab"], size=(rows, job["seq_len"] + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


class WindowedRows(Dataset):
    """Epoch 0 is warm-up; epochs from 1 on are the window until it is up."""

    def __init__(self, x, y, job, seconds, tracer):
        super().__init__({"features": x, "label": y})
        self.job, self.seconds, self.tracer = job, float(seconds), tracer
        self.epoch = self.cursor = self.window_steps = 0
        self.t_open = self.t_close = None
        self.paused = 0.0                 # seconds the profiler took to start and stop

    def batches(self, batch_size, columns, *, seed=None, drop_remainder=True):
        now = time.perf_counter()
        epoch, self.epoch = self.epoch, self.epoch + 1
        if self.t_close is not None:
            return iter(())               # closed: every later epoch is empty
        if epoch == 0:
            n = self.job["warmup_steps"]
        else:
            if self.t_open is None:
                self.t_open = now
            if now - self.t_open - self.paused >= self.seconds:
                self.t_close = now
                self.tracer.stop()
                return iter(())
            self.paused += self.tracer.at_epoch(epoch)
            n = self.job["steps_per_epoch"]
            self.window_steps += n
        cols = [self[c] for c in columns]
        first, self.cursor = self.cursor, self.cursor + n
        pool = len(self) // batch_size

        def rows():
            for s in range(first, first + n):
                lo = (s % pool) * batch_size
                yield tuple(c[lo:lo + batch_size] for c in cols)

        return rows()


class ProbedMeshTrainer(MeshTrainer):
    """``MeshTrainer`` whose engine's ``run_step`` is watched: after step 1
    and step ``PROBE_STEPS`` a jitted reduction of the returned state is
    queued behind the step (small vectors; nothing is copied or kept)."""

    probes = None

    def _build_engine(self):
        engine, to_engine, from_engine = super()._build_engine()
        inner, seen = engine.run_step, self.probes

        def run_step(params, nt, opt_state, batch):
            out = inner(params, nt, opt_state, batch)
            seen.after_step(out[0], out[2])
            return out

        engine.run_step = run_step
        return engine, to_engine, from_engine


class StateProbes:
    """Per-leaf norms of the first gradient and of the parameters' change."""

    def __init__(self, m, key):
        self.n = 0
        self.grad_norms = self.delta_norms = None
        self._key = key

        def norms(tree):
            return weights.leaf_norms(m, weights.from_program_tree(tree, m["depth"]))

        self._grad = jax.jit(lambda mu: norms(
            jax.tree.map(lambda a: a / (1.0 - reference.ADAM_B1), mu)))
        # the change is taken against weights made again from the key, inside
        # the jit: no copy of the initial weights is held through the steps
        self._delta = jax.jit(lambda p, key: norms(jax.tree.map(
            jnp.subtract, p, weights.program_tree(m, key, "float32"))))

    def after_step(self, params, opt_state):
        self.n += 1
        if self.n == 1:
            mu = next(s.mu for s in jax.tree.leaves(
                opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu"))
            self.grad_norms = self._grad(mu)
        if self.n == PROBE_STEPS:
            self.delta_norms = self._delta(params, self._key)

    def readings(self):
        if self.grad_norms is None or self.delta_norms is None:
            raise RuntimeError(f"the probes saw {self.n} steps; the trainer's "
                               f"engine.run_step was not driven")
        return jax.device_get((self.grad_norms, self.delta_norms))


def build_spec(m, job, key):
    """The program's model at the configuration's sizes, initialised with the
    benchmark's weights (one jitted call on the device from the seed's key)."""
    spec = program_lm(m, attn_impl=job["attn_impl"], fused_ce=job["fused_ce"],
                      ce_chunk=job["ce_chunk"], remat=job["remat"])
    make = jax.jit(lambda k: weights.program_tree(m, k, "float32"))
    return dataclasses.replace(spec, init=lambda _rng: (make(key), {}))


def drive(loaded, seed: int, seconds: float, trace: bool, devices, t0: float) -> dict:
    """Run the cell's window; returns the facts the metrics and checks read."""
    m, job = loaded["config"]["model"], loaded["traffic"]
    chips = loaded["cell"]["chips"]
    key = weights.seed_key(seed)
    x, y = token_pool(m, job, seed)
    tracer = Tracer(trace, epochs=(job["trace_from_epoch"], job["trace_epochs"]))
    compiles = CompileCounter()
    ds = WindowedRows(x, y, job, seconds, tracer)
    probes = StateProbes(m, key)
    trainer = ProbedMeshTrainer(
        build_spec(m, job, key), loss="sparse_softmax_cross_entropy",
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
    losses = [float(v) for v in trainer.get_history().losses()]
    grad_norms, delta_norms = probes.readings()
    window_steps = ds.window_steps
    window_s = ds.t_close - ds.t_open - ds.paused
    peak = memory_peak_bytes(devices[:chips])
    del trainer, probes
    gc.collect()
    batch = job["batch_size"]
    first = [(x[i * batch:(i + 1) * batch], y[i * batch:(i + 1) * batch])
             for i in range(PROBE_STEPS)]
    ref = reference.train_steps(m, seed, first, job["learning_rate"],
                                rows_per_block=job["reference_rows_per_block"])
    program = {"losses": losses[:PROBE_STEPS], "grad_norms": grad_norms,
               "delta_norms": delta_norms}
    finite = all(np.isfinite(v) for v in losses)
    return {
        "checks": checks.train(program, ref, job["limits"]),
        "attempted": len(losses), "failed": 0 if finite else len(losses),
        "window": {"seconds": window_s, "steps": window_steps,
                   "tokens": window_steps * batch * job["seq_len"],
                   "paused_for_profiler_s": ds.paused},
        "end_to_end": {"train_tokens_per_s": window_steps * batch * job["seq_len"] / window_s,
                       "setup_s": ds.t_open - t0},
        "memory_peak_bytes": peak,
        "compiles_in_window": compiles.between(ds.t_open, ds.t_close),
        "trace_dir": tracer.directory if trace else None,
        "trace_slice_s": tracer.slice_s,
    }
