"""Trainer hierarchy — the reference's user-facing API, TPU-native underneath.

Parity surface (reference ``distkeras/trainers.py``): ``Trainer``,
``SingleTrainer``, ``DistributedTrainer``, ``AsynchronousDistributedTrainer``,
and the five algorithms ``ADAG, DOWNPOUR, AEASGD, EAMSGD, DynSGD`` with their
constructor kwargs (``num_workers, batch_size, features_col, label_col,
num_epoch, communication_window, rho, momentum, learning_rate`` — SURVEY.md
§5.6) and ``train(dataset, shuffle=False) -> trained model``.

What changed underneath (north_star): instead of shipping a pickled worker
closure to Spark executors and exchanging weights with a driver-hosted socket
PS, ``train`` builds a :class:`~distkeras_tpu.parallel.LocalSGDEngine` over a
device mesh and runs jitted communication windows whose merge rules ARE the
parameter exchange (XLA collectives over ICI). Two backends:

- ``backend="collective"`` (default): deterministic lockstep local-SGD — the
  fast path on a TPU slice.
- ``backend="ps"``: genuinely asynchronous host-threaded workers against an
  in-process (or TCP) parameter server — preserves the reference's async
  semantics, and is the path that generalizes to PS-over-DCN across slices
  (``distkeras_tpu.parameter_servers``).

Models may be Keras 3 models (the reference contract — trained weights are
written back into the model you passed) or native
:class:`~distkeras_tpu.model.ModelSpec` objects (zero-overhead path).
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distkeras_tpu import utils
from distkeras_tpu.data import Dataset, padded_chunks, prefetch_to_device
from distkeras_tpu.model import ModelSpec, from_keras, keras_weights_to_model
from distkeras_tpu.observability import programs as _programs
from distkeras_tpu.observability import trace as _trace
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.parallel.local_sgd import LocalSGDEngine
from distkeras_tpu.parallel.merge_rules import (
    ADAGMerge,
    DownpourMerge,
    DynSGDMerge,
    ElasticAverageMerge,
    MergeRule,
)
from distkeras_tpu.parallel.mesh import get_mesh, put_global


def _with_clipping(base, clipnorm, clipvalue):
    """Chain Keras-style gradient clipping in front of an optax transform.

    Parity: the reference's ``worker_optimizer`` was a Keras 1.x optimizer,
    whose constructors accepted ``clipnorm``/``clipvalue``. ``clipvalue``
    keeps Keras's elementwise semantics (``optax.clip``); ``clipnorm`` is
    lowered to GLOBAL-norm clipping (``optax.clip_by_global_norm``) — the
    modern form (one fused norm over the whole gradient pytree, a single
    scalar on TPU) rather than Keras 1.x's per-tensor norms.
    """
    pre = []
    if clipnorm is not None:
        pre.append(optax.clip_by_global_norm(float(clipnorm)))
    if clipvalue is not None:
        pre.append(optax.clip(float(clipvalue)))
    return optax.chain(*pre, base) if pre else base


def resolve_optimizer(worker_optimizer, learning_rate: float,
                      momentum: float = 0.0, nesterov: bool = False,
                      clipnorm=None, clipvalue=None):
    """Map the reference's Keras optimizer names onto optax transforms."""
    if isinstance(worker_optimizer, optax.GradientTransformation):
        return _with_clipping(worker_optimizer, clipnorm, clipvalue)
    name = str(worker_optimizer).lower()
    if name == "sgd":
        base = (
            optax.sgd(learning_rate, momentum=momentum, nesterov=nesterov)
            if momentum else optax.sgd(learning_rate)
        )
    elif name == "adam":
        base = optax.adam(learning_rate)
    elif name == "fused_adam":
        from distkeras_tpu.ops.pallas_kernels import fused_adam

        base = fused_adam(learning_rate)
    elif name == "adagrad":
        base = optax.adagrad(learning_rate)
    elif name == "rmsprop":
        base = optax.rmsprop(learning_rate)
    elif name == "adadelta":
        base = optax.adadelta(learning_rate)
    elif name == "adamw":
        base = optax.adamw(learning_rate)
    elif name == "adamax":
        base = optax.adamax(learning_rate)
    elif name == "nadam":
        base = optax.nadam(learning_rate)
    else:
        raise ValueError(f"unknown worker_optimizer {worker_optimizer!r}")
    return _with_clipping(base, clipnorm, clipvalue)


def _reject_worker_axis_model(spec, where: str) -> None:
    """Engines without the stacked-worker vmap axis must refuse models whose
    training-mode apply runs collectives over it (sync BatchNorm) — a clear
    error instead of JAX's 'unbound axis name' trace failure."""
    if getattr(spec, "requires_worker_axis", False):
        raise ValueError(
            f"model '{spec.name}' runs collectives over the stacked-worker "
            f"axis (e.g. sync_bn=True) and cannot train on {where}; use the "
            f"collective backend of the six distributed trainers, or a "
            f"per-worker variant of the model"
        )


def _as_cols(features_col) -> list[str]:
    """Coerce a feature-column name or list of names to a list."""
    return (
        [features_col] if isinstance(features_col, str) else list(features_col)
    )


def _make_loss_step(spec: ModelSpec, loss_fn: Callable, n_feat: int,
                    loss_name=None):
    """Build ``loss_step(params, nt, batch)`` for a batch laid out as
    ``(*features, label)`` — shared by all training engines.

    When the spec carries a fused implementation for this loss name
    (``ModelSpec.fused_losses``), the step routes through it instead of
    ``loss(y, apply(x))`` — the model computes its own loss without
    materializing the full output (e.g. the chunked large-vocab
    cross-entropy of ``transformer_lm(fused_ce=True)``)."""
    fused = (spec.fused_losses or {}).get(loss_name)
    if fused is not None:
        def fused_step(params, nt, batch):
            feats, y = batch[:n_feat], batch[n_feat]
            x = feats[0] if n_feat == 1 else tuple(feats)
            return fused(params, nt, x, y, training=True)

        return fused_step

    def loss_step(params, nt, batch):
        feats, y = batch[:n_feat], batch[n_feat]
        x = feats[0] if n_feat == 1 else tuple(feats)
        out, new_nt = spec.apply(params, nt, x, training=True)
        return loss_fn(y, out), new_nt

    return loss_step


def _fits_device_budget(ds: Dataset, cols, budget_bytes: int) -> bool:
    """One accounting rule for the auto resident-vs-stream input decision,
    shared by DistributedTrainer and MeshTrainer."""
    row_bytes = sum(
        int(np.prod(ds[c].shape[1:])) * ds[c].dtype.itemsize for c in cols
    )
    return len(ds) * row_bytes <= budget_bytes


def _bcast_host_port(host: str, port: int) -> tuple[str, int]:
    """Broadcast process 0's PS address to every controller (fixed-size
    uint8 buffer over the jax.distributed collective fabric)."""
    from jax.experimental import multihost_utils

    buf = np.zeros(256, np.uint8)
    b = (host or "").encode()
    if len(b) > buf.size:
        raise ValueError(f"host address too long to broadcast: {host!r}")
    buf[:len(b)] = np.frombuffer(b, np.uint8)
    buf = np.asarray(multihost_utils.broadcast_one_to_all(buf))
    port = int(np.asarray(
        multihost_utils.broadcast_one_to_all(np.asarray([port], np.int32))
    )[0])
    return bytes(buf).rstrip(b"\x00").decode(), port


def _validate_ema_decay(ema_decay):
    """Shared range check for the trainers' ``ema_decay`` kwarg."""
    if ema_decay is None:
        return None
    ema_decay = float(ema_decay)
    if not 0.0 <= ema_decay < 1.0:
        raise ValueError(f"ema_decay must be in [0, 1), got {ema_decay}")
    return ema_decay


def _ema_tracking(center_like, decay, use_resident):
    """Build the per-step EMA carry for a streaming training loop.

    Returns ``(use_resident, ema, ema_step)``: the resident input mode is
    overridden (with a warning) because EMA folds in every intermediate
    center, which a whole-epoch-in-one-dispatch path never materializes.
    ``ema`` is a jitted COPY of ``center_like`` (the engines donate their
    state buffers, so the EMA needs its own), in the same layout/sharding.
    """
    if use_resident:
        import warnings

        warnings.warn(
            "ema_decay tracks the center per step/window, which needs the "
            "streaming input path; overriding the resident input mode for "
            "this run",
            stacklevel=3,
        )
        use_resident = False
    d = decay
    ema_step = jax.jit(
        lambda e, c: jax.tree.map(lambda a, b: d * a + (1.0 - d) * b, e, c),
        donate_argnums=(0,),
    )
    ema = jax.jit(lambda c: jax.tree.map(jnp.copy, c))(center_like)
    return use_resident, ema, ema_step


def _drain(x):
    """Synchronize for TIMING: host-fetch a compute-dependent value.

    JAX dispatch is asynchronous, so an epoch's wall time is only honest
    when the clock stops after its work has finished. Fetching a program
    output to the host is that sync point: the value exists only once the
    dispatch has drained, so the per-epoch metrics cover the compute they
    name — at the cost of one small transfer per epoch, and only on the
    ``log_metrics`` paths.
    """
    jax.block_until_ready(x)
    jax.tree.map(np.asarray, x)


def _phase(name, **args):
    """A span of ``MeshTrainer.train`` crossed once a run or once an epoch:
    kept in the run log, tracing on or off, and an annotation in a profiler
    slice (``observability.trace``). It adds no synchronisation."""
    return _trace.span(name, cat="train", args=args or None, profile=True,
                       log=True)


def _counters(nt):
    """What a model's training steps have added so far to the int32 leaves of
    its state's ``counters`` collection, by path (``{"blocks_0/moe/moe_tokens":
    int64 array, ...}``), or ``None`` for a model that counts nothing. The
    device's int32 is read as unsigned, so a difference of two readings is
    right modulo 2**32."""
    from flax.traverse_util import flatten_dict

    tree = nt.get("counters") if isinstance(nt, dict) else None
    counts = {path: leaf for path, leaf in flatten_dict(tree or {}, sep="/").items()
              if leaf.dtype == np.int32}
    if not counts:
        return None
    return {path: np.asarray(leaf).view(np.uint32).astype(np.int64)
            for path, leaf in jax.device_get(counts).items()}


def _profile_trace_ctx(profile_dir):
    """``jax.profiler.trace`` context for a training run (or a no-op).

    Under multi-process ``jax.distributed`` each controller traces into its
    own ``process{i}/`` subdirectory: jax profiler traces are per-process,
    and two controllers on one host writing the same directory would
    interleave their session files.
    """
    if not profile_dir:
        return contextlib.nullcontext()
    return jax.profiler.trace(_profile_path(profile_dir))


def _profile_path(profile_dir) -> str:
    path = str(profile_dir)
    if jax.process_count() > 1:
        path = os.path.join(path, f"process{jax.process_index()}")
    return path


class _Validator:
    """Per-epoch held-out evaluation (beyond-reference; the reference only
    ever evaluated after training, via ``evaluators.py`` — SURVEY.md §2b #17).

    Keras-style ``validation_data``: after each epoch the center/global
    parameters are scored on a held-out ``Dataset``. Evaluation is one jitted
    masked apply per fixed-size chunk (same static-shape padding scheme as
    ``ModelPredictor``): the pad rows carry mask 0, so the reported
    ``val_loss`` is the exact mean over real rows for every NAMED loss (all
    of ``ops.losses`` is mean-reduced). A custom callable loss is scored as
    the mean of its single-row values — for a non-mean-reduced or
    batch-coupled callable that is a different scale than the training
    loss, so prefer named losses when comparing the two curves.
    ``val_accuracy`` is
    reported when the label column is integer-typed and the model emits a
    trailing class dimension (argmax classification).
    """

    def __init__(self, spec: ModelSpec, loss_fn: Callable, ds: Dataset,
                 features_col: list[str], label_col: str, batch_size: int,
                 mesh=None, fused_loss=None):
        if len(ds) == 0:
            raise ValueError("validation_data has 0 rows")
        if fused_loss is not None and len(features_col) != 1:
            raise ValueError(
                "fused-loss validation supports a single features column"
            )
        self.ds = ds
        self.mesh = mesh
        self.cols = list(features_col) + [label_col]
        self.bs = int(batch_size)
        n_feat = len(features_col)
        label_integer = np.issubdtype(
            np.asarray(ds[label_col][:1]).dtype, np.integer
        )

        def eval_batch(params, nt, arrs, mask):
            feats, y = arrs[:n_feat], arrs[n_feat]
            x = feats[0] if n_feat == 1 else tuple(feats)
            if fused_loss is not None:
                # a model with a fused loss (transformer_lm(fused_ce=True))
                # must not materialize its full output at eval either: one
                # fused call over the whole chunk with the row mask (pad
                # rows excluded inside the op, so peak memory stays at the
                # op's own chunk·V ceiling). Rows share the static L, so
                # the masked token mean × real-row count equals the sum of
                # per-row means the plain path accumulates. Accuracy stays
                # undefined exactly as for per-token labels below.
                loss = fused_loss(params, nt, x, y, training=False,
                                  mask=mask)[0]
                return loss * jnp.sum(mask), jnp.full((), -1.0)
            out, _ = spec.apply(params, nt, x, training=False)
            # loss_fn is mean-reduced; vmap over single-row slices recovers
            # per-row losses for any named loss, so pad rows mask out exactly
            per_row = jax.vmap(
                lambda yy, oo: loss_fn(yy[None], oo[None])
            )(y, out)
            loss_sum = jnp.sum(per_row * mask)
            # Accuracy only for one-label-per-row classification (y rank 1,
            # out [bs, C]) — per-token labels get val_loss only (the [bs]
            # row mask can't weight a token axis).
            if (label_integer and y.ndim == 1 and out.ndim == 2
                    and out.shape[-1] >= 2):
                pred = jnp.argmax(out, axis=-1).astype(y.dtype)
                correct = jnp.sum((pred == y).astype(jnp.float32) * mask)
            else:
                correct = jnp.full((), -1.0)  # sentinel: accuracy undefined
            return loss_sum, correct

        self._eval = jax.jit(eval_batch)

    def __call__(self, params, nt) -> dict:
        n = len(self.ds)
        cols = [np.asarray(self.ds[c]) for c in self.cols]
        # Multi-controller SPMD: when the params being scored span devices
        # this process cannot address, the jitted eval is a GLOBAL program —
        # host batches must enter as global (replicated) arrays, and every
        # controller runs the same chunk loop in lockstep (the framework's
        # standard multi-host data plane; see parallel.mesh.put_global).
        # Host-resident params (e.g. a gathered pipeline layout) keep the
        # plain process-local eval.
        rep = None
        if self.mesh is not None and jax.process_count() > 1 and any(
            isinstance(l, jax.Array) and not l.is_fully_addressable
            for l in jax.tree.leaves((params, nt))
        ):
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(self.mesh, PartitionSpec())
        loss_sum, correct_sum, acc_defined = 0.0, 0.0, True
        for chunk, real in padded_chunks(cols, self.bs):
            mask = np.zeros(self.bs, np.float32)
            mask[:real] = 1.0
            if rep is not None:
                chunk = [put_global(c, rep) for c in chunk]
                mask = put_global(mask, rep)
            ls, cs = self._eval(params, nt, tuple(chunk), mask)
            loss_sum += float(ls)
            cs = float(cs)
            if cs < 0:
                acc_defined = False
            else:
                correct_sum += cs
        rec = {"val_loss": loss_sum / n}
        if acc_defined:
            rec["val_accuracy"] = correct_sum / n
        return rec


def _as_spec(model) -> tuple[ModelSpec, Any]:
    """Accept a Keras model or a ModelSpec; return (spec, keras_model|None)."""
    if isinstance(model, ModelSpec):
        return model, None
    if hasattr(model, "stateless_call"):
        return from_keras(model), model
    raise TypeError(
        f"model must be a Keras 3 model or a distkeras_tpu ModelSpec, got "
        f"{type(model)}"
    )


class Trainer:
    """Abstract base trainer.

    Parity: reference ``distkeras/trainers.py :: Trainer`` —
    ``__init__(keras_model, loss, worker_optimizer)``, ``train()``,
    ``record_training_start/end``, ``get_training_time``, ``get_history``.
    """

    def __init__(self, keras_model, loss="mse", worker_optimizer="sgd",
                 learning_rate: float = 0.01, seed: int = 0,
                 clipnorm=None, clipvalue=None):
        self.spec, self.keras_model = _as_spec(keras_model)
        self.loss = loss
        self.loss_fn = get_loss(loss)
        self.worker_optimizer = worker_optimizer
        self.learning_rate = learning_rate
        # Keras-optimizer parity: the reference's worker_optimizer was a
        # Keras 1.x optimizer carrying clipnorm/clipvalue — see
        # _with_clipping for the TPU lowering.
        self.clipnorm = clipnorm
        self.clipvalue = clipvalue
        self.seed = seed
        self.history = utils.History()
        self.timer = utils.Timer()
        self.trained_params_ = None
        self.trained_nt_ = None
        self.log_metrics = False
        self.metrics_: list[dict] = []

    #: checkpoint defaults shared by the subclasses that expose the kwargs
    checkpoint_async = False
    _async_ckpt = None

    def _dispatch_checkpoint(self, payload, epoch: int):
        """One place for the async-or-sync checkpoint write (shared by the
        collective and GSPMD trainers)."""
        from distkeras_tpu import checkpoint as ckpt

        if self.checkpoint_async:
            if self._async_ckpt is None:
                self._async_ckpt = ckpt.AsyncCheckpointer()
            self._async_ckpt.save(self.checkpoint_dir, payload, step=epoch)
        else:
            ckpt.save_checkpoint(self.checkpoint_dir, payload, step=epoch)

    def _finish_checkpoints(self):
        """Join any in-flight async save (re-raising its failure) — runs in
        a ``finally`` so an aborted run never silently drops or kills a
        checkpoint mid-write."""
        if self._async_ckpt is not None:
            self._async_ckpt.wait()

    # -- parity bookkeeping API ------------------------------------------

    def record_training_start(self):
        self.timer.start()

    def record_training_end(self):
        self.timer.stop()

    def get_training_time(self) -> float:
        return self.timer.elapsed()

    def get_history(self):
        return self.history

    def get_averaged_loss(self, last: int = 50) -> float:
        losses = [float(l) for l in self.history.losses()[-last:]]
        return float(np.mean(losses)) if losses else float("nan")

    def _epoch_metrics(self, epoch: int | None, rows: int, updates: int,
                       elapsed: float, label: str = "epoch"):
        """Record + optionally stream throughput (per epoch, or whole-run
        with ``epoch=None`` for the free-running PS backend)."""
        rec = {
            "samples_per_sec": round(rows / elapsed, 1),
            "updates_per_sec": round(updates / elapsed, 2),
            "wall_time": round(elapsed, 4),
        }
        if epoch is not None:
            rec = {"epoch": epoch, **rec}
        self.metrics_.append(rec)
        self.history.append(**rec)
        if self.log_metrics:
            print(json.dumps({"metric": label, **rec}), flush=True)

    def _make_validator(self):
        """Build the validation_data evaluator (or None) — fail-fast: called
        before training starts on every backend."""
        if getattr(self, "validation_data", None) is None:
            return None
        return _Validator(
            self.spec, self.loss_fn,
            self._coerce_dataset(self.validation_data),
            self.features_col, self.label_col, self.batch_size,
            mesh=getattr(self, "mesh", None),
            fused_loss=(self.spec.fused_losses or {}).get(self.loss),
        )

    def _validate_epoch(self, validator, params, nt, epoch):
        """Score held-out data and record/stream the result (beyond-reference
        Keras-style validation; see _Validator)."""
        rec = validator(params, nt)
        rec = {"epoch": epoch, **rec} if epoch is not None else dict(rec)
        self.metrics_.append(rec)
        self.history.append(**rec)
        if self.log_metrics:
            print(json.dumps({"metric": "validation", **rec}), flush=True)

    def _materialize_history(self):
        """Pull device loss scalars to host and expand per-epoch loss arrays
        into one record per window (the reference's per-window history)."""
        expanded = []
        for rec in self.history.records:
            if "losses" in rec:
                arr = np.asarray(jax.device_get(rec["losses"]))
                expanded.extend(
                    {"loss": float(v), "epoch": rec.get("epoch")} for v in arr
                )
            elif "loss" in rec:
                rec["loss"] = float(jax.device_get(rec["loss"]))
                expanded.append(rec)
            else:
                expanded.append(rec)
        self.history.records = expanded

    # -- core -------------------------------------------------------------

    def train(self, dataset, shuffle: bool = False):
        raise NotImplementedError

    def _coerce_dataset(self, dataset) -> Dataset:
        if isinstance(dataset, Dataset):
            return dataset
        if isinstance(dataset, tuple) and len(dataset) == 2:
            return Dataset.from_arrays(*dataset)
        raise TypeError(f"expected Dataset or (features, labels), got {type(dataset)}")

    def _finalize(self, params, nt):
        self.trained_params_ = params
        self.trained_nt_ = nt
        if self.keras_model is not None:
            keras_weights_to_model(self.keras_model, params, nt)
            return self.keras_model
        return params


class DistributedTrainer(Trainer):
    """Shared machinery for all mesh-distributed trainers.

    Parity: reference ``distkeras/trainers.py :: DistributedTrainer`` (+
    ``AsynchronousDistributedTrainer``) — owns ``num_workers, batch_size,
    features_col, label_col, num_epoch, communication_window`` and the
    allocate-worker / allocate-parameter-server seams. Here the "parameter
    server" is a merge rule and the "worker placement" is mesh sharding.
    """

    #: subclasses override
    default_window = 1

    def __init__(self, keras_model, loss="mse", worker_optimizer="sgd",
                 learning_rate: float = 0.01,
                 num_workers: int | None = None, batch_size: int = 32,
                 features_col="features", label_col: str = "label",
                 num_epoch: int = 1, communication_window: int | None = None,
                 backend: str = "collective", mesh=None, seed: int = 0,
                 device_data: bool | None = None,
                 ps_transport: str = "inprocess", ps_port: int = 0,
                 ps_host: str | None = None, worker_id_offset: int = 0,
                 compression=None, pull_compression: str | None = None,
                 checkpoint_dir=None, checkpoint_every: int = 1,
                 resume: bool = False, checkpoint_async: bool = False,
                 profile_dir=None,
                 log_metrics: bool = False,
                 trace: bool = False,
                 trace_dir=None,
                 trace_sample: float = 1.0,
                 analyze: bool = False,
                 watch: bool = False,
                 watch_rules=None,
                 watch_dir=None,
                 watch_hook=None,
                 scrape_interval: float = 0.5,
                 tolerate_worker_failures: bool = False,
                 worker_restart_budget: int = 0,
                 worker_restart_delay: float = 0.0,
                 retry_policy=None,
                 heartbeat_interval: float | None = None,
                 lease_timeout: float | None = None,
                 fault_plan=None,
                 ps_wal_dir=None, ps_snapshot_every: int = 100,
                 ps_wal_group_window: int = 8,
                 ps_wal_group_interval: float = 0.25,
                 ps_standby: bool = False,
                 ps_failover_timeout: float | None = None,
                 ps_num_shards: int = 1,
                 ps_chain_length: int = 1,
                 ps_fused_exchange: bool = True,
                 ps_pipeline_depth: int = 0,
                 elastic: bool = False,
                 autoscale_target=None,
                 preempt_drain_timeout: float = 5.0,
                 max_pool_size: int | None = None,
                 directory: bool = False,
                 directory_standby: bool = True,
                 ps_directory=None,
                 deploy_streamer=None,
                 prefetch: int = 1, ema_decay: float | None = None,
                 clipnorm=None, clipvalue=None, validation_data=None):
        super().__init__(keras_model, loss, worker_optimizer,
                         learning_rate=learning_rate, seed=seed,
                         clipnorm=clipnorm, clipvalue=clipvalue)
        # Keras-style per-epoch validation (beyond-reference — SURVEY.md §5.5
        # build note): a held-out Dataset (or (X, y)) scored after each epoch
        # on the collective backend, and after the run on the free-running PS
        # backend; val_loss/val_accuracy land in the history + metrics stream.
        self.validation_data = validation_data
        self.mesh = mesh if mesh is not None else get_mesh(num_workers)
        self.num_workers = (
            int(num_workers) if num_workers is not None
            else int(np.prod(self.mesh.devices.shape))
        )
        self.batch_size = int(batch_size)
        self.features_col: list[str] = _as_cols(features_col)
        self.label_col = label_col
        self.num_epoch = int(num_epoch)
        self.communication_window = int(
            communication_window if communication_window is not None
            else self.default_window
        )
        if backend not in ("collective", "ps"):
            raise ValueError(f"backend must be 'collective' or 'ps', got {backend!r}")
        self.backend = backend
        # PS-backend options: in-process PS (single host, worker threads
        # call the center directly), a TCP socket PS (the DCN/multi-slice
        # story), the C++ native PS (same TCP story with a pickle-free
        # flat-f32 wire and a GIL-free fold — distkeras_tpu/native_ps.py),
        # or the shared-memory ring PS (``shm`` — zero-syscall mmap ring
        # pairs for the colocated regime, distkeras_tpu/shm.py, ISSUE 12).
        if ps_transport not in ("inprocess", "socket", "native", "shm"):
            raise ValueError(
                f"ps_transport must be 'inprocess', 'socket', 'native', "
                f"or 'shm', got {ps_transport!r}"
            )
        self.ps_transport = ps_transport
        self.ps_port = ps_port
        # ps_host points this trainer's workers at an EXTERNAL socket PS
        # (another process/host — the reference's driver-hosted PS serving
        # remote executors, reference ``distkeras/parameter_servers.py ::
        # SocketParameterServer``). The PS owner decides the global worker
        # count; worker_id_offset de-conflicts ids across trainer processes.
        if ps_host is not None and ps_transport not in ("socket", "native"):
            raise ValueError(
                "ps_host requires ps_transport='socket' or 'native' (an "
                "external PS is only reachable over TCP; "
                "ps_transport='shm' is colocated-only — its rings live in "
                "this host's /dev/shm, so point ps_host at a socket/native "
                "server instead)"
            )
        self.ps_host = ps_host
        self.worker_id_offset = int(worker_id_offset)
        # Lossy commit compression for the PS/DCN path ("int8" / "topk" /
        # a parallel.compression.Codec) with worker-side error feedback —
        # see parallel/compression.py. The collective backend's merges are
        # XLA psums over ICI, where compression has nothing to buy.
        if compression is not None:
            from distkeras_tpu.parallel.compression import (
                Int8Codec,
                resolve_codec,
            )

            codec = resolve_codec(compression)  # fail fast on bad values
            if backend != "ps":
                raise ValueError(
                    "compression applies to backend='ps' only (collective "
                    "merges ride ICI psums, not a wire)"
                )
            if ps_transport == "native" and type(codec) is not Int8Codec:
                raise ValueError(
                    "ps_transport='native' supports the stock "
                    "compression='int8' only (its C++ fold is that codec); "
                    "use 'socket' for other codecs"
                )
        self.compression = compression
        # Lossy PULL compression (the other wire direction): int8 block/
        # leaf quantization of the center with SERVER-side per-worker error
        # feedback (DoubleSqueeze-style bidirectional compression) — the
        # stream of decoded pulls telescopes to the true center stream.
        # With compression='int8' too, the PS round-trip moves ~2/8 of the
        # uncompressed bytes. Default None = exact f32 pulls.
        if pull_compression is not None:
            from distkeras_tpu.parallel.compression import (
                validate_pull_compression,
            )

            validate_pull_compression(pull_compression)
            if backend != "ps":
                raise ValueError(
                    "pull_compression applies to backend='ps' only "
                    "(collective merges ride ICI psums, not a wire)"
                )
        self.pull_compression = pull_compression
        # device_data=True stages each epoch in HBM and scans all windows in
        # one dispatch; None = auto (on when the epoch fits the budget).
        # NOTE on shuffle semantics: with shuffle=False the two paths are
        # bit-identical (tested). With shuffle=True they differ: the streaming
        # path reshuffles rows globally across workers each epoch and drops
        # the tail, while the resident path fixes worker shard assignment once
        # (like Spark partitions), shuffles within each shard on device, and
        # wrap-pads the tail so no row is permanently excluded. Auto mode
        # therefore picks between two valid but different shuffle regimes
        # based on dataset size; pass device_data explicitly if the exact
        # regime matters.
        self.device_data = device_data
        self.device_data_budget_bytes = 512 * 1024 * 1024
        # Streaming input pipeline depth (SURVEY.md §7.3 #4): superbatches
        # are assembled and placed on device `prefetch` windows ahead on a
        # background thread; 0 = plain synchronous feed. Bit-identical
        # either way (ordering preserved); resident mode makes it moot.
        # Default 1 (double buffering): hides the host prep while keeping
        # only ~2 extra placed superbatches resident — raise it only with
        # HBM headroom to spare.
        self.prefetch = int(prefetch)
        # Polyak/EMA averaging of the center (beyond-reference; the EASGD
        # paper itself evaluates the averaged center): per communication
        # window on the collective backend, per commit on the PS backend.
        # The averaged model lands in `ema_params_` next to the returned
        # (raw) center; EMA state is not checkpointed (resume restarts it
        # from the restored center).
        ema_decay = _validate_ema_decay(ema_decay)
        if ema_decay is not None:
            if backend == "ps" and ps_host is not None:
                raise ValueError(
                    "ema_decay with an external ps_host must be configured "
                    "on the PS owner's server (the center lives there)"
                )
        self.ema_decay = ema_decay
        self.ema_params_ = None
        # Checkpoint/resume (absent in the reference — SURVEY.md §5.4):
        # snapshot full TrainState every `checkpoint_every` epochs;
        # checkpoint_async=True writes on a background thread (the next
        # epoch's compute overlaps the device_get + serialize + write).
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = int(checkpoint_every)
        self.resume = bool(resume)
        self.checkpoint_async = bool(checkpoint_async)
        self._async_ckpt = None
        # Observability (SURVEY.md §5.1/§5.5 build notes — beyond-reference):
        # profile_dir writes a jax.profiler trace of the run; log_metrics
        # streams one JSON line per epoch (loss, samples/sec, updates/sec)
        # to stdout and records the same in the history.
        self.profile_dir = profile_dir
        self.log_metrics = bool(log_metrics)
        # Flight recorder (ISSUE 11, distkeras_tpu/observability): spans
        # across the worker window lifecycle, the PS fold/WAL/chain
        # paths, and elastic membership, stitched by correlation id into
        # one Perfetto-loadable timeline. trace=True enables recording;
        # trace_dir= also writes the Chrome trace JSON (path lands in
        # trace_path_); trace_sample keeps a deterministic fraction of
        # spans. PS backend only — the collective backend's device
        # timeline is profile_dir's job (jax.profiler).
        # analyze=True (ISSUE 14): run the post-hoc critical-path
        # analyzer over the recorded spans after the run — implies
        # trace=True (there is nothing to analyze without the flight
        # recorder); the report lands in analysis_. Strictly post-hoc:
        # the hot path pays only the tracing it already opted into.
        self.analyze = bool(analyze)
        self.trace = bool(trace) or trace_dir is not None or self.analyze
        self.trace_dir = trace_dir
        self.trace_sample = float(trace_sample)
        if self.trace and backend != "ps":
            raise ValueError(
                "trace/trace_dir/analyze apply to backend='ps' only "
                "(use profile_dir for the collective backend's XLA "
                "timeline)"
            )
        if not 0.0 < self.trace_sample <= 1.0:
            raise ValueError(
                f"trace_sample must be in (0, 1], got {trace_sample}"
            )
        self.trace_path_ = None
        self.analysis_ = None
        # The watchtower (ISSUE 13, distkeras_tpu/observability/watch):
        # continuous time-series telemetry + the SLO/anomaly watchdog.
        # watch=True runs the background scraper at scrape_interval
        # seconds over the PS stats surface / per-worker progress / the
        # loss curve, evaluating watch_rules (None = default_rules())
        # after every scrape; alert transitions land in watch_alerts_,
        # fire watch_hook, and ride the `metrics` wire action; watch_dir=
        # dumps series + ledger as one JSON (path in watch_path_). PS
        # backend only, like trace — the collective backend has no
        # server-side surface to scrape.
        self.watch = (bool(watch) or watch_dir is not None
                      or watch_rules is not None or watch_hook is not None)
        self.watch_rules = watch_rules
        self.watch_dir = watch_dir
        self.watch_hook = watch_hook
        self.scrape_interval = float(scrape_interval)
        if self.watch and backend != "ps":
            raise ValueError(
                "watch/watch_dir/watch_rules apply to backend='ps' only "
                "(the watchtower scrapes the PS stats surface; the "
                "collective backend exposes none)"
            )
        if watch_hook is not None and not callable(watch_hook):
            raise ValueError("watch_hook must be callable")
        if self.scrape_interval <= 0:
            raise ValueError(
                f"scrape_interval must be positive, got {scrape_interval}"
            )
        self.watch_alerts_ = None
        self.watch_path_ = None
        self.watchtower_ = None
        # Failure tolerance (beyond-reference, SURVEY.md §5.3 — the reference
        # delegated retry wholesale to Spark): on the PS backend, True lets
        # surviving hogwild workers finish the run when a peer dies (the run
        # still fails if every worker dies). The collective backend is one
        # SPMD program, so partial failure doesn't apply there.
        self.tolerate_worker_failures = bool(tolerate_worker_failures)
        # Resilience subsystem knobs (distkeras_tpu/resilience; PS backend
        # only — the collective backend is one SPMD program):
        #
        # - worker_restart_budget=K: a dead hogwild worker is restarted up
        #   to K times from its latest checkpoint snapshot + a fresh center
        #   pull (recovery.WorkerSupervisor) instead of merely tolerated;
        #   worker_restart_delay is the cooldown before each relaunch.
        # - retry_policy: a resilience.RetryPolicy — pulls/commits that hit
        #   transient transport failures reconnect and retry with
        #   exponential backoff; retried commits carry per-worker seqnos
        #   the server deduplicates (exactly-once folds).
        # - heartbeat_interval: workers renew a liveness lease on the PS at
        #   window boundaries; lease_timeout (default 5× the interval)
        #   controls stale-worker eviction, surfaced in ps.stats() and fed
        #   into DynSGD staleness accounting.
        # - fault_plan: a resilience.FaultPlan injected into the run (the chaos
        #   tests; install()ed by the caller for wire
        #   faults, kill-at-window faults hook the worker loop here).
        self.worker_restart_budget = int(worker_restart_budget)
        if self.worker_restart_budget < 0:
            raise ValueError(
                f"worker_restart_budget must be >= 0, got "
                f"{worker_restart_budget}"
            )
        self.worker_restart_delay = float(worker_restart_delay)
        self.retry_policy = retry_policy
        if heartbeat_interval is not None and heartbeat_interval <= 0:
            raise ValueError(
                f"heartbeat_interval must be positive, got "
                f"{heartbeat_interval}"
            )
        self.heartbeat_interval = heartbeat_interval
        if lease_timeout is not None and lease_timeout <= 0:
            raise ValueError(
                f"lease_timeout must be positive, got {lease_timeout}"
            )
        self.lease_timeout = lease_timeout
        self.fault_plan = fault_plan
        # PS durability + failover (resilience/wal.py; PS backend only):
        #
        # - ps_wal_dir: write-ahead commit log + periodic fsync'd center
        #   snapshots — a crashed PS restarts in place from (snapshot,
        #   wal) with center/EMA/staleness/dedup state reconstructed
        #   bit-identically, on every transport (the native C++ server
        #   writes the same CRC frame format; recover_ps_state replays
        #   either side's log).
        # - ps_snapshot_every: commits between snapshots (log truncation
        #   cadence).
        # - ps_wal_group_window: group commit — defer each commit's ACK
        #   and land up to this many on ONE fsync (ACK => fsync'd, at
        #   ~1/window the sync cost; the default). 1 = the PR 5 behavior
        #   (flush per record, periodic fsync, immediate ACK); 0 =
        #   time-bounded async (immediate ACK, fsync on the interval).
        # - ps_wal_group_interval: seconds bounding the durability window
        #   in EVERY mode (a pull-heavy quiet period still gets fsync'd).
        # - ps_standby (socket transport): a warm replica streams every
        #   applied commit from the primary; the trainer-side
        #   PSFailoverSupervisor promotes it (with a fencing-epoch bump,
        #   so a zombie primary's late folds are rejected) when the
        #   primary's lease lapses.
        # - ps_failover_timeout: seconds without a successful primary
        #   ping before failover (defaults to lease_timeout, else 2 s).
        self.ps_wal_dir = ps_wal_dir
        self.ps_snapshot_every = int(ps_snapshot_every)
        if self.ps_snapshot_every <= 0:
            raise ValueError(
                f"ps_snapshot_every must be positive, got {ps_snapshot_every}"
            )
        self.ps_wal_group_window = int(ps_wal_group_window)
        if self.ps_wal_group_window < 0:
            raise ValueError(
                f"ps_wal_group_window must be >= 0 (0 = time-bounded "
                f"async, 1 = per-record flush, N = group size), got "
                f"{ps_wal_group_window}"
            )
        self.ps_wal_group_interval = float(ps_wal_group_interval)
        if self.ps_wal_group_interval <= 0:
            raise ValueError(
                f"ps_wal_group_interval must be positive, got "
                f"{ps_wal_group_interval}"
            )
        self.ps_standby = bool(ps_standby)
        if ps_failover_timeout is not None and ps_failover_timeout <= 0:
            raise ValueError(
                f"ps_failover_timeout must be positive, got "
                f"{ps_failover_timeout}"
            )
        self.ps_failover_timeout = ps_failover_timeout
        if self.ps_standby and ps_transport != "socket":
            raise ValueError(
                "ps_standby requires ps_transport='socket' (the replica "
                "is a second socket server; the in-process PS shares the "
                "trainer's fate and the native PS has no replication "
                "stream yet)"
            )
        if self.ps_standby and ps_host is not None:
            raise ValueError(
                "ps_standby applies to the PS this trainer hosts; an "
                "external ps_host owner runs its own standby"
            )
        # Sharded center (distkeras_tpu/sharding; DESIGN.md "Sharded
        # center & chain replication"):
        # - ps_num_shards: partition the param tree across N PS shards by
        #   byte-weighted consistent hashing over leaf paths; workers fan
        #   pulls/commits to every shard in parallel. Bit-identical to the
        #   single-PS run (same per-shard fold order and τ), with commit
        #   throughput scaling with N.
        # - ps_chain_length: total replicas per shard INCLUDING the
        #   primary — chain replication (each link streams every pre-ACK
        #   record to the next; per-shard failover promotes down the
        #   chain). ps_chain_length=2 with ps_num_shards=1 is the PR 5
        #   hot-standby topology, which this subsumes.
        self.ps_num_shards = int(ps_num_shards)
        if self.ps_num_shards < 1:
            raise ValueError(
                f"ps_num_shards must be >= 1, got {ps_num_shards}"
            )
        self.ps_chain_length = int(ps_chain_length)
        if self.ps_chain_length < 1:
            raise ValueError(
                f"ps_chain_length must be >= 1, got {ps_chain_length}"
            )
        sharded = self.ps_num_shards > 1 or self.ps_chain_length > 1
        if self.ps_chain_length > 1 and ps_transport != "socket":
            raise ValueError(
                "ps_chain_length > 1 requires ps_transport='socket' "
                "(chain replicas are socket servers; the in-process PS "
                "shares the trainer's fate and the native PS has no "
                "replication stream)"
            )
        if sharded and ps_host is not None:
            raise ValueError(
                "ps_num_shards/ps_chain_length apply to the center this "
                "trainer hosts; an external ps_host owner runs its own "
                "sharded group"
            )
        if sharded and self.ps_standby:
            raise ValueError(
                "ps_standby is the pre-sharding single hot standby; with "
                "ps_num_shards/ps_chain_length use ps_chain_length >= 2 "
                "(chain replication subsumes it)"
            )
        # Pipelined fused exchange (ISSUE 10; DESIGN.md "Pipelined
        # exchange"):
        # - ps_fused_exchange (default True): route each window's
        #   commit+pull through the single-round-trip EXCHANGE wire
        #   action — the fold and the fresh post-fold center in ONE RTT
        #   instead of two, identical semantics (False keeps the classic
        #   pair, the A/B for the bit-identical tests).
        # - ps_pipeline_depth: 0 (default) = the serial loop, bit-
        #   identical to the pre-pipeline behavior; 1 = launch window
        #   N+1's on-device compute, then exchange window N on the host
        #   while the device runs — the committed delta is one window
        #   stale, priced into DynSGD τ via the exchange's lag flag.
        #   Depth > 1 is declined by design (see DESIGN.md: each extra
        #   window multiplies staleness for a latency the single-deep
        #   pipeline already hides).
        self.ps_fused_exchange = bool(ps_fused_exchange)
        self.ps_pipeline_depth = int(ps_pipeline_depth)
        if self.ps_pipeline_depth not in (0, 1):
            raise ValueError(
                f"ps_pipeline_depth must be 0 (serial) or 1 (one window "
                f"in flight), got {ps_pipeline_depth} — deeper pipelines "
                f"buy no additional overlap (one RTT already hides behind "
                f"one window) and multiply DynSGD staleness per extra "
                f"window; see DESIGN.md 'Pipelined exchange'"
            )
        if self.ps_pipeline_depth and backend != "ps":
            raise ValueError(
                "ps_pipeline_depth applies to backend='ps' only (the "
                "collective backend has no worker-hosted exchange loop)"
            )
        if self.ps_pipeline_depth and checkpoint_dir and not elastic:
            raise ValueError(
                "ps_pipeline_depth >= 1 is incompatible with fixed-pool "
                "epoch-barrier checkpointing (checkpoint_dir): the "
                "barrier would snapshot with one window still "
                "un-exchanged — drop checkpoint_dir or run depth 0"
            )
        if self.ps_pipeline_depth and not self.ps_fused_exchange:
            raise ValueError(
                "ps_pipeline_depth >= 1 requires ps_fused_exchange=True: "
                "only the fused EXCHANGE action carries the lag flag that "
                "prices the pipeline's one-window staleness into DynSGD τ "
                "— the unfused commit();pull() pair would silently "
                "under-price it"
            )
        if self.ps_pipeline_depth and compression is not None \
                and ps_transport == "native":
            raise ValueError(
                "ps_pipeline_depth >= 1 with compression on "
                "ps_transport='native' is unsupported: the segmented "
                "int8 commit wire has no fused EXCHANGE frame, and its "
                "2-RTT fallback cannot carry the pipeline's lag pricing "
                "— use ps_transport='socket' or drop one of the two"
            )
        if not self.ps_fused_exchange and backend != "ps":
            raise ValueError(
                "ps_fused_exchange applies to backend='ps' only"
            )
        # Elastic membership (distkeras_tpu/resilience/elastic.py;
        # DESIGN.md "Elastic membership & autoscaling"):
        # - elastic=True: the PS worker pool is DYNAMIC — data shards are
        #   window blocks leased from a shared assigner (exactly-once per
        #   epoch across membership changes), new workers live-join
        #   mid-run, and a preempted worker drains cleanly (finish the
        #   in-flight window, flush the commit, hand its blocks back,
        #   deregister retiring its dedup seqno) instead of dying into a
        #   restart budget.
        # - autoscale_target: rounds/s the autoscaler tracks (or a full
        #   ElasticPolicy) — under target it live-joins workers up to
        #   max_pool_size, over target (or for persistent τ-tail
        #   stragglers) it drains one.
        # - preempt_drain_timeout: seconds a preempted worker gets to
        #   drain before being force-drained (blocks released on its
        #   behalf, drain reported with timeout=True, lease eviction as
        #   backstop).
        # - max_pool_size: autoscaler/join ceiling (default 2×workers).
        self.elastic = bool(elastic)
        self.autoscale_target = autoscale_target
        # deploy_streamer= (ISSUE 16): a deploy.WeightStreamer to attach
        # to the trainer-hosted center(s) before workers start — serving
        # replicas then stream every fold live (train-while-serve). The
        # streamer outlives the run; the caller owns its lifecycle.
        self.deploy_streamer = deploy_streamer
        if deploy_streamer is not None and ps_host is not None:
            raise ValueError(
                "deploy_streamer= streams from the PS this trainer "
                "hosts; with an external ps_host, attach the streamer "
                "on the PS owner's side instead"
            )
        self.preempt_drain_timeout = float(preempt_drain_timeout)
        self.max_pool_size = (
            None if max_pool_size is None else int(max_pool_size)
        )
        if self.elastic and backend != "ps":
            raise ValueError(
                "elastic=True applies to backend='ps' only (the "
                "collective backend is one fixed SPMD program)"
            )
        if self.elastic and ps_host is not None:
            raise ValueError(
                "elastic=True manages the pool this trainer hosts; an "
                "external ps_host owner runs its own elastic coordinator"
            )
        if self.elastic and worker_restart_budget:
            raise ValueError(
                "elastic=True and worker_restart_budget are mutually "
                "exclusive: elastic membership replaces restart-in-place "
                "(a preempted/dead worker's blocks go back to the pool; "
                "scale-up goes through the live-join path)"
            )
        if not self.elastic:
            if autoscale_target is not None:
                raise ValueError(
                    "autoscale_target requires elastic=True (the "
                    "autoscaler grows/shrinks the pool through the "
                    "live-join and drain paths)"
                )
            if max_pool_size is not None:
                raise ValueError("max_pool_size requires elastic=True")
        if isinstance(autoscale_target, (int, float)) \
                and autoscale_target <= 0:
            raise ValueError(
                f"autoscale_target must be positive, got "
                f"{autoscale_target}"
            )
        if self.preempt_drain_timeout <= 0:
            raise ValueError(
                f"preempt_drain_timeout must be positive, got "
                f"{preempt_drain_timeout}"
            )
        if self.max_pool_size is not None \
                and self.max_pool_size < self.num_workers:
            raise ValueError(
                f"max_pool_size ({max_pool_size}) must be >= num_workers "
                f"({self.num_workers})"
            )
        if fault_plan is not None and getattr(
                fault_plan, "kill_ps_after_commits", None) is not None:
            # fail fast: a PS kill with no recovery path would crash the
            # run mid-training after every worker exhausts its retry
            # deadline, and on non-socket transports the kill hook is
            # never wired (the chaos would silently test nothing)
            if ps_transport != "socket":
                raise ValueError(
                    "fault_plan.kill_ps_after_commits requires "
                    "ps_transport='socket' (the in-process PS shares the "
                    "trainer's fate; the native PS has no kill/failover "
                    "wiring)"
                )
            if ps_host is not None:
                raise ValueError(
                    "fault_plan.kill_ps_after_commits applies to the PS "
                    "this trainer hosts, not an external ps_host"
                )
            if ps_wal_dir is None and not self.ps_standby \
                    and self.ps_chain_length <= 1:
                raise ValueError(
                    "fault_plan.kill_ps_after_commits needs a recovery "
                    "path: set ps_wal_dir (restart-in-place), "
                    "ps_standby=True, or ps_chain_length >= 2 (chain "
                    "failover)"
                )
            ks = getattr(fault_plan, "kill_shard_id", None)
            if ks is not None and ks >= self.ps_num_shards:
                raise ValueError(
                    f"fault_plan.kill_shard_id={ks} is out of range for "
                    f"ps_num_shards={self.ps_num_shards}"
                )
        # Membership directory (distkeras_tpu/directory; DESIGN.md
        # "Membership directory & routing", ISSUE 15):
        # - directory=True: host the replicated coordination service next
        #   to the PS fleet — a WAL-backed DirectoryServer (plus a
        #   standby fed by the apply-and-forward stream unless
        #   directory_standby=False) mapping ("ps", "shard-NN") →
        #   (endpoint, fence epoch, lease). Every worker's client is
        #   minted from a directory LOOKUP (zero endpoint constructor
        #   args — elastic joiners on other hosts discover the fleet),
        #   failover supervisors publish promotions to it atomically
        #   with the epoch bump (publish-then-fence), and their healthy
        #   pings renew the lease so a dead shard's entry expires.
        # - directory_standby: replicate the directory itself (default
        #   True — an unreplicated directory would reintroduce exactly
        #   the one-process topology knowledge this removes).
        # - ps_directory=seeds ("host:port" or (host, port), singly or
        #   a list): discover an EXTERNAL fleet through its directory —
        #   the serving-process analogue of ps_host with the wiring
        #   looked up instead of hand-passed.
        self.directory = bool(directory)
        self.directory_standby = bool(directory_standby)
        self.ps_directory = ps_directory
        if self.directory or ps_directory is not None:
            if backend != "ps":
                raise ValueError(
                    "directory/ps_directory apply to backend='ps' only"
                )
            if self.directory and ps_transport != "socket":
                raise ValueError(
                    "directory=True requires ps_transport='socket' (the "
                    "directory registers TCP endpoints; the in-process "
                    "and shm transports have no cross-host endpoints to "
                    "publish)"
                )
            if self.directory and ps_directory is not None:
                raise ValueError(
                    "directory=True hosts the directory; ps_directory= "
                    "discovers an external one — set exactly one"
                )
            if ps_host is not None:
                raise ValueError(
                    "directory/ps_directory replace ps_host: endpoints "
                    "come from the directory, not constructor arguments"
                )
            if ps_directory is not None and (
                    sharded or ps_standby or ps_wal_dir is not None):
                raise ValueError(
                    "ps_directory discovers a fleet some OTHER process "
                    "hosts — the server-side knobs (ps_num_shards, "
                    "ps_chain_length, ps_standby, ps_wal_dir) belong to "
                    "that owner"
                )
            if ps_directory is not None \
                    and ps_transport not in ("socket",):
                raise ValueError(
                    "ps_directory requires ps_transport='socket' (the "
                    "discovered endpoints are TCP servers)"
                )
        if fault_plan is not None \
                and getattr(fault_plan, "has_directory_events", False) \
                and not self.directory:
            raise ValueError(
                "fault_plan carries directory kill/partition events but "
                "directory=True is not set — nothing would ever consult "
                "them, so the chaos would silently test nothing"
            )
        if backend != "ps" and (
                worker_restart_budget or retry_policy is not None
                or heartbeat_interval is not None or lease_timeout is not None
                or fault_plan is not None or ps_wal_dir is not None
                or ps_standby or sharded):
            raise ValueError(
                "the resilience knobs (worker_restart_budget, retry_policy, "
                "heartbeat_interval, lease_timeout, fault_plan, ps_wal_dir, "
                "ps_standby, ps_num_shards, ps_chain_length) apply to "
                "backend='ps' only (the collective backend is one SPMD "
                "program)"
            )
        self.resilience_stats_ = None

    # -- seams kept from the reference ------------------------------------

    def allocate_merge_rule(self) -> MergeRule:
        """The algorithm's commit/fold semantics (reference
        ``allocate_parameter_server`` seam)."""
        raise NotImplementedError

    def allocate_optimizer(self):
        return resolve_optimizer(
            self.worker_optimizer, self.learning_rate,
            clipnorm=self.clipnorm, clipvalue=self.clipvalue,
        )

    def _loss_step(self) -> Callable:
        return _make_loss_step(self.spec, self.loss_fn, len(self.features_col),
                               loss_name=self.loss)

    # -- training ----------------------------------------------------------

    def train(self, dataset, shuffle: bool = False):
        ds = self._coerce_dataset(dataset)
        if self.backend == "ps":
            if self.checkpoint_async:
                raise ValueError(
                    "checkpoint_async is not supported on backend='ps' (the "
                    "hogwild workers checkpoint at a cross-thread barrier); "
                    "use the collective backend or synchronous checkpoints"
                )
            _reject_worker_axis_model(
                self.spec, "backend='ps' (independent hogwild host threads)"
            )
        ctx = _profile_trace_ctx(self.profile_dir)
        try:
            with ctx:
                if self.backend == "ps":
                    if jax.process_count() > 1:
                        # the multi-slice story, automated: process 0 hosts
                        # the PS, every controller runs its local hogwild
                        # workers against it over TCP/DCN
                        return self._train_ps_multiprocess(ds, shuffle)
                    return self._train_ps(ds, shuffle)
                return self._train_collective(ds, shuffle)
        finally:
            # idempotent join: an aborted run must neither drop the
            # in-flight async checkpoint nor swallow its failure
            self._finish_checkpoints()

    def _build_engine(self) -> LocalSGDEngine:
        """The collective backend's engine for this trainer's configuration
        (also what chip_smoke.py inspects for where the state is placed)."""
        return LocalSGDEngine(
            spec=self.spec,
            loss_step=self._loss_step(),
            optimizer=self.allocate_optimizer(),
            rule=self.allocate_merge_rule(),
            mesh=self.mesh,
            num_workers=self.num_workers,
            window=self.communication_window,
            batch_size=self.batch_size,
        )

    def _train_collective(self, ds: Dataset, shuffle: bool):
        engine = self._build_engine()
        params, nt = self.spec.init_np(self.seed)
        state = engine.init_state(params, nt)
        start_epoch = 0
        if self.checkpoint_dir and self.resume:
            from distkeras_tpu import checkpoint as ckpt

            if ckpt.latest_step(self.checkpoint_dir) is not None:
                payload, step = ckpt.restore_checkpoint(self.checkpoint_dir)
                host_state = payload["state"]
                w_leaves = jax.tree.leaves(host_state.workers)
                ckpt_w = w_leaves[0].shape[0] if w_leaves else self.num_workers
                if ckpt_w == self.num_workers:
                    state = engine.init_state_from(host_state)
                else:
                    # Elastic resume (beyond-reference failure recovery,
                    # SURVEY.md §5.3): the checkpointed center is the model;
                    # re-broadcast it into a fresh W-worker state. Worker-
                    # local divergence and optimizer moments restart — the
                    # honest semantics when the replica count changes.
                    ckpt.warn_elastic_resume(ckpt_w, self.num_workers)
                    nt0 = jax.tree.map(lambda x: x[0], host_state.nt)
                    state = engine.init_state(host_state.center, nt0)
                    state = state.replace(step=jnp.asarray(host_state.step))
                start_epoch = int(payload["epoch"]) + 1
        cols = self.features_col + [self.label_col]
        validator = self._make_validator()

        use_resident = self.device_data
        if use_resident is None:
            use_resident = _fits_device_budget(
                ds, cols, self.device_data_budget_bytes
            )

        ema, ema_step = None, None
        if self.ema_decay is not None:
            use_resident, ema, ema_step = _ema_tracking(
                state.center, self.ema_decay, use_resident
            )

        self.record_training_start()
        if use_resident:
            # Upload each worker's row shard to HBM once (the rebuilt
            # rdd.repartition); epochs shuffle and scan entirely on device.
            # Shard assignment uses the same window-major interleave as the
            # streaming path; when shuffling, the tail wraps so no row is
            # permanently excluded.
            staged = engine.stage_dataset(ds.worker_shards(
                self.num_workers, self.batch_size, self.communication_window,
                cols, seed=self.seed if shuffle else None, cover_all=shuffle,
            ))
            rows_pw = staged[0].shape[1]
            n_windows = rows_pw // (self.communication_window * self.batch_size)
            epoch_rows = (
                self.num_workers * n_windows
                * self.communication_window * self.batch_size
            )
            for epoch in range(start_epoch, self.num_epoch):
                seed = (self.seed + epoch) if shuffle else None
                t0 = time.perf_counter() if self.log_metrics else 0.0
                state, losses = engine.run_epoch_resident(state, staged, seed)
                # losses: device array [windows] — no host sync in the loop
                # unless metrics are being streamed
                self.history.append(losses=losses, epoch=epoch)
                if self.log_metrics:
                    _drain(losses)
                    self._epoch_metrics(
                        epoch, epoch_rows, n_windows, time.perf_counter() - t0
                    )
                if validator is not None:
                    self._validate_epoch(
                        validator, state.center,
                        engine.worker_nt_device(state, 0), epoch,
                    )
                self._maybe_checkpoint(state, epoch)
        else:
            win_rows = (
                self.num_workers * self.communication_window * self.batch_size
            )
            for epoch in range(start_epoch, self.num_epoch):
                seed = (self.seed + epoch) if shuffle else None
                t0 = time.perf_counter() if self.log_metrics else 0.0
                n_windows = 0
                batch_iter = ds.superbatches(
                    self.num_workers, self.batch_size,
                    self.communication_window, cols, seed=seed,
                )
                if self.prefetch:
                    batch_iter = prefetch_to_device(
                        batch_iter, engine.place_batch, depth=self.prefetch
                    )
                for batch in batch_iter:
                    state, loss = engine.run_window(state, batch)
                    if ema_step is not None:
                        ema = ema_step(ema, state.center)
                    self.history.append(loss=loss, epoch=epoch)
                    n_windows += 1
                if self.log_metrics and n_windows:
                    _drain(loss)
                    self._epoch_metrics(
                        epoch, n_windows * win_rows, n_windows,
                        time.perf_counter() - t0,
                    )
                if validator is not None:
                    self._validate_epoch(
                        validator, state.center,
                        engine.worker_nt_device(state, 0), epoch,
                    )
                self._maybe_checkpoint(state, epoch)
        jax.block_until_ready(state.center)
        if ema is not None:
            self.ema_params_ = jax.tree.map(np.asarray, jax.device_get(ema))
        self._finish_checkpoints()
        self.record_training_end()
        self._materialize_history()
        return self._finalize(
            engine.center_params(state), engine.worker_nt(state, 0)
        )

    def _train_ps(self, ds: Dataset, shuffle: bool, runner=None):
        from distkeras_tpu.workers import run_async_training

        # fail-fast: a malformed validation_data must not cost a full run
        validator = self._make_validator()
        self.record_training_start()
        t0 = time.perf_counter()
        # a run that DIES mid-flight must not leak an enabled tracer
        # into the caller's process: run_async_training records its
        # recorder ownership on the trainer (`_trace_owner_`, the single
        # source of truth — it clears it itself on the success path)
        tgt = runner or self
        try:
            params, nt, history = run_async_training(tgt, ds, shuffle)
        except BaseException:
            if getattr(tgt, "_trace_owner_", False):
                from distkeras_tpu.observability import trace as _trace

                _trace.disable()
            # same contract for the watchtower (ISSUE 13): a run that
            # dies mid-flight must not leave its scraper thread polling
            # a stopped server for the rest of the process
            wt = getattr(tgt, "_watchtower_active_", None)
            if wt is not None:
                try:
                    wt.stop()
                finally:
                    tgt._watchtower_active_ = None
            raise
        elapsed = time.perf_counter() - t0
        self.record_training_end()
        for rec in history:
            self.history.append(**rec)
        if self.log_metrics and elapsed > 0:
            # hogwild epochs overlap freely — report whole-run throughput
            n_updates = sum(1 for r in history if "loss" in r)
            rows = n_updates * self.communication_window * self.batch_size
            self._epoch_metrics(None, rows, n_updates, elapsed, label="run")
        if validator is not None:
            # hogwild epochs overlap freely — score once, after the run
            self._validate_epoch(validator, params, nt, None)
        return self._finalize(params, nt)

    def _train_ps_multiprocess(self, ds: Dataset, shuffle: bool):
        """``backend='ps'`` across ``jax.distributed`` controllers — the
        multi-slice/DCN story with zero user plumbing: process 0 hosts the
        PS (socket, or the native C++ server), every controller runs
        ``num_workers / process_count`` local hogwild workers against it
        with offset worker ids over TCP, and a post-barrier pull hands
        every controller the SAME trained center. Rows are partitioned
        contiguously per process (the rebuilt Spark executor shard).

        Rows split STRIDED (process ``i`` takes rows ``i::process_count``)
        so label-sorted datasets never hand a controller a single-class
        shard and no tail row is dropped — the same guarantees
        ``worker_shards`` makes within a process. History/metrics stay
        per-controller views of the free-running async run; when
        ``validation_data`` is set, the LAST validation record scores the
        returned post-barrier center, which is identical everywhere.

        Not supported on this path: ``checkpoint_dir`` (every controller
        would write one directory — checkpoint the PS owner's center
        instead) and ``ema_decay`` (the averaged center would live only
        with process 0's server).
        """
        import copy

        from jax.experimental import multihost_utils

        from distkeras_tpu import networking

        pc, pi = jax.process_count(), jax.process_index()
        if self.num_workers % pc:
            raise ValueError(
                f"num_workers {self.num_workers} must be divisible by "
                f"process_count {pc} (each controller runs an equal share "
                f"of hogwild workers)"
            )
        if self.checkpoint_dir:
            raise NotImplementedError(
                "checkpoint_dir under multi-process backend='ps' is not "
                "supported (controllers would collide in one directory); "
                "checkpoint the PS owner's center instead"
            )
        if self.ema_decay is not None:
            raise NotImplementedError(
                "ema_decay under multi-process backend='ps' is not "
                "supported (the averaged center would live only with "
                "process 0's server)"
            )
        if self.ps_host is not None:
            raise ValueError(
                "ps_host is incompatible with multi-process backend='ps' "
                "(process 0 hosts the server automatically)"
            )
        if self.ps_num_shards > 1 or self.ps_chain_length > 1:
            raise NotImplementedError(
                "ps_num_shards/ps_chain_length under multi-process "
                "backend='ps' are not supported yet (the shim points every "
                "controller at ONE process-0 server; a sharded group needs "
                "per-shard endpoint broadcast)"
            )
        if self.directory or self.ps_directory is not None:
            raise NotImplementedError(
                "directory/ps_directory under the multi-process shim are "
                "not supported yet (the shim broadcasts process 0's one "
                "endpoint; the directory is the mechanism that would "
                "replace that broadcast)"
            )
        W_local = self.num_workers // pc
        transport = "native" if self.ps_transport == "native" else "socket"
        # one init serves the server template AND the final pull's
        # FlatSpec (shapes only) — no per-stage re-inits of a big model
        params0, _ = self.spec.init_np(self.seed)
        ps = None
        host, port = "", 0
        if pi == 0:
            rule = self.allocate_merge_rule()
            if transport == "native":
                from distkeras_tpu.native_ps import NativeSocketParameterServer

                ps = NativeSocketParameterServer(
                    params0, rule, self.num_workers, host="0.0.0.0",
                    port=self.ps_port,
                )
            else:
                from distkeras_tpu.parameter_servers import (
                    SocketParameterServer,
                )

                ps = SocketParameterServer(
                    params0, rule, self.num_workers, host="0.0.0.0",
                    port=self.ps_port,
                )
            ps.initialize()
            ps.start()
            host = networking.determine_host_address()
            port = ps.port
        host, port = _bcast_host_port(host, port)

        # strided per-process row partition: disjoint, covers every row,
        # and a label-sorted dataset still gives each controller all
        # classes; worker_shards inside the runner raises its own sizing
        # error if a share is too small
        shard = Dataset({c: ds[c][pi::pc] for c in ds.columns})

        shim = copy.copy(self)  # shares spec/history; overrides the wiring
        shim.num_workers = W_local
        shim.ps_transport = transport
        shim.ps_host = host
        shim.ps_port = port
        shim.worker_id_offset = pi * W_local
        try:
            self._train_ps(shard, shuffle, runner=shim)
            # all controllers' commits must land before anyone reads the
            # final center, and the server must outlive every reader
            multihost_utils.sync_global_devices("distkeras_ps_drain")
            if transport == "native":
                from distkeras_tpu.native_ps import FlatSpec, NativePSClient

                client = NativePSClient(
                    host, port, 2**32 - 2, FlatSpec(params0)
                )
            else:
                from distkeras_tpu.parameter_servers import (
                    ParameterServerClient,
                )

                client = ParameterServerClient(host, port, 2**32 - 2)
            final = client.pull()
            client.close()
            multihost_utils.sync_global_devices("distkeras_ps_final")
        finally:
            if ps is not None:
                ps.stop()
        # non-trainables trained per-controller on different shards —
        # broadcast process 0's so every controller returns the identical
        # (center, nt) model
        nt = multihost_utils.broadcast_one_to_all(self.trained_nt_)
        nt = jax.tree.map(np.asarray, nt)
        validator = self._make_validator()
        if validator is not None:
            # the LAST validation record scores the returned global center
            # (the earlier one was this controller's pre-drain snapshot)
            self._validate_epoch(validator, final, nt, None)
        return self._finalize(final, nt)

    def _maybe_checkpoint(self, state, epoch: int):
        if not self.checkpoint_dir:
            return
        from distkeras_tpu import checkpoint as ckpt

        if not ckpt.should_checkpoint(epoch, self.checkpoint_every,
                                      self.num_epoch):
            return
        self._dispatch_checkpoint({"state": state, "epoch": epoch}, epoch)

class AsynchronousDistributedTrainer(DistributedTrainer):
    """Parity alias: the reference's base class for the five asynchronous
    algorithms (reference ``distkeras/trainers.py ::
    AsynchronousDistributedTrainer``, which added ``communication_window``;
    here ``DistributedTrainer`` already carries it)."""


class SingleTrainer(DistributedTrainer):
    """One replica, no communication — the correctness oracle.

    Parity: reference ``distkeras/trainers.py :: SingleTrainer`` (coalesce to
    one partition, plain local minibatch loop — SURVEY.md §3.2).
    """

    default_window = 1

    def __init__(self, keras_model, loss="mse", worker_optimizer="sgd",
                 learning_rate: float = 0.01, batch_size: int = 32,
                 features_col="features", label_col: str = "label",
                 num_epoch: int = 1, seed: int = 0, mesh=None,
                 prefetch: int = 1, ema_decay: float | None = None,
                 clipnorm=None, clipvalue=None, validation_data=None):
        super().__init__(
            keras_model, loss, worker_optimizer, learning_rate=learning_rate,
            num_workers=1, batch_size=batch_size, features_col=features_col,
            label_col=label_col, num_epoch=num_epoch, communication_window=1,
            backend="collective",
            mesh=mesh if mesh is not None else get_mesh(1), seed=seed,
            prefetch=prefetch, ema_decay=ema_decay,
            clipnorm=clipnorm, clipvalue=clipvalue,
            validation_data=validation_data,
        )

    def allocate_merge_rule(self) -> MergeRule:
        return ADAGMerge()  # with W=1 the merge is the identity fold


class ADAG(AsynchronousDistributedTrainer):
    """Asynchronous Distributed Adaptive Gradients — the recommended default.

    Parity: reference ``distkeras/trainers.py :: ADAG``. Sync lowering: mean
    of worker commits each window; with ``communication_window=1`` this is
    exactly synchronous all-reduce data parallelism (the north-star config).
    """

    default_window = 12

    def allocate_merge_rule(self) -> MergeRule:
        return ADAGMerge()


class DOWNPOUR(AsynchronousDistributedTrainer):
    """Downpour SGD (Dean et al. 2012).

    Parity: reference ``distkeras/trainers.py :: DOWNPOUR`` — workers push
    unscaled weight deltas.
    """

    default_window = 5

    def allocate_merge_rule(self) -> MergeRule:
        return DownpourMerge()


class AEASGD(AsynchronousDistributedTrainer):
    """Asynchronous Elastic-Averaging SGD (Zhang, Choromanska & LeCun 2015).

    Parity: reference ``distkeras/trainers.py :: AEASGD`` with its ``rho``
    elastic force; workers keep their own variables between windows.
    """

    default_window = 32

    def __init__(self, keras_model, loss="mse", worker_optimizer="sgd",
                 learning_rate: float = 0.04, rho: float = 3.0, **kw):
        super().__init__(keras_model, loss, worker_optimizer,
                         learning_rate=learning_rate, **kw)
        self.rho = float(rho)

    def allocate_merge_rule(self) -> MergeRule:
        return ElasticAverageMerge(
            alpha=self.rho * self.learning_rate, num_workers=self.num_workers
        )


class EAMSGD(AEASGD):
    """Elastic averaging + Nesterov momentum on the worker update.

    Parity: reference ``distkeras/trainers.py :: EAMSGD`` (adds ``momentum``).
    The merge rule is AEASGD's; only the worker optimizer differs.
    """

    def __init__(self, keras_model, loss="mse", worker_optimizer="sgd",
                 learning_rate: float = 0.04, rho: float = 3.0,
                 momentum: float = 0.9, **kw):
        super().__init__(keras_model, loss, worker_optimizer,
                         learning_rate=learning_rate, rho=rho, **kw)
        self.momentum = float(momentum)

    def allocate_optimizer(self):
        return resolve_optimizer(
            self.worker_optimizer, self.learning_rate,
            momentum=self.momentum, nesterov=True,
            clipnorm=self.clipnorm, clipvalue=self.clipvalue,
        )


class MeshTrainer(Trainer):
    """Sync SPMD trainer over an N-D mesh — the full parallelism portfolio.

    Beyond-reference (SURVEY.md §2b.2 lists TP as "natural extension via
    jax.sharding"): trains ONE set of parameters over a device mesh, with the
    distribution strategy selected by ``strategy``:

    - ``"spmd"`` (default) — data parallelism over ``dp`` × Megatron tensor
      parallelism over ``tp``; ``parameter_sharding`` picks the layout
      (``"megatron"``, ``"fsdp"``/ZeRO-3, ``"fsdp+megatron"``). Math equals
      single-device training on the global batch (tests/test_tensor_parallel).
    - ``"pipeline"`` — GPipe: the transformer's encoder blocks are pipeline
      stages over a ``pp`` axis (``depth == mesh.shape['pp']``), each device
      storing exactly its stage; optional ``dp`` axis composes data
      parallelism. ``microbatches`` controls the bubble fraction.
    - ``"sequence"`` — ring attention: activations sharded along L over an
      ``sp`` axis (per-chip activation memory O(L/N)); optional ``dp`` axis.
    - ``"expert"`` — GShard MoE over an ``ep`` axis: experts sharded, tokens
      exchanged with ``all_to_all``, gating aux loss (weight ``aux_weight``)
      folded into the objective. Needs a ``moe_transformer_classifier`` model.

    The reference's product surface was exactly this one-class-per-strategy
    ergonomics (reference ``distkeras/trainers.py``); here every strategy is a
    kwarg on the same trainer, and checkpoint/resume, profiling, metrics, and
    the resident input path apply to all of them.

    ``mesh_shape`` e.g. ``{"dp": 2, "tp": 4}``; ``param_specs`` overrides the
    automatic partitioning rules with an explicit PartitionSpec pytree.

    ``grad_accum=A`` accumulates gradients over A equal microbatches per
    optimizer update (a ``lax.scan`` inside the jitted step) — ~A× less
    activation memory at the same effective batch size.

    ``checkpoint_dir``/``checkpoint_every``/``resume`` snapshot the sharded
    training state (params + optimizer in their mesh layout) at epoch
    boundaries and restore it back onto the mesh — resume-equality is
    pinned by tests/test_fsdp.py. Under multi-process ``jax.distributed``
    the snapshot is process-sharded (each controller writes its own shards;
    tests/test_multihost.py pins cluster resume equality). ``profile_dir`` wraps
    training in ``jax.profiler.trace``. ``input_mode="resident"`` uploads the
    dataset once and runs each epoch as one jitted scan (no per-step host
    round-trip); ``"auto"`` chooses resident when the dataset fits the
    ``device_data_budget_bytes`` budget, mirroring DistributedTrainer.
    """

    device_data_budget_bytes = 1 << 30

    def __init__(self, keras_model, loss="sparse_softmax_cross_entropy",
                 worker_optimizer="adam", learning_rate: float = 1e-3,
                 mesh=None, mesh_shape: dict | None = None, param_specs=None,
                 strategy: str = "spmd",
                 parameter_sharding: str = "megatron",
                 grad_accum: int = 1, microbatches: int | None = None,
                 aux_weight: float = 1e-2,
                 batch_size: int = 32, features_col="features",
                 label_col: str = "label", num_epoch: int = 1, seed: int = 0,
                 log_metrics: bool = False,
                 checkpoint_dir=None, checkpoint_every: int = 1,
                 resume: bool = False, checkpoint_async: bool = False,
                 profile_dir=None,
                 input_mode: str = "auto", prefetch: int = 1,
                 ema_decay: float | None = None,
                 clipnorm=None, clipvalue=None, validation_data=None):
        from distkeras_tpu.parallel.strategies import STRATEGIES
        from distkeras_tpu.parallel.tensor import get_mesh_nd

        super().__init__(keras_model, loss, worker_optimizer,
                         learning_rate=learning_rate, seed=seed,
                         clipnorm=clipnorm, clipvalue=clipvalue)
        # Keras-style per-epoch validation — same contract as
        # DistributedTrainer.validation_data; the engine-layout params are
        # gathered to the standard layout before scoring.
        self.validation_data = validation_data
        if mesh is None:
            mesh = get_mesh_nd(mesh_shape or {"dp": len(jax.devices())})
        self.mesh = mesh
        self.param_specs = param_specs
        if strategy not in ("spmd",) + tuple(STRATEGIES):
            raise ValueError(
                f"strategy={strategy!r}: expected 'spmd', "
                f"{', '.join(repr(s) for s in STRATEGIES)}"
            )
        self.strategy = strategy
        if parameter_sharding not in ("megatron", "fsdp", "fsdp+megatron"):
            raise ValueError(
                f"parameter_sharding={parameter_sharding!r}: expected "
                f"'megatron', 'fsdp', or 'fsdp+megatron'"
            )
        if strategy != "spmd" and parameter_sharding != "megatron":
            raise ValueError(
                f"parameter_sharding={parameter_sharding!r} only applies to "
                f"strategy='spmd'; {strategy!r} fixes its own layout"
            )
        self.parameter_sharding = parameter_sharding
        self.grad_accum = int(grad_accum)
        self.microbatches = microbatches
        self.aux_weight = float(aux_weight)
        self.batch_size = int(batch_size)
        self.features_col: list[str] = _as_cols(features_col)
        self.label_col = label_col
        self.num_epoch = int(num_epoch)
        self.log_metrics = bool(log_metrics)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = int(checkpoint_every)
        self.resume = bool(resume)
        self.checkpoint_async = bool(checkpoint_async)
        self._async_ckpt = None
        self.profile_dir = profile_dir
        if input_mode not in ("auto", "stream", "resident"):
            raise ValueError(
                f"input_mode={input_mode!r}: expected 'auto', 'stream', or "
                f"'resident'"
            )
        self.input_mode = input_mode
        # streaming prefetch depth (see DistributedTrainer.prefetch)
        self.prefetch = int(prefetch)
        # Polyak/EMA of the global params per step (see
        # DistributedTrainer.ema_decay); needs the streaming input path
        self.ema_decay = _validate_ema_decay(ema_decay)
        self.ema_params_ = None

    def _build_engine(self):
        """Construct the strategy's engine + params re-layout callables."""
        from distkeras_tpu.parallel.fsdp import FSDPEngine
        from distkeras_tpu.parallel.strategies import STRATEGIES
        from distkeras_tpu.parallel.tensor import SPMDEngine

        optimizer = resolve_optimizer(
            self.worker_optimizer, self.learning_rate,
            clipnorm=self.clipnorm, clipvalue=self.clipvalue,
        )
        ident = lambda p: p
        if self.strategy == "spmd":
            loss_step = _make_loss_step(
                self.spec, self.loss_fn, len(self.features_col),
                loss_name=self.loss,
            )
            if self.parameter_sharding == "megatron":
                engine = SPMDEngine(
                    self.spec, loss_step, optimizer, self.mesh,
                    param_specs=self.param_specs,
                    grad_accum=self.grad_accum,
                )
            else:
                engine = FSDPEngine(
                    self.spec, loss_step, optimizer, self.mesh,
                    tensor_parallel=(
                        self.parameter_sharding == "fsdp+megatron"
                    ),
                    param_specs=self.param_specs,
                    grad_accum=self.grad_accum,
                )
            return engine, ident, ident

        dp_axis = "dp" if "dp" in self.mesh.shape else None
        kwargs = {}
        if self.strategy == "pipeline":
            kwargs = dict(dp_axis=dp_axis, microbatches=self.microbatches)
        elif self.strategy == "sequence":
            kwargs = dict(dp_axis=dp_axis)
        elif self.strategy == "expert":
            kwargs = dict(aux_weight=self.aux_weight)
        if (self.spec.fused_losses or {}).get(self.loss) is not None:
            import warnings

            # strategy engines rebuild the forward mesh-specialized from the
            # flax module, so they cannot consume the spec's fused loss —
            # the full-output loss runs instead, at full-output memory
            warnings.warn(
                f"strategy={self.strategy!r} trains with the unfused "
                f"{self.loss!r} loss (the model's fused implementation — "
                f"e.g. transformer_lm(fused_ce=True) — only applies under "
                f"strategy='spmd' and the collective/ps trainers); expect "
                f"full-logits memory"
            )
        loss_step, specs_for, to_engine, from_engine = STRATEGIES[
            self.strategy
        ](self.spec, self.loss_fn, self.mesh, **kwargs)
        # one init serves both the specs derivation and (via the cache)
        # train()'s fresh-start state — no duplicate Flax init
        with _phase("train.init_weights"):
            self._init_cache = self.spec.init_np(self.seed)
        specs = (self.param_specs if self.param_specs is not None
                 else specs_for(to_engine(self._init_cache[0])))
        engine = SPMDEngine(
            self.spec, loss_step, optimizer, self.mesh, param_specs=specs,
            dp_axis=dp_axis, grad_accum=self.grad_accum,
        )
        return engine, to_engine, from_engine

    def train(self, dataset, shuffle: bool = False):
        try:
            return self._train_impl(dataset, shuffle)
        finally:
            # idempotent join: an aborted run must neither drop the
            # in-flight async checkpoint nor swallow its failure
            self._finish_checkpoints()

    def _train_impl(self, dataset, shuffle: bool = False):
        _reject_worker_axis_model(
            self.spec, "MeshTrainer (single-model GSPMD, no worker axis)"
        )
        # checkpoint_dir works multi-process: saves dispatch to the
        # process-sharded format (checkpoint._save_sharded) and restores
        # reassemble global arrays on every controller.  profile_dir and
        # validation_data work multi-process too: per-process trace subdirs
        # (_profile_trace_ctx) and global-array eval batches (_Validator).
        ds = self._coerce_dataset(dataset)
        cols = self.features_col + [self.label_col]
        with _phase("train.build_engine"):
            engine, to_engine, from_engine = self._build_engine()
            validator = self._make_validator()

        def run_validation(epoch):
            if self.strategy == "spmd":
                # engine layout == model layout: score the sharded params
                # in place — the jitted eval compiles over their mesh
                # (GSPMD), so a model that only fits sharded stays sharded
                self._validate_epoch(validator, params, nt, epoch)
                return
            # pipeline/sequence/expert layouts need the from_engine
            # re-layout, which today goes through host (full-pytree gather
            # per epoch — fine for models these strategies train here);
            # under jax.distributed the gather must be the cross-process
            # allgather (some shards live on devices this controller
            # cannot address), after which eval runs process-locally
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                host_p = multihost_utils.process_allgather(params, tiled=True)
                host_nt = multihost_utils.process_allgather(nt, tiled=True)
            else:
                host_p = jax.tree.map(np.asarray, jax.device_get(params))
                host_nt = jax.tree.map(np.asarray, jax.device_get(nt))
            self._validate_epoch(validator, from_engine(host_p), host_nt,
                                 epoch)

        start_epoch = 0
        restored = None
        self._counters_seen = self.counters_ = None
        if self.checkpoint_dir and self.resume:
            from distkeras_tpu import checkpoint as ckpt

            if ckpt.latest_step(self.checkpoint_dir) is not None:
                with _phase("train.init_weights", source="checkpoint"):
                    restored, _ = ckpt.restore_checkpoint(
                        self.checkpoint_dir)
                start_epoch = int(restored["epoch"]) + 1
                self._counters_seen = _counters(restored["nt"])
        if restored is not None:
            with _phase("train.init_state"):
                params, nt, opt = engine.place_state(
                    restored["params"], restored["nt"], restored["opt"]
                )
        else:
            if not getattr(self, "_init_cache", None):
                with _phase("train.init_weights"):
                    self._init_cache = self.spec.init_np(self.seed)
            p0, nt0 = self._init_cache
            with _phase("train.init_state"):
                params, nt, opt = engine.init_state(to_engine(p0), nt0)
        self._init_cache = None

        def end_epoch(epoch, rows, steps, t0, fetch, ready=None):
            """From the last step's return to the loop's next turn;
            ``fetch`` is what ``log_metrics`` syncs on (None: no sync)."""
            with _phase("train.epoch_end", epoch=epoch) as sp:
                # an epoch that was handed no batch leaves no entry
                sp.log = bool(steps)
                if fetch is not None:
                    with _phase("train.drain"):
                        jax.block_until_ready(ready)
                        _drain(fetch)
                        self._record_counters(epoch, nt)
                    with _phase("train.log_metrics"):
                        self._epoch_metrics(epoch, rows, steps,
                                            time.perf_counter() - t0)
                if validator is not None:
                    with _phase("train.validate"):
                        run_validation(epoch)
                if self.checkpoint_dir:
                    with _phase("train.checkpoint"):
                        self._maybe_checkpoint(params, nt, opt, epoch)

        use_resident = {
            "stream": False, "resident": True,
            "auto": _fits_device_budget(
                ds, cols, self.device_data_budget_bytes
            ),
        }[self.input_mode]

        ema, ema_step = None, None
        if self.ema_decay is not None:
            # EMA carries live in the ENGINE layout (sharded stays sharded)
            use_resident, ema, ema_step = _ema_tracking(
                params, self.ema_decay, use_resident
            )

        ctx = _profile_trace_ctx(self.profile_dir)
        self.record_training_start()
        with ctx:
            if use_resident:
                with _phase("train.stage_epoch"):
                    staged = engine.stage_epoch(tuple(ds[c] for c in cols))
                rows = (staged[0].shape[0] // self.batch_size) \
                    * self.batch_size
                steps = rows // self.batch_size
                for epoch in range(start_epoch, self.num_epoch):
                    with _phase("train.epoch", epoch=epoch, steps=steps):
                        seed = (self.seed + epoch) if shuffle else None
                        t0 = time.perf_counter() if self.log_metrics else 0.0
                        params, nt, opt, losses = engine.run_epoch_resident(
                            params, nt, opt, staged, self.batch_size, seed
                        )
                        self.history.append(losses=losses, epoch=epoch)
                        # params too: loss scalars can stream back before
                        # the epoch's update compute drains
                        end_epoch(epoch, rows, steps, t0,
                                  losses if self.log_metrics else None,
                                  ready=params)
            else:
                step_num = 0
                for epoch in range(start_epoch, self.num_epoch):
                    with _phase("train.epoch", epoch=epoch) as ep:
                        seed = (self.seed + epoch) if shuffle else None
                        t0 = time.perf_counter() if self.log_metrics else 0.0
                        n_steps = 0
                        with _trace.span("train.input", profile=True):
                            batch_iter = ds.batches(self.batch_size, cols,
                                                    seed=seed)
                            if self.prefetch:
                                batch_iter = prefetch_to_device(
                                    batch_iter, engine.place_batch,
                                    depth=self.prefetch,
                                )
                            batch_iter = iter(batch_iter)
                        while True:
                            with _trace.span("train.input", profile=True):
                                b = next(batch_iter, None)
                            if b is None:
                                break
                            with _trace.span("train.step", profile=True,
                                             step=step_num):
                                params, nt, opt, loss = engine.run_step(
                                    params, nt, opt, b
                                )
                            if ema_step is not None:
                                ema = ema_step(ema, params)
                            self.history.append(loss=loss, epoch=epoch)
                            n_steps += 1
                            step_num += 1
                        ep.args["steps"] = n_steps
                        ep.log = bool(n_steps)
                        end_epoch(epoch, n_steps * self.batch_size, n_steps,
                                  t0, loss if self.log_metrics and n_steps
                                  else None)
        with _phase("train.finish"):
            jax.block_until_ready(jax.tree.leaves(params)[0])
            self._finish_checkpoints()
            self.record_training_end()
            self._materialize_history()
            if self.profile_dir and getattr(engine, "_step_handle", None):
                # beside the trace, the table that lays its device time on
                # the program's scopes (observability.programs; an engine
                # that noted no step leaves none)
                _programs.save("train_step", _profile_path(self.profile_dir))
        with _phase("train.fetch_params"):
            if jax.process_count() > 1:
                # gather sharded leaves to host: under jax.distributed some
                # shards live on devices this controller cannot address
                from jax.experimental import multihost_utils

                params = multihost_utils.process_allgather(params, tiled=True)
                if ema is not None:
                    ema = multihost_utils.process_allgather(ema, tiled=True)
            if ema is not None:
                self.ema_params_ = from_engine(
                    jax.tree.map(np.asarray, jax.device_get(ema))
                )
            host_params = from_engine(
                jax.tree.map(np.asarray, jax.device_get(params)))
            host_nt = jax.tree.map(np.asarray, jax.device_get(nt))
        return self._finalize(host_params, host_nt)

    def _record_counters(self, epoch: int, nt):
        """Fetch the model's counters where the loss was just fetched (the
        step added to them on the device; nothing is synchronised for them)
        and record what the epoch added, by path: in the history
        (``counters``), in the run log (``train.counters``, args ``epoch``
        and ``counts``) and, summed over this run, in ``counters_``."""
        with _phase("train.counters", epoch=epoch) as sp:
            total = _counters(nt)
            sp.log = total is not None
            if total is None:
                return
            seen = self._counters_seen or {}
            new = {path: (n - seen[path]) % 2 ** 32 if path in seen else n
                   for path, n in total.items()}
            self._counters_seen = total
            run = self.counters_ or {}
            self.counters_ = {path: run.get(path, 0) + n
                              for path, n in new.items()}
            lists = {path: n.tolist() for path, n in new.items()}
            sp.args["counts"] = lists
            self.history.append(epoch=epoch, counters=lists)

    def _maybe_checkpoint(self, params, nt, opt, epoch: int):
        if not self.checkpoint_dir:
            return
        from distkeras_tpu import checkpoint as ckpt

        if not ckpt.should_checkpoint(epoch, self.checkpoint_every,
                                      self.num_epoch):
            return
        # the engine layout is saved as-is and re-placed on resume;
        # save_checkpoint dispatches per process topology (one host blob
        # single-process, per-controller shard files under jax.distributed)
        self._dispatch_checkpoint(
            {"params": params, "nt": nt, "opt": opt, "epoch": epoch}, epoch
        )


class DynSGD(AsynchronousDistributedTrainer):
    """Staleness-aware dynamic-learning-rate SGD (after Jiang et al. 2017).

    Parity: reference ``distkeras/trainers.py :: DynSGD`` — commits scaled by
    ``1/(τ+1)``; see ``DynSGDMerge`` for the deterministic lockstep lowering.
    """

    default_window = 10

    def allocate_merge_rule(self) -> MergeRule:
        return DynSGDMerge()
