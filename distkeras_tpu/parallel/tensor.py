"""Tensor parallelism — Megatron-style parameter sharding via GSPMD.

The reference has no tensor parallelism of any kind (SURVEY.md §2b.2: its only
strategy is PS-based data parallelism), so nothing here is a port: this is the
TPU-native model-parallel extension for models whose weight matrices outgrow
one chip.

The design is the idiomatic XLA recipe — *pick a mesh, annotate shardings, let
the compiler insert collectives*: parameters are placed with
``jax.sharding.NamedSharding`` partition specs (column-parallel for QKV and
MLP-up kernels, row-parallel for attention-out and MLP-down, vocab-parallel
for the embedding — Shoeybi et al. 2019), the batch is sharded over the
``dp`` axis, and GSPMD propagates the shardings through the jitted train step,
lowering the row-parallel contractions to ``psum`` over ICI. No hand-written
collectives, no Python in the loop — one compiled SPMD program whose math is
bit-for-bit the single-device program's (pinned by tests/test_tensor_parallel.py
on an 8-device dp×tp mesh).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.observability import programs
from distkeras_tpu.ops import kernel_mesh
from distkeras_tpu.parallel.mesh import put_global


def get_mesh_nd(axes: dict[str, int], devices=None) -> Mesh:
    """Build an N-D mesh, e.g. ``get_mesh_nd({'dp': 2, 'tp': 4})``.

    The product of axis sizes must equal the device count used. Axis order is
    the dict order: put the fastest-communicating axis (tp) last so it maps to
    the innermost/nearest devices on a real slice.
    """
    devices = list(devices if devices is not None else jax.devices())
    sizes = [int(s) for s in axes.values()]
    need = int(np.prod(sizes))
    if need > len(devices):
        raise ValueError(f"mesh {axes} needs {need} devices, have {len(devices)}")
    if need < len(devices):
        import warnings

        warnings.warn(
            f"mesh {axes} uses {need} of {len(devices)} visible devices; "
            f"the rest stay idle",
            stacklevel=2,
        )
    grid = np.asarray(devices[:need]).reshape(sizes)
    return Mesh(grid, tuple(axes.keys()))


# ---------------------------------------------------------------------------
# Partition-spec rules
# ---------------------------------------------------------------------------

#: layer-name → (kernel spec maker, bias spec maker); `tp` filled in at call
_MEGATRON_RULES: dict[str, tuple] = {
    # column-parallel: output features split over tp
    "qkv": (lambda tp: P(None, tp), lambda tp: P(tp)),
    "mlp_up": (lambda tp: P(None, tp), lambda tp: P(tp)),
    # row-parallel: input features split over tp (GSPMD inserts the psum)
    "attn_out": (lambda tp: P(tp, None), lambda tp: P()),
    "mlp_down": (lambda tp: P(tp, None), lambda tp: P()),
}


def megatron_specs(params, tp_axis: str = "tp"):
    """PartitionSpec pytree for a transformer params tree (Megatron layout).

    Matches the explicit layer names used by
    :class:`distkeras_tpu.models.transformer.TransformerClassifier`
    (``qkv/attn_out/mlp_up/mlp_down/embed``); everything else (layernorms,
    the small classifier head) is replicated. Works for any pytree — unknown
    leaves just get ``P()``.
    """

    def spec_for(path, leaf):
        keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        for k in keys:
            if k in _MEGATRON_RULES:
                kern, bias = _MEGATRON_RULES[k]
                last = keys[-1]
                if last == "kernel" and leaf.ndim == 2:
                    return kern(tp_axis)
                if last == "bias" and leaf.ndim == 1:
                    return bias(tp_axis)
            if k == "embed" and keys[-1] == "embedding" and leaf.ndim == 2:
                return P(tp_axis, None)  # vocab-parallel embedding table
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def shard_pytree(tree, mesh: Mesh, specs):
    """Place a host pytree onto the mesh per a PartitionSpec pytree."""
    return jax.tree.map(
        lambda x, s: put_global(x, NamedSharding(mesh, s)), tree, specs
    )


def batch_sharding(mesh: Mesh, dp_axis: str = "dp") -> NamedSharding:
    """Sharding for input batches: leading (batch) axis over ``dp``."""
    return NamedSharding(mesh, P(dp_axis))


# ---------------------------------------------------------------------------
# The SPMD train step
# ---------------------------------------------------------------------------


class SPMDEngine:
    """Sync SPMD training of ONE model over a (dp, tp) mesh.

    Unlike :class:`~distkeras_tpu.parallel.local_sgd.LocalSGDEngine` (which
    stacks W independent replicas and merges them through an algorithm's
    rule), this engine trains a single set of parameters with standard
    synchronous data parallelism over ``dp`` and Megatron tensor parallelism
    over ``tp`` — gradients are averaged over the whole global batch by the
    same contraction that computes them, so the math equals single-device
    training on the global batch.

    ``loss_step(params, nt, batch) -> (loss, new_nt)`` as elsewhere.

    ``grad_accum=A`` splits each global batch into A equal microbatches and
    accumulates their gradients in a ``lax.scan`` before the single optimizer
    update — activation memory drops ~A× while the update stays the
    full-batch one (exactly for loss/gradients over equal-size mean-loss
    microbatches; pinned by tests/test_fsdp.py). Non-trainable state ``nt``
    (e.g. BatchNorm running stats) is threaded through the scan and updated
    once per microbatch, so it follows standard grad-accum semantics rather
    than matching a single full-batch step. The scan carry holds one
    grads-sized buffer, not A of them.
    """

    def __init__(self, spec, loss_step, optimizer, mesh: Mesh,
                 param_specs=None, dp_axis: str = "dp",
                 tp_axis: str = "tp", grad_accum: int = 1):
        self.spec = spec
        self.loss_step = loss_step
        self.optimizer = optimizer
        self.mesh = mesh
        self.dp_axis = dp_axis
        self.tp_axis = tp_axis
        self.grad_accum = int(grad_accum)
        if self.grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        self.param_specs = param_specs  # resolved at init_state
        self._batch_sharding = batch_sharding(mesh, dp_axis)
        self._step = None
        self._step_fn = None
        self._step_handle = None
        self._resident = None

    def _resolve_specs(self, params):
        if self.param_specs is None:
            if self.tp_axis in self.mesh.shape:
                self.param_specs = megatron_specs(params, self.tp_axis)
            else:
                # dp-only mesh: the documented layout is plain replication
                self.param_specs = jax.tree.map(lambda _: P(), params)

    def init_state(self, params, nt):
        """Shard params per the specs; opt state pinned to the same layout."""
        self._resolve_specs(params)
        params = shard_pytree(params, self.mesh, self.param_specs)
        rep = NamedSharding(self.mesh, P())
        nt = jax.tree.map(lambda x: put_global(x, rep), nt)
        # moments/accumulators inherit the params' layout (with FSDP specs
        # this IS ZeRO optimizer-state partitioning); scalars replicate
        def train_init_state(params):
            return self.optimizer.init(params)

        opt_state = jax.jit(
            train_init_state, out_shardings=self._opt_shardings(params)
        )(params)
        self._build_step()
        return params, nt, opt_state

    def place_state(self, params, nt, opt_state):
        """Place restored host state onto the mesh (the resume path): params
        per the specs, optimizer state back into its ZeRO/Megatron layout."""
        self._resolve_specs(params)
        params = shard_pytree(params, self.mesh, self.param_specs)
        rep = NamedSharding(self.mesh, P())
        nt = jax.tree.map(lambda x: put_global(x, rep), nt)
        opt_state = jax.tree.map(put_global, opt_state,
                                 self._opt_shardings(params))
        self._build_step()
        return params, nt, opt_state

    def _opt_shardings(self, params):
        """Sharding tree for ``optimizer.init``'s output: any params-shaped
        subtree (adam mu/nu, momentum trace, …) gets ``param_specs``; every
        other leaf (step counts, schedules) is replicated. Leaves whose shape
        differs from the matching param (adafactor's factored v_row/v_col)
        also replicate — their layout is the compiler's to choose."""
        ptreedef = jax.tree.structure(params)
        opt_shapes = jax.eval_shape(self.optimizer.init, params)

        def params_like(x):
            return (not isinstance(x, jax.ShapeDtypeStruct)
                    and jax.tree.structure(x) == ptreedef)

        def sub_specs(sub):
            return jax.tree.map(
                lambda spec, p, o: (spec if tuple(p.shape) == tuple(o.shape)
                                    else P()),
                self.param_specs, params, sub,
            )

        specs = jax.tree.map(
            lambda sub: (sub_specs(sub) if params_like(sub)
                         else jax.tree.map(lambda _: P(), sub)),
            opt_shapes, is_leaf=params_like,
        )
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)

    def _build_step(self):
        tx, loss_step = self.optimizer, self.loss_step
        mesh, specs = self.mesh, self.param_specs
        A, dp_axis = self.grad_accum, self.dp_axis

        def grads_of(params, nt, batch):
            if A == 1:
                return jax.value_and_grad(loss_step, has_aux=True)(
                    params, nt, batch
                )
            # [B, …] → [A, B/A, …], microbatch dim sharded over dp
            mb_sh = NamedSharding(mesh, P(None, dp_axis))
            mbs = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x.reshape((A, x.shape[0] // A) + x.shape[1:]), mb_sh
                ),
                batch,
            )

            def micro(carry, mb):
                nt_c, acc, loss_sum = carry
                (loss, new_nt), g = jax.value_and_grad(
                    loss_step, has_aux=True
                )(params, nt_c, mb)
                acc = jax.tree.map(jnp.add, acc, g)
                return (new_nt, acc, loss_sum + loss), None

            zero = jax.tree.map(jnp.zeros_like, params)
            (nt, acc, loss_sum), _ = jax.lax.scan(
                micro, (nt, zero, jnp.zeros((), jnp.float32)), mbs
            )
            grads = jax.tree.map(lambda g: g / A, acc)
            return (loss_sum / A, nt), grads

        # the functions' names are the programs' in a profiler trace
        # (``jit_train_step`` on its ``XLA Modules`` line) and in the run
        # log's ``jax.compile`` entries
        def train_step(params, nt, opt_state, batch):
            # forward AND backward are traced in here: a Pallas kernel in
            # the model runs per device on its own rows of the dp split
            with kernel_mesh(mesh, dp_axis):
                (loss, new_nt), grads = grads_of(params, nt, batch)
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            # pin the output layout so donation reuses the input buffers
            params = jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, s)
                ),
                params, specs,
            )
            return params, new_nt, opt_state, loss

        self._step_fn = train_step
        self._step = jax.jit(train_step, donate_argnums=(0, 2))
        self._step_handle = None
        self._resident = None

    def _check_batch(self, B: int):
        dp = self.mesh.shape.get(self.dp_axis, 1)
        if B % dp:
            raise ValueError(
                f"global batch size {B} not divisible by mesh axis "
                f"'{self.dp_axis}' of size {dp}"
            )
        if B % (self.grad_accum * dp):
            raise ValueError(
                f"global batch size {B} not divisible by grad_accum "
                f"{self.grad_accum} × dp {dp} = {self.grad_accum * dp}"
            )

    def place_batch(self, batch_arrays: tuple) -> tuple:
        """Host batch → dp-sharded global arrays (run_step's placement,
        exposed so the prefetching input pipeline can do it ahead of time
        on a background thread — ``data.prefetch_to_device``)."""
        return tuple(
            put_global(a, self._batch_sharding) for a in batch_arrays
        )

    def run_step(self, params, nt, opt_state, batch_arrays: tuple):
        """One global-batch step; ``batch_arrays`` host arrays ``[B, …]``
        (or already-placed global arrays from :meth:`place_batch`)."""
        self._check_batch(batch_arrays[0].shape[0])
        if not isinstance(batch_arrays[0], jax.Array):
            batch_arrays = self.place_batch(batch_arrays)
        out = self._step(params, nt, opt_state, batch_arrays)
        if self._step_handle is None:
            # after the first call, when the trace is cached: what the step's
            # compiled text can be had from again (observability.programs)
            self._step_handle = programs.note(
                "train_step", self._step, (params, nt, opt_state, batch_arrays))
        return out

    # -- device-resident epoch (upload once, whole epoch in one dispatch) ----

    def stage_epoch(self, col_arrays: tuple):
        """Upload full data columns ``[N, …]`` once, rows sharded over dp.

        The resident counterpart of the per-step host feed: after this, an
        epoch is ONE dispatch with zero host↔device traffic (mirrors
        ``LocalSGDEngine.stage_dataset`` — the rebuilt ``rdd.repartition``).
        """
        return tuple(put_global(a, self._batch_sharding) for a in col_arrays)

    def run_epoch_resident(self, params, nt, opt_state, staged: tuple,
                           batch_size: int, shuffle_seed: int | None):
        """One epoch over staged columns in one jitted scan.

        Shuffles on device when ``shuffle_seed`` is given (a global
        permutation — rows migrate across dp shards through XLA collectives).
        Rows beyond the last full batch are dropped, matching the streaming
        path's ``Dataset.batches``. Returns ``(params, nt, opt_state,
        losses[S])``.
        """
        if self._resident is None:
            self._build_resident()
        self._check_batch(int(batch_size))
        key = jax.random.PRNGKey(0 if shuffle_seed is None else shuffle_seed)
        return self._resident(params, nt, opt_state, staged, key,
                              shuffle_seed is not None, int(batch_size))

    def _build_resident(self):
        mesh, dp_axis = self.mesh, self.dp_axis
        step = self._step_fn

        def train_epoch_resident(params, nt, opt_state, staged, key,
                                 do_shuffle, B):
            rows = staged[0].shape[0]
            S = rows // B
            if do_shuffle:
                perm = jax.random.permutation(key, rows)
                staged = tuple(jnp.take(c, perm, axis=0) for c in staged)
            mb_sh = NamedSharding(mesh, P(None, dp_axis))
            data = tuple(
                jax.lax.with_sharding_constraint(
                    c[: S * B].reshape((S, B) + c.shape[1:]), mb_sh
                )
                for c in staged
            )

            def body(carry, b):
                p, n, o = carry
                p, n, o, loss = step(p, n, o, b)
                return (p, n, o), loss

            (params, nt, opt_state), losses = jax.lax.scan(
                body, (params, nt, opt_state), data
            )
            return params, nt, opt_state, losses

        self._resident = jax.jit(
            train_epoch_resident, donate_argnums=(0, 2),
            static_argnums=(5, 6),
        )


def assert_param_shardings(params, specs, mesh: Mesh):
    """Test helper: every leaf carries exactly its requested NamedSharding."""

    def check(path, leaf, spec):
        want = NamedSharding(mesh, spec)
        got = leaf.sharding
        if not got.is_equivalent_to(want, leaf.ndim):
            raise AssertionError(
                f"{jax.tree_util.keystr(path)}: sharding {got} != {want}"
            )

    jax.tree_util.tree_map_with_path(check, params, specs)
