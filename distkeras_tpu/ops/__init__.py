"""Numerical ops: losses, metrics, and Pallas TPU kernels
(``ops.pallas_kernels.fused_adam``, selectable as
``worker_optimizer="fused_adam"``)."""

import contextlib
import contextvars
import importlib

from distkeras_tpu.ops import losses, metrics
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.metrics import accuracy


def __getattr__(name):
    # pallas modules import jax.experimental.pallas; keep them lazy so plain
    # loss/metric users never pay for it. import_module, not `from … import`:
    # the latter asks this hook for the attribute first and recurses.
    if name in ("pallas_kernels", "quant"):
        return importlib.import_module(f"{__name__}.{name}")
    raise AttributeError(f"module 'distkeras_tpu.ops' has no attribute {name!r}")


#: What a rematted block keeps of its first forward beside its input, by the
#: name ``jax.ad_checkpoint.checkpoint_name`` gives it where it is made;
#: everything else the block's backward computes again. ``flash_out`` and
#: ``flash_lse`` are the flash forward's output and per-row log-sum-exp
#: (``flash_attention._fa_fwd``): BOTH, because the kernel runs again for
#: whichever of its two results is missing; ``router_bias`` is the balanced
#: selection bias (``models.lm._mlp_router``), whose sorts are not run twice.
REMAT_SAVED = ("flash_out", "flash_lse", "router_bias")


def remat_policy():
    """The ``jax.checkpoint`` policy of every rematted block (``nn.remat`` in
    ``models.lm`` and ``models.transformer``): save :data:`REMAT_SAVED`."""
    import jax

    return jax.checkpoint_policies.save_only_these_names(*REMAT_SAVED)


def native_kernels() -> bool:
    """True when Pallas kernels compile for the chip, False when they run in
    the interpreter — THE place the ops ask which backend they are on. It
    only ever picks how a kernel runs and what ``"auto"`` means; a kernel
    that was asked for by name is never swapped for its reference here."""
    import jax

    return jax.default_backend() == "tpu"


def interpreted(interpret: bool | None) -> bool:
    """A kernel's ``interpret`` argument resolved: as given, or by default
    "compiled on a TPU, interpreter elsewhere"."""
    return not native_kernels() if interpret is None else bool(interpret)


#: (mesh, batch axis) of the SPMD step being traced — see kernel_mesh()
_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "distkeras_kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh(mesh, batch_axis: str):
    """While a program over ``mesh`` is being TRACED, tell the kernels which
    mesh axis its batches are split on. The compiler partitions XLA ops by
    itself but refuses a Mosaic kernel inside a jit over more than one
    device ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map") — which interpret mode on a virtual CPU mesh
    never shows. An engine that jits over a mesh enters this around the
    model's forward AND backward; :func:`on_each_device` does the wrapping."""
    token = _KERNEL_MESH.set((mesh, batch_axis))
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def on_each_device(kernel, *arrays, whole=(), out_dims=None):
    """``kernel(*arrays, *whole)`` for a kernel that is independent across
    dim 0 of every one of ``arrays`` (``None`` entries pass through) and of
    its results (or, for a kernel of several results, across the dim of each
    that ``out_dims`` names): under :func:`kernel_mesh`, a ``shard_map`` in
    which each device runs it on its own rows — split on the batch axis,
    whole along every other mesh axis — and on all of every array in
    ``whole`` (a weight, a table); with no mesh declared, on a one-device
    mesh, or inside a region that is already manual (a strategy's own
    ``shard_map``), the plain call."""
    import jax
    from jax.sharding import PartitionSpec as P

    declared = _KERNEL_MESH.get()
    if (declared is None or declared[0].size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return kernel(*arrays, *whole)
    mesh, batch_axis = declared
    # a mesh without that axis (tp only): every device runs the whole batch
    axis = batch_axis if batch_axis in mesh.axis_names else None
    rows = P(axis)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=tuple(None if a is None else rows for a in arrays)
        + (P(),) * len(whole),
        out_specs=rows if out_dims is None
        else tuple(P(*(None,) * d, axis) for d in out_dims),
        check_vma=False,
    )(*arrays, *whole)


def kernel_impl(op: str, impl: str = "auto", **dims) -> str:
    """Which implementation a call to ``op`` with these dims will take.

    ``kernel_impl("attention", L=2048)`` → ``"flash"`` | ``"reference"``;
    ``kernel_impl("lstm_scan", B=64, H=512)`` and
    ``kernel_impl("q_matmul", k=2048, n=8192)`` → ``"pallas"`` |
    ``"xla"``, as is ``kernel_impl("qk_prep", "pallas", S=8192, D=128)``
    (what stands between a q / k projection and the flash kernels) and
    ``kernel_impl("mla_prep", "pallas", S=8192, nope=128, rope=64, v=128,
    heads=32)`` (between latent attention's projections and them). The
    dispatchers themselves (``flash_attention.attention``,
    ``recurrent.lstm_scan``, ``quant.q_matmul``, ``QKNormAttention``,
    ``LatentAttention``) decide
    through the same functions, so what this returns is what runs — the
    answer a smoke run or a test asserts on instead of trusting that
    ``"auto"`` found the chip.
    """
    resolvers = {
        "attention": ("flash_attention", "attention_impl"),
        "lstm_scan": ("recurrent", "lstm_impl"),
        "q_matmul": ("quant", "q_matmul_impl"),
        "qk_prep": ("qk_prep", "qk_prep_impl"),
        "mla_prep": ("mla_prep", "mla_prep_impl"),
        "gated_delta": ("gated_delta", "gated_delta_impl"),
    }
    if op not in resolvers:
        raise ValueError(f"unknown op {op!r}; one of {sorted(resolvers)}")
    module, fn = resolvers[op]
    mod = importlib.import_module(f"{__name__}.{module}")
    return getattr(mod, fn)(impl, **dims)


__all__ = ["losses", "metrics", "get_loss", "accuracy", "interpreted",
           "kernel_impl", "kernel_mesh", "native_kernels", "on_each_device"]
