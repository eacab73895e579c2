"""Int8 weight-only quantization: TPU-native serving for trained models.

Beyond-reference (the Spark-era reference served float32 Keras weights and
nothing else — SURVEY.md §2b #15): symmetric per-output-channel int8
post-training quantization, built for the TPU memory system.

Why weight-only, and why a Pallas kernel:

- **Autoregressive decode is HBM-bandwidth-bound.** Every decode step
  streams every weight matrix once to multiply a tiny ``[B, 1, d]``
  activation. Int8 weights halve the bytes per step, which is what bounds
  a step in the weight-dominated regime (small batch, cache smaller than
  the weights).
- **The dequant must happen AFTER the HBM read.** An XLA-level
  ``q.astype(bf16) * scale`` before the matmul is loop-invariant inside
  the decode ``lax.scan`` — the compiler may hoist it and materialize a
  full bf16 copy in HBM, forfeiting the entire win. The Pallas kernel
  makes the schedule explicit: int8 tiles stream HBM→VMEM, are widened to
  bf16 in-register, hit the MXU, and the per-channel scale is applied to
  the f32 accumulator. No bf16 weight tensor ever exists in HBM.
- **Activations stay bf16.** v5e's MXU runs int8×int8 at 2× bf16 peak,
  but decode is nowhere near compute-bound — weight-only takes the
  bandwidth win and keeps activation precision (no calibration needed).

Accuracy: symmetric absmax per output channel; the scale is exact in f32
and applied after the f32 accumulation, so ``q_matmul`` equals the exact
``x @ (q · scale)`` product up to matmul dtype rounding (pinned by
tests/test_quant.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distkeras_tpu import ops

_LANES = 128
_MAX_K = 8192   # deepest contraction one full-depth tile pair still fits
#: bytes the kernel's tiles may take: Mosaic's default scoped-VMEM limit is
#: 16 MiB on a v5e (the smallest of the supported chips); the rest is headroom
_VMEM_BUDGET = 15 * 2 ** 20


class QTensor(NamedTuple):
    """An int8-quantized matrix: ``q [K, N] int8`` with per-output-channel
    ``scale [N] f32``; the represented value is ``q.astype(f32) * scale``."""

    q: jax.Array
    scale: jax.Array


def quantize(w, axis: int = 0) -> QTensor:
    """Symmetric absmax int8 quantization of a 2-D weight.

    ``axis`` is the reduction (input) dimension of the matmul the weight
    feeds — scales are per *output* channel, so dequantization commutes
    with the contraction and can be applied to the accumulator.
    """
    w = jnp.asarray(w)
    if w.ndim != 2:
        raise ValueError(f"quantize expects a 2-D weight, got {w.shape}")
    if axis not in (0, 1):
        raise ValueError(f"axis must be 0 or 1, got {axis}")
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(wf / jnp.expand_dims(scale, axis))
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return QTensor(q=q, scale=scale)


def dequantize(qt: QTensor, axis: int = 0, dtype=jnp.float32):
    """Materialize the represented weight (test/debug path — the runtime
    paths never do this in HBM)."""
    return (qt.q.astype(jnp.float32)
            * jnp.expand_dims(qt.scale, axis)).astype(dtype)


def _q_matmul_xla(x, qt: QTensor, out_dtype):
    """Reference lowering: widen-in-graph matmul, scale on the f32 result.

    Matches the kernel bit-for-bit in f32 and is the fallback wherever the
    kernel's tiling constraints don't hold. (Inside a decode scan XLA may
    hoist the widening — that is exactly what the Pallas path prevents.)
    """
    acc = jax.lax.dot_general(
        x, qt.q.astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (acc * qt.scale).astype(out_dtype)


def _q_matmul_kernel(x_ref, q_ref, s_ref, o_ref):
    """One output tile: int8 weight tile → bf16 in-register → MXU → scale."""
    w = q_ref[...].astype(x_ref.dtype)
    acc = jnp.dot(x_ref[...], w, preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def _pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


@functools.partial(jax.jit, static_argnames=("bm", "bn", "out_dtype",
                                             "interpret"))
def _q_matmul_pallas(x2, q, scale, *, bm, bn, out_dtype, interpret):
    m, k = x2.shape
    n = q.shape[1]
    mp = _pad_to(m, bm)
    xp = jnp.pad(x2, ((0, mp - m), (0, 0)))
    out = pl.pallas_call(
        _q_matmul_kernel,
        grid=(mp // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        interpret=interpret,
        name="q_matmul",
    )(xp, q, scale.reshape(1, n))
    return out[:m]


def _pick_tiles(m: int, k: int, n: int, x_bytes: int, o_bytes: int):
    """``(bm, bn)`` for a full-depth ``[bm, K] @ [K, bn]`` output tile.

    Start from the widest tiles (256 rows × 512 lanes) and halve whichever
    input tile takes more VMEM until the working set fits the budget. What
    the compiler allocates, read off its own refusals for a v5e: both input
    tiles and the output tile, double-buffered — at K = 8192 in bf16 the old
    fixed 256 × 512 choice came to 8 + 8 + 0.5 MiB against the 16 MiB limit,
    so every prefill of more than 240 rows was refused — and for some tile
    shapes one more ``[bm, K]`` buffer (256 × 256 was refused at 16.27 MiB
    where its tiles sum to 12.25). The budget always counts that buffer. The
    smallest tiles (16 × 128) take 3.6 MiB at K = 8192 in f32, so every
    shape the ``K <= 8192`` guard admits fits.
    """
    def lane_tile(upto):   # widest multiple of 128 that divides N
        return max(c for c in range(_LANES, upto + 1, _LANES) if n % c == 0)

    bm, bn = min(_pad_to(max(m, 1), 16), 256), lane_tile(min(n, 512))
    while True:
        x_tile, q_tile = 3 * bm * k * x_bytes, 2 * k * bn
        if x_tile + q_tile + 2 * bm * bn * o_bytes <= _VMEM_BUDGET:
            return bm, bn
        if bm > 16 and (x_tile >= q_tile or bn == _LANES):
            bm = _pad_to(bm // 2, 16)
        else:
            bn = lane_tile(bn - _LANES)


def q_matmul_impl(impl: str = "auto", *, k: int, n: int) -> str:
    """``"pallas"`` or ``"xla"``: what :func:`q_matmul` runs for a
    ``[K, N]`` weight (``ops.kernel_impl("q_matmul", …)`` is the public
    door). ``"auto"`` is the kernel whenever its tiling constraints hold
    (K and N multiples of 128, K small enough for a full-depth VMEM tile)
    — on every backend: off-TPU the kernel runs in the interpreter."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"impl must be 'auto', 'pallas', or 'xla', "
                         f"got {impl!r}")
    tileable = k % _LANES == 0 and n % _LANES == 0 and k <= _MAX_K
    if impl == "auto":
        return "pallas" if tileable else "xla"
    if impl == "pallas" and not tileable:
        raise ValueError(
            f"impl='pallas' needs K, N multiples of {_LANES} and "
            f"K <= {_MAX_K}; got K={k}, N={n} (use impl='auto' to fall back)"
        )
    return impl


def q_matmul(x, qt: QTensor, *, impl: str = "auto", out_dtype=None,
             interpret: bool | None = None):
    """``x [..., K] @ dequant(qt) [K, N] → [..., N]``.

    ``impl``: ``"pallas"`` (fused in-VMEM dequant kernel), ``"xla"``
    (widen-in-graph fallback), or ``"auto"`` — see :func:`q_matmul_impl`.
    ``interpret`` defaults to "kernel on TPU, interpreter elsewhere" so CI
    exercises the same code path on CPU.
    """
    k, n = qt.q.shape
    if x.shape[-1] != k:
        raise ValueError(f"x trailing dim {x.shape[-1]} != weight rows {k}")
    out_dtype = out_dtype or x.dtype
    if q_matmul_impl(impl, k=k, n=n) == "xla":
        return _q_matmul_xla(x, qt, out_dtype)
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, k)
    bm, bn = _pick_tiles(m, k, n, jnp.dtype(x.dtype).itemsize,
                         jnp.dtype(out_dtype).itemsize)
    out = _q_matmul_pallas(x2, qt.q, qt.scale, bm=bm, bn=bn,
                           out_dtype=out_dtype,
                           interpret=ops.interpreted(interpret))
    return out.reshape(*lead, n)


def _q_interceptor(next_fun, args, kwargs, context):
    """flax method interceptor: any ``nn.Dense`` whose params arrived
    quantized (``kernel_q``/``scale``[/``bias``] — what
    :func:`quantize_dense_tree` produces) is served by :func:`q_matmul`
    instead of its own kernel read; everything else runs unchanged."""
    import flax.linen as nn

    m = context.module
    if (type(m) is nn.Dense and context.method_name == "__call__"
            and m.has_variable("params", "kernel_q")):
        q = m.get_variable("params", "kernel_q")
        s = m.get_variable("params", "scale")
        x = args[0] if args else kwargs["inputs"]  # Dense(…)(inputs=x)
        # mirror nn.Dense's promote-to-module-dtype semantics so the
        # quantized forward keeps the fp model's compute dtypes
        cdt = m.dtype if m.dtype is not None else x.dtype
        x = x.astype(cdt)
        out = q_matmul(x, QTensor(q, s), out_dtype=cdt)
        if m.use_bias:
            out = out + jnp.asarray(
                m.get_variable("params", "bias")
            ).astype(cdt)
        return out
    return next_fun(*args, **kwargs)


def quantize_serving(spec, params, state=None):
    """Generic int8 weight-only serving for a flax-backed ``ModelSpec``.

    ``(spec, trained params) → (int8 spec, int8 params)``: the model is
    traced once (``jax.eval_shape`` on the spec's recorded example input)
    to find exactly the ``nn.Dense`` modules in the forward; their kernels
    become int8 matrices + per-output-channel scales
    (:func:`quantize_dense_tree`), and the returned spec's ``apply``
    serves them through a flax method interceptor — no model-code
    changes, so the whole zoo (MLP, the transformer classifiers, custom
    modules BUILT FROM ``nn.Dense``) quantizes the same way. Kernel/bias
    pairs owned by anything other than ``nn.Dense`` (e.g.
    ``nn.DenseGeneral``, convolutions) stay in float — the trace is what
    guarantees nothing is converted that the interceptor cannot serve.
    Inference-only: the returned apply rejects ``training=True``.
    ``models.quantize_lm`` remains the LM-family door (its ``QDense``
    modules also cover the cached-decode entry points, which never pass
    through ``nn.Dense.__call__``).
    """
    import dataclasses

    import flax.linen as nn

    if getattr(spec, "module", None) is None:
        raise ValueError(
            "quantize_serving needs a flax-backed ModelSpec (built by "
            "from_flax, e.g. the models/ zoo); Keras and hand-written "
            "specs have no flax module to intercept"
        )
    if getattr(spec, "example", None) is None:
        raise ValueError(
            "quantize_serving needs the spec's example input to trace the "
            "module (ModelSpec.example — from_flax records it)"
        )
    base_apply = spec.apply
    state = {} if state is None else state

    # trace once to record which param paths belong to real nn.Dense
    # modules reached by the serving forward
    dense_paths: set[tuple] = set()

    def record(next_fun, args, kwargs, context):
        m = context.module
        if type(m) is nn.Dense and context.method_name == "__call__":
            dense_paths.add(tuple(m.path))
        return next_fun(*args, **kwargs)

    x0 = spec.example
    x0 = x0[0] if isinstance(x0, tuple) and len(x0) == 1 else x0
    with nn.intercept_methods(record):
        jax.eval_shape(
            lambda p, s, x: base_apply(p, s, x, False), params, state, x0
        )

    def apply(params, state, x, training):
        if training:
            raise ValueError(
                "int8 weight-only quantization is a serving path; train "
                "the float model and re-quantize"
            )
        with nn.intercept_methods(_q_interceptor):
            return base_apply(params, state, x, training)

    # fused_losses closures capture the FLOAT module and param layout —
    # they must not ride into the int8 serving spec (training it is an
    # error the quantized apply raises; a stale fused fn would bypass it)
    qspec = dataclasses.replace(spec, apply=apply, name=spec.name + "_int8",
                                fused_losses=None)
    return qspec, quantize_dense_tree(params, paths=dense_paths)


def quantize_dense_tree(params, paths: set | None = None):
    """Walk a flax param tree and quantize Dense-shaped leaf groups.

    A subtree ``{"kernel": [K, N] float, "bias": ...}`` (exactly the param
    set ``nn.Dense`` creates) becomes ``{"kernel_q": int8, "scale": f32,
    "bias": ...}`` — the param set ``models.lm.QDense`` and the serving
    interceptor read. Everything else (embeddings, LayerNorm
    scales/biases, conv kernels) passes through unchanged.

    ``paths`` (from :func:`quantize_serving`'s recording trace) restricts
    conversion to subtrees KNOWN to belong to ``nn.Dense`` modules — and
    within it, bias-less Dense params (``{"kernel"}`` alone,
    ``use_bias=False``) convert too. Without ``paths`` (the
    ``quantize_lm`` door) only exact ``{kernel, bias}`` pairs convert,
    since a bare 2-D ``kernel`` could belong to anything.
    """
    from collections.abc import Mapping

    def convert(node):
        qt = quantize(node["kernel"], axis=0)
        out = {"kernel_q": qt.q, "scale": qt.scale}
        if "bias" in node:
            out["bias"] = node["bias"]
        return out

    def rec(node, path):
        if isinstance(node, Mapping):
            is_dense_shape = (
                set(node) in ({"kernel", "bias"}, {"kernel"})
                and getattr(node.get("kernel"), "ndim", 0) == 2
            )
            if paths is not None:
                if path in paths and is_dense_shape:
                    return convert(node)
            elif set(node) == {"kernel", "bias"} and is_dense_shape:
                return convert(node)
            return {k: rec(v, path + (k,)) for k, v in node.items()}
        return node

    return rec(params, ())
