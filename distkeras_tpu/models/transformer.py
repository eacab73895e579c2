"""Transformer encoder classifier — the long-context model family.

Beyond-reference addition (the Spark-era reference's newest model was an
LSTM): a pre-norm transformer encoder whose attention runs through the same
math as :mod:`distkeras_tpu.parallel.sequence` — single-device training uses
:func:`attention_reference`, and the identical per-head computation can be
executed sequence-parallel with :func:`ring_attention` on a mesh (equality is
pinned by tests/test_sequence_parallel.py). bf16 activations keep the QKV/MLP
matmuls on the MXU; all control flow is static for XLA.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import ops
from distkeras_tpu.model import ModelSpec, from_flax
from distkeras_tpu.parallel.mesh import put_global
from distkeras_tpu.parallel.sequence import attention_reference


def sincos_positions(maxlen: int, dim: int) -> np.ndarray:
    """Fixed sinusoidal position table [maxlen, dim] (Vaswani et al. 2017)."""
    pos = np.arange(maxlen)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    table = np.zeros((maxlen, dim), np.float32)
    table[:, 0::2] = np.sin(angle)
    table[:, 1::2] = np.cos(angle)
    return table


def attention_sublayer(x, mask, *, dim, heads, causal, dtype,
                       attn_impl: str = "reference",
                       sp_axis: str | None = None, sp_size: int | None = None,
                       attn_window: int | None = None):
    """Pre-norm self-attention + residual, shared by the dense and MoE
    encoder blocks (must be called from a compact ``__call__``).

    Layer names are load-bearing: parallel.tensor.megatron_specs shards
    qkv/mlp_up column-wise and attn_out/mlp_down row-wise over 'tp'.
    ``attn_impl``: "reference" (XLA einsums), "flash" (the Pallas kernel in
    ops.flash_attention), "auto" (kernel when shapes are tile-friendly), or
    "ring" (sequence-parallel ring attention — only valid when the caller is
    already inside ``shard_map`` over mesh axis ``sp_axis`` of size
    ``sp_size``, with ``x``/``mask`` holding this shard's sequence slice).
    ``attn_window``: sliding-window (local) attention span — on the flash
    path the kernel only visits in-band tiles, so long-context compute
    scales as O(L·window).
    """
    B, L, _ = x.shape
    h = nn.LayerNorm(dtype=jnp.float32, name="ln_attn")(x)
    qkv = nn.Dense(3 * dim, dtype=dtype, name="qkv")(h.astype(dtype))
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shape = (B, L, heads, dim // heads)
    q, k, v = (t.reshape(shape) for t in (q, k, v))
    if attn_impl == "ring":
        from distkeras_tpu.parallel.sequence import ring_attention_shard

        window = attn_window
        if window is not None and window >= sp_size * L:
            window = None  # band covers the whole (global) sequence
        # no f32 pre-cast: the ring body casts per block internally, and
        # rotating K/V in bf16 halves the per-step ICI payload; under a
        # window the ring only rotates through the band's blocks
        att = ring_attention_shard(
            q, k, v, mask,
            axis_name=sp_axis, axis_size=sp_size, causal=causal,
            scale=(dim // heads) ** -0.5, window=window,
        )
    elif attn_impl == "reference":
        att = attention_reference(q, k, v, causal=causal, key_mask=mask,
                                  window=attn_window)
    else:
        from distkeras_tpu.ops.flash_attention import attention

        att = attention(q, k, v, causal=causal, key_mask=mask,
                        impl=attn_impl, window=attn_window)
    att = att.reshape(B, L, dim)
    return x + nn.Dense(dim, dtype=dtype, name="attn_out")(
        att.astype(dtype)
    ).astype(jnp.float32)


class EncoderBlock(nn.Module):
    dim: int
    heads: int
    mlp_ratio: int = 4
    causal: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "reference"
    sp_axis: str | None = None   # set (with sp_size) for attn_impl="ring"
    sp_size: int | None = None
    attn_window: int | None = None  # sliding-window (local) attention span

    @nn.compact
    def __call__(self, x, mask=None, training: bool = False):
        x = attention_sublayer(x, mask, dim=self.dim, heads=self.heads,
                               causal=self.causal, dtype=self.dtype,
                               attn_impl=self.attn_impl,
                               sp_axis=self.sp_axis, sp_size=self.sp_size,
                               attn_window=self.attn_window)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln_mlp")(x)
        h = nn.Dense(self.mlp_ratio * self.dim, dtype=self.dtype,
                     name="mlp_up")(h.astype(self.dtype))
        h = nn.gelu(h)
        h = nn.Dense(self.dim, dtype=self.dtype, name="mlp_down")(h)
        return x + h.astype(jnp.float32)


class TransformerClassifier(nn.Module):
    """Token sequence → class logits (IMDB-style inputs: tokens + mask).

    Setup-style so the encoder stack is addressable piecewise: the
    ``embed_tokens`` / ``head_logits`` methods and the per-block params
    (``blocks_0 … blocks_{depth-1}``) let
    :func:`pipelined_transformer_forward` run the homogeneous block stack
    pipeline-parallel over a ``pp`` mesh axis while embed/head stay
    replicated.
    """

    vocab: int = 20000
    maxlen: int = 200
    dim: int = 128
    heads: int = 4
    depth: int = 2
    num_classes: int = 2
    causal: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "reference"
    sp_axis: str | None = None   # set (with sp_size) for attn_impl="ring"
    sp_size: int | None = None
    attn_window: int | None = None  # sliding-window (local) attention span
    #: rematerialize each block's activations in the backward pass
    #: (jax.checkpoint): ~L·dim per block of saved activations traded for
    #: one extra forward — the standard long-context memory lever. Under
    #: ``attn_impl="flash"`` a block also keeps the kernel's output and
    #: log-sum-exp (``ops.REMAT_SAVED``: ``L·dim`` of ``dtype`` more), and
    #: the extra forward is one without the flash kernel
    remat: bool = False

    def setup(self):
        self.embed = nn.Embed(self.vocab, self.dim, dtype=self.dtype)
        # nn.remat preserves the params tree (blocks_i names unchanged), so
        # checkpoints/megatron specs/pipelining all work regardless of remat;
        # training (arg 3, counting self as 0) is a static python bool
        block_cls = (nn.remat(EncoderBlock, static_argnums=(3,),
                              policy=ops.remat_policy())
                     if self.remat else EncoderBlock)
        self.blocks = [
            block_cls(dim=self.dim, heads=self.heads, causal=self.causal,
                      dtype=self.dtype, attn_impl=self.attn_impl,
                      sp_axis=self.sp_axis, sp_size=self.sp_size,
                      attn_window=self.attn_window)
            for _ in range(self.depth)
        ]
        self.ln_head = nn.LayerNorm(dtype=jnp.float32)
        self.head = nn.Dense(self.num_classes, dtype=self.dtype)

    def embed_tokens(self, tokens):
        x = self.embed(tokens)
        table = jnp.asarray(sincos_positions(self.maxlen, self.dim))
        if self.sp_axis is not None:
            # this shard holds sequence positions [off, off + L_local)
            off = jax.lax.axis_index(self.sp_axis) * tokens.shape[1]
            pos = jax.lax.dynamic_slice(
                table, (off, 0), (tokens.shape[1], self.dim)
            )
        else:
            pos = table[: tokens.shape[1]]
        return x.astype(jnp.float32) + pos[None]

    def head_logits(self, x, mask):
        m = mask.astype(jnp.float32)[..., None]
        num = jnp.sum(x * m, axis=1)
        den = jnp.sum(m, axis=1)
        if self.sp_axis is not None:
            # masked mean over the FULL sequence: combine shard partials
            num = jax.lax.psum(num, self.sp_axis)
            den = jax.lax.psum(den, self.sp_axis)
        pooled = num / jnp.maximum(den, 1.0)
        h = self.ln_head(pooled)
        return self.head(h.astype(self.dtype)).astype(jnp.float32)

    def __call__(self, tokens, mask=None, training: bool = False):
        if mask is None:
            mask = jnp.ones(tokens.shape, jnp.float32)
        x = self.embed_tokens(tokens)
        for blk in self.blocks:
            x = blk(x, mask, training)
        return self.head_logits(x, mask)


def pipelined_transformer_forward(module: TransformerClassifier, params,
                                  tokens, mask, mesh, axis: str = "pp",
                                  microbatches: int | None = None,
                                  batch_axis: str | None = None):
    """Transformer forward with the encoder blocks pipelined over ``axis``.

    Embed and head run replicated; the ``depth`` homogeneous blocks are the
    pipeline stages (``depth == mesh.shape[axis]`` required). Numerically
    equal to ``module.apply`` (pinned by tests/test_pipeline_parallel.py) and
    differentiable, so a full training step can run pipeline-parallel.
    """
    from distkeras_tpu.parallel.pipeline import (
        pipeline_apply,
        stack_stage_params,
    )

    if module.depth != mesh.shape[axis]:
        raise ValueError(
            f"depth {module.depth} != mesh axis '{axis}' size "
            f"{mesh.shape[axis]}"
        )
    if mask is None:
        mask = jnp.ones(tokens.shape, jnp.float32)
    x = module.apply({"params": params}, tokens,
                     method=TransformerClassifier.embed_tokens)
    stage_params = stack_stage_params(
        [params[f"blocks_{i}"] for i in range(module.depth)]
    )
    impl = "reference" if module.attn_impl == "ring" else module.attn_impl
    block = EncoderBlock(dim=module.dim, heads=module.heads,
                         causal=module.causal, dtype=module.dtype,
                         attn_impl=impl, attn_window=module.attn_window)

    def stage(p, act):
        h, m = act
        return block.apply({"params": p}, h, m, False), m

    x, _ = pipeline_apply(stage, stage_params, (x, mask), mesh, axis=axis,
                          microbatches=microbatches, batch_axis=batch_axis)
    return module.apply({"params": params}, x, mask,
                        method=TransformerClassifier.head_logits)


def sequence_parallel_transformer_forward(module: TransformerClassifier,
                                          params, tokens, mask, mesh,
                                          axis: str = "sp",
                                          batch_axis: str | None = None):
    """Full transformer forward with activations sharded along L over ``axis``.

    One ``shard_map`` program: every pointwise layer (embed lookup, layernorm,
    QKV/MLP matmuls) runs on its shard's sequence slice, attention is the
    ring-rotation body from :mod:`distkeras_tpu.parallel.sequence`
    (``ppermute`` K/V/mask exchanges over ICI), position embeddings are
    offset per shard, and the masked-mean head combines shard partials with
    ``psum``. Per-chip activation memory is O(L/N) — context length scales
    linearly with the mesh. Numerically equal to ``module.apply`` on the
    gathered sequence (pinned by tests/test_sequence_parallel.py) and
    differentiable, so full training steps run sequence-parallel.

    ``batch_axis`` composes data parallelism on a 2-D mesh (e.g.
    ``get_mesh_nd({"dp": 2, "sp": 4})``): the batch dimension shards over
    ``batch_axis``, the sequence over ``axis``, and the returned logits are
    sharded over ``batch_axis`` — a dp×sp training step when differentiated
    (the batch-mean loss's gradient psum over dp is inserted by GSPMD).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    N = mesh.shape[axis]
    L = tokens.shape[1]
    if L % N:
        raise ValueError(f"sequence length {L} not divisible by mesh axis "
                         f"'{axis}' of size {N}")
    if L > module.maxlen:
        raise ValueError(
            f"sequence length {L} exceeds the model's maxlen "
            f"{module.maxlen} (the plain forward would fail too)"
        )
    if batch_axis is not None and tokens.shape[0] % mesh.shape[batch_axis]:
        raise ValueError(
            f"batch {tokens.shape[0]} not divisible by mesh axis "
            f"'{batch_axis}' of size {mesh.shape[batch_axis]}"
        )
    if mask is None:
        mask = jnp.ones(tokens.shape, jnp.float32)
    shard_fn = _sp_forward_fn(
        module.clone(attn_impl="ring", sp_axis=axis, sp_size=N), mesh, axis,
        batch_axis,
    )
    sh = NamedSharding(mesh, P(batch_axis, axis))
    tokens = put_global(tokens, sh)
    mask = put_global(mask, sh)
    return shard_fn(params, tokens, mask)


@functools.lru_cache(maxsize=32)
def _sp_forward_fn(smod, mesh, axis, batch_axis=None):
    """Build + jit the shard_map'd SP forward once per
    (module, mesh, axis, batch_axis);
    flax modules are frozen dataclasses, so they key the cache by config.
    Without this every call would rebuild shard_map and recompile."""
    from jax.sharding import PartitionSpec as P

    def body(params, toks_l, mask_l):
        return smod.apply({"params": params}, toks_l, mask_l, False)

    io = P(batch_axis, axis)
    # P() is a pytree PREFIX: it broadcasts over the whole params tree
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), io, io),
        out_specs=P(batch_axis),
        check_vma=False,
    ))


def transformer_classifier(vocab=20000, maxlen=200, dim=128, heads=4, depth=2,
                           num_classes=2, causal=False,
                           dtype=jnp.bfloat16,
                           attn_impl="reference",
                           remat=False,
                           attn_window=None) -> ModelSpec:
    module = TransformerClassifier(
        vocab=vocab, maxlen=maxlen, dim=dim, heads=heads, depth=depth,
        num_classes=num_classes, causal=causal, dtype=dtype,
        attn_impl=attn_impl, remat=remat, attn_window=attn_window,
    )
    example = (
        jnp.zeros((1, maxlen), jnp.int32),
        jnp.ones((1, maxlen), jnp.float32),
    )
    return from_flax(module, example, name="transformer_classifier")
