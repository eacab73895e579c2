"""Decoder-only causal language model + TPU-idiomatic autoregressive decoding.

Beyond-reference model family (the Spark-era reference topped out at an LSTM
classifier — SURVEY.md §2b.2 "reference predates long-context"): a pre-norm
causal transformer LM trainable by every trainer in this framework (the
next-token objective is plain ``sparse_softmax_cross_entropy`` on the
``[B, L, V]`` logits against the shifted token labels), plus a
:func:`generate` path built the TPU way:

- **Static shapes everywhere**: the prompt is one fixed-length prefill, the
  KV cache is a preallocated ``[B, maxlen, Hkv, Dh]`` buffer per block
  (``Hkv = kv_heads`` under grouped-query attention, else ``heads``)
  updated with ``lax.dynamic_update_slice``, and the decode loop is a
  single ``lax.scan`` over ``max_new_tokens`` steps — one XLA compilation,
  no per-token Python.
- **MXU-friendly**: cache and activations live in the model dtype (bf16 on
  TPU); attention math accumulates in f32 like the training path.
- The per-block parameter names (``qkv``/``attn_out``/``mlp_up``/
  ``mlp_down``) match the encoder family, so ``parallel.tensor``'s Megatron
  sharding rules apply unchanged and ``MeshTrainer`` trains the LM with any
  ``parameter_sharding``.
"""

from __future__ import annotations

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from distkeras_tpu import ops
from distkeras_tpu.model import ModelSpec, from_flax
from distkeras_tpu.models.transformer import sincos_positions


def rope_angles(maxlen: int, head_dim: int, base: float = 10000.0):
    """Rotary position-embedding angle table ``[maxlen, head_dim // 2]``
    (Su et al. 2021): position ``p`` rotates feature pair ``i`` by
    ``p · base^(-2i/head_dim)``."""
    inv = base ** (-np.arange(0, head_dim, 2) / head_dim)
    return (np.arange(maxlen)[:, None] * inv[None, :]).astype(np.float32)


def rope_angles_at(positions, head_dim: int, base: float = 10000.0):
    """:func:`rope_angles` at the ``positions`` given (a vector, in any order
    and with repeats) instead of ``0 .. maxlen - 1``."""
    inv = base ** (-np.arange(0, head_dim, 2) / head_dim)
    return (np.asarray(positions)[:, None] * inv[None, :]).astype(np.float32)


def apply_rope(x, angles):
    """Rotate feature pairs of ``x`` [..., L, H, Dh] by per-position
    ``angles`` [L, Dh//2] (pairing (x[2i], x[2i+1]), rotation in f32, cast
    back to x.dtype). ``angles`` may also be ``[B, L, Dh//2]`` — the paged
    decode path, where every row sits at its own absolute position."""
    f32 = x.astype(jnp.float32)
    x1, x2 = f32[..., 0::2], f32[..., 1::2]
    # angles broadcast over batch and heads: [L, Dh/2] → [L, 1, Dh/2]
    # (or [B, L, Dh/2] → [B, L, 1, Dh/2] for per-row positions)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    out = jnp.stack([r1, r2], axis=-1).reshape(f32.shape)
    return out.astype(x.dtype)


def head_norm_rope(a, w, angles, heads: int, eps: float, rotary: int | None = None):
    """A q or k projection's result ``a [B, S, heads·Dh]`` as float32
    ``[B, S, heads, Dh]``: an RMSNorm over each head with the weight
    ``w [Dh]``, then :func:`apply_rope` by ``angles [S, Dh // 2]``. The plain
    chain that ``ops.qk_prep`` does in one kernel (and hands over head-major,
    in ``a.dtype``): what runs at shapes the kernel refuses, and what its
    tests compare with. ``rotary``: only a head's first ``rotary`` columns
    are rotated (``angles [S, rotary // 2]``), the others pass as they are."""
    B, S, _ = a.shape
    a = a.astype(jnp.float32).reshape(B, S, heads, -1)
    a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps) * w
    if rotary is None:
        return apply_rope(a, angles)
    return jnp.concatenate([apply_rope(a[..., :rotary], angles), a[..., rotary:]], -1)


def latent_qkv(q, kv, k_rope, angles, heads: int, nope: int):
    """Latent attention's operands from its projections' results: ``q [B, S,
    heads·(nope + dr)]``, ``kv [B, S, heads·(nope + dv)]`` (a head's key part
    with no position, then its value) and the ONE rotary key a token
    ``k_rope [B, S, dr]`` to ``q`` and ``k [B, S, heads, nope + dr]`` and
    ``v [B, S, heads, dv]``: :func:`apply_rope` by ``angles [S, dr // 2]`` on
    the last ``dr`` columns of every q head and on ``k_rope``, which is laid
    beside every head's key part. The plain chain that ``ops.mla_prep`` does
    in one kernel each way (and hands over head-major): what runs at shapes
    the kernel refuses, and what its tests compare with."""
    B, S, _ = q.shape
    q, kv = q.reshape(B, S, heads, -1), kv.reshape(B, S, heads, -1)
    q = jnp.concatenate(
        [q[..., :nope], apply_rope(q[..., nope:], angles)], axis=-1)
    k_rope = apply_rope(k_rope[..., None, :], angles)      # [B, S, 1, dr]
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope, (B, S, heads, k_rope.shape[-1]))], axis=-1)
    return q, k, kv[..., nope:]


class QDense(nn.Module):
    """Dense over an int8 weight-only-quantized kernel (``ops.quant``).

    Param set: ``kernel_q`` int8 ``[in, features]``, per-output-channel
    ``scale`` f32, ``bias`` in the activation dtype — exactly what
    :func:`distkeras_tpu.ops.quant.quantize_dense_tree` produces from a
    trained ``nn.Dense`` subtree. The matmul streams int8 from HBM and
    dequantizes in VMEM (Pallas), which is the decode bandwidth win.
    """

    features: int
    dtype: jnp.dtype = jnp.bfloat16
    impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        from distkeras_tpu.ops.quant import QTensor, q_matmul

        k = x.shape[-1]
        q = self.param("kernel_q", nn.initializers.zeros,
                       (k, self.features), jnp.int8)
        s = self.param("scale", nn.initializers.ones,
                       (self.features,), jnp.float32)
        b = self.param("bias", nn.initializers.zeros,
                       (self.features,), self.dtype)
        out = q_matmul(x, QTensor(q, s), impl=self.impl, out_dtype=x.dtype)
        # trained biases arrive f32 (flax master params); add in the
        # activation dtype like nn.Dense(dtype=...) does — a bare f32 add
        # would silently promote the whole downstream block to f32
        return out + b.astype(out.dtype)


class DecoderBlock(nn.Module):
    """Pre-norm causal block with three entry points sharing one parameter
    set: ``__call__`` (training / full forward), ``prefill`` (full forward
    that also returns this block's K/V for the cache), and ``step`` (one
    decode position against the cache)."""

    dim: int
    heads: int
    mlp_ratio: int = 4
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "reference"
    attn_window: int | None = None  # sliding-window (local) attention span
    #: grouped-query attention: number of shared K/V heads (None = heads,
    #: i.e. standard MHA; 1 = MQA). Query head h reads K/V head h // group.
    #: The KV cache shrinks heads/kv_heads ×, the decode win GQA exists for.
    kv_heads: int | None = None
    #: rotary position embeddings: rotate q/k at projection time (the cache
    #: stores PRE-ROTATED keys); ``maxlen`` bounds the decode angle table
    rope: bool = False
    maxlen: int = 0
    #: int8 weight-only serving: every Dense becomes a QDense (params from
    #: quantize_lm); architecture and entry points are otherwise identical
    quant: bool = False

    @property
    def _hkv(self) -> int:
        return self.kv_heads if self.kv_heads is not None else self.heads

    def _rope_qk(self, q, k, pos):
        """Rotate q and k for RoPE. ``pos`` is the first position the inputs
        occupy: 0 with a static length-L forward, a traced scalar with the
        single-position decode step."""
        if not self.rope:
            return q, k
        dh = self.dim // self.heads
        L = q.shape[1]
        if isinstance(pos, int) and pos == 0:
            angles = jnp.asarray(rope_angles(L, dh))
        else:
            table = jnp.asarray(rope_angles(self.maxlen, dh))
            angles = jax.lax.dynamic_slice(table, (pos, 0), (L, dh // 2))
        return apply_rope(q, angles), apply_rope(k, angles)

    def setup(self):
        if self.rope and self.maxlen < 1:
            raise ValueError(
                "DecoderBlock(rope=True) needs maxlen >= 1 for the decode "
                "angle table (TransformerLM passes its own maxlen)"
            )
        f32 = jnp.float32
        dh = self.dim // self.heads
        dense = QDense if self.quant else nn.Dense
        self.ln_attn = nn.LayerNorm(dtype=f32)
        # one fused projection, width (H + 2·Hkv)·Dh; splitting at H·Dh /
        # (H+Hkv)·Dh reduces to the classic thirds split when Hkv == H, so
        # MHA checkpoints/params are unchanged by the GQA seam
        self.qkv = dense((self.heads + 2 * self._hkv) * dh,
                         dtype=self.dtype)
        self.attn_out = dense(self.dim, dtype=self.dtype)
        self.ln_mlp = nn.LayerNorm(dtype=f32)
        self.mlp_up = dense(self.mlp_ratio * self.dim, dtype=self.dtype)
        self.mlp_down = dense(self.dim, dtype=self.dtype)

    def _project_qkv(self, x):
        """→ q [B, L, H, Dh], k/v [B, L, Hkv, Dh]."""
        B, L, _ = x.shape
        dh = self.dim // self.heads
        hkv = self._hkv
        h = self.ln_attn(x)
        qkv = self.qkv(h.astype(self.dtype))
        q = qkv[..., : self.heads * dh].reshape(B, L, self.heads, dh)
        k = qkv[..., self.heads * dh: (self.heads + hkv) * dh]
        v = qkv[..., (self.heads + hkv) * dh:]
        return q, k.reshape(B, L, hkv, dh), v.reshape(B, L, hkv, dh)

    def _mlp(self, x):
        h = self.ln_mlp(x)
        h = self.mlp_up(h.astype(self.dtype))
        h = nn.gelu(h)
        h = self.mlp_down(h)
        return x + h.astype(jnp.float32)

    def _attn_full(self, x, mask):
        B, L, _ = x.shape
        q, k, v = self._project_qkv(x)
        q, k = self._rope_qk(q, k, 0)   # k rotated BEFORE caching
        # GQA needs no expansion: both attention paths read the shared Hkv
        # heads directly (the flash kernels via index maps — no repeated-KV
        # tensor is ever materialized)
        from distkeras_tpu.ops.flash_attention import BLOCK_Q, attention

        # "flash" is the kernel on every backend whenever the length is a
        # tile multiple (training shapes are maxlen-derived and always
        # are); decode prompts are ragged by nature, so a prefill length
        # that is not takes the reference — the ONLY reason it ever does
        impl = self.attn_impl
        if impl == "flash" and L % BLOCK_Q:
            impl = "reference"
        att = attention(q, k, v, causal=True, key_mask=mask,
                        impl=impl, window=self.attn_window)
        att = att.reshape(B, L, self.dim)
        x = x + self.attn_out(att.astype(self.dtype)).astype(jnp.float32)
        return x, k, v

    def __call__(self, x, mask=None, training: bool = False):
        x, _, _ = self._attn_full(x, mask)
        return self._mlp(x)

    def prefill(self, x, mask=None):
        x, k, v = self._attn_full(x, mask)
        return self._mlp(x), k, v

    def step(self, x_t, k_cache, v_cache, pos):
        """One decode position. ``x_t``: [B, 1, dim] residual stream;
        ``k_cache``/``v_cache``: [B, cache_len, Hkv, Dh]; ``pos`` may be a
        traced scalar. ``cache_len`` is ``maxlen`` normally, or ``window``
        for sliding-window models — then the cache is a RING: position
        ``p`` lives in slot ``p % window`` (decode reads ``window``, not
        ``maxlen``, keys per step — the bandwidth the window promises)."""
        cache_len = k_cache.shape[1]
        if cache_len >= self.maxlen:
            # the non-ring step IS the T=1 multi-token pass; one shared
            # body keeps cached decode and the speculative verify forward
            # (extend) from ever drifting apart
            return self.extend(x_t, k_cache, v_cache, pos)
        q, k, v = self._project_qkv(x_t)  # q [B,1,H,Dh]; k/v [B,1,Hkv,Dh]
        q, k = self._rope_qk(q, k, pos)   # cache holds pre-rotated keys
        slot = pos % cache_len
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, slot, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, slot, 0, 0)
        )
        B = x_t.shape[0]
        dh = self.dim // self.heads
        hkv = self._hkv
        group = self.heads // hkv
        # same dtype path as attention_reference (parallel/sequence.py:39-52)
        # so cached decode is bit-compatible with the full forward in bf16:
        # q·k in model dtype, softmax in f32, p·v back in model dtype.
        # GQA: the [H] head axis factors as [Hkv, group] (group-major match
        # with the kernels' index maps); the cache stays Hkv-wide.
        qg = q.reshape(B, 1, hkv, group, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache) \
            .astype(jnp.float32) * (dh ** -0.5)
        kp = jnp.arange(cache_len)
        # slot s holds absolute position pos - ((pos - s) % window),
        # automatically causal and in-band; only never-written slots
        # (absolute < 0, early decode) need masking
        valid = pos - ((pos - kp) % cache_len) >= 0
        s = jnp.where(valid[None, None, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum(
            "bhgqk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache
        )
        att = att.reshape(B, 1, self.dim)
        x_t = x_t + self.attn_out(att.astype(self.dtype)).astype(jnp.float32)
        return self._mlp(x_t), k_cache, v_cache

    def extend(self, x, k_cache, v_cache, pos0):
        """``T`` consecutive decode positions in one pass: ``x`` [B, T, dim]
        residual stream occupying absolute positions ``pos0 .. pos0+T-1``
        (``pos0`` may be a traced scalar). Cache entries for those positions
        are written and each query attends causally to every cached position
        ≤ its own — the multi-token sibling of :meth:`step`, and speculative
        decoding's verify forward (T candidate tokens scored against the
        cache in one batched matmul instead of T sequential steps). Ring
        (sliding-window) caches are not supported — a wrapped
        ``dynamic_update_slice`` cannot write a contiguous span."""
        B, T, _ = x.shape
        cache_len = k_cache.shape[1]
        if cache_len < self.maxlen:
            raise ValueError(
                "extend() needs a full-length cache; sliding-window models "
                "use a ring cache that cannot take a contiguous span write"
            )
        q, k, v = self._project_qkv(x)
        q, k = self._rope_qk(q, k, pos0)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, pos0, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, pos0, 0, 0)
        )
        dh = self.dim // self.heads
        hkv = self._hkv
        group = self.heads // hkv
        # same dtype/GQA discipline as step(): q·k in model dtype, softmax
        # f32, p·v in model dtype; the [H] axis factors as [Hkv, group]
        qg = q.reshape(B, T, hkv, group, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache) \
            .astype(jnp.float32) * (dh ** -0.5)
        kp = jnp.arange(cache_len)[None, :]
        qp = pos0 + jnp.arange(T)[:, None]
        valid = kp <= qp                          # causal: cache ≤ own pos
        if self.attn_window is not None:
            valid &= qp - kp < self.attn_window
        s = jnp.where(valid[None, None, None, :, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum(
            "bhgqk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache
        )
        att = att.reshape(B, T, self.dim)
        x = x + self.attn_out(att.astype(self.dtype)).astype(jnp.float32)
        return self._mlp(x), k_cache, v_cache

    def paged_extend(self, x, k_pool, v_pool, tables, write_slots,
                     positions, block_size: int):
        """``T`` decode positions per row against a BLOCK-PAGED cache — the
        serving tier's generalization of :meth:`extend`'s addressing:
        instead of one ``[B, cache_len]`` buffer per sequence, all
        sequences share a flat slot pool ``[S, Hkv, Dh]`` (``S =
        num_blocks · block_size``) and a per-row **block table**
        ``tables`` [B, nb] maps logical block ``t // block_size`` of row
        ``b`` to pool block ``tables[b, t // bs]`` (generalizing the ring
        cache's ``slot = pos % cache_len`` to table indexing). Every row
        sits at its OWN absolute position: row ``b``'s ``T`` tokens occupy
        ``positions[b] .. positions[b]+T-1`` and are written to flat pool
        slots ``write_slots[b]`` ([B, T], precomputed by the caller —
        shared across layers, so it is computed once per step, not per
        block). ``block_size`` must be a static Python int.

        Math is the :meth:`extend` body unchanged (q·k in model dtype,
        softmax f32, p·v in model dtype; GQA head-axis factoring): the
        gather reconstructs each row's logical ``[nb·bs, Hkv, Dh]`` cache
        exactly — at BLOCK granularity (``B·nb`` contiguous
        ``block_size``-row chunks, not ``B·L`` scalar rows: gather cost on
        CPU/TPU tracks the index count, and this is the difference between
        the paged step tracking the dense step's cost or trailing it) —
        and unwritten slots are masked by the per-row causal validity
        ``kp <= positions[b]+t``, so paged decode is bit-identical to
        dense-cache decode: the parity oracle in tests/test_serving.py.
        Sliding windows keep their band mask."""
        B, T, _ = x.shape
        bs = int(block_size)
        nb = tables.shape[1]
        L = nb * bs
        q, k, v = self._project_qkv(x)
        if self.rope:
            dh = self.dim // self.heads
            table = jnp.asarray(rope_angles(self.maxlen, dh))
            # per-row angle rows [B, T, Dh/2] — same table rows the dense
            # step slices at its (shared) scalar position
            angles = table[positions[:, None] + jnp.arange(T)[None, :]]
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)
        k_pool = k_pool.at[write_slots].set(k.astype(k_pool.dtype))
        v_pool = v_pool.at[write_slots].set(v.astype(v_pool.dtype))
        hkv_, dh_ = k_pool.shape[1], k_pool.shape[2]
        kb = k_pool.reshape(-1, bs, hkv_, dh_)[tables]   # [B, nb, bs, ...]
        vb = v_pool.reshape(-1, bs, hkv_, dh_)[tables]
        k_seq = kb.reshape(B, L, hkv_, dh_)              # [B, L, Hkv, Dh]
        v_seq = vb.reshape(B, L, hkv_, dh_)
        dh = self.dim // self.heads
        hkv = self._hkv
        group = self.heads // hkv
        qg = q.reshape(B, T, hkv, group, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_seq) \
            .astype(jnp.float32) * (dh ** -0.5)
        kp = jnp.arange(L)[None, None, :]
        qp = (positions[:, None] + jnp.arange(T)[None, :])[:, :, None]
        valid = kp <= qp                  # per-row causal; unwritten slots
        if self.attn_window is not None:  # (kp > qp) are masked here too
            valid &= qp - kp < self.attn_window
        s = jnp.where(valid[:, None, None, :, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum(
            "bhgqk,bkhd->bqhgd", p.astype(v_seq.dtype), v_seq
        )
        att = att.reshape(B, T, self.dim)
        x = x + self.attn_out(att.astype(self.dtype)).astype(jnp.float32)
        return self._mlp(x), k_pool, v_pool


@dataclasses.dataclass(frozen=True)
class ZayaDims:
    """The sizes of a ZAYA1 block (:class:`ZayaBlock`) that ``dim``, ``heads``
    and ``kv_heads`` do not give. Defaults are ZAYA1-8B's published ones."""

    head_dim: int = 128
    #: kernel lengths of CCA's two causal convolutions over the sequence
    #: (``cca_time0`` depthwise, ``cca_time1`` grouped by head)
    conv_kernels: tuple = (2, 2)
    rotary_fraction: float = 0.5      # leading share of a head that is rotated
    rope_base: float = 5e6
    router_dim: int = 256
    experts: int = 16                 # the router's width
    #: ``(first, count)``: the experts this model holds of all ``experts``
    #: (an ``ep`` rank's share); ``None`` holds them all
    experts_held: tuple | None = None
    expert_dim: int = 2048
    norm_eps: float = 1e-5

    @property
    def held(self) -> tuple:
        return self.experts_held or (0, self.experts)


def _delay(x, n: int):
    """``x`` [B, S, C] moved ``n`` positions later, zeros in front."""
    if not n:
        return x
    return jnp.pad(x, ((0, 0), (n, 0), (0, 0)))[:, : x.shape[1]]


def _causal_depthwise(x, w):
    """``out[t] = sum_j w[j] * x[t - (k-1) + j]``: one filter ``w[:, c]`` a
    channel, left-padded with zeros."""
    k = w.shape[0]
    return sum(_delay(x, k - 1 - j) * w[j] for j in range(k))


def _causal_grouped(x, w):
    """The same over ``w`` [k, heads, dh, dh]: each head's channels mix among
    themselves."""
    k, heads, dh, _ = w.shape
    B, S, _ = x.shape
    return sum(
        jnp.einsum("bshd,hde->bshe",
                   _delay(x, k - 1 - j).reshape(B, S, heads, dh), w[j])
        for j in range(k)).reshape(B, S, heads * dh)


def _rescaled(mod, x, y):
    """``(a * x + b) + (g * y + e)``: a ZAYA sublayer's learned scaling of
    the residual stream ``x`` and of its own result ``y``."""
    def vec(name, init):
        return mod.param(name, init, (x.shape[-1],), jnp.float32)

    a, b = vec("res_scale", nn.initializers.ones), vec("res_bias", nn.initializers.zeros)
    g, e = vec("out_scale", nn.initializers.ones), vec("out_bias", nn.initializers.zeros)
    return (a * x + b) + (g * y.astype(jnp.float32) + e)


#: sweeps over the experts by which a training step balances a router's bias
_BALANCE_SWEEPS = 2


def _balanced_bias(p, bias, sweeps: int = _BALANCE_SWEEPS):
    """The router's balancing bias after ``sweeps`` sweeps over the experts
    from ``bias`` ``[E]``: in turn each expert's bias goes where exactly
    ``T // E`` of ``p`` ``[T, E]``'s rows prefer it to the best of the others
    (half-way between the two rows at that cut), the others' held; the mean
    is taken off at the end. One sort of ``T`` numbers an expert a sweep.

    A stand-in: ZAYA1's own balancing rule is a training procedure that its
    ``config.json`` does not give. Without any, Adam on random weights sends
    nearly every token of a layer to a few experts within tens of steps."""
    T, E = p.shape
    k = T // E

    def one(i, b):
        e = i % E
        others = jnp.where(jnp.arange(E) == e, -jnp.inf, p + b)
        margin = jnp.sort(jnp.max(others, axis=-1) - p[:, e])
        return b.at[e].set(0.5 * (margin[k - 1] + margin[k]))

    bias = jax.lax.fori_loop(0, sweeps * E, one, bias)
    return bias - jnp.mean(bias)


class CCAttention(nn.Module):
    """ZAYA1's attention sublayer, compressed convolutional attention:
    projections into a latent of ``heads`` / ``kv_heads`` heads of
    ``z.head_dim``, two causal convolutions over the sequence on q and on k,
    a value whose second half is the previous token's, the q-k mean,
    L2-normalised q and k with a learned temperature a key head, rotary on
    the leading ``z.rotary_fraction`` of each head, flash attention."""

    dim: int
    heads: int
    kv_heads: int
    z: ZayaDims
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "reference"

    @nn.compact
    def __call__(self, x, mask=None):
        from distkeras_tpu.ops.flash_attention import BLOCK_Q, attention

        z, f32 = self.z, jnp.float32
        B, S, _ = x.shape
        H, K, dh = self.heads, self.kv_heads, z.head_dim
        G = H // K
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        lecun = nn.initializers.lecun_normal()
        h = nn.RMSNorm(epsilon=z.norm_eps, dtype=f32, name="ln")(x)
        h = h.astype(self.dtype)
        q0 = dense(H * dh, name="q")(h)
        k0 = dense(K * dh, name="k")(h)
        # the first half of the K/V heads carries this token's value, the
        # second half the previous token's
        v = jnp.concatenate(
            [dense(K * dh // 2, name="v_now")(h),
             _delay(dense(K * dh // 2, name="v_prev")(h), 1)], axis=-1)
        t0, t1 = z.conv_kernels
        conv = {
            "q0": self.param("conv_q0", lecun, (t0, H * dh), f32),
            "q1": self.param("conv_q1", lecun, (t1, H, dh, dh), f32),
            "k0": self.param("conv_k0", lecun, (t0, K * dh), f32),
            "k1": self.param("conv_k1", lecun, (t1, K, dh, dh), f32),
        }
        conv = {n: w.astype(self.dtype) for n, w in conv.items()}
        with jax.named_scope("cca_conv"):
            cq = _causal_grouped(_causal_depthwise(q0, conv["q0"]), conv["q1"])
            ck = _causal_grouped(_causal_depthwise(k0, conv["k0"]), conv["k1"])
        # the q-k mean: a query head is averaged with its key head, a key
        # head with the mean of its group's query heads
        qh = q0.astype(f32).reshape(B, S, K, G, dh)
        kh = k0.astype(f32).reshape(B, S, K, 1, dh)
        q = cq.astype(f32).reshape(B, S, K, G, dh) + (qh + kh) / 2
        k = ck.astype(f32).reshape(B, S, K, dh) \
            + (kh[:, :, :, 0] + jnp.mean(qh, axis=3)) / 2
        tau = self.param("tau", nn.initializers.ones, (K,), f32)

        def unit(a):
            return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True)) \
                * dh ** 0.5

        q = unit(q).reshape(B, S, H, dh)
        k = unit(k) * tau[:, None]
        rot = int(dh * z.rotary_fraction)
        angles = jnp.asarray(rope_angles(S, rot, z.rope_base))
        q, k = (jnp.concatenate([apply_rope(a[..., :rot], angles),
                                 a[..., rot:]], -1) for a in (q, k))
        impl = self.attn_impl
        if impl == "flash" and S % BLOCK_Q:
            impl = "reference"
        o = attention(q.astype(self.dtype), k.astype(self.dtype),
                      v.reshape(B, S, K, dh), causal=True, key_mask=mask,
                      impl=impl)
        o = dense(self.dim, name="out")(
            o.reshape(B, S, H * dh).astype(self.dtype))
        return _rescaled(self, x, o)


def _zaya_router(mod, h, r):
    """ZAYA1's router, a part of :class:`RoutedExperts` (its parameters and
    state are ``mod``'s own): a float32 MLP over a state ``r`` that passes
    from layer to layer (``r = h Wd + gamma * r``), top-1 of ``p + bias``.
    The balancing bias is state, not a parameter: argmax passes it no
    gradient, and a training step (the ``counters`` collection mutable) first
    balances it on its own tokens (:func:`_balanced_bias`) and leaves the
    result for the next. Returns ``(chosen [B, S], weight [B, S], r)``."""
    z, f32 = mod.z, jnp.float32
    B, S, _ = h.shape
    E, R = z.experts, z.router_dim
    # the router runs in float32 (``HIGHEST``: true float32 on a TPU)
    rdense = functools.partial(nn.Dense, use_bias=False, dtype=f32,
                               precision=jax.lax.Precision.HIGHEST)
    gamma = mod.param("router_gamma", nn.initializers.ones, (R,), f32)
    r = rdense(R, name="router_down")(h) + gamma * r
    s = nn.RMSNorm(epsilon=z.norm_eps, dtype=f32, name="ln_router")(r)
    s = nn.gelu(rdense(R, name="router_w1")(s))
    s = nn.gelu(rdense(R, name="router_w2")(s))
    p = jax.nn.softmax(rdense(E, name="router_w3")(s), axis=-1)
    bias = mod.variable("counters", "router_bias",
                        lambda: jnp.zeros((E,), f32))
    b = bias.value
    if mod.counting():
        with jax.named_scope("moe_balance"):
            # kept over remat: the backward pass does not sort again
            b = checkpoint_name(_balanced_bias(
                jax.lax.stop_gradient(p).reshape(B * S, E), b),
                "router_bias")
        bias.value = b
    chosen = jnp.argmax(p + b, axis=-1)
    return chosen, jnp.take_along_axis(p, chosen[..., None], -1)[..., 0], r


def _topk_router(mod, h, r):
    """A linear softmax router, a part of :class:`RoutedExperts`: float32
    ``p = softmax(h Wr)``, the ``z.experts_per_token`` largest, their
    probabilities renormalised to sum to 1 over all of them, held or not. No
    bias, no state (``r`` passes through). Returns ``(chosen [B, S, k],
    weight [B, S, k], r)``."""
    z = mod.z
    logits = nn.Dense(z.experts, use_bias=False, dtype=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST, name="router")(h)
    top, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                z.experts_per_token)
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True), r


def _sigmoid_router(mod, h, r):
    """A sigmoid router with a selection bias, a part of
    :class:`RoutedExperts` (DeepSeek-V3's ``noaux_tc`` with one group):
    float32 ``s = sigmoid(h Wr)``; chosen are the ``z.experts_per_token``
    largest of ``s + b``; a chosen expert's weight is ``z.route_scale * s_e /
    (sum of the chosen s + 1e-20)``: of ``s``, not of ``s + b``, so the
    weights sum to ``z.route_scale`` and not to 1. ``b`` (``router_bias``,
    float32 ``[experts]``) is state in the ``counters`` collection: no
    gradient reaches it, and a training step (the collection mutable) leaves
    ``b + z.bias_rate * sign(mean(n) - n)`` for the next, ``n`` the step's own
    (token, expert) pairs by expert over ALL experts, held or not (the
    auxiliary-loss-free balancing rule). No state beside the residual stream
    (``r`` passes through). Returns ``(chosen [B, S, k], weight [B, S, k],
    r)``."""
    z, f32 = mod.z, jnp.float32
    logits = nn.Dense(z.experts, use_bias=False, dtype=f32,
                      precision=jax.lax.Precision.HIGHEST, name="router")(h)
    s = jax.nn.sigmoid(logits)
    bias = mod.variable("counters", "router_bias",
                        lambda: jnp.zeros((z.experts,), f32))
    b = bias.value
    _, chosen = jax.lax.top_k(s + b, z.experts_per_token)
    top = jnp.take_along_axis(s, chosen, axis=-1)
    weight = z.route_scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    if mod.counting():
        with jax.named_scope("moe_bias"):
            n = jnp.bincount(chosen.reshape(-1), length=z.experts) \
                .astype(f32)
            bias.value = b + z.bias_rate * jnp.sign(jnp.mean(n) - n)
    return chosen, weight, r


def _added(mod, x, y):
    """The plain residual: ``x + y``."""
    return x + y.astype(jnp.float32)


#: rows the first chunk of the top-k expert layer holds over the balanced
#: load's. A chunk costs its rows whatever share of them is held, so up to
#: this margin a step's time does not follow the router; past it a layer pays
#: for its excess. A layer's held share wanders with the seed, the batch and
#: training (0.05 to 0.22 where an even router gives 0.125, over 0.172 in one
#: layer and epoch of thirty; v5e, PR 31)
HELD_ROWS_MARGIN = 1.375
#: and the rows a later chunk holds: what a load over the first chunk costs
#: follows its excess (a gather, two products and a scatter-add of this many
#: rows a time)
HELD_ROWS_LATER = 4096


def held_rows(tokens: int, z) -> tuple:
    """``(first, later)``: the chunks of (token, expert) pairs a top-k layer
    computes at a time (``dropless_experts(rows=...)``). The first holds
    :data:`HELD_ROWS_MARGIN` times the held experts' share of ``tokens * k``
    pairs under an even router, a multiple of 512, at most every pair; the
    later ones :data:`HELD_ROWS_LATER`."""
    pairs = tokens * z.experts_per_token
    even = pairs * z.held[1] / z.experts
    first = min(pairs, 512 * int(np.ceil(HELD_ROWS_MARGIN * even / 512)))
    return first, min(HELD_ROWS_LATER, pairs)


def _swiglu(mod, h, width: int, name: str):
    """``(silu(h Wg) * (h Wu)) Wd`` at ``width``, bias-free, in ``h``'s dtype:
    gate and up side by side in one ``[d, 2 width]`` matrix ``<name>_in``
    (as the routed experts hold theirs), down ``<name>_out``; parameters of
    ``mod``, a compact module."""
    dense = functools.partial(nn.Dense, use_bias=False, dtype=h.dtype)
    gate, up = jnp.split(dense(2 * width, name=name + "_in")(h), 2, axis=-1)
    return dense(h.shape[-1], name=name + "_out")(nn.silu(gate) * up)


class RoutedExperts(nn.Module):
    """An expert sublayer: RMSNorm, a router (``router``, a part: a function
    ``(module, h, r) -> (chosen, weight, r)`` that makes its parameters and
    state in this module), the held SwiGLU experts through
    :func:`parallel.expert.dropless_experts`, and the residual (``join``, a
    part: ``(module, x, y) -> x``). ``__call__(x, r) -> (x, r)``; ``r`` is
    the router's state beside the residual stream, or None. The defaults are
    ZAYA1's: :func:`_zaya_router` (top-1) and the learned scaling
    :func:`_rescaled`; :func:`_topk_router` and :func:`_added` make the
    top-k layer of a plain pre-norm block, :func:`_sigmoid_router` one whose
    weights sum to a scaling factor. ``shared_dim > 0`` adds a shared expert:
    one SwiGLU of that width on EVERY token beside the routed sum (every chip
    of an ``ep`` axis holds it whole; it is added once).

    The ``counters`` collection holds what no gradient reaches:
    ``moe_tokens`` (int32 ``[experts]``), increased in a training step (the
    collection mutable) by the (token, expert) pairs routed to each expert,
    held or not, and whatever the router keeps there (ZAYA1's
    ``router_bias``). With ``intermediates`` mutable, ``moe_chosen`` holds
    every token's expert(s)."""

    dim: int
    z: object
    dtype: jnp.dtype = jnp.bfloat16
    router: object = _zaya_router
    join: object = _rescaled
    shared_dim: int = 0
    #: the shared expert's result times ``sigmoid(h w)``, ``w`` a ``[dim, 1]``
    #: ``shared_gate`` of this module's own
    shared_gate: bool = False
    #: the sublayer's norm: a class called as ``norm(epsilon=, dtype=,
    #: name=)``
    norm: object = nn.RMSNorm

    def counting(self) -> bool:
        return self.is_mutable_collection("counters") \
            and not self.is_initializing()

    @nn.compact
    def __call__(self, x, r):
        from distkeras_tpu.parallel.expert import dropless_experts

        z, f32 = self.z, jnp.float32
        B, S, d = x.shape
        E, F = z.experts, z.expert_dim
        first, count = z.held
        lecun = nn.initializers.lecun_normal()
        h = self.norm(epsilon=z.norm_eps, dtype=f32, name="ln")(x)
        chosen, weight, r = self.router(self, h, r)
        self.sow("intermediates", "moe_chosen", chosen)
        w_in = self.param("experts_in", lecun, (count, d, 2 * F), f32)
        w_out = self.param("experts_out", lecun, (count, F, d), f32)
        pairs = chosen.shape[2:]               # () for top-1, (k,) for top-k
        y, tokens = dropless_experts(
            h.astype(self.dtype).reshape(B * S, d),
            chosen.reshape((B * S,) + pairs).astype(jnp.int32),
            weight.reshape((B * S,) + pairs),
            w_in, w_out, experts=(first, count), total=E,
            rows=held_rows(B * S, z) if pairs else None)
        seen = self.variable("counters", "moe_tokens",
                             lambda: jnp.zeros((E,), jnp.int32))
        if self.counting():
            seen.value = seen.value + tokens
        y = y.reshape(B, S, d)
        if self.shared_dim:
            with jax.named_scope("moe_shared"):
                shared = _swiglu(self, h.astype(self.dtype), self.shared_dim,
                                 "shared").astype(jnp.float32)
                if self.shared_gate:
                    shared = shared * jax.nn.sigmoid(nn.Dense(
                        1, use_bias=False, dtype=self.dtype,
                        name="shared_gate")(h.astype(self.dtype)).astype(f32))
                y = y + shared
        return self.join(self, x, y), r


class _RoutedBlock(nn.Module):
    """A layer whose second sublayer is :class:`RoutedExperts`: ``__call__``
    takes and returns ``(x, r)``, the residual stream and the router's state
    (None for a router without one). A model's layers may be of two kinds
    (:class:`MlaBlock`: leading layers with a dense SwiGLU in the expert
    sublayer's place); ``(x, r)`` passes through both alike. A block's ``setup`` makes its first
    sublayer, which ``attend(x, mask) -> x`` runs, and ``self.moe``. Training only: the
    dropless experts are not on the paged path, so the serving entry points
    raise, with ``no_serving``, the block's own reason."""

    dim: int
    heads: int
    kv_heads: int
    z: object
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "reference"

    no_serving = "the dropless experts are not on the paged path"

    def __call__(self, x, r, mask=None, training: bool = False):
        return self.moe(self.attend(x, mask), r)

    def attend(self, x, mask):
        raise NotImplementedError

    def _no_serving(self, *_, **__):
        raise NotImplementedError(
            f"{type(self).__name__} has no serving path: {self.no_serving}")

    prefill = step = extend = paged_extend = _no_serving


class ZayaBlock(_RoutedBlock):
    """ZAYA1's layer: :class:`CCAttention`, then :class:`RoutedExperts`,
    whose router state ``r`` travels beside the residual stream ``x``:
    ``__call__`` takes and returns ``(x, r)``.

    Training only: CCA's cache would hold a convolution tail and a shifted
    value beside K/V, and the experts are not on the paged path, so the
    serving entry points raise.
    """

    no_serving = ("CCA's cache would hold a convolution tail and a shifted "
                  "value beside compressed K/V, and the dropless experts are "
                  "not on the paged path")

    def setup(self):
        self.cca = CCAttention(self.dim, self.heads, self.kv_heads, self.z,
                               self.dtype, self.attn_impl)
        self.moe = RoutedExperts(self.dim, self.z, self.dtype)

    def attend(self, x, mask):
        return self.cca(x, mask)


@dataclasses.dataclass(frozen=True)
class SdarDims:
    """The sizes of a block-diffusion expert block (:class:`SdarBlock`) that
    ``dim``, ``heads`` and ``kv_heads`` do not give, and the constants of its
    training objective. Defaults are SDAR-30B-A3B-Chat's published ones; the
    block length and the noise's law are the ones assumed for it."""

    head_dim: int = 128
    rope_base: float = 1e6
    experts: int = 128                # the router's width
    experts_per_token: int = 8
    #: ``(first, count)``: the experts this model holds of all ``experts``
    #: (an ``ep`` rank's share); ``None`` holds them all
    experts_held: tuple | None = None
    expert_dim: int = 768
    norm_eps: float = 1e-6
    #: tokens a block: a block is noised at one level and denoised together
    block_length: int = 4
    #: the least noise level: ``t = noise_floor + (1 - noise_floor) * u``
    noise_floor: float = 1e-3

    @property
    def held(self) -> tuple:
        return self.experts_held or (0, self.experts)


def block_diffusion_noise(key, step, tokens, block: int, floor: float,
                          mask_id: int):
    """The noise of one block-diffusion training step on ``tokens [R, L]``:
    ``(noised [R, L], t [R, L], masked [R, L] bool)``. From ``key`` (raw
    ``uint32[2]``) and the ``step`` count alone: a level ``t = floor + (1 -
    floor) * u``, ``u ~ U[0, 1)``, a row and block of ``block`` tokens; each
    token is masked (replaced by ``mask_id``) with probability ``t``,
    independently."""
    R, L = tokens.shape
    level, each = jax.random.split(jax.random.fold_in(key, step))
    u = jax.random.uniform(level, (R, L // block), jnp.float32)
    t = jnp.repeat(floor + (1.0 - floor) * u, block, axis=1)
    masked = jax.random.uniform(each, (R, L), jnp.float32) < t
    return jnp.where(masked, mask_id, tokens), t, masked


class QKNormAttention(nn.Module):
    """The attention sublayer of :class:`SdarBlock` over a block-diffusion
    stream ``x [B, 2 L, dim]`` (a noised copy of each row, then its clean
    copy): RMSNorm, bias-free q / k / v projections (``heads`` query and
    ``kv_heads`` key-value heads of ``z.head_dim``), an RMSNorm over each
    head of q and of k (one learned ``[head_dim]`` weight each), rotary over
    the whole head at the token's position IN ITS ROW (``0 .. L-1`` twice),
    attention under the block-diffusion mask
    (``ops.flash_attention.band_predicate``), the output projection and the
    residual. Under ``attn_impl="flash"`` at heads of a multiple of 128 the
    norm, the rotation and the move into the kernels' head-major layout are
    ONE kernel each way between a projection and ``flash_fwd``
    (``ops.qk_prep``; ``ops.kernel_impl("qk_prep", …)`` says whether), at any
    other shape the ``jnp`` chain :func:`head_norm_rope`. A training step
    (the ``counters`` collection mutable) leaves in
    ``counters/first_block`` (float32 ``[block_length, dim]``, no counter:
    overwritten, not added to) what came out at the first row's first noised
    block, whose queries see that block's keys and no other: the one place
    where what the mask does INSIDE a block is the whole result, read from the
    step that ran and not from a forward made alike."""

    dim: int
    heads: int
    kv_heads: int
    z: SdarDims
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "reference"

    @nn.compact
    def __call__(self, x, mask=None):
        from distkeras_tpu.ops import kernel_impl
        from distkeras_tpu.ops.flash_attention import (BLOCK_Q, attention,
                                                       flash_attention)
        from distkeras_tpu.ops.qk_prep import qk_prep

        z, f32 = self.z, jnp.float32
        B, S, _ = x.shape
        H, K, dh = self.heads, self.kv_heads, z.head_dim
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        h = nn.RMSNorm(epsilon=z.norm_eps, dtype=f32, name="ln")(x)
        h = h.astype(self.dtype)
        q, k = dense(H * dh, name="q")(h), dense(K * dh, name="k")(h)
        v = dense(K * dh, name="v")(h).reshape(B, S, K, dh)
        wq, wk = (self.param(name, nn.initializers.ones, (dh,), f32)
                  for name in ("q_norm", "k_norm"))
        # positions come from a vector, not from the length: both copies of a
        # row stand at 0 .. L-1
        row = np.arange(S // 2)
        angles = jnp.asarray(rope_angles_at(np.concatenate([row, row]), dh,
                                            z.rope_base))
        impl = self.attn_impl
        if impl == "flash" and (S // 2) % BLOCK_Q:
            impl = "reference"
        prep = "pallas" if impl == "flash" else "xla"
        if kernel_impl("qk_prep", prep, S=S, D=dh) == "pallas":
            q, k = (qk_prep(a, w, angles, heads=n, eps=z.norm_eps)
                    for a, w, n in ((q, wq, H), (k, wk, K)))
            o = flash_attention(q, k, v, key_mask=mask, qk_major=True,
                                block_diffusion=z.block_length)
        else:
            q, k = (head_norm_rope(a, w, angles, n, z.norm_eps)
                    .astype(self.dtype)
                    for a, w, n in ((q, wq, H), (k, wk, K)))
            o = attention(q, k, v, key_mask=mask, impl=impl,
                          block_diffusion=z.block_length)
        o = dense(self.dim, name="out")(
            o.reshape(B, S, H * dh).astype(self.dtype))
        x = x + o.astype(f32)
        first = self.variable(
            "counters", "first_block",
            lambda: jnp.zeros((z.block_length, self.dim), f32))
        if self.is_mutable_collection("counters") \
                and not self.is_initializing():
            first.value = jax.lax.stop_gradient(x[0, :z.block_length])
        return x


class SdarBlock(_RoutedBlock):
    """SDAR's layer over a block-diffusion stream: :class:`QKNormAttention`,
    then :class:`RoutedExperts` with a linear softmax router over all
    ``z.experts``, the ``z.experts_per_token`` largest renormalised, plain
    residuals. ``__call__`` takes and returns ``(x, r)`` like every routed
    block; its router has no state and ``r`` is None.

    Training only: generating by diffusion over blocks (a step that fills a
    block over several denoising passes against a block-causal cache) is not
    written, and the experts are not on the paged path."""

    no_serving = ("generation by diffusion over blocks (several denoising "
                  "passes a block, against a block-causal cache) is not "
                  "written, and the dropless experts are not on the paged "
                  "path")

    def setup(self):
        self.attn = QKNormAttention(self.dim, self.heads, self.kv_heads,
                                    self.z, self.dtype, self.attn_impl)
        self.moe = RoutedExperts(self.dim, self.z, self.dtype,
                                 router=_topk_router, join=_added)

    def attend(self, x, mask):
        return self.attn(x, mask)


@dataclasses.dataclass(frozen=True)
class MlaDims:
    """The sizes of a latent-attention expert block (:class:`MlaBlock`) that
    ``dim`` and ``heads`` do not give. Defaults are
    kanana-2-30b-a3b-instruct-2601's published ones (``model_type``
    ``deepseek_v3`` without a query latent); ``bias_rate`` is the DeepSeek-V3
    report's, which its ``config.json`` has no key for."""

    qk_nope_dim: int = 128            # a head's part of q and k with no position
    qk_rope_dim: int = 64             # and its rotary part; k's is ONE for all heads
    v_dim: int = 128
    kv_rank: int = 512                # the key-value latent's width
    rope_base: float = 1e6
    experts: int = 128                # the router's width
    experts_per_token: int = 6
    #: ``(first, count)``: the experts this model holds of all ``experts``
    #: (an ``ep`` rank's share); ``None`` holds them all
    experts_held: tuple | None = None
    expert_dim: int = 768
    #: shared experts: ONE SwiGLU of ``shared_experts * expert_dim`` on every
    #: token, whole on every chip
    shared_experts: int = 2
    #: the first ``dense_layers`` layers have a dense SwiGLU of ``dense_dim``
    #: in the expert sublayer's place
    dense_layers: int = 1
    dense_dim: int = 6144
    route_scale: float = 2.448        # what a token's routed weights sum to
    bias_rate: float = 0.001          # the balancing bias's step
    norm_eps: float = 1e-6

    @property
    def held(self) -> tuple:
        return self.experts_held or (0, self.experts)


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA), the attention sublayer of
    :class:`MlaBlock`: RMSNorm; ``q = h Wq`` as ``heads`` heads of
    ``qk_nope_dim + qk_rope_dim``; ``(c, k_rope) = split(h Wkva, kv_rank |
    qk_rope_dim)``, ``k_rope`` one vector a token for ALL heads; ``RMSNorm(c)
    Wkvb`` as ``heads`` heads of ``qk_nope_dim + v_dim`` (a key part with no
    position and the value); rotary over the ``qk_rope_dim`` columns of q's
    and of k's rope part only, pairs ``(2i, 2i+1)``; ``k_h = [k_nope_h ;
    k_rope]``; causal softmax attention at scale ``(qk_nope_dim +
    qk_rope_dim) ** -0.5`` with values ``v_dim`` wide (the flash kernels take
    the two widths as they are); the output projection and the residual.
    Float32 norms, products in ``dtype``. Everything between the projections
    and the attention call lies in the scope ``mla_latent``: the 512 norm,
    ``Wkvb``'s product, and then either ``ops.mla_prep`` (one kernel each way
    from the projections' results to the flash kernels' head-major operands,
    where ``ops.kernel_impl("mla_prep", …)`` says ``"pallas"``:
    ``attn_impl="flash"``, ``qk_nope_dim`` and ``v_dim`` multiples of 128,
    ``qk_rope_dim`` 64, an even count of heads, rows of a multiple of 128) or
    :func:`latent_qkv`'s ``jnp`` lines."""

    dim: int
    heads: int
    z: MlaDims
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "reference"

    @nn.compact
    def __call__(self, x, mask=None):
        from distkeras_tpu.ops import kernel_impl
        from distkeras_tpu.ops.flash_attention import (BLOCK_Q, attention,
                                                       flash_attention)
        from distkeras_tpu.ops.mla_prep import mla_prep

        z, f32 = self.z, jnp.float32
        B, S, _ = x.shape
        H, dn, dr, dv, R = (self.heads, z.qk_nope_dim, z.qk_rope_dim, z.v_dim,
                            z.kv_rank)
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        h = nn.RMSNorm(epsilon=z.norm_eps, dtype=f32, name="ln")(x)
        h = h.astype(self.dtype)
        q = dense(H * (dn + dr), name="q")(h)
        kva = dense(R + dr, name="kv_a")(h)
        impl = self.attn_impl
        if impl == "flash" and S % BLOCK_Q:
            impl = "reference"
        prep = kernel_impl("mla_prep", "pallas" if impl == "flash" else "xla",
                           S=S, nope=dn, rope=dr, v=dv, heads=H)
        with jax.named_scope("mla_latent"):
            c = nn.RMSNorm(epsilon=z.norm_eps, dtype=f32, name="kv_norm")(
                kva[..., :R])
            kv = dense(H * (dn + dv), name="kv_b")(c.astype(self.dtype))
            angles = jnp.asarray(rope_angles(S, dr, z.rope_base))
            if prep == "pallas":
                q, k, v = mla_prep(q, kv, kva[..., R:], angles, heads=H,
                                   nope=dn)
                attend = functools.partial(flash_attention, qk_major=True,
                                           heads=H)
            else:
                q, k, v = latent_qkv(q, kv, kva[..., R:], angles, H, dn)
                attend = functools.partial(attention, impl=impl)
        o = attend(q, k, v, causal=True, key_mask=mask)
        o = dense(self.dim, name="out")(
            o.reshape(B, S, H * dv).astype(self.dtype))
        return x + o.astype(f32)


class DenseSwiGLU(nn.Module):
    """The second sublayer of an :class:`MlaBlock` that has no experts:
    RMSNorm, one bias-free SwiGLU of ``width``, the residual."""

    width: int
    eps: float
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        h = nn.RMSNorm(epsilon=self.eps, dtype=jnp.float32, name="ln")(x)
        y = _swiglu(self, h.astype(self.dtype), self.width, "mlp")
        return x + y.astype(jnp.float32)


class MlaBlock(_RoutedBlock):
    """A DeepSeek-V3-style layer: :class:`LatentAttention`, then either
    :class:`RoutedExperts` behind :func:`_sigmoid_router` with a shared
    expert (``z.shared_experts * z.expert_dim`` wide) and plain residuals, or,
    in a model's leading ``z.dense_layers`` layers (``dense=True``), a
    :class:`DenseSwiGLU` of ``z.dense_dim``: such a layer has no router and no
    ``moe_tokens``. ``__call__`` takes and returns ``(x, r)`` either way; the
    router has no state beside the residual stream and ``r`` is None.

    Training only: a cache for latent attention is one ``kv_rank +
    qk_rope_dim`` wide row a token (the latent and the shared rotary key),
    where ``serving/paged_cache.py`` pools per-head K and V, and the experts
    are not on the paged path."""

    dense: bool = False

    no_serving = ("a latent cache is one (kv_rank + qk_rope_dim)-wide row a "
                  "token (the key-value latent and the shared rotary key) "
                  "where serving/paged_cache.py pools per-head K and V, and "
                  "the dropless experts are not on the paged path")

    def setup(self):
        z = self.z
        self.attn = LatentAttention(self.dim, self.heads, z, self.dtype,
                                    self.attn_impl)
        if self.dense:
            self.mlp = DenseSwiGLU(z.dense_dim, z.norm_eps, self.dtype)
        else:
            self.moe = RoutedExperts(
                self.dim, z, self.dtype, router=_sigmoid_router, join=_added,
                shared_dim=z.shared_experts * z.expert_dim)

    def attend(self, x, mask):
        return self.attn(x, mask)

    def __call__(self, x, r, mask=None, training: bool = False):
        x = self.attend(x, mask)
        return (self.mlp(x), r) if self.dense else self.moe(x, r)


def _check_mla_options(mla: MlaDims, *, quant, attn_window, pos_embedding,
                       depth, zaya=None, sdar=None):
    """Raise for an option the latent-attention block cannot honour."""
    refused = {
        "quant=True (quantize_lm knows the dense block's matrices only)":
            quant,
        "attn_window (latent attention attends to every earlier position)":
            attn_window is not None,
        "pos_embedding other than 'rope' (the rotary part of q and k is "
        "rotated in the block)": pos_embedding != "rope",
        "zaya= or sdar= as well (a model has one family of block)":
            zaya is not None or sdar is not None,
        "an odd qk_rope_dim (rotary pairs)": mla.qk_rope_dim % 2,
        "more experts a token than the router has":
            not 1 <= mla.experts_per_token <= mla.experts,
        "dense_layers outside 0 .. depth": not 0 <= mla.dense_layers <= depth,
    }
    for what, hit in refused.items():
        if hit:
            raise ValueError(
                f"the latent-attention block cannot honour {what}")


@dataclasses.dataclass(frozen=True)
class NextDims:
    """The sizes of a hybrid linear-attention expert block (:class:`NextBlock`)
    that ``dim``, ``heads`` and ``kv_heads`` do not give. Defaults are
    Qwen3-Next-80B-A3B's published ones."""

    head_dim: int = 256               # the full-attention layers' heads
    rotary_dim: int = 64              # a head's leading columns that are rotated
    rope_base: float = 1e7
    #: every ``full_attention_interval``-th layer has gated full attention,
    #: the others Gated DeltaNet
    full_attention_interval: int = 4
    linear_key_heads: int = 16
    linear_value_heads: int = 32      # value head j reads key head j // 2
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    experts: int = 512                # the router's width
    experts_per_token: int = 10
    #: ``(first, count)``: the experts this model holds of all ``experts``
    #: (an ``ep`` rank's share); ``None`` holds them all
    experts_held: tuple | None = None
    expert_dim: int = 512
    shared_dim: int = 512             # the gated shared expert's width
    norm_eps: float = 1e-6

    @property
    def held(self) -> tuple:
        return self.experts_held or (0, self.experts)


class CentredRMSNorm(nn.Module):
    """RMSNorm whose learned weight is centred on 0: ``x / sqrt(mean(x^2) +
    eps) * (1 + scale)``, ``scale`` initialised to 0. Float32."""

    epsilon: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.epsilon) * (1.0 + w)


class GatedDeltaNet(nn.Module):
    """The linear-attention sublayer of :class:`NextBlock`: a centred RMSNorm;
    ``h Wqkvz`` and ``h Wba`` with their columns grouped by key head (per key
    head ``g``: q, k, the v of value heads ``2g`` and ``2g + 1``, their z; and
    b then a of those two heads); a causal depthwise convolution of
    ``z.conv_kernel`` taps with SiLU over the ``[q; k; v]`` channels; q and k
    L2-normed (q also by ``linear_key_dim ** -0.5``); ``beta = sigmoid(b)``,
    ``g = -exp(A_log) softplus(a + dt_bias)``; the gated delta rule
    (``ops.gated_delta``); a gated RMSNorm ``o / rms(o) * w * silu(z)`` a
    value head with one ``[linear_value_dim]`` weight; the output projection
    and the residual. A ``mask [B, S]`` (1 = a real token) keeps a padded
    token from decaying or writing the state."""

    dim: int
    z: NextDims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, mask=None):
        from distkeras_tpu.ops.gated_delta import gated_delta

        z, f32 = self.z, jnp.float32
        B, S, _ = x.shape
        Hk, Hv, dk, dv = (z.linear_key_heads, z.linear_value_heads,
                          z.linear_key_dim, z.linear_value_dim)
        G = Hv // Hk
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        h = CentredRMSNorm(z.norm_eps, name="ln")(x).astype(self.dtype)
        qkvz = dense(Hk * (2 * dk + 2 * G * dv), name="qkvz")(h).reshape(B, S, Hk, -1)
        ba = dense(2 * Hv, name="ba")(h).reshape(B, S, Hk, 2, G)
        q, k, v, gate = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + G * dv], axis=-1)
        conv = self.param("conv", nn.initializers.lecun_normal(),
                          (z.conv_kernel, Hk * (2 * dk + G * dv)), f32)

        # elementwise passes over [B, S, 8192]-wide arrays: made again in the
        # backward from their bf16 input rather than kept in float32
        @jax.checkpoint
        def conv_qkv(mixed, w):
            with jax.named_scope("gdn_conv"):
                mixed = nn.silu(_causal_depthwise(mixed, w.astype(self.dtype)))
            q, k, v = jnp.split(mixed, [Hk * dk, 2 * Hk * dk], axis=-1)
            unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
            q = unit(q.astype(f32).reshape(B, S, Hk, dk)) * dk ** -0.5
            k = unit(k.astype(f32).reshape(B, S, Hk, dk))
            return q.astype(self.dtype), k.astype(self.dtype), v.reshape(B, S, Hv, dv)

        @jax.checkpoint
        def gated_norm(o, gate, w):
            with jax.named_scope("gdn_norm"):
                o = o.astype(f32)
                o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + z.norm_eps) * w
                o = o * nn.silu(gate.reshape(B, S, Hv, dv).astype(f32))
            return o.reshape(B, S, Hv * dv).astype(self.dtype)

        q, k, v = conv_qkv(jnp.concatenate([a.reshape(B, S, -1) for a in (q, k, v)], -1), conv)
        a_log = self.param("A_log", lambda key, s: jnp.log(
            jax.random.uniform(key, s, f32, 1.0, 16.0)), (Hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (Hv,), f32)
        beta = jax.nn.sigmoid(ba[..., 0, :].reshape(B, S, Hv).astype(f32))
        g = -jnp.exp(a_log) * jax.nn.softplus(
            ba[..., 1, :].reshape(B, S, Hv).astype(f32) + dt_bias)
        if mask is not None:
            keep = jnp.asarray(mask, f32)[..., None]
            beta, g = beta * keep, g * keep
        o = gated_delta(q, k, v, g, beta)
        o = gated_norm(o, gate, self.param("norm", nn.initializers.ones, (dv,), f32))
        return x + dense(self.dim, name="out")(o).astype(f32)


class GatedAttention(nn.Module):
    """The full-attention sublayer of :class:`NextBlock`: a centred RMSNorm;
    ``h Wq`` as ``heads`` heads of a query and its output gate, ``z.head_dim``
    each; ``kv_heads`` key and value heads; a centred RMSNorm over each q and
    k head (:func:`head_norm_rope` with ``1 + w``), rotary on a head's first
    ``z.rotary_dim`` columns; causal attention through the flash kernels
    (query head ``i`` reads key-value head ``i // (heads // kv_heads)``);
    ``(o * sigmoid(gate)) Wo`` and the residual."""

    dim: int
    heads: int
    kv_heads: int
    z: NextDims
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "reference"

    @nn.compact
    def __call__(self, x, mask=None):
        from distkeras_tpu.ops.flash_attention import BLOCK_Q, attention

        z, f32 = self.z, jnp.float32
        B, S, _ = x.shape
        H, K, dh = self.heads, self.kv_heads, z.head_dim
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        h = CentredRMSNorm(z.norm_eps, name="ln")(x).astype(self.dtype)
        q, gate = jnp.split(dense(2 * H * dh, name="q")(h).reshape(B, S, H, 2 * dh), 2, -1)
        k, v = dense(K * dh, name="k")(h), dense(K * dh, name="v")(h)
        wq, wk = (self.param(name, nn.initializers.zeros, (dh,), f32)
                  for name in ("q_norm", "k_norm"))
        angles = jnp.asarray(rope_angles(S, z.rotary_dim, z.rope_base))

        # made again in the backward from their bf16 inputs rather than kept
        # in float32, as the linear-attention layers' elementwise passes are
        @functools.partial(jax.checkpoint, static_argnums=(2,))
        def norm_rope(a, w, n):
            return head_norm_rope(a, 1.0 + w, angles, n, z.norm_eps,
                                  z.rotary_dim).astype(self.dtype)

        @jax.checkpoint
        def gated(o, gate):
            o = o.astype(f32) * jax.nn.sigmoid(gate.astype(f32))
            return o.reshape(B, S, H * dh).astype(self.dtype)

        impl = self.attn_impl
        if impl == "flash" and S % BLOCK_Q:
            impl = "reference"
        o = attention(norm_rope(q.reshape(B, S, -1), wq, H), norm_rope(k, wk, K),
                      v.reshape(B, S, K, dh), causal=True, key_mask=mask, impl=impl)
        return x + dense(self.dim, name="out")(gated(o, gate)).astype(f32)


class NextBlock(_RoutedBlock):
    """A Qwen3-Next layer: :class:`GatedDeltaNet` (``gdn``), or in a layer of
    ``full=True`` :class:`GatedAttention` (``attn``), then
    :class:`RoutedExperts` behind :func:`_topk_router` with a gated shared
    expert of ``z.shared_dim``, centred RMSNorms and plain residuals.
    ``__call__`` takes and returns ``(x, r)``; the router has no state and
    ``r`` is None.

    Training only: a linear-attention layer's cache is a fixed-size state a
    value head (and its convolution's last taps), which ``serving/`` does not
    hold beside paged K/V, and the experts are not on the paged path."""

    full: bool = False

    no_serving = ("Gated DeltaNet's cache is a fixed-size state a value head "
                  "and a convolution tail, which serving/paged_cache.py does "
                  "not hold beside paged K/V, and the dropless experts are not "
                  "on the paged path")

    def setup(self):
        z = self.z
        if self.full:
            self.attn = GatedAttention(self.dim, self.heads, self.kv_heads, z, self.dtype,
                                       self.attn_impl)
        else:
            self.gdn = GatedDeltaNet(self.dim, z, self.dtype)
        self.moe = RoutedExperts(self.dim, z, self.dtype, router=_topk_router, join=_added,
                                 shared_dim=z.shared_dim, shared_gate=True,
                                 norm=CentredRMSNorm)

    def attend(self, x, mask):
        return self.attn(x, mask) if self.full else self.gdn(x, mask)


def _check_next_options(nxt: NextDims, *, quant, attn_window, pos_embedding, others=()):
    """Raise for an option the hybrid linear-attention block cannot honour."""
    refused = {
        "quant=True (quantize_lm knows the dense block's matrices only)": quant,
        "attn_window (the full-attention layers attend to every earlier position)":
            attn_window is not None,
        "pos_embedding other than 'rope' (the block rotates q and k itself)":
            pos_embedding != "rope",
        "zaya=, sdar= or mla= as well (a model has one family of block)":
            any(o is not None for o in others),
        "an odd rotary_dim, or one wider than head_dim":
            nxt.rotary_dim % 2 or nxt.rotary_dim > nxt.head_dim,
        "linear_value_heads that are no multiple of linear_key_heads":
            nxt.linear_value_heads % nxt.linear_key_heads,
        "more experts a token than the router has":
            not 1 <= nxt.experts_per_token <= nxt.experts,
    }
    for what, hit in refused.items():
        if hit:
            raise ValueError(f"the hybrid linear-attention block cannot honour {what}")


def _check_sdar_options(sdar: SdarDims, *, quant, attn_window, pos_embedding,
                        maxlen, zaya=None):
    """Raise for an option the block-diffusion block cannot honour."""
    refused = {
        "quant=True (quantize_lm knows the dense block's matrices only)":
            quant,
        "attn_window (the block-diffusion mask is no band)":
            attn_window is not None,
        "pos_embedding other than 'rope' (q and k are rotated at the "
        "token's position in its row)": pos_embedding != "rope",
        "zaya= as well (a model has one kind of block)": zaya is not None,
        "a block_length that is no power of two up to 128, or does not "
        "divide maxlen": sdar.block_length < 1
            or sdar.block_length & (sdar.block_length - 1)
            or sdar.block_length > 128 or maxlen % sdar.block_length,
        "an odd head_dim (rotary pairs)": sdar.head_dim % 2,
        "more experts a token than the router has":
            not 1 <= sdar.experts_per_token <= sdar.experts,
    }
    for what, hit in refused.items():
        if hit:
            raise ValueError(f"the block-diffusion block cannot honour {what}")


def _check_zaya_options(zaya: ZayaDims, *, quant, attn_window, pos_embedding,
                        kv_heads):
    """Raise for an option the ZAYA block cannot honour; none is ignored."""
    refused = {
        "quant=True (quantize_lm knows the dense block's matrices only)":
            quant,
        "attn_window (CCA attends to every earlier position)":
            attn_window is not None,
        "pos_embedding other than 'rope' (CCA rotates q and k itself)":
            pos_embedding != "rope",
        "kv_heads=None or odd (the value's halves are this token's and the "
        "previous one's)": not kv_heads or kv_heads % 2,
        "an odd rotary width": int(zaya.head_dim * zaya.rotary_fraction) % 2,
    }
    for what, hit in refused.items():
        if hit:
            raise ValueError(f"the ZAYA block cannot honour {what}")


def moe_tokens(counters) -> np.ndarray | None:
    """The tokens (under top-k: (token, expert) pairs) routed to each expert
    of each expert layer, ``[expert layers, experts]``, from a model's
    counters by path (``{"blocks_<i>/moe/moe_tokens": counts}``, as
    ``MeshTrainer.counters_`` and a history record's ``counters`` hold them);
    ``None`` where there are none. Rows follow the layers that HAVE experts,
    in order: for a model whose leading layers are dense
    (``MlaDims.dense_layers``) row 0 is layer ``dense_layers``, not layer
    0."""
    rows = {int(path.split("/")[0].removeprefix("blocks_")): counts
            for path, counts in (counters or {}).items()
            if path.endswith("/moe/moe_tokens")}
    if not rows:
        return None
    return np.stack([np.asarray(rows[i], np.int64) for i in sorted(rows)])


class TransformerLM(nn.Module):
    """Token sequence → next-token logits ``[B, L, vocab]`` (training), with
    ``prefill``/``decode_step`` methods for cached autoregressive decoding."""

    vocab: int = 1024
    maxlen: int = 256
    dim: int = 128
    heads: int = 4
    depth: int = 2
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "reference"
    attn_window: int | None = None  # sliding-window (local) attention span
    kv_heads: int | None = None     # GQA shared K/V heads (1 = MQA)
    #: "sincos" (additive table at the embedding, Vaswani et al.) or "rope"
    #: (rotary q/k rotations in every block, Su et al. — relative positions,
    #: nothing added to the residual stream)
    pos_embedding: str = "sincos"
    #: int8 weight-only serving mode — see :func:`quantize_lm`
    quant: bool = False
    #: rematerialize each block in the backward pass (jax.checkpoint) —
    #: the long-context training memory lever, same as the encoder family;
    #: decode entry points (prefill/step) are never differentiated and
    #: stay unwrapped. A block keeps its input and what ``ops.REMAT_SAVED``
    #: names: under ``attn_impl="flash"`` the kernel's output, ``B·L·H·Dv``
    #: of ``dtype`` a layer (half of the block's float32 input where
    #: ``H·Dv = dim``, as much as it under latent attention's ``2·dim``),
    #: and one float32 a row and head, so that its backward does not run
    #: the flash forward a second time
    remat: bool = False
    #: share the token embedding with the output head (Press & Wolf 2017):
    #: logits = hidden @ embedding.T — V·dim fewer parameters, and the
    #: embedding receives both input- and output-side gradients
    tie_embeddings: bool = False
    #: the block type: ``None`` is the dense pre-LN GELU :class:`DecoderBlock`;
    #: a :class:`ZayaDims` makes every layer a :class:`ZayaBlock` (RMSNorm,
    #: CCA, routed experts) and the head's norm an RMSNorm
    zaya: ZayaDims | None = None
    #: a :class:`SdarDims` makes every layer a :class:`SdarBlock` (RMSNorm,
    #: per-head q/k norms, top-k experts) run over a block-diffusion stream:
    #: a forward doubles each row (``hidden`` returns the clean copy's
    #: states), ``noised_hidden`` is the training objective's
    sdar: SdarDims | None = None
    #: a :class:`MlaDims` makes every layer an :class:`MlaBlock` (latent
    #: attention; the leading ``dense_layers`` with a dense SwiGLU, the others
    #: with sigmoid-routed experts and a shared expert): layers of two kinds
    #: in the one list of blocks
    mla: MlaDims | None = None
    #: a :class:`NextDims` makes every layer a :class:`NextBlock` (Gated
    #: DeltaNet, or gated full attention in every
    #: ``full_attention_interval``-th layer, then top-k experts beside a
    #: gated shared expert): layers of two kinds in the one list of blocks
    next: NextDims | None = None

    def setup(self):
        if self.kv_heads is not None and self.heads % self.kv_heads:
            raise ValueError(
                f"heads {self.heads} must be a multiple of kv_heads "
                f"{self.kv_heads}"
            )
        if self.pos_embedding not in ("sincos", "rope"):
            raise ValueError(
                f"unknown pos_embedding {self.pos_embedding!r}; use "
                f"'sincos' or 'rope'"
            )
        if self.pos_embedding == "rope" and (self.dim // self.heads) % 2:
            raise ValueError(
                f"RoPE needs an even head dim, got dim//heads = "
                f"{self.dim // self.heads}"
            )
        self.embed = nn.Embed(self.vocab, self.dim, dtype=self.dtype)
        if self.next is not None:
            _check_next_options(self.next, quant=self.quant, attn_window=self.attn_window,
                                pos_embedding=self.pos_embedding,
                                others=(self.zaya, self.sdar, self.mla))
            every = self.next.full_attention_interval
            self._setup_routed(NextBlock, self.next,
                               [dict(full=(i + 1) % every == 0) for i in range(self.depth)],
                               norm=CentredRMSNorm)
            return
        if self.mla is not None:
            _check_mla_options(self.mla, quant=self.quant,
                               attn_window=self.attn_window,
                               pos_embedding=self.pos_embedding,
                               depth=self.depth, zaya=self.zaya,
                               sdar=self.sdar)
            self._setup_routed(
                MlaBlock, self.mla,
                [dict(dense=i < self.mla.dense_layers)
                 for i in range(self.depth)])
            return
        if self.zaya is not None:
            _check_zaya_options(self.zaya, quant=self.quant,
                                attn_window=self.attn_window,
                                pos_embedding=self.pos_embedding,
                                kv_heads=self.kv_heads)
            self._setup_routed(ZayaBlock, self.zaya)
            return
        if self.sdar is not None:
            _check_sdar_options(self.sdar, quant=self.quant,
                                attn_window=self.attn_window,
                                pos_embedding=self.pos_embedding,
                                maxlen=self.maxlen, zaya=self.zaya)
            self._setup_routed(SdarBlock, self.sdar)
            # what the noise of a training step is drawn from, and what the
            # steps have masked: state beside the experts' counters
            self.bd_key = self.variable(
                "counters", "bd_key",
                lambda: jax.random.key_data(self.make_rng("params")))
            self.bd_step = self.variable(
                "counters", "bd_step", lambda: jnp.zeros((), jnp.int32))
            self.bd_masked = self.variable(
                "counters", "bd_masked_tokens",
                lambda: jnp.zeros((), jnp.int32))
            return
        # nn.remat preserves the params tree (blocks_i names unchanged) and
        # transforms __call__ only — prefill/step run through the same
        # parameters un-rematted, which is exactly right for decode
        block_cls = (nn.remat(DecoderBlock, static_argnums=(3,),
                              policy=ops.remat_policy())
                     if self.remat else DecoderBlock)
        self.blocks = [
            block_cls(dim=self.dim, heads=self.heads, dtype=self.dtype,
                      attn_impl=self.attn_impl,
                      attn_window=self.attn_window,
                      kv_heads=self.kv_heads,
                      rope=self.pos_embedding == "rope",
                      maxlen=self.maxlen,
                      quant=self.quant)
            for _ in range(self.depth)
        ]
        self.ln_head = nn.LayerNorm(dtype=jnp.float32)
        if not self.tie_embeddings:
            head = QDense if self.quant else nn.Dense
            self.lm_head = head(self.vocab, dtype=self.dtype)

    def _setup_routed(self, block, dims, kinds=None, norm=nn.RMSNorm):
        """Layers of a routed block (:class:`_RoutedBlock`), the head's
        RMSNorm (of class ``norm``) and, untied, its bias-free head.
        ``kinds``: a layer's own fields, where the layers are not all of one
        kind."""
        block_cls = (nn.remat(block, static_argnums=(4,),
                              policy=ops.remat_policy())
                     if self.remat else block)
        self.blocks = [
            block_cls(dim=self.dim, heads=self.heads,
                      kv_heads=self.kv_heads or self.heads, z=dims,
                      dtype=self.dtype, attn_impl=self.attn_impl, **kind)
            for kind in kinds or [{}] * self.depth
        ]
        self.ln_head = norm(epsilon=dims.norm_eps, dtype=jnp.float32)
        if not self.tie_embeddings:
            self.lm_head = nn.Dense(self.vocab, use_bias=False,
                                    dtype=self.dtype)

    def _embed_at(self, tokens, pos0: int | jax.Array = 0):
        """Embed ``tokens`` occupying positions ``pos0 .. pos0+L``."""
        x = self.embed(tokens).astype(jnp.float32)
        if self.pos_embedding == "rope":
            return x  # positions enter through the per-block q/k rotations
        table = jnp.asarray(sincos_positions(self.maxlen, self.dim))
        pos = jax.lax.dynamic_slice(
            table, (pos0, 0), (tokens.shape[1], self.dim)
        )
        return x + pos[None]

    def _head(self, h):
        """Output projection over post-``ln_head`` hiddens — the ONE place
        the head cast discipline lives (bf16 matmul, f32 logits); shared by
        training, prefill, and decode so the paths cannot drift. Tied mode
        contracts against the embedding table (``nn.Embed.attend``)."""
        h16 = h.astype(self.dtype)
        if self.tie_embeddings:
            return self.embed.attend(h16).astype(jnp.float32)
        return self.lm_head(h16).astype(jnp.float32)

    def _logits(self, x):
        return self._head(self.ln_head(x))

    def __call__(self, tokens, mask=None, training: bool = False):
        # one forward definition: the unfused path is exactly hidden() + the
        # head matmul, so the fused_ce loss can never drift from training's
        return self._head(self.hidden(tokens, mask, training))

    def hidden(self, tokens, mask=None, training: bool = False):
        """Final pre-head hidden states ``[B, L, dim]`` (after the head
        LayerNorm, f32) — the ``fused_ce`` loss path consumes these and
        applies ``lm_head`` chunk-by-chunk, so the ``[B, L, vocab]`` logits
        tensor never materializes (``ops/fused_ce.py``)."""
        if self.sdar is not None:
            # a clean row's states under the block-causal mask are those of
            # the clean copy of a stream whose noised copy is not read
            both = None if mask is None else jnp.concatenate([mask, mask], 1)
            return self._routed_hidden(
                jnp.concatenate([tokens, tokens], axis=1), both,
                training)[:, tokens.shape[1]:]
        if self.zaya is not None or self.mla is not None or self.next is not None:
            return self._routed_hidden(tokens, mask, training)
        x = self._embed_at(tokens)
        for blk in self.blocks:
            x = blk(x, mask, training)
        return self.ln_head(x)

    def _routed_hidden(self, tokens, mask, training):
        x = self._embed_at(tokens)
        # a router's state passes from layer to layer beside x
        r = None if self.zaya is None else jnp.zeros(
            x.shape[:2] + (self.zaya.router_dim,), jnp.float32)
        for blk in self.blocks:
            x, r = blk(x, r, mask, training)
        return self.ln_head(x)

    def noised_hidden(self, tokens, training: bool = False):
        """The block-diffusion objective's states for clean rows ``tokens
        [R, L]``: ``(hidden [R, L, dim], weight [R, L])``. The step's noise
        (:func:`block_diffusion_noise` from the state's ``bd_key`` and
        ``bd_step``) masks some tokens (id ``vocab - 1``: the data never
        holds it); the stream ``[noised ; clean]`` goes through the layers,
        and ``hidden`` is the NOISED copy's states after the head's norm:
        position ``i`` predicts token ``i`` itself. ``weight`` is ``1 / t``
        at a masked position and 0 elsewhere. With the ``counters``
        collection mutable (a training step) ``bd_step`` goes up by one and
        ``bd_masked_tokens`` by the positions masked."""
        if self.sdar is None:
            raise ValueError("noised_hidden is the block-diffusion block's "
                             "(transformer_lm(sdar=...))")
        L = tokens.shape[1]
        with jax.named_scope("bd_noise"):
            noised, t, masked = block_diffusion_noise(
                self.bd_key.value, self.bd_step.value, tokens,
                self.sdar.block_length, self.sdar.noise_floor, self.vocab - 1)
            stream = jnp.concatenate([noised, tokens], axis=1)
            weight = jnp.where(masked, 1.0 / t, 0.0)
        h = self._routed_hidden(stream, None, training)[:, :L]
        if self.is_mutable_collection("counters") \
                and not self.is_initializing():
            self.bd_step.value = self.bd_step.value + 1
            self.bd_masked.value = self.bd_masked.value \
                + jnp.sum(masked, dtype=jnp.int32)
        return h, weight

    def prefill(self, tokens):
        """Full forward over the prompt; returns ``(logits, caches)`` with
        per-block K/V buffers holding positions ``< L``. Cache length is
        ``maxlen``, or ``attn_window`` for sliding-window models — then the
        buffer is a ring (slot ``p % window``) seeded with the last
        ``window`` prompt positions; decode never reads beyond the band, so
        nothing else is needed."""
        B, L = tokens.shape
        dh = self.dim // self.heads
        hkv = self.kv_heads if self.kv_heads is not None else self.heads
        cache_len = self.maxlen
        if self.attn_window is not None:
            cache_len = min(self.maxlen, int(self.attn_window))
        x = self._embed_at(tokens)
        caches = []
        ring_pos = None
        if cache_len < self.maxlen:
            slots = jnp.arange(cache_len)
            # absolute position living in each slot after prefill; negative
            # ⇒ never written, masked by step()'s validity
            ring_pos = (L - 1) - ((L - 1 - slots) % cache_len)
        for blk in self.blocks:
            x, k, v = blk.prefill(x, None)   # k/v hold Hkv heads under GQA
            if ring_pos is not None:
                kc = jnp.take(k, jnp.maximum(ring_pos, 0), axis=1)
                vc = jnp.take(v, jnp.maximum(ring_pos, 0), axis=1)
                caches.append((kc.astype(self.dtype),
                               vc.astype(self.dtype)))
                continue
            kc = jnp.zeros((B, cache_len, hkv, dh), self.dtype)
            vc = jnp.zeros_like(kc)
            kc = jax.lax.dynamic_update_slice(
                kc, k.astype(self.dtype), (0, 0, 0, 0)
            )
            vc = jax.lax.dynamic_update_slice(
                vc, v.astype(self.dtype), (0, 0, 0, 0)
            )
            caches.append((kc, vc))
        return self._logits(x), tuple(caches)

    def decode_step(self, tok, caches, pos):
        """One decode step: ``tok`` [B] int32 at position ``pos`` (traced
        scalar ok) → ``(next-token logits [B, vocab], updated caches)``."""
        x = self._embed_at(tok[:, None], pos)
        new_caches = []
        for blk, (kc, vc) in zip(self.blocks, caches):
            x, kc, vc = blk.step(x, kc, vc, pos)
            new_caches.append((kc, vc))
        return self._logits(x)[:, 0], tuple(new_caches)

    def extend(self, tokens, caches, pos0):
        """Multi-token cached decode: ``tokens`` [B, T] occupying absolute
        positions ``pos0 .. pos0+T-1`` → ``(logits [B, T, vocab], updated
        caches)``; ``logits[:, t]`` predicts position ``pos0+t+1``.
        Speculative decoding's verify forward — T candidate tokens scored
        against the cache at one batched pass's cost."""
        x = self._embed_at(tokens, pos0)
        new_caches = []
        for blk, (kc, vc) in zip(self.blocks, caches):
            x, kc, vc = blk.extend(x, kc, vc, pos0)
            new_caches.append((kc, vc))
        return self._logits(x), tuple(new_caches)

    # -- block-paged decode (the serving tier's entry points) ---------------

    def _embed_rows(self, tokens, positions):
        """Embed ``tokens`` [B, T] where row ``b`` occupies absolute
        positions ``positions[b] .. positions[b]+T-1`` (per-row positions —
        the paged decode batch mixes sequences of different lengths)."""
        x = self.embed(tokens).astype(jnp.float32)
        if self.pos_embedding == "rope":
            return x
        table = jnp.asarray(sincos_positions(self.maxlen, self.dim))
        T = tokens.shape[1]
        pos = table[positions[:, None] + jnp.arange(T)[None, :]]
        return x + pos

    def prefill_raw(self, tokens):
        """Full forward over the prompt returning ``(logits, kvs)`` with
        per-block UNPADDED K/V ``[B, L, Hkv, Dh]`` (keys pre-rotated under
        RoPE, cast to the cache dtype) — the serving tier scatters these
        into its block pool instead of a dense ``[B, maxlen]`` buffer."""
        x = self._embed_at(tokens)
        kvs = []
        for blk in self.blocks:
            x, k, v = blk.prefill(x, None)
            kvs.append((k.astype(self.dtype), v.astype(self.dtype)))
        return self._logits(x), tuple(kvs)

    def paged_extend_rows(self, tokens, k_pools, v_pools, tables,
                          write_slots, positions, block_size: int):
        """Multi-token decode against the block-paged cache: ``tokens``
        [B, T], row ``b`` occupying positions ``positions[b] ..
        positions[b]+T-1``; ``k_pools``/``v_pools`` are per-layer flat slot
        pools (tuple of ``[S, Hkv, Dh]``), ``tables`` [B, nb] the per-row
        block tables and ``write_slots`` [B, T] this call's flat write
        targets. Returns ``(logits [B, T, vocab], k_pools, v_pools)``;
        ``logits[:, t]`` predicts row position ``positions[b]+t+1``. T=1
        is the serving decode step; T=K+1 is the speculative verify
        forward — same body, same parity guarantees as the dense
        :meth:`extend`."""
        x = self._embed_rows(tokens, positions)
        new_k, new_v = [], []
        for blk, kp, vp in zip(self.blocks, k_pools, v_pools):
            x, kp, vp = blk.paged_extend(x, kp, vp, tables, write_slots,
                                         positions, block_size)
            new_k.append(kp)
            new_v.append(vp)
        return self._logits(x), tuple(new_k), tuple(new_v)

    def paged_decode_step(self, tok, k_pools, v_pools, tables, write_slot,
                          positions, block_size: int):
        """One paged decode step: ``tok`` [B] int32, each row at its own
        ``positions[b]`` writing flat pool slot ``write_slot[b]`` →
        ``(next-token logits [B, vocab], updated pools)``."""
        logits, k_pools, v_pools = self.paged_extend_rows(
            tok[:, None], k_pools, v_pools, tables, write_slot[:, None],
            positions, block_size,
        )
        return logits[:, 0], k_pools, v_pools


def _check_decode_args(fn_name: str, model, prompt, max_new_tokens: int):
    """Shared validation for generate()/beam_search(): returns
    ``(module, prompt int32 [B, Lp])`` or raises."""
    module = model.module if isinstance(model, ModelSpec) else model
    if not isinstance(module, TransformerLM):
        raise TypeError(
            f"{fn_name}() needs a TransformerLM (or its ModelSpec from "
            f"transformer_lm()), got {type(module)}"
        )
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim != 2:
        raise ValueError(f"prompt must be [batch, length], got {prompt.shape}")
    if prompt.shape[1] + max_new_tokens > module.maxlen:
        raise ValueError(
            f"prompt length {prompt.shape[1]} + max_new_tokens "
            f"{max_new_tokens} exceeds the model's maxlen {module.maxlen}"
        )
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    return module, prompt


def _warp_fn(temperature: float, top_k: int | None,
             top_p: float | None = None):
    """Logit-warping for sampling: temperature scale, then top-k, then
    nucleus (top-p) truncation. Returns warped logits (filtered tokens at
    -1e30); ``softmax(warped)`` is the distribution every sampling path —
    plain :func:`generate` and speculative verify alike — draws from.

    Tie behavior at the nucleus boundary: every token whose warped logit
    EQUALS the cutoff survives (strict ``scaled < cutoff`` filter), so with
    exactly-tied logits the kept support can exceed the minimal nucleus by
    the tied tokens — the conventional choice (matches the common HF
    implementation), and the one that keeps the filter permutation-
    invariant. Requires temperature > 0."""

    def warp(logits):
        scaled = logits.astype(jnp.float32) / temperature
        if top_k is not None:
            kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, -1e30, scaled)
        if top_p is not None and top_p < 1.0:
            desc = jnp.sort(scaled, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(desc, axis=-1)
            # keep a token iff the mass strictly BEFORE it is < top_p: the
            # minimal nucleus covering top_p, never empty
            keep = jnp.cumsum(probs, axis=-1) - probs < top_p
            cutoff = jnp.min(
                jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True
            )
            scaled = jnp.where(scaled < cutoff, -1e30, scaled)
        return scaled

    return warp


def _sample_fn(temperature: float, top_k: int | None,
               top_p: float | None = None):
    """Greedy for temperature==0, else temperature/top-k/top-p categorical.

    Filters compose in the conventional order: top-k first, then nucleus
    (top-p) over the surviving distribution — smallest prefix of
    descending-probability tokens whose mass reaches ``top_p`` (the top-1
    token always survives; see :func:`_warp_fn` for tie behavior)."""
    if temperature == 0.0:
        def sample(logits, key):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return sample
    warp = _warp_fn(temperature, top_k, top_p)

    def sample(logits, key):
        return jax.random.categorical(
            key, warp(logits), axis=-1
        ).astype(jnp.int32)

    return sample


@functools.lru_cache(maxsize=64)
def _generate_program(module: TransformerLM, max_new_tokens: int,
                      temperature: float, top_k: int | None,
                      top_p: float | None = None,
                      eos_id: int | None = None):
    """One jitted prefill+scan program per (module, decode config) — flax
    modules are frozen dataclasses, so the lru_cache key is by value and
    repeated generate()/GeneratorPredictor chunks reuse the compilation
    (jit itself still specializes per prompt shape).

    With ``eos_id`` the scan becomes a ``lax.while_loop`` carrying a
    per-row ``done`` flag: a finished row keeps its static shape but emits
    ``eos_id`` pads, and the loop exits early once EVERY row is done (the
    only early stop a static-shape program gets for free). The eos-free
    path is byte-identical to before — eos costs nothing when unused."""
    sample = _sample_fn(temperature, top_k, top_p)

    def run(params, prompt, key):
        lp = prompt.shape[1]
        logits, caches = module.apply(
            {"params": params}, prompt, method=TransformerLM.prefill
        )
        key, k0 = jax.random.split(key)
        tok = sample(logits[:, -1], k0)

        if eos_id is None:
            def body(carry, key_i):
                tok, caches, pos = carry
                logits, caches = module.apply(
                    {"params": params}, tok, caches, pos,
                    method=TransformerLM.decode_step,
                )
                nxt = sample(logits, key_i)
                return (nxt, caches, pos + 1), tok

            keys = jax.random.split(key, max_new_tokens)[1:]
            (last, _, _), toks = jax.lax.scan(
                body, (tok, caches, jnp.asarray(lp, jnp.int32)), keys
            )
            # toks: [max_new-1, B] emitted per step, plus the final carry
            out = jnp.concatenate([toks, last[None]], axis=0)
            return jnp.concatenate(
                [prompt, out.T.astype(jnp.int32)], axis=1
            )

        # eos path: mask-and-carry a per-row done flag into a preallocated
        # eos-padded output buffer; while_loop exits when all rows finish
        B = prompt.shape[0]
        done = tok == eos_id
        out = jnp.full((B, max_new_tokens), eos_id, jnp.int32)
        out = out.at[:, 0].set(tok)

        def cond(carry):
            n = carry[0]
            return (n < max_new_tokens) & ~jnp.all(carry[4])

        def body(carry):
            n, tok, caches, out, done = carry
            logits, caches = module.apply(
                {"params": params}, tok, caches, lp + n - 1,
                method=TransformerLM.decode_step,
            )
            nxt = sample(logits, jax.random.fold_in(key, n))
            nxt = jnp.where(done, eos_id, nxt)   # pad after EOS
            out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, n))
            return (n + 1, nxt, caches, out, done | (nxt == eos_id))

        _, _, _, out, _ = jax.lax.while_loop(
            cond, body,
            (jnp.asarray(1, jnp.int32), tok, caches, out, done),
        )
        return jnp.concatenate([prompt, out], axis=1)

    return jax.jit(run)


def generate(model, params, prompt, max_new_tokens: int, *,
             temperature: float = 0.0, top_k: int | None = None,
             top_p: float | None = None, seed: int = 0,
             eos_id: int | None = None):
    """Autoregressive decoding: ``prompt`` [B, Lp] int32 → [B, Lp+new] int32.

    One jitted program: prefill writes the KV caches for the whole prompt in
    a single batched forward, then a ``lax.scan`` emits one token per step
    against the cache (O(L) per token instead of the O(L²) of re-running the
    full forward). ``temperature=0`` is greedy; otherwise categorical
    sampling at the given temperature, optionally truncated to the ``top_k``
    highest-probability tokens and/or the smallest nucleus of tokens whose
    probability mass reaches ``top_p`` (applied after ``top_k``).
    Deterministic for a fixed ``seed``.

    ``eos_id`` stops a row at its first end-of-sequence token: the row pads
    with ``eos_id`` from there on (static output shape — shapes never
    depend on data), and the decode loop exits early once every row has
    finished. Rows that never emit ``eos_id`` run the full budget. Count
    real tokens with :func:`distkeras_tpu.serving.per_row_new_token_counts`
    — the same retire rule the serving tier applies per step. NOTE: the
    eos path draws its sampling keys from a different (per-step
    ``fold_in``) schedule than the eos-free scan, so sampled streams with
    and without ``eos_id`` are not token-for-token comparable; greedy
    streams are identical up to the first eos.
    """
    module, prompt = _check_decode_args(
        "generate", model, prompt, max_new_tokens
    )
    if top_k is not None and not 1 <= int(top_k) <= module.vocab:
        raise ValueError(
            f"top_k must be in [1, vocab={module.vocab}], got {top_k}"
        )
    if top_p is not None and not 0.0 < float(top_p) <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if eos_id is not None and not 0 <= int(eos_id) < module.vocab:
        raise ValueError(f"eos_id {eos_id} outside vocab {module.vocab}")
    run = _generate_program(
        module, int(max_new_tokens), float(temperature), top_k,
        None if top_p is None else float(top_p),
        None if eos_id is None else int(eos_id),
    )
    return np.asarray(run(params, prompt, jax.random.PRNGKey(seed)))


@functools.lru_cache(maxsize=32)
def _speculative_program(target: TransformerLM, draft: TransformerLM,
                         max_new_tokens: int, spec_tokens: int):
    """One jitted speculative-decode program per (target, draft, config)."""
    K = spec_tokens

    def run(t_params, d_params, prompt):
        B, lp = prompt.shape
        cap = max_new_tokens + K + 1  # emission block may overhang the tail

        t_logits, t_caches = target.apply(
            {"params": t_params}, prompt, method=TransformerLM.prefill
        )
        _, d_caches = draft.apply(
            {"params": d_params}, prompt, method=TransformerLM.prefill
        )
        tok0 = jnp.argmax(t_logits[:, -1], axis=-1).astype(jnp.int32)
        out = jnp.zeros((B, cap), jnp.int32)
        out = jax.lax.dynamic_update_slice(out, tok0[:, None], (0, 0))

        def cond(carry):
            return carry[1] < max_new_tokens

        def body(carry):
            (out, n, last, t_caches, d_caches, rounds, accepted,
             proposed) = carry
            cur = lp + n - 1  # absolute position of `last`; not yet cached

            def draft_step(c, i):
                tok, caches = c
                logits, caches = draft.apply(
                    {"params": d_params}, tok, caches, cur + i,
                    method=TransformerLM.decode_step,
                )
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (nxt, caches), nxt

            (_, d_caches), props = jax.lax.scan(
                draft_step, (last, d_caches), jnp.arange(K)
            )
            props = props.T  # [B, K]: proposals for positions cur+1..cur+K

            # verify: one cached forward over [last, props…]; logits[:, t]
            # is the target's prediction for position cur+t+1
            block = jnp.concatenate([last[:, None], props], axis=1)
            t_logits, t_caches = target.apply(
                {"params": t_params}, block, t_caches, cur,
                method=TransformerLM.extend,
            )
            g = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)  # [B, K+1]

            # accepted prefix per row, then lockstep on the batch minimum:
            # every row's first `a` proposals equal its own greedy tokens,
            # so emitting props[:, :a] + g[:, a] is exact for every row —
            # uniform positions keep the cache writes dynamic_update_slice
            match = (props == g[:, :K]).astype(jnp.int32)
            a_row = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # [B]
            a = jnp.min(a_row)

            cols = jnp.arange(K + 1)[None, :]
            emit = jnp.where(
                cols == a, g,
                jnp.concatenate(
                    [props, jnp.zeros((B, 1), jnp.int32)], axis=1
                ),
            )  # [B, K+1]: props below a, the correction g[:, a] at a,
            #    garbage above (overwritten by the next round or trimmed)
            out = jax.lax.dynamic_update_slice(out, emit, (0, n))
            last = jnp.take_along_axis(
                g, jnp.full((B, 1), a, jnp.int32), axis=1
            )[:, 0]
            # stats clamp to the emission budget: the final round's block
            # may overhang max_new_tokens; proposals (and accepts) beyond
            # the budget never land in `out`, so they don't count.
            # PER-ROW sums (ADVICE r4): acceptance reports mean draft/
            # target agreement across rows, not the batch-min lockstep
            # advancement (which `rounds` captures). Rows past the
            # batch-min re-propose their overhang next round, so the same
            # POSITION can be counted in proposed/accepted more than once
            # — agreement-per-proposal semantics, documented in
            # speculative_generate's docstring.
            room = max_new_tokens - n
            return (out, n + a + 1, last, t_caches, d_caches, rounds + 1,
                    accepted + jnp.sum(jnp.minimum(a_row, room)),
                    proposed + B * jnp.minimum(K, room))

        out, _, _, _, _, rounds, accepted, proposed = jax.lax.while_loop(
            cond,
            body,
            (out, jnp.asarray(1, jnp.int32), tok0, t_caches, d_caches,
             jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
             jnp.asarray(0, jnp.int32)),
        )
        full = jnp.concatenate([prompt, out[:, :max_new_tokens]], axis=1)
        return full, rounds, accepted, proposed

    return jax.jit(run)


@functools.lru_cache(maxsize=32)
def _speculative_sampled_program(target: TransformerLM,
                                 draft: TransformerLM,
                                 max_new_tokens: int, spec_tokens: int,
                                 temperature: float, top_k: int | None,
                                 top_p: float | None):
    """Sampled speculative decoding (Leviathan et al. 2023, §3): the draft
    SAMPLES K proposals from its warped distribution q; each proposal x_i is
    accepted with probability min(1, p(x_i)/q(x_i)) against the target's
    warped distribution p, and the first rejection is replaced by a sample
    from the residual norm(max(p − q, 0)). Per position the emitted token is
    then distributed EXACTLY as p — acceptance only moves latency, never the
    distribution. Both p and q are warped identically (temperature/top-k/
    top-p), so the preserved distribution is the one plain
    :func:`generate` samples from.

    Lockstep batching: each round every row advances by the batch-minimum
    accepted length ``a``. All rows accepted their first ``a`` proposals, so
    positions 0..a-1 emit proposals; at the cut position each row emits its
    own scheme token — its accepted proposal if it accepted position ``a``,
    else its residual resample (and a fresh p-sample at a == K, where no
    proposal exists). Dropped later proposals were never emitted, so the
    per-row output stream stays exactly p-distributed."""
    K = spec_tokens
    warp = _warp_fn(temperature, top_k, top_p)

    def run(t_params, d_params, prompt, key):
        B, lp = prompt.shape
        cap = max_new_tokens + K + 1

        t_logits, t_caches = target.apply(
            {"params": t_params}, prompt, method=TransformerLM.prefill
        )
        _, d_caches = draft.apply(
            {"params": d_params}, prompt, method=TransformerLM.prefill
        )
        key, k0 = jax.random.split(key)
        tok0 = jax.random.categorical(
            k0, warp(t_logits[:, -1]), axis=-1
        ).astype(jnp.int32)
        out = jnp.zeros((B, cap), jnp.int32)
        out = jax.lax.dynamic_update_slice(out, tok0[:, None], (0, 0))

        def cond(carry):
            return carry[1] < max_new_tokens

        def body(carry):
            (out, n, last, t_caches, d_caches, rounds, accepted,
             proposed) = carry
            cur = lp + n - 1
            kd, ka, kc = jax.random.split(
                jax.random.fold_in(key, rounds), 3
            )

            def draft_step(c, i):
                tok, caches = c
                logits, caches = draft.apply(
                    {"params": d_params}, tok, caches, cur + i,
                    method=TransformerLM.decode_step,
                )
                wl = warp(logits)                          # [B, V] f32
                nxt = jax.random.categorical(
                    jax.random.fold_in(kd, i), wl, axis=-1
                ).astype(jnp.int32)
                return (nxt, caches), (nxt, jax.nn.log_softmax(wl, -1))

            (_, d_caches), (props, q_lp) = jax.lax.scan(
                draft_step, (last, d_caches), jnp.arange(K)
            )
            props = props.T                    # [B, K]
            q_lp = jnp.swapaxes(q_lp, 0, 1)    # [B, K, V]

            block = jnp.concatenate([last[:, None], props], axis=1)
            t_logits, t_caches = target.apply(
                {"params": t_params}, block, t_caches, cur,
                method=TransformerLM.extend,
            )
            p_lp = jax.nn.log_softmax(warp(t_logits), -1)  # [B, K+1, V]

            # accept x_i iff log u < log p(x_i) − log q(x_i)
            idx = props[..., None]
            p_at = jnp.take_along_axis(p_lp[:, :K], idx, axis=-1)[..., 0]
            q_at = jnp.take_along_axis(q_lp, idx, axis=-1)[..., 0]
            log_u = jnp.log(jax.random.uniform(
                ka, (B, K), jnp.float32, minval=1e-37
            ))
            accept = (log_u < p_at - q_at).astype(jnp.int32)   # [B, K]
            a_row = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)
            a = jnp.min(a_row)

            # cut-position token per row (position cur+a+1):
            #  • a == K: no proposal exists — fresh sample from p_K
            #  • row accepted position a: its proposed token stands
            #  • row rejected position a: residual resample from
            #    norm(max(p − q, 0)) (zero-mass guard: if p ≤ q everywhere
            #    the rejection had probability 0; fall back to p)
            a_k = jnp.minimum(a, K - 1)
            ga = jnp.full((B, 1, 1), a_k, jnp.int32)
            p_cut = jnp.take_along_axis(
                p_lp, jnp.broadcast_to(ga, (B, 1, p_lp.shape[-1])), axis=1
            )[:, 0]                                             # [B, V]
            q_cut = jnp.take_along_axis(
                q_lp, jnp.broadcast_to(ga, (B, 1, q_lp.shape[-1])), axis=1
            )[:, 0]
            residual = jnp.maximum(jnp.exp(p_cut) - jnp.exp(q_cut), 0.0)
            has_mass = jnp.sum(residual, -1, keepdims=True) > 0
            res_logits = jnp.where(
                has_mass,
                jnp.where(residual > 0, jnp.log(residual), -jnp.inf),
                p_cut,
            )
            kc1, kc2 = jax.random.split(kc)
            res_tok = jax.random.categorical(
                kc1, res_logits, axis=-1
            ).astype(jnp.int32)
            p_k_tok = jax.random.categorical(
                kc2, p_lp[:, K], axis=-1
            ).astype(jnp.int32)
            accept_at_a = jnp.take_along_axis(
                accept, jnp.full((B, 1), a_k, jnp.int32), axis=1
            )[:, 0].astype(bool)
            prop_at_a = jnp.take_along_axis(
                props, jnp.full((B, 1), a_k, jnp.int32), axis=1
            )[:, 0]
            cut_tok = jnp.where(
                a == K, p_k_tok,
                jnp.where(accept_at_a, prop_at_a, res_tok),
            )

            cols = jnp.arange(K + 1)[None, :]
            emit = jnp.where(
                cols == a, cut_tok[:, None],
                jnp.concatenate(
                    [props, jnp.zeros((B, 1), jnp.int32)], axis=1
                ),
            )
            out = jax.lax.dynamic_update_slice(out, emit, (0, n))
            # per-row stat sums, clamped to the emission budget (see the
            # greedy program): acceptance is mean per-row agreement per
            # PROPOSAL — overhang positions past the batch-min cut are
            # re-proposed (and re-counted) next round, as documented in
            # speculative_generate's docstring
            room = max_new_tokens - n
            return (out, n + a + 1, cut_tok, t_caches, d_caches,
                    rounds + 1,
                    accepted + jnp.sum(jnp.minimum(a_row, room)),
                    proposed + B * jnp.minimum(K, room))

        out, _, _, _, _, rounds, accepted, proposed = jax.lax.while_loop(
            cond,
            body,
            (out, jnp.asarray(1, jnp.int32), tok0, t_caches, d_caches,
             jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
             jnp.asarray(0, jnp.int32)),
        )
        full = jnp.concatenate([prompt, out[:, :max_new_tokens]], axis=1)
        return full, rounds, accepted, proposed

    return jax.jit(run)


def speculative_generate(target, target_params, draft, draft_params, prompt,
                         max_new_tokens: int, *, spec_tokens: int = 4,
                         temperature: float = 0.0, top_k: int | None = None,
                         top_p: float | None = None, seed: int = 0):
    """Speculative decoding (Leviathan et al. 2023): a cheap ``draft``
    model proposes ``spec_tokens`` tokens autoregressively; the ``target``
    model scores all of them in ONE cached forward
    (:meth:`TransformerLM.extend`) and keeps an accepted prefix plus a
    correction token.

    ``temperature=0`` (default) is the greedy scheme: proposals are kept
    while they match the target's own argmax, and the output is **exactly**
    the target's greedy :func:`generate` stream — the draft changes the
    number of target passes (latency), never the tokens. (Exactness rides
    on both paths sharing ONE attention/cache body — ``decode_step`` and
    ``extend`` route through the same block code — so the verify block's
    logits are the same program XLA compiles for plain decode. The
    remaining hazard is EXACT bf16 logit ties: a saturated bf16 model can
    emit several identically-rounded max logits (measured: a 4-way tie on
    a 400M model trained to saturation), and the multi-token verify
    matmul may round a tie one ulp differently than the single-token
    step, after which the two streams are different-but-equally-valid
    greedy decodes. The test suite asserts bitwise equality on f32
    models, where ties have measure zero; a bf16 comparison has to fall
    back to an argmax-within-two-ulps check when streams differ (one
    true ulp is the drift of plain greedy itself against a full-forward
    oracle).)

    ``temperature>0`` is the paper's rejection-sampling scheme: the draft
    SAMPLES each proposal from its warped distribution ``q``; proposal
    ``x`` is accepted with probability ``min(1, p(x)/q(x))`` against the
    target's warped distribution ``p``, and the first rejection is
    replaced by a sample from ``norm(max(p − q, 0))``. Each emitted token
    is then distributed EXACTLY as ``p`` — the same distribution plain
    ``generate(..., temperature, top_k, top_p)`` samples from (the
    warps compose identically) — while the draft only moves latency.
    Deterministic for a fixed ``seed``.

    Returns ``(tokens [B, Lp+new] int32, stats)`` where ``stats`` reports
    ``rounds`` (target verify passes), ``proposed``/``accepted`` draft
    tokens SUMMED PER ROW (final-round proposals that overhang
    ``max_new_tokens`` are excluded from both counts), and the
    ``acceptance`` rate — the mean per-row draft/target agreement PER
    PROPOSAL, not per distinct emitted position. Because the lockstep
    advances every row by the batch-MINIMUM accepted length, a row that
    accepted further than the minimum re-proposes the overhang positions
    next round, and those re-proposals are counted again in both
    ``proposed`` and ``accepted`` (typically re-accepted, having already
    agreed once). The per-position sums can therefore exceed the number
    of distinct emitted positions — ``acceptance`` remains an unbiased
    estimate of P(draft token == target token at a sampled proposal),
    which is the draft-quality number the ratio is meant to report, but
    ``accepted`` is NOT "distinct tokens emitted via the draft".
    Latency is governed separately by the batch-minimum lockstep: every
    row advances ``~max_new_tokens/rounds`` positions per verify pass, so
    per-pass progress can trail ``acceptance·K`` when one slow row drags
    the batch — ``rounds`` is the latency stat, ``acceptance`` the
    draft-quality stat.

    Batched prompts are supported lockstep: each round advances every row
    by the batch-minimum accepted length (still exact for every row: at
    the cut position each row emits its own accepted proposal / residual
    resample, and discarded later proposals were never emitted).
    TPU shape discipline throughout: one jitted program, a
    ``lax.while_loop`` over rounds, static ``[B, K+1]`` verify blocks.
    Sliding-window (``attn_window``) models are not supported — their
    ring caches cannot take the verify block's contiguous span write.
    """
    tm, prompt = _check_decode_args(
        "speculative_generate", target, prompt, max_new_tokens
    )
    dm = draft.module if isinstance(draft, ModelSpec) else draft
    if not isinstance(dm, TransformerLM):
        raise TypeError(
            f"speculative_generate() needs a TransformerLM draft (or its "
            f"ModelSpec), got {type(dm)}"
        )
    if dm.vocab != tm.vocab:
        raise ValueError(
            f"draft vocab {dm.vocab} != target vocab {tm.vocab}"
        )
    if tm.attn_window is not None or dm.attn_window is not None:
        raise ValueError(
            "speculative_generate does not support sliding-window models "
            "(ring caches cannot take the verify block's span write)"
        )
    K = int(spec_tokens)
    if K < 1:
        raise ValueError(f"spec_tokens must be >= 1, got {spec_tokens}")
    need = prompt.shape[1] + int(max_new_tokens) + K - 1
    for name, m in (("target", tm), ("draft", dm)):
        if need > m.maxlen:
            raise ValueError(
                f"prompt {prompt.shape[1]} + max_new_tokens "
                f"{max_new_tokens} + spec_tokens {K} - 1 = {need} exceeds "
                f"the {name}'s maxlen {m.maxlen} (the verify block probes "
                f"spec_tokens positions past the emitted stream)"
            )
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k is not None and not 1 <= int(top_k) <= tm.vocab:
        raise ValueError(
            f"top_k must be in [1, vocab={tm.vocab}], got {top_k}"
        )
    if top_p is not None and not 0.0 < float(top_p) <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if temperature == 0.0:
        run = _speculative_program(tm, dm, int(max_new_tokens), K)
        toks, rounds, accepted, proposed = run(
            target_params, draft_params, prompt
        )
    else:
        run = _speculative_sampled_program(
            tm, dm, int(max_new_tokens), K, float(temperature), top_k,
            None if top_p is None else float(top_p),
        )
        toks, rounds, accepted, proposed = run(
            target_params, draft_params, prompt, jax.random.PRNGKey(seed)
        )
    # ONE device->host transfer for all four outputs: each separate fetch
    # is its own synchronous device round-trip
    toks, rounds, accepted, proposed = jax.device_get(
        (toks, rounds, accepted, proposed)
    )
    rounds, accepted, proposed = int(rounds), int(accepted), int(proposed)
    stats = {
        "rounds": rounds,
        "proposed": proposed,
        "accepted": accepted,
        "acceptance": accepted / proposed if proposed else 0.0,
    }
    return np.asarray(toks), stats


@functools.lru_cache(maxsize=64)
def _beam_program(module: TransformerLM, max_new_tokens: int, beams: int,
                  length_penalty: float, eos_id: int | None):
    """One jitted prefill+scan beam-search program per (module, config)."""

    def run(params, prompt):
        B, lp = prompt.shape
        K, V = beams, module.vocab
        NEG = jnp.float32(-1e30)

        logits, caches = module.apply(
            {"params": params}, prompt, method=TransformerLM.prefill
        )
        logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), -1)
        # eos stays a legal FIRST pick — it just finishes that beam
        # immediately (a prompt is never "already finished")
        scores, tok0 = jax.lax.top_k(logp0, K)          # [B, K]
        # every beam shares the prompt's cache: tile rows to [B*K, …]
        caches = jax.tree.map(
            lambda c: jnp.repeat(c, K, axis=0), caches
        )
        toks = jnp.zeros((B, K, max_new_tokens), jnp.int32)
        toks = toks.at[:, :, 0].set(tok0)
        finished = (
            tok0 == eos_id if eos_id is not None
            else jnp.zeros((B, K), bool)
        )

        def body(carry, i):
            scores, toks, caches, finished = carry
            tok = jax.lax.dynamic_index_in_dim(
                toks, i - 1, axis=2, keepdims=False
            )                                            # [B, K]
            logits, caches = module.apply(
                {"params": params}, tok.reshape(B * K), caches,
                lp + i - 1, method=TransformerLM.decode_step,
            )
            logp = jax.nn.log_softmax(
                logits.astype(jnp.float32), -1
            ).reshape(B, K, V)
            if eos_id is not None:
                # finished beams emit only eos at zero cost — their score
                # is frozen and they stay comparable with live beams
                only_eos = jnp.full((V,), NEG).at[eos_id].set(0.0)
                logp = jnp.where(finished[:, :, None], only_eos, logp)
            cand = scores[:, :, None] + logp             # [B, K, V]
            scores, flat = jax.lax.top_k(cand.reshape(B, K * V), K)
            parent, tok_new = flat // V, flat % V        # [B, K]
            # reorder beam-major state to follow the surviving parents
            gather = (jnp.arange(B)[:, None] * K + parent).reshape(-1)
            caches = jax.tree.map(
                lambda c: jnp.take(c, gather, axis=0), caches
            )
            toks = jnp.take_along_axis(toks, parent[:, :, None], axis=1)
            toks = toks.at[:, :, i].set(tok_new)
            finished = jnp.take_along_axis(finished, parent, axis=1)
            if eos_id is not None:
                finished = finished | (tok_new == eos_id)
            return (scores, toks, caches, finished), None

        if max_new_tokens > 1:
            (scores, toks, caches, finished), _ = jax.lax.scan(
                body, (scores, toks, caches, finished),
                jnp.arange(1, max_new_tokens),
            )
        if length_penalty:
            # GNMT length normalization: rank by score / ((5+len)/6)^alpha,
            # len = tokens up to and including eos (or all, if none)
            if eos_id is not None:
                hit = toks == eos_id
                first = jnp.argmax(hit, axis=2)
                any_hit = jnp.any(hit, axis=2)
                length = jnp.where(any_hit, first + 1, max_new_tokens)
            else:
                length = jnp.full((B, K), max_new_tokens)
            norm = ((5.0 + length.astype(jnp.float32)) / 6.0) \
                ** jnp.float32(length_penalty)
            ranked = scores / norm
        else:
            ranked = scores
        order = jnp.argsort(-ranked, axis=1)
        ranked = jnp.take_along_axis(ranked, order, axis=1)
        toks = jnp.take_along_axis(toks, order[:, :, None], axis=1)
        full = jnp.concatenate(
            [jnp.broadcast_to(prompt[:, None], (B, K, lp)), toks], axis=2
        )
        return full.astype(jnp.int32), ranked

    return jax.jit(run)


def beam_search(model, params, prompt, max_new_tokens: int, *,
                beams: int = 4, length_penalty: float = 0.0,
                eos_id: int | None = None):
    """KV-cached beam-search decoding: ``prompt`` [B, Lp] int32 →
    ``(tokens [B, beams, Lp+new], scores [B, beams])``, best beam first.

    Same TPU shape discipline as :func:`generate` — one jitted program
    (prefill + ``lax.scan``), static shapes throughout, the per-block KV
    caches tiled to ``B·beams`` rows and re-gathered each step to follow
    surviving parents. ``scores`` are accumulated token log-probabilities;
    with ``length_penalty`` α > 0 they are GNMT-normalized
    (``score / ((5+len)/6)^α``). ``eos_id`` finishes a beam: its score
    freezes and it pads with ``eos_id`` while staying in the candidate set.
    ``beams=1`` reduces exactly to greedy :func:`generate`.
    """
    module, prompt = _check_decode_args(
        "beam_search", model, prompt, max_new_tokens
    )
    if not 1 <= int(beams) <= module.vocab:
        raise ValueError(
            f"beams must be in [1, vocab={module.vocab}], got {beams}"
        )
    if eos_id is not None and not 0 <= int(eos_id) < module.vocab:
        raise ValueError(f"eos_id {eos_id} outside vocab {module.vocab}")
    run = _beam_program(
        module, int(max_new_tokens), int(beams), float(length_penalty),
        None if eos_id is None else int(eos_id),
    )
    toks, scores = jax.device_get(run(params, prompt))  # one transfer
    return np.asarray(toks), np.asarray(scores)


def transformer_lm(vocab=1024, maxlen=256, dim=128, heads=4, depth=2,
                   dtype=jnp.bfloat16, attn_impl="reference",
                   attn_window=None, kv_heads=None,
                   pos_embedding="sincos", fused_ce=False,
                   ce_chunk=256, remat=False,
                   tie_embeddings=False, zaya=None, sdar=None,
                   mla=None, next=None) -> ModelSpec:
    """Causal-LM ModelSpec. Train with ``loss="sparse_softmax_cross_entropy"``
    on ``features=tokens [B, L]`` / ``label=tokens shifted left [B, L]``
    (see :func:`next_token_dataset`); decode with :func:`generate`.
    ``attn_window`` enables Mistral-style sliding-window attention (training
    compute O(L·window) on the flash path; decode masks the cache to the
    window band). ``kv_heads`` enables grouped-query attention (``1`` =
    multi-query): query head ``h`` reads shared K/V head ``h // group``, and
    the decode KV cache shrinks ``heads / kv_heads`` ×. ``pos_embedding``:
    "sincos" (additive, the default) or "rope" (rotary q/k rotations —
    relative positions; composes with GQA and sliding windows).
    ``fused_ce=True`` computes the training loss as a chunked fused
    linear+cross-entropy (``ce_chunk`` rows of logits at a time,
    ``ops/fused_ce.py``) so the ``[B, L, vocab]`` logits tensor never
    materializes — the large-vocab memory lever; inference/`generate` are
    unchanged. ``remat=True`` checkpoints each decoder block (the
    long-context activation-memory lever; composes with ``fused_ce``).
    ``tie_embeddings=True`` shares the token embedding with the output
    head (V·dim fewer parameters; the head matmul contracts against the
    embedding table, so int8 ``quantize_lm`` leaves the head in the
    trained dtype).
    ``zaya=ZayaDims(...)`` makes every layer a :class:`ZayaBlock` (ZAYA1:
    compressed convolutional attention and dropless top-1 experts behind a
    router MLP; ``heads`` / ``kv_heads`` heads of ``zaya.head_dim``,
    ``pos_embedding="rope"``). It trains like the dense model, with or without
    ``fused_ce`` and ``remat``; the model's state then holds
    ``counters/blocks_<i>/moe/moe_tokens``, int32 ``[experts]``, to which every
    training step adds the tokens it routed to each expert, and
    ``.../router_bias``, the router's balancing bias, which every training
    step balances on its own tokens (:func:`moe_tokens` reads the counters by
    layer). An option the
    block cannot honour (``attn_window``, another ``pos_embedding``) and the
    serving entry points raise.
    ``sdar=SdarDims(...)`` makes every layer a :class:`SdarBlock` (SDAR:
    RMSNorm, bias-free grouped-query attention with an RMSNorm a head on q
    and k, dropless top-k experts behind a linear router;
    ``pos_embedding="rope"``) trained by DIFFUSION OVER BLOCKS, which is the
    fused loss's business (``fused_ce=True`` is required): ``x`` is the clean
    rows ``[R, L]`` and ``y`` the same tokens; each step noises the rows on
    the device from the state's ``counters/bd_key`` and ``bd_step``, runs a
    noised and a clean copy of every row under one block-diffusion mask
    (``flash_attention(block_diffusion=...)``) and takes the cross-entropy of
    the noised copy's masked positions, unshifted, weighted ``1 / t`` over
    ``R L``. The state also counts ``bd_masked_tokens`` and, a layer,
    ``moe_tokens`` ((token, expert) pairs by expert). A plain forward
    (``spec.apply``) gives a clean row's logits under the block-causal
    mask.
    ``mla=MlaDims(...)`` makes every layer an :class:`MlaBlock` (a
    DeepSeek-V3-style layer: RMSNorm, multi-head latent attention whose
    queries and keys are ``qk_nope_dim + qk_rope_dim`` wide and whose values
    ``v_dim``, through the flash kernels as they are;
    ``pos_embedding="rope"``; ``kv_heads`` is not read). The first
    ``mla.dense_layers`` layers have a dense SwiGLU, the others dropless
    top-k experts behind a sigmoid router with a balancing bias plus a shared
    expert: layers of two kinds in one model. It trains like the dense model
    (next-token loss, with or without ``fused_ce`` and ``remat``); the state
    holds, an EXPERT layer, ``counters/blocks_<i>/moe/moe_tokens`` ((token,
    expert) pairs by expert; :func:`moe_tokens` gives ``[expert layers,
    experts]``) and ``.../router_bias``, which every training step moves by
    ``mla.bias_rate`` against the step's own load. The serving entry points
    raise.
    ``next=NextDims(...)`` makes every layer a :class:`NextBlock` (Qwen3-Next:
    centred RMSNorms; Gated DeltaNet, a linear-attention sublayer whose
    gated delta rule runs chunk by chunk (``ops.gated_delta``), in three
    layers of every four, and gated full attention at ``next.head_dim`` with
    partial rotary through the flash kernels in the fourth; dropless top-k
    experts behind a linear softmax router beside a gated shared expert;
    ``pos_embedding="rope"``). It trains like the dense model (next-token
    loss, with or without ``fused_ce`` and ``remat``); the state holds
    ``counters/blocks_<i>/moe/moe_tokens`` ((token, expert) pairs by
    expert). The serving entry points raise."""
    if next is not None:
        _check_next_options(next, quant=False, attn_window=attn_window,
                            pos_embedding=pos_embedding, others=(zaya, sdar, mla))
    if mla is not None:
        _check_mla_options(mla, quant=False, attn_window=attn_window,
                           pos_embedding=pos_embedding, depth=depth,
                           zaya=zaya, sdar=sdar)
    if zaya is not None:
        # here, by name, and not at the module's first trace
        _check_zaya_options(zaya, quant=False, attn_window=attn_window,
                            pos_embedding=pos_embedding, kv_heads=kv_heads)
    if sdar is not None:
        _check_sdar_options(sdar, quant=False, attn_window=attn_window,
                            pos_embedding=pos_embedding, maxlen=maxlen,
                            zaya=zaya)
        if not fused_ce:
            raise ValueError(
                "the block-diffusion block cannot honour fused_ce=False: its "
                "training objective (the noise, the two copies, the 1/t "
                "weights) is the fused loss's")
    module = TransformerLM(
        vocab=vocab, maxlen=maxlen, dim=dim, heads=heads, depth=depth,
        dtype=dtype, attn_impl=attn_impl, attn_window=attn_window,
        kv_heads=kv_heads, pos_embedding=pos_embedding, remat=remat,
        tie_embeddings=tie_embeddings, zaya=zaya, sdar=sdar, mla=mla, next=next,
    )
    example = jnp.zeros((1, maxlen), jnp.int32)
    spec = from_flax(module, example, name="transformer_lm",
                     mutable_collections=("batch_stats", "counters"))
    if fused_ce:
        from distkeras_tpu.ops.fused_ce import chunked_softmax_cross_entropy

        chunk = int(ce_chunk)

        def fused(params, state, x, y, training, mask=None):
            counting = training and "counters" in state
            h = module.apply(
                {"params": params, **state}, x, training=training,
                method=(TransformerLM.hidden if sdar is None
                        else TransformerLM.noised_hidden),
                mutable=["counters"] if counting else False,
            )
            if counting:
                h, counted = h
                state = {**state, **counted}
            weight = None
            if sdar is not None:
                h, weight = h
            b_, l_, d_ = h.shape
            token_mask = None
            if mask is not None:
                # per-row validity [B] broadcasts to every token of the row
                # (the validator's padded-chunk mask); [B, L] passes through
                mask = jnp.asarray(mask, jnp.float32)
                token_mask = (
                    jnp.repeat(mask, l_) if mask.ndim == 1
                    else mask.reshape(b_ * l_)
                )
            if module.tie_embeddings:
                # the head IS the embedding: contract against its transpose
                # (same math as nn.Embed.attend in _head), no bias
                kernel = params["embed"]["embedding"].T.astype(module.dtype)
                bias = None
            else:
                kernel = params["lm_head"]["kernel"].astype(module.dtype)
                bias = params["lm_head"].get("bias")
            if weight is not None:
                # the objective's own weights and normaliser: 1 / t at the
                # masked positions over ALL R L positions (the mean of the
                # weights is 1), times the rows' validity where one is given
                weight = weight.reshape(b_ * l_)
                token_mask = weight if token_mask is None \
                    else weight * token_mask
            loss = chunked_softmax_cross_entropy(
                h.astype(module.dtype).reshape(b_ * l_, d_),
                jnp.reshape(y, (b_ * l_,)),
                kernel,
                bias,
                mask=token_mask,
                chunk=chunk,
                denominator=None if weight is None else float(b_ * l_),
            )
            return loss, state

        spec = dataclasses.replace(
            spec, fused_losses={"sparse_softmax_cross_entropy": fused}
        )
    return spec


def quantize_lm(model, params) -> tuple[ModelSpec, dict]:
    """Post-training int8 weight-only quantization of a trained LM.

    ``(spec, trained_params) → (int8 spec, int8 params)``: every Dense
    kernel (qkv/attn_out/mlp_up/mlp_down/lm_head in every block) becomes an
    int8 matrix + per-output-channel f32 scale served by :class:`QDense`;
    embeddings and LayerNorms stay in their trained dtypes. The returned
    pair drops into :func:`generate` and ``predictors.GeneratorPredictor``
    unchanged — same architecture, same entry points, ~half the weight
    bytes per decode step (see ``ops/quant.py`` for the TPU rationale).
    """
    from distkeras_tpu.ops.quant import quantize_dense_tree

    module = model.module if isinstance(model, ModelSpec) else model
    if not isinstance(module, TransformerLM):
        raise TypeError(
            f"quantize_lm() needs a TransformerLM (or its ModelSpec), got "
            f"{type(module)}"
        )
    if module.quant:
        raise ValueError("model is already quantized")
    qmodule = module.clone(quant=True)
    example = jnp.zeros((1, module.maxlen), jnp.int32)
    qspec = from_flax(qmodule, example, name="transformer_lm_int8")
    return qspec, quantize_dense_tree(params)


def next_token_dataset(tokens: np.ndarray):
    """``[N, L+1]`` token rows → Dataset with ``features`` ``[N, L]`` and the
    next-token ``label`` ``[N, L]`` (inputs shifted left by one)."""
    from distkeras_tpu.data import Dataset

    tokens = np.asarray(tokens, np.int32)
    return Dataset(
        {"features": tokens[:, :-1], "label": tokens[:, 1:]}
    )
