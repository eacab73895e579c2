"""Sequence/context parallelism: ring attention over a mesh axis.

The reference predates long-context entirely (SURVEY.md §5.7 — its longest
sequences were IMDB-LSTM inputs on one replica), so nothing here is a port:
this is the TPU-native long-context extension. Sequences are sharded along
their length over a mesh axis; each device holds one Q/K/V block and computes
exact attention by rotating K/V blocks around the ring with
``jax.lax.ppermute`` (ICI neighbor exchanges, overlapped by XLA with the
block computation) while maintaining a numerically stable online softmax —
the blockwise/ring-attention construction of Liu et al. 2023. Peak memory per
chip is O(L/N · L/N) for scores instead of O(L²), so context length scales
linearly with the ring size.

No Python control flow inside: the ring is a ``lax.fori_loop`` with a static
trip count, shard_map'ed over the mesh — one compiled SPMD program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.parallel.mesh import put_global

_NEG = -1e9  # finite "masked" score: keeps the online softmax NaN-free


def attention_reference(q, k, v, causal: bool = False, scale=None,
                        key_mask=None, window: int | None = None,
                        block_diffusion: int | None = None):
    """Plain single-device softmax attention — the correctness oracle.

    Shapes: q/k/v ``[B, L, H, D]`` → ``[B, L, H, D]``. ``key_mask`` is an
    optional ``[B, Lk]`` validity mask (1 = attend, 0 = ignore, e.g.
    padding). ``window`` restricts attention to a sliding local band:
    query ``i`` sees keys ``(i-window, i]`` when causal, ``|i-j| < window``
    otherwise; ``block_diffusion=G`` is the block-diffusion training mask
    over a noised and a clean copy of each row (same contract as
    ``ops.flash_attention``: one predicate serves both).
    """
    from distkeras_tpu.ops.flash_attention import (
        _canonical_diffusion, _gqa_groups, band_predicate)

    if window is not None and int(window) < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    rep = _gqa_groups(q, k)  # shared validation with the flash kernels
    if rep > 1:
        # grouped-query attention: expand the shared K/V heads (query head
        # h reads kv head h // group — same convention as the flash
        # kernels' index maps and the LM cache decode)
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    Lq, Lk = s.shape[-2], s.shape[-1]
    # one shared band predicate with the flash kernels — the oracle and the
    # kernel cannot drift apart on window semantics
    band = band_predicate(jnp.arange(Lq)[:, None], jnp.arange(Lk)[None, :],
                          causal, window,
                          _canonical_diffusion(block_diffusion, Lq, causal,
                                               window))
    if band is not None:
        s = jnp.where(band, s, _NEG)
    if key_mask is not None:
        valid = key_mask[:, None, None, :].astype(bool)
        if band is not None:
            valid = valid & band[None, None]
        s = jnp.where(valid, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if key_mask is not None:
        # rows whose whole band is masked yield zeros (same convention as
        # ring_attention and the flash kernel), not the mean of values a
        # softmax over uniform -1e9 would give
        p = p * valid
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def ring_window_steps(axis_size: int, block_len: int, causal: bool,
                      window: int | None) -> tuple[int, int]:
    """Static ring trip counts under a sliding window: ``(fwd, bwd)``.

    ``fwd`` counts the self block plus lower-position blocks reached by
    rotating the ring forward; ``bwd`` the higher-position blocks reached
    by the reverse chain (0 when causal). Unwindowed: ``(axis_size, 0)``
    — the classic full ring. A window only needs the blocks it can touch:
    ``1 + ceil((window-1)/block_len)`` per side, so a ring of 8 shards
    with a one-block window runs 2 hops instead of 8 — communication AND
    compute scale with the band, the distributed twin of the flash
    kernel's restricted grid. ``fwd + bwd <= axis_size`` always (the
    clamp also guarantees no block is ever visited by both chains)."""
    if window is None:
        return axis_size, 0
    side_hops = -(-(window - 1) // block_len)  # ceil; 0 when window == 1
    fwd = min(axis_size, 1 + side_hops)
    if causal:
        return fwd, 0
    return fwd, min(axis_size - fwd, side_hops)


def _ring_attention_shard(q, k, v, key_mask=None, *, axis_name, axis_size,
                          causal, scale, window=None):
    """Per-shard body: my Q block against the contributing K/V blocks via
    ring rotation (all blocks unwindowed; only the band's blocks under a
    sliding window — see :func:`ring_window_steps`).

    ``key_mask`` presence is static: the no-padding path compiles with no
    mask rotation or masking ops at all.
    """
    from distkeras_tpu.ops.flash_attention import band_predicate

    if window is not None and int(window) < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    has_mask = key_mask is not None
    idx = jax.lax.axis_index(axis_name)
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    qf = q.astype(jnp.float32) * scale

    q_pos = idx * Lq + jnp.arange(Lq)  # global positions of my queries
    fwd_perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    bwd_perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]
    n_fwd, n_bwd = ring_window_steps(axis_size, Lk, causal, window)

    def fold(src, k_blk, v_blk, km_blk, m, l, o):
        """Fold block ``src`` into the online softmax state."""
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32))
        k_pos = src * Lk + jnp.arange(Lk)
        valid = band_predicate(q_pos[:, None], k_pos[None, :], causal,
                               window)                       # [Lq, Lk]|None
        if valid is not None:
            valid = jnp.broadcast_to(valid[None, None], s.shape)
        if has_mask:
            km = km_blk.astype(bool)[:, None, None, :]       # [B,1,1,Lk]
            valid = km if valid is None else (valid & km)
        if valid is not None:
            s = jnp.where(valid, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m - m_new)                            # [B, H, Lq]
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
        )
        return m_new, l, o

    def rotate(k_blk, v_blk, km_blk, perm):
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        if has_mask:
            km_blk = jax.lax.ppermute(km_blk, axis_name, perm)
        return k_blk, v_blk, km_blk

    def step_fwd(i, carry):
        k_blk, v_blk, km_blk, m, l, o = carry
        # rotate FIRST: after i+1 forward hops I hold block idx - i - 1
        k_blk, v_blk, km_blk = rotate(k_blk, v_blk, km_blk, fwd_perm)
        src = (idx - i - 1) % axis_size
        m, l, o = fold(src, k_blk, v_blk, km_blk, m, l, o)
        return k_blk, v_blk, km_blk, m, l, o

    def step_bwd(i, carry):
        k_blk, v_blk, km_blk, m, l, o = carry
        # rotate FIRST: after i+1 reverse hops I hold block idx + i + 1
        k_blk, v_blk, km_blk = rotate(k_blk, v_blk, km_blk, bwd_perm)
        src = (idx + i + 1) % axis_size
        m, l, o = fold(src, k_blk, v_blk, km_blk, m, l, o)
        return k_blk, v_blk, km_blk, m, l, o

    m0 = jnp.full((B, H, Lq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    o0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    km0 = key_mask if has_mask else ()
    # self block outside the loops, rotate-then-fold inside: each chain
    # does exactly the hops it folds (a window=1 band does ZERO ppermutes;
    # the classic full ring does axis_size - 1, not axis_size)
    m, l, o = fold(idx, k, v, km0, m0, l0, o0)
    if n_fwd > 1:
        *_, m, l, o = jax.lax.fori_loop(
            0, n_fwd - 1, step_fwd, (k, v, km0, m, l, o)
        )
    if n_bwd:
        # upper-side chain restarts from my OWN block and rotates the
        # other way; the (m, l, o) state carries over
        *_, m, l, o = jax.lax.fori_loop(
            0, n_bwd, step_bwd, (k, v, km0, m, l, o)
        )
    out = o / jnp.maximum(l, 1e-30)[..., None]               # [B, H, Lq, D]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)           # [B, Lq, H, D]


#: public alias — the per-shard ring body, for composing ring attention into
#: a larger computation that is ALREADY inside shard_map over the sequence
#: axis (e.g. models.transformer.sequence_parallel_transformer_forward)
ring_attention_shard = _ring_attention_shard


def ring_attention(q, k, v, mesh: Mesh, axis: str | None = None,
                   causal: bool = False, scale=None, key_mask=None,
                   window: int | None = None):
    """Exact attention with Q/K/V sharded along sequence length over ``axis``.

    ``q/k/v``: ``[B, L, H, D]`` with ``L % mesh_axis_size == 0``; ``key_mask``
    an optional ``[B, L]`` validity mask (padding), sharded and rotated with
    K/V. Returns the attention output with the same sharding. Matches
    :func:`attention_reference` to f32 tolerance (pinned by the unit tests on
    an 8-device mesh); rows whose keys are ALL masked yield zeros in both.
    ``window`` enables sliding-window (local) attention with the same band
    contract as the flash kernel — AND the ring only rotates through the
    blocks the band touches (:func:`ring_window_steps`), so per-chip
    communication and compute scale with the window, not with L.
    """
    axis = axis or mesh.axis_names[0]
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by mesh axis "
            f"'{axis}' of size {n}"
        )
    if window is not None:
        window = int(window)
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if window >= q.shape[1]:
            window = None  # band covers everything: the classic full ring
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    body = functools.partial(
        _ring_attention_shard, axis_name=axis, axis_size=n,
        causal=causal, scale=scale, window=window,
    )
    spec = P(None, axis, None, None)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (put_global(x, sharding) for x in (q, k, v))
    if key_mask is None:
        shard_fn = jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        return jax.jit(shard_fn)(q, k, v)
    mspec = P(None, axis)
    shard_fn = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec, mspec), out_specs=spec,
        check_vma=False,
    )
    key_mask = put_global(key_mask, NamedSharding(mesh, mspec))
    return jax.jit(shard_fn)(q, k, v, key_mask)
