"""Flash attention — a Pallas TPU kernel for the transformer hot path.

The reference has no attention at all (its newest model was an LSTM —
SURVEY.md §5.7), so this is TPU-native surplus: the memory-bound softmax
attention of the transformer/MoE families as a streaming online-softmax
kernel (Dao et al. 2022 construction, TPU grid edition).

Forward: grid ``(batch·head, q-blocks, k-blocks)`` with the k axis innermost.
Each step multiplies one ``[block_q, D]`` query tile against one
``[block_k, D]`` key/value tile on the MXU (f32 accumulation over bf16
inputs) and folds the result into VMEM scratch accumulators ``(m, l, acc)``
via the numerically stable online softmax; the last k step normalizes and
writes the output tile. Peak on-chip memory is ``O(block_q · block_k)`` —
independent of sequence length — where XLA's fused attention materializes
the full ``O(L²)`` score tensor per head in HBM (it OOMs at L=16k on a v5e
where this kernel keeps running). The kernel also emits per-row log-sum-exp,
which makes the backward pass a textbook recompute: ``p = exp(qk − lse)``,
no saved probabilities.

Backward: two Pallas kernels with the same tile-streaming structure, so
training memory is also ``O(block_q · block_k)`` per core instead of the
``O(L²)`` score/probability tensors a plain-XLA backward materializes.
``delta = rowsum(dO · O)`` is precomputed in XLA (one elementwise pass),
then a dq kernel (grid ``(batch·head, q-blocks, k-blocks)``, k innermost,
``dq += ds @ k``) and a dk/dv kernel (grid ``(batch·head, k-blocks,
q-blocks)``, q innermost, ``dk += dsᵀ @ q``, ``dv += pᵀ @ dO``) each
rebuild their probability tile from the saved lse and fold into VMEM
accumulators. Causal tiles that cannot contribute are skipped on both
sides of the diagonal (dq skips above, dk/dv below). ``_attention_bwd_math``
keeps the plain-XLA gradient identities as the small-shape oracle.

On TPU the kernel compiles natively; elsewhere (the 8-device CPU mesh in CI)
it runs in Pallas interpret mode, so the SAME code path is oracle-tested
everywhere (tests/test_flash_attention.py pins it against
``parallel.sequence.attention_reference``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu import ops

_NEG = -1e9  # matches parallel.sequence: finite mask keeps softmax NaN-free

BLOCK_Q = 128   # q rows per grid step
BLOCK_K = 512   # k/v rows per inner grid step


def _first_k_tile(iq, *, block_q, block_k, window):
    """Index of the first k tile inside the attention band of q block
    ``iq`` (0 when unwindowed). Floor division handles the negative
    numerator near the sequence start."""
    if window is None:
        return 0
    return jnp.maximum(0, (iq * block_q - window + 1) // block_k)


def _last_k_tile(iq, nk, *, block_q, block_k, causal, window):
    """Index of the last contributing k tile for q block ``iq``: the causal
    diagonal and/or the upper edge of the window band, else the last tile."""
    last = nk - 1
    if causal:
        last = jnp.minimum(last, (iq * block_q + block_q - 1) // block_k)
    elif window is not None:
        last = jnp.minimum(
            last, (iq * block_q + block_q - 1 + window - 1) // block_k
        )
    return last


def band_predicate(q_pos, k_pos, causal, window):
    """THE causal/sliding-window validity predicate, shared by the kernels
    (both orientations), the XLA backward oracle, and
    ``attention_reference``: query ``i`` sees key ``j`` iff ``j <= i`` when
    causal, ``i - j < window`` (and ``j - i < window`` when bidirectional)
    under a window. ``q_pos``/``k_pos`` broadcast; returns None when
    everything is valid."""
    if not causal and window is None:
        return None
    valid = None
    if causal:
        valid = q_pos >= k_pos
    if window is not None:
        band = q_pos - k_pos < window          # lower edge of the band
        if not causal:
            band &= k_pos - q_pos < window     # symmetric upper edge
        valid = band if valid is None else (valid & band)
    return valid


def _band_valid(iq, kt, *, block_q, block_k, causal, window):
    """[bq, bk] tile of :func:`band_predicate` for q tile ``iq`` × k tile
    ``kt`` (None when everything is valid)."""
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = kt * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return band_predicate(q_pos, k_pos, causal, window)


def _num_band_tiles(n_tiles, span, block):
    """Static size of the restricted grid axis: max tiles of width ``block``
    an arbitrarily aligned index range of length ``span`` can touch."""
    return min(n_tiles, (span - 2) // block + 2)


def _restricted_k_axis(nk, bq, bk, causal, window):
    """(nkt, k_tile(iq, j)) for the forward/dq grids: the static size of the
    k axis and the index map from (q tile, band step) → real k tile. With no
    window the axis is the full nk and the map is the identity on j; with a
    window only the tiles the band can touch are visited (and DMA'd), so
    compute and bandwidth are O(L·window) — clamped duplicate tiles at the
    sequence end are guarded off in-kernel by ``kt <= last_k``."""
    if window is None:
        return nk, (lambda i, j: j)
    span = bq + window - 1 if causal else bq + 2 * window - 2

    def k_tile(i, j):
        fk = _first_k_tile(i, block_q=bq, block_k=bk, window=window)
        return jnp.minimum(fk + j, nk - 1)

    return _num_band_tiles(nk, span, bk), k_tile


def _restricted_q_axis(nq, bq, bk, causal, window):
    """(nqt, q_tile(jk, i)) for the dkv grid — the transposed mirror of
    :func:`_restricted_k_axis`."""
    if window is None:
        return nq, (lambda j, i: i)
    span = bk + window - 1 if causal else bk + 2 * window - 2

    def q_tile(j, i):
        fq = _first_q_tile(j, block_q=bq, block_k=bk, causal=causal,
                           window=window)
        return jnp.minimum(fq + i, nq - 1)

    return _num_band_tiles(nq, span, bq), q_tile


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc, *,
               scale, causal, block_q, block_k, window=None, nk=None,
               km_ref=None):
    """One (bh, iq, jk) step: fold a [bq, bk] score tile into the online
    softmax state; finalize on this q block's last contributing k step.

    With ``window`` set the grid's k axis is restricted to the band (the
    BlockSpec index map only loads in-band tiles), so ``jk`` counts tiles
    from the band start: the real k tile is ``first_k + jk``."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    if nk is None:
        nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc[:] = jnp.zeros_like(acc)

    # under causal/window masking, k tiles outside the band contribute
    # nothing — the restricted grid never visits tiles below the band, and
    # the guards below skip tiles past its end (≈2× at long causal context)
    kt = _first_k_tile(iq, block_q=block_q, block_k=block_k,
                       window=window) + jk
    last_k = _last_k_tile(iq, nk, block_q=block_q, block_k=block_k,
                          causal=causal, window=window)

    @pl.when(kt <= last_k)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale        # [bq, D]
        k = k_ref[0].astype(jnp.float32)                # [bk, D]
        v = v_ref[0].astype(jnp.float32)                # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [bq, bk]
        valid = _band_valid(iq, kt, block_q=block_q, block_k=block_k,
                            causal=causal, window=window)
        if km_ref is not None:
            km = km_ref[0].astype(jnp.float32) > 0.5     # [1, bk]
            km = jnp.broadcast_to(km, s.shape)
            valid = km if valid is None else (valid & km)
        if valid is not None:
            s = jnp.where(valid, s, _NEG)

        m_prev = m_s[:]                                  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                   # [bq, 1]
        l_s[:] = l_s[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_s[:] = m_new

    @pl.when(kt == last_k)
    def _():
        l = jnp.maximum(l_s[:], 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_s[:] + jnp.log(l)


def _pick_block_q(L):
    """q tile height: taller q tiles amortize per-grid-step pipeline
    overhead and cut the number of (m, l, acc) rescale passes. Round 5
    re-measured the ladder on a v5e DOWN to L = 1024 (fwd+bwd, causal):
    512-row tiles win 1.5× at L = 2048 for BOTH D=64 (thin heads — the
    VERDICT r4 #4 gap: the per-step overhead, not the 64-wide MXU
    contraction, was the recoverable part) and D=128, matching the
    2.0–2.1× already measured at L ≥ 8192 (SCALING.md flash table).
    Gated at L >= 1024 — exactly the measured range: L = 512 would get a
    single 512-row tile (a config no measurement covered), so it keeps
    the default ladder, as do lengths that aren't 512-multiples
    (tile rule)."""
    return 512 if L >= 1024 and L % 512 == 0 else BLOCK_Q


def _pick_block_k(L):
    """k tile width: largest tile-aligned block that divides L (128 always
    does); 1024 whenever L allows it (same round-5 measurement as
    _pick_block_q — fewer, wider k steps beat the old 512 ladder at every
    L ≥ 1024 tried). Every (bq, bk) combination keeps bk % bq == 0 or
    bq % bk == 0, which the backward's causal tile-skipping index math
    relies on."""
    if L % 1024 == 0:
        return 1024
    return next(c for c in (BLOCK_K, 384, 256, 128) if L % c == 0)


def _gqa_groups(q, k):
    """Validated GQA group size: q heads per shared k/v head (1 = MHA)."""
    H, Hkv = q.shape[2], k.shape[2]
    if H % Hkv:
        raise ValueError(
            f"q heads {H} must be a multiple of kv heads {Hkv}"
        )
    return H // Hkv


def _kv_row(b, H, Hkv):
    """Grid row (over B·H) → k/v array row (over B·Hkv): query head h
    reads shared head h // group — the same [Hkv, group] factoring as the
    LM's cache decode and jnp.repeat expansion."""
    if H == Hkv:
        return b
    return (b // H) * Hkv + (b % H) // (H // Hkv)


def _fa_forward(q, k, v, key_mask, *, scale, causal, interpret,
                window=None):
    """q [B, L, H, D], k/v [B, L, Hkv, D] with Hkv | H (grouped-query
    attention reads shared K/V heads straight from the index maps — no
    repeated-KV materialization), + key_mask [B, L] →
    (out [B, L, H, D], lse)."""
    B, L, H, D = q.shape
    Hkv = k.shape[2]
    _gqa_groups(q, k)
    if L % BLOCK_Q:
        raise ValueError(
            f"sequence length {L} must be a multiple of {BLOCK_Q}"
        )
    bq = _pick_block_q(L)
    bk = _pick_block_k(L)

    def bh(x):  # [B, L, h, D] → [B·h, L, D]
        h = x.shape[2]
        return jnp.moveaxis(x, 2, 1).reshape(B * h, L, D)

    nk = L // bk
    nkt, k_tile = _restricted_k_axis(nk, bq, bk, causal, window)
    grid = (B * H, L // bq, nkt)
    qspec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    kvspec = pl.BlockSpec(
        (1, bk, D), lambda b, i, j: (_kv_row(b, H, Hkv), k_tile(i, j), 0)
    )
    ospec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    # lse carries a trailing singleton so its block obeys the (8, 128)
    # tile rule (last dim equal to the array dim is allowed)
    lspec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    out_shape = [
        jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
        jax.ShapeDtypeStruct((B * H, L, 1), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((bq, 1), jnp.float32),   # running max m
        pltpu.VMEM((bq, 1), jnp.float32),   # running denom l
        pltpu.VMEM((bq, D), jnp.float32),   # running numerator acc
    ]
    in_specs = [qspec, kvspec, kvspec]
    args = [bh(q), bh(k), bh(v)]
    if key_mask is None:
        kernel = functools.partial(
            _fa_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
            window=window, nk=nk,
        )
    else:
        H_ = H
        # mask ships as [B, 1, L] so its block obeys the (8, 128) tile rule
        in_specs.append(
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b // H_, 0,
                                                      k_tile(i, j)))
        )
        args.append(key_mask.astype(jnp.float32)[:, None, :])

        def kernel(q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref,
                   m_s, l_s, acc):
            _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc,
                       scale=scale, causal=causal, block_q=bq, block_k=bk,
                       window=window, nk=nk, km_ref=km_ref)

    o, lse = pl.pallas_call(
        kernel, grid=grid,
        in_specs=in_specs,
        out_specs=[ospec, lspec],
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    out = jnp.moveaxis(o.reshape(B, H, L, D), 1, 2)
    return out, lse[..., 0]


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref, *rest,
                      scale, causal, block_q, block_k, window=None, nk=None):
    """One (bh, iq, jk) step: rebuild the [bq, bk] probability tile from the
    saved lse and fold ``ds @ k`` into the dq accumulator; write on this q
    block's last contributing k step."""
    if len(rest) == 3:
        km_ref, dq_ref, acc = rest
    else:
        km_ref, (dq_ref, acc) = None, rest
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    if nk is None:
        nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    kt = _first_k_tile(iq, block_q=block_q, block_k=block_k,
                       window=window) + jk
    last_k = _last_k_tile(iq, nk, block_q=block_q, block_k=block_k,
                          causal=causal, window=window)

    @pl.when(kt <= last_k)
    def _():
        qs = q_ref[0].astype(jnp.float32) * scale       # [bq, D]
        kk = k_ref[0].astype(jnp.float32)               # [bk, D]
        vv = v_ref[0].astype(jnp.float32)               # [bk, D]
        gg = g_ref[0].astype(jnp.float32)               # [bq, D]
        s = jax.lax.dot_general(
            qs, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [bq, bk]
        valid = _band_valid(iq, kt, block_q=block_q, block_k=block_k,
                            causal=causal, window=window)
        if km_ref is not None:
            km = km_ref[0].astype(jnp.float32) > 0.5     # [1, bk]
            km = jnp.broadcast_to(km, s.shape)
            valid = km if valid is None else (valid & km)
        if valid is not None:
            s = jnp.where(valid, s, _NEG)
        p = jnp.exp(s - lse_ref[0])                      # lse [bq, 1]
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        dp = jax.lax.dot_general(
            gg, vv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [bq, bk]
        ds = p * (dp - d_ref[0])                         # delta [bq, 1]
        acc[:] += jax.lax.dot_general(
            ds, kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(kt == last_k)
    def _():
        dq_ref[0] = acc[:].astype(dq_ref.dtype)


def _first_q_tile(jk, *, block_q, block_k, causal, window):
    """First q tile that can see k tile ``jk``: the causal diagonal and/or
    the lower edge of the window band (0 when unrestricted)."""
    if causal:
        return (jk * block_k) // block_q
    if window is not None:
        return jnp.maximum(0, (jk * block_k - window + 1) // block_q)
    return 0


def _last_q_tile(jk, nq, *, block_q, block_k, window):
    """Last q tile inside k tile ``jk``'s band (``nq - 1`` unwindowed)."""
    if window is None:
        return nq - 1
    return jnp.minimum(
        nq - 1, (jk * block_k + block_k - 1 + window - 1) // block_q
    )


def _band_valid_t(jk, qt, *, block_q, block_k, causal, window):
    """Transposed [bk, bq] tile of :func:`band_predicate` for k tile ``jk``
    × q tile ``qt``."""
    k_pos = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0
    )
    q_pos = qt * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1
    )
    return band_predicate(q_pos, k_pos, causal, window)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref, *rest,
                       scale, causal, block_q, block_k, window=None,
                       nq=None, gqa_groups=None):
    """One (bh, jk, iq) step — or (b·hkv, jk, gg, iq) under grouped-query
    attention, where the extra ``gg`` axis walks the q heads sharing this
    k/v head and the dk/dv accumulators run across the whole group:
    rebuild the transposed [bk, bq] probability tile and fold ``pᵀ @ dO``
    / ``dsᵀ @ q`` into the dv/dk accumulators; write on the group's last
    contributing q step."""
    if len(rest) == 5:
        km_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        km_ref = None
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    jk = pl.program_id(1)
    if gqa_groups is None:
        last_g = None
        iq = pl.program_id(2)
        if nq is None:
            nq = pl.num_programs(2)
        first_step = iq == 0
    else:
        grp = pl.program_id(2)  # in-group q head (gg names the dO tile)
        iq = pl.program_id(3)
        assert nq is not None
        first_step = (grp == 0) & (iq == 0)
        last_g = grp == gqa_groups - 1

    @pl.when(first_step)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    first_q = _first_q_tile(jk, block_q=block_q, block_k=block_k,
                            causal=causal, window=window)
    if window is None:
        # full grid: iq is the real q tile, skip those before the band
        qt = iq
        last_q = nq - 1
    else:
        # restricted grid: iq counts tiles from the band start
        qt = first_q + iq
        last_q = _last_q_tile(jk, nq, block_q=block_q, block_k=block_k,
                              window=window)

    @pl.when((qt >= first_q) & (qt <= last_q))
    def _():
        qs = q_ref[0].astype(jnp.float32) * scale       # [bq, D]
        kk = k_ref[0].astype(jnp.float32)               # [bk, D]
        vv = v_ref[0].astype(jnp.float32)               # [bk, D]
        gg = g_ref[0].astype(jnp.float32)               # [bq, D]
        st = jax.lax.dot_general(
            kk, qs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [bk, bq]
        valid = _band_valid_t(jk, qt, block_q=block_q, block_k=block_k,
                              causal=causal, window=window)
        if km_ref is not None:
            km = km_ref[0].astype(jnp.float32) > 0.5     # [bk, 1]
            km = jnp.broadcast_to(km, st.shape)
            valid = km if valid is None else (valid & km)
        if valid is not None:
            st = jnp.where(valid, st, _NEG)
        pt = jnp.exp(st - lse_ref[0])                    # lse [1, bq]
        if valid is not None:
            pt = jnp.where(valid, pt, 0.0)
        dv_acc[:] += jax.lax.dot_general(
            pt, gg, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            vv, gg, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [bk, bq]
        dst = pt * (dpt - d_ref[0])                      # delta [1, bq]
        dk_acc[:] += jax.lax.dot_general(
            dst, qs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    write = qt == last_q if last_g is None else ((qt == last_q) & last_g)

    @pl.when(write)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _fa_backward(q, k, v, key_mask, out, lse, g, *, scale, causal,
                 interpret, window=None):
    """Blockwise flash-attention backward: (dq, dk, dv) via two Pallas
    kernels, ``O(block_q · block_k)`` on-chip — no [B, H, L, L] tensors.
    Under grouped-query attention (k/v hold Hkv < H heads) dq reads the
    shared heads through the index maps and the dkv grid gains a group
    axis whose accumulators sum the whole group — dk/dv come out
    Hkv-wide, no repeated-KV tensors anywhere."""
    B, L, H, D = q.shape
    Hkv = k.shape[2]
    groups = _gqa_groups(q, k)
    bq = _pick_block_q(L)
    bk = _pick_block_k(L)  # same ladders as the forward — keep in lockstep

    def bh(x):  # [B, L, h, D] → [B·h, L, D]
        h = x.shape[2]
        return jnp.moveaxis(x, 2, 1).reshape(B * h, L, D)

    qb, kb, vb, gb = bh(q), bh(k), bh(v), bh(g)
    # delta = rowsum(dO · O): one elementwise pass, [B·H, L]
    delta = jnp.sum(gb.astype(jnp.float32) * bh(out).astype(jnp.float32),
                    axis=-1)
    lse_col, d_col = lse[..., None], delta[..., None]      # [B·H, L, 1]
    lse_row, d_row = lse[:, None, :], delta[:, None, :]    # [B·H, 1, L]
    H_ = H
    nk, nq = L // bk, L // bq
    # same restricted band axes as the forward (one shared builder, so the
    # forward and backward grids cannot drift apart)
    nkt, k_tile = _restricted_k_axis(nk, bq, bk, causal, window)
    nqt, q_tile = _restricted_q_axis(nq, bq, bk, causal, window)

    qspec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    kvspec_q = pl.BlockSpec(
        (1, bk, D), lambda b, i, j: (_kv_row(b, H, Hkv), k_tile(i, j), 0)
    )
    colspec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))

    dq_specs = [qspec, kvspec_q, kvspec_q, qspec, colspec, colspec]
    dq_args = [qb, kb, vb, gb, lse_col, d_col]
    if key_mask is not None:
        dq_specs.append(
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b // H_, 0,
                                                      k_tile(i, j)))
        )
        dq_args.append(key_mask.astype(jnp.float32)[:, None, :])
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, window=window, nk=nk),
        grid=(B * H, nq, nkt),
        in_specs=dq_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(*dq_args)

    # dk/dv: k blocks on the parallel axis, q innermost; under GQA the
    # grid is (B·Hkv, nk, group, nqt) with the group axis outside the q
    # walk so the accumulators span every q head sharing the k/v head
    def q_row_of(b, gg):
        # b over B·Hkv, gg the in-group q head → row over B·H
        return (b // Hkv) * H + (b % Hkv) * groups + gg

    if groups == 1:
        grid = (B * H, nk, nqt)
        kvspec = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
        qspec2 = pl.BlockSpec(
            (1, bq, D), lambda b, j, i: (b, q_tile(j, i), 0)
        )
        rowspec = pl.BlockSpec(
            (1, 1, bq), lambda b, j, i: (b, 0, q_tile(j, i))
        )
        kmspec = pl.BlockSpec((1, bk, 1), lambda b, j, i: (b // H_, j, 0))
    else:
        grid = (B * Hkv, nk, groups, nqt)
        kvspec = pl.BlockSpec((1, bk, D), lambda b, j, gg, i: (b, j, 0))
        qspec2 = pl.BlockSpec(
            (1, bq, D),
            lambda b, j, gg, i: (q_row_of(b, gg), q_tile(j, i), 0),
        )
        rowspec = pl.BlockSpec(
            (1, 1, bq),
            lambda b, j, gg, i: (q_row_of(b, gg), 0, q_tile(j, i)),
        )
        kmspec = pl.BlockSpec(
            (1, bk, 1), lambda b, j, gg, i: (b // Hkv, j, 0)
        )
    dkv_specs = [qspec2, kvspec, kvspec, qspec2, rowspec, rowspec]
    dkv_args = [qb, kb, vb, gb, lse_row, d_row]
    if key_mask is not None:
        dkv_specs.append(kmspec)
        dkv_args.append(key_mask.astype(jnp.float32)[..., None])
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, window=window, nq=nq,
                          gqa_groups=None if groups == 1 else groups),
        grid=grid,
        in_specs=dkv_specs,
        out_specs=[kvspec, kvspec],
        out_shape=[jax.ShapeDtypeStruct((B * Hkv, L, D), k.dtype),
                   jax.ShapeDtypeStruct((B * Hkv, L, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
        name="flash_dkv",
    )(*dkv_args)

    def unbh(x):  # [B·h, L, D] → [B, L, h, D]
        h = x.shape[0] // B
        return jnp.moveaxis(x.reshape(B, h, L, D), 1, 2)

    return unbh(dq), unbh(dk), unbh(dv)


def _attention_bwd_math(q, k, v, key_mask, lse, g, *, scale, causal,
                        window=None):
    """Recompute-based backward (plain XLA): p from saved lse, then the
    standard flash-attention gradient identities. GQA: k/v may hold
    Hkv < H heads — expanded here, with dk/dv group-summed back."""
    B, L, H, D = q.shape
    Hkv = k.shape[2]
    groups = _gqa_groups(q, k)
    if groups > 1:
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    qf = q.astype(jnp.float32) * scale
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    band = band_predicate(jnp.arange(L)[:, None], jnp.arange(L)[None, :],
                          causal, window)
    valid = (None if band is None
             else jnp.broadcast_to(band[None, None], s.shape))
    if key_mask is not None:
        km = key_mask.astype(bool)[:, None, None, :]
        valid = km if valid is None else (valid & km)
    if valid is not None:
        s = jnp.where(valid, s, _NEG)
    lse_b = lse.reshape(B, H, L)                       # [B, H, L]
    p = jnp.exp(s - lse_b[..., None])
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    gf = g.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, gf)
    dp = jnp.einsum("bqhd,bkhd->bhqk", gf, vf)
    # d(softmax): ds = p * (dp - rowsum(dp * p))
    row = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = p * (dp - row)
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k.astype(jnp.float32)) * scale
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
    if groups > 1:
        # sum the group's q-head contributions back onto the shared head
        dk = dk.reshape(B, L, Hkv, groups, D).sum(axis=3)
        dv = dv.reshape(B, L, Hkv, groups, D).sum(axis=3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# A Mosaic kernel cannot be partitioned by the compiler (see
# ``ops.on_each_device``), and attention is independent across the batch: the
# forward and the backward each run through it, every array batch-major in
# dim 0 — q/k/v/out/g ``[B, …]``, the mask ``[B, L]``, lse ``[B·H, L]``.


def _forward(q, k, v, key_mask, scale, causal, interpret, window):
    return ops.on_each_device(
        functools.partial(_fa_forward, scale=scale, causal=causal,
                          interpret=interpret, window=window),
        q, k, v, key_mask,
    )


def _backward(q, k, v, key_mask, out, lse, g, scale, causal, interpret,
              window):
    return ops.on_each_device(
        functools.partial(_fa_backward, scale=scale, causal=causal,
                          interpret=interpret, window=window),
        q, k, v, key_mask, out, lse, g,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_core(q, k, v, key_mask, causal, scale, interpret, window):
    out, _ = _forward(q, k, v, key_mask, scale, causal, interpret, window)
    return out


def _fa_fwd(q, k, v, key_mask, causal, scale, interpret, window):
    out, lse = _forward(q, k, v, key_mask, scale, causal, interpret, window)
    # saving `out` adds no memory under jit: it aliases the primal output
    return out, (q, k, v, key_mask, out, lse)


def _fa_bwd(causal, scale, interpret, window, res, g):
    q, k, v, key_mask, out, lse = res
    dq, dk, dv = _backward(q, k, v, key_mask, out, lse, g, scale, causal,
                           interpret, window)
    dmask = None if key_mask is None else jnp.zeros_like(key_mask)
    return dq, dk, dv, dmask


_flash_core.defvjp(_fa_fwd, _fa_bwd)


def _canonical_window(window, L):
    """Validate ``window``; a band covering the whole sequence is None."""
    if window is None:
        return None
    window = int(window)
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    return None if window >= L else window


def flash_attention(q, k, v, causal: bool = False, scale=None, key_mask=None,
                    interpret: bool | None = None, window: int | None = None):
    """Pallas flash attention; same contract as ``attention_reference``.

    ``q/k/v`` [B, L, H, D] → [B, L, H, D]; optional ``key_mask`` [B, L]
    (1 = attend). Gradients flow to q/k/v (the mask gets zero cotangent, as
    with the hard mask in the reference). ``window`` enables sliding-window
    (local) attention: query ``i`` sees keys ``(i-window, i]`` when causal,
    ``|i-j| < window`` otherwise; the kernel grid only visits in-band tiles,
    so compute AND k/v DMA scale as O(L·window).
    """
    return _flash_core(
        q, k, v, key_mask, bool(causal),
        float(scale if scale is not None else q.shape[-1] ** -0.5),
        ops.interpreted(interpret),
        _canonical_window(window, q.shape[1]),
    )


def attention_impl(impl: str = "auto", *, L: int) -> str:
    """``"flash"`` or ``"reference"``: what :func:`attention` runs for a
    length-``L`` call (``ops.kernel_impl("attention", …)`` is the public
    door). A named implementation is returned as asked; ``"auto"`` is the
    kernel only when it compiles natively AND ``L`` is a tile multiple —
    interpret mode off-TPU is for testing, not speed."""
    if impl not in ("flash", "reference", "auto"):
        raise ValueError(
            f"unknown attention impl {impl!r}; use 'flash', 'reference', "
            f"or 'auto'"
        )
    if impl != "auto":
        return impl
    return "reference" if L % BLOCK_Q or not ops.native_kernels() else "flash"


def attention(q, k, v, causal: bool = False, scale=None, key_mask=None,
              impl: str = "auto", window: int | None = None):
    """Dispatch between the Pallas kernel and the XLA reference.

    ``impl``: ``"flash"`` forces the kernel (requires ``L % 128 == 0``),
    ``"reference"`` the XLA path, ``"auto"`` is decided by
    :func:`attention_impl`. ``key_mask`` is treated as a static-presence
    argument (its values are traced, its presence is not). ``window``:
    sliding-window (local) attention span — see :func:`flash_attention`.
    """
    from distkeras_tpu.parallel.sequence import attention_reference

    if attention_impl(impl, L=q.shape[1]) == "reference":
        return attention_reference(q, k, v, causal=causal, scale=scale,
                                   key_mask=key_mask, window=window)
    return flash_attention(q, k, v, causal, scale, key_mask, window=window)
