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
accumulators. ``_attention_bwd_math`` keeps the plain-XLA gradient identities
as the small-shape oracle.

The band (causal diagonal and/or sliding window) is paid for at its own
grain, not the grid's: a grid step holds a tall tile (a step costs about a
microsecond whatever it computes) and decides from its tile's offset alone
what to run — nothing for a tile outside the band (its index map names the
block already held, so it copies nothing either), one body with no mask for
a tile wholly inside, and for a tile the band's edge crosses the pieces
``_band_plan`` laid out while tracing. ``band_census`` counts, from the same
index arithmetic, what that comes to at a length.

One mask is no band: block-diffusion training (``block_diffusion=G``) runs a
noised copy of each row and then its clean copy as one stream, under a block
diagonal (noised over noised), a strict block-causal quarter (noised over
clean), an empty quarter and a block-causal one (``band_predicate``). A tile
lies in one quarter (tiles are cut from the row's length), so the same three
fates hold, decided from the quarter and the tile's offset within the row
(``_chunk_diffusion``); a q tile's steps are its own noised tile, then the
clean tiles up to its diagonal.

On TPU the kernel compiles natively; elsewhere (the 8-device CPU mesh in CI)
it runs in Pallas interpret mode, so the SAME code path is oracle-tested
everywhere (tests/test_flash_attention.py pins it against
``parallel.sequence.attention_reference``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
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


def _copies_see(qb, kb, q_clean: bool, k_clean: bool):
    """Block diffusion's table, a quarter at a time: may a query of block
    ``qb`` see a key of block ``kb``, for a noised or a clean query and a
    noised or a clean key? Noised sees noised in its own block; noised sees
    clean in earlier blocks; clean sees clean in its own and earlier blocks;
    clean never sees noised."""
    if k_clean:
        return kb <= qb if q_clean else kb < qb
    return False if q_clean else kb == qb


def band_predicate(q_pos, k_pos, causal, window, diffusion=None, copies=None):
    """THE validity predicate, shared by the kernels (both orientations), the
    XLA backward oracle, and ``attention_reference``: query ``i`` sees key
    ``j`` iff ``j <= i`` when causal, ``i - j < window`` (and ``j - i <
    window`` when bidirectional) under a window. ``q_pos``/``k_pos``
    broadcast; returns None when everything is valid.

    ``diffusion=(block, length)`` is the block-diffusion training mask and no
    band: a stream of ``2 * length`` positions holds a NOISED copy of a row
    (``< length``) and then its CLEAN copy; with ``b(i) = (i % length) //
    block``, a noised query sees the noised keys of its own block (both
    directions) and the clean keys of earlier blocks, a clean query the clean
    keys of its own and earlier blocks and no noised key
    (:func:`_copies_see`). ``block`` is a power of two (a shift, not a
    division: the kernels run this on vectors). ``copies=(q_clean, k_clean)``,
    Python bools: the caller knows which copy its queries and its keys lie in
    (a kernel's piece lies in one quarter of the mask) and passes positions
    WITHIN THE ROW; the mask is then one comparison of blocks, where telling
    the copies apart element by element costs a dozen vector operations a
    pair (v5e, PR 31: the forward 164.8 ms a call so, 13.8 with one)."""
    if diffusion is not None:
        block, length = diffusion
        shift = block.bit_length() - 1
        if copies is not None:
            return _copies_see(q_pos >> shift, k_pos >> shift, *copies)
        q_noised, k_noised = q_pos < length, k_pos < length
        q_clean, k_clean = q_pos >= length, k_pos >= length
        qb = (q_pos - length * q_clean) >> shift
        kb = (k_pos - length * k_clean) >> shift
        return (q_noised & k_noised & _copies_see(qb, kb, False, False)) | (
            k_clean & ((q_noised & _copies_see(qb, kb, False, True))
                       | (q_clean & _copies_see(qb, kb, True, True))))
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


def _band_valid(q0, k0, rows, cols, causal, window, diffusion=None,
                copies=None):
    """[rows, cols] tile of :func:`band_predicate` for the queries from ``q0``
    and the keys from ``k0`` (None when everything is valid). Under
    ``diffusion`` the piece lies in the quarter ``copies = (q_clean,
    k_clean)`` of the mask (static: a plan's piece knows it); the blocks are
    worked out on a column of queries and a row of keys, in-row positions,
    and only the one comparison is ``[rows, cols]``."""
    if diffusion is not None:
        length = diffusion[1]
        q_pos = q0 - length * copies[0] \
            + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        k_pos = k0 - length * copies[1] \
            + jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        return band_predicate(q_pos, k_pos, False, None, diffusion, copies)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return band_predicate(q_pos, k_pos, causal, window)


def _band_valid_t(q0, k0, rows, cols, causal, window, diffusion=None,
                  copies=None):
    """Transposed [cols, rows] tile (keys down, queries across) of
    :func:`band_predicate`: what the dk/dv kernel masks with."""
    if diffusion is not None:
        length = diffusion[1]
        k_pos = k0 - length * copies[1] \
            + jax.lax.broadcasted_iota(jnp.int32, (cols, 1), 0)
        q_pos = q0 - length * copies[0] \
            + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        return band_predicate(q_pos, k_pos, False, None, diffusion, copies)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (cols, rows), 0)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (cols, rows), 1)
    return band_predicate(q_pos, k_pos, causal, window)


def _piece_valid(band, keys, shape):
    """What a body masks with: its band tile (None off the band's edge) and
    its slice of the key mask (None without one), or None for no mask."""
    if keys is None:
        return band
    keys = jnp.broadcast_to(keys.astype(jnp.float32) > 0.5, shape)
    return keys if band is None else (band & keys)


# The grain of the band. A grid step holds a tall [block_q, block_k] tile
# (the per-step overhead the ladders below were chosen for), but the band's
# edge is a diagonal, so a step decides from its scalar tile indices what it
# runs. Whether a tile lies inside the band depends only on how far its first
# query is past its first key, ``d = q0 - k0``, and a grid has few such
# offsets, so everything but the test of ``d`` is decided while tracing:
#
# - a tile wholly inside the band takes ONE body with no mask at all;
# - a tile outside it is left out (and fetches nothing: the index maps are
#   clamped to the last tile that contributes);
# - for each offset at which the band's edge crosses the tile, the tile is
#   halved (its longer side, both when square) down to ``_FINE`` rows and keys
#   and each piece decided again: outside, inside (no mask), or on the edge
#   (today's masked body). The pieces of one offset run back to back under
#   one test of ``d``.
#
# What cutting is worth differs by kernel (v5e, PR 28, chip_smoke's kernels
# leg): dq and dk/dv pay by the (query, key) pair, so they run the pieces;
# the forward pays by the row of every body it folds into the online softmax
# (its two row reductions and the [rows, 1] statistics: 83 % of a 512 x 1024
# body, the same at head 64 and 128), so it runs one masked body over the
# columns the band touches and never two bodies over the same rows.
_FINE = 256
_WIDEST = 1024   # keys a body of dq or dk/dv covers at most


def _chunk_band(d, rows, cols, causal, window):
    """``(some, every)``: whether some / every (query, key) pair of a
    ``[rows, cols]`` chunk lies inside the band, when the chunk's first query
    is ``d`` positions past its first key. The band is an interval of
    ``q - k`` around 0 and a chunk holds every ``q - k`` between its top-right
    and its bottom-left corner, so :func:`band_predicate` at those two
    corners decides both. Python ints while tracing, scalars in a kernel."""
    if not causal and window is None:
        return True, True
    lo, hi = d - (cols - 1), d + rows - 1     # top-right, bottom-left
    in_lo = band_predicate(lo, 0, causal, window)
    in_hi = band_predicate(hi, 0, causal, window)
    return in_lo | in_hi | ((lo < 0) & (hi > 0)), in_lo & in_hi


def _chunk_diffusion(q0, k0, rows, cols, diffusion):
    """``(key, some, every)`` of the ``[rows, cols]`` chunk whose first query
    and key are at ``q0`` and ``k0`` of a block-diffusion stream: whether
    some / every pair of it is visible, and ``key``, which names what of the
    chunk is: its quarter of the mask (noised or clean queries over noised or
    clean keys) and how far its first query is past its first key within the
    row, on which alone ``some``, ``every`` and the mask inside the chunk
    depend. The chunk lies in one quarter (a tile never crosses the middle of
    the stream) and begins and ends on block boundaries (blocks divide
    ``BLOCK_Q``), so the first and last block of its queries and of its keys
    decide. Python ints while tracing, scalars in a kernel."""
    block, length = diffusion
    shift = block.bit_length() - 1
    q_clean, k_clean = q0 >= length, k0 >= length
    q_noised, k_noised = q0 < length, k0 < length
    qp, kp = q0 - length * q_clean, k0 - length * k_clean
    q_lo, q_hi = qp >> shift, (qp + rows - 1) >> shift
    k_lo, k_hi = kp >> shift, (kp + cols - 1) >> shift
    some = (q_noised & k_noised & (k_lo <= q_hi) & (q_lo <= k_hi)) | (
        k_clean & ((k_lo < q_hi) | (q_clean & (k_lo <= q_hi))))
    every = (q_noised & k_noised & (k_lo == q_hi) & (k_hi == q_lo)) | (
        k_clean & ((k_hi < q_lo) | (q_clean & (k_hi <= q_lo))))
    return qp - kp + 2 * length * (k_clean + 2 * q_clean), some, every


def _chunk(q0, k0, rows, cols, causal, window, diffusion):
    """``(key, some, every)`` of a chunk under either kind of mask: a band's
    key is ``q0 - k0`` (:func:`_chunk_band`)."""
    if diffusion is not None:
        return _chunk_diffusion(q0, k0, rows, cols, diffusion)
    return (q0 - k0,) + _chunk_band(q0 - k0, rows, cols, causal, window)


def _band_pieces(at, rows, cols, mask, r=0, c=0):
    """The bodies a ``[rows, cols]`` tile at ``at = (q0, k0)`` takes under
    ``mask = (causal, window, diffusion)``, as a list of ``(r, c, rows, cols,
    edge)``: the piece at ``(r, c)`` of the tile, under the mask if ``edge``
    (True for a band; under block diffusion the quarter of the mask the piece
    lies in, :func:`_edge`). Static: ``at`` holds Python ints."""
    _, some, every = _chunk(at[0] + r, at[1] + c, rows, cols, *mask)
    if not some:
        return []
    if every:
        return [(r, c, rows, cols, False)]
    halved = lambda n: n // 2 if n % (2 * _FINE) == 0 else n
    hr = halved(rows) if rows >= cols else rows
    hc = halved(cols) if cols >= rows else cols
    if (hr, hc) == (rows, cols):
        return [(r, c, rows, cols, _edge(at, mask[2]))]
    return [piece for rr in range(r, r + rows, hr)
            for cc in range(c, c + cols, hc)
            for piece in _band_pieces(at, hr, hc, mask, rr, cc)]


def _edge(at, diffusion):
    """What a masked piece of the tile at ``at = (q0, k0)`` carries as its
    ``edge``: True, or under block diffusion ``(q_clean, k_clean)``, the
    copies its queries and keys lie in (a tile lies in one quarter)."""
    if diffusion is None:
        return True
    return at[0] >= diffusion[1], at[1] >= diffusion[1]


def _band_plan(L, tiles, causal, window, one_body=False, diffusion=None):
    """``((key, pieces, whole), ...)``: for every kind of ``[bq, bk]`` tile
    (``tiles``) of the length-``L`` grid that touches the mask (a band's
    kinds are the offsets ``d = q0 - k0``, block diffusion's
    :func:`_chunk_diffusion`'s keys), the pieces that tile runs, cut down to
    ``_FINE``, and whether the whole tile is visible. ``one_body`` (the
    forward): the one body over the bounding box of what :func:`_band_pieces`
    keeps of the tile, masked unless that is the whole tile. Else (dq, dk/dv,
    which pay by the pair): those pieces themselves, of each ``_WIDEST`` keys
    of the tile apart, so that a wide tile's float32 ``[rows, keys]``
    temporaries stay what they were at 1024 keys a step."""
    bq, bk = tiles
    mask = (causal, window, diffusion)
    if diffusion is None:     # a band's tiles differ by their offset alone
        kinds = {d: (d, 0) for d in {i * bq - j * bk for i in range(L // bq)
                                     for j in range(L // bk)}}
    else:
        kinds = {_chunk_diffusion(i * bq, j * bk, bq, bk, diffusion)[0]:
                 (i * bq, j * bk)
                 for i in range(L // bq) for j in range(L // bk)}
    plan = []
    for key in sorted(kinds):
        at = kinds[key]
        if one_body:
            pieces = _band_pieces(at, bq, bk, mask)
            if len(pieces) > 1:
                r0 = min(r for r, *_ in pieces)
                c0 = min(c for _, c, *_ in pieces)
                r1 = max(r + rows for r, _, rows, _, _ in pieces)
                c1 = max(c + cols for _, c, _, cols, _ in pieces)
                pieces = [(r0, c0, r1 - r0, c1 - c0, _edge(at, diffusion))]
        else:
            wide = min(bk, _WIDEST)
            pieces = [piece for c in range(0, bk, wide)
                      for piece in _band_pieces(at, bq, wide, mask, 0, c)]
        if pieces:
            plan.append((key, tuple(pieces),
                         bool(_chunk(*at, bq, bk, *mask)[2])))
    return tuple(plan)


def _run_band(fold, tile, live, plan):
    """One grid step's bodies, ``fold(r, c, rows, cols, edge)`` each: the
    pieces the plan has for the step's tile, ``tile = (key, some, every)``
    from :func:`_chunk` on the step's scalars. The wholly visible tiles all
    run the same unmasked pieces, under one test of ``every``; each kind of
    tile that the mask's edge crosses has its own pieces under a test of
    ``key``; a tile outside the mask runs nothing."""
    key, _, every = tile
    inside = {pieces for _, pieces, whole in plan if whole}
    assert len(inside) <= 1, inside
    for pieces in inside:
        @pl.when(live & every)
        def _():
            for piece in pieces:
                fold(*piece)
    for at, pieces, whole in plan:
        if not whole:
            @pl.when(live & (key == at))
            def _():
                for piece in pieces:
                    fold(*piece)


def _num_band_tiles(n_tiles, span, block):
    """Static size of the restricted grid axis: max tiles of width ``block``
    an arbitrarily aligned index range of length ``span`` can touch."""
    return min(n_tiles, (span - 2) // block + 2)


def _pick(cond, a, b):
    """``a if cond else b`` for Python values, ``jnp.where`` for scalars."""
    if isinstance(cond, (bool, int)):
        return a if cond else b
    return jnp.where(cond, a, b)


def _restricted_k_axis(nk, bq, bk, causal, window, diffusion=None):
    """(nkt, k_tile(iq, j)) for the forward/dq grids: the static size of the
    k axis and the index map from (q tile, band step) → real k tile. With a
    window only the tiles the band can touch are visited, so compute and
    bandwidth are O(L·window); under block diffusion a q tile's steps are its
    own noised tile (noised queries only) and then the clean tiles up to its
    diagonal. The map is clamped to the q tile's last contributing k tile: a
    step past it (guarded off in-kernel by ``kt <= last_k``: above the causal
    diagonal, or past the sequence end) names the block already held and
    copies nothing."""
    if diffusion is not None:
        nkt = nk // 2 + 1
    elif window is None:
        nkt = nk
    else:
        span = bq + window - 1 if causal else bq + 2 * window - 2
        nkt = _num_band_tiles(nk, span, bk)

    def k_tile(i, j):
        return jnp.minimum(*_k_step(i, j, nk, block_q=bq, block_k=bk,
                                    causal=causal, window=window,
                                    diffusion=diffusion))

    return nkt, k_tile


def _restricted_q_axis(nq, bq, bk, causal, window, diffusion=None):
    """(nqt, q_tile(jk, i)) for the dkv grid — the transposed mirror of
    :func:`_restricted_k_axis`: band steps count from the first q tile that
    sees the k tile, and the steps past the last one are clamped to it."""
    if window is None:
        nqt = nq
    else:
        span = bk + window - 1 if causal else bk + 2 * window - 2
        nqt = _num_band_tiles(nq, span, bq)

    def q_tile(j, i):
        return jnp.minimum(*_q_step(j, i, nq, block_q=bq, block_k=bk,
                                    causal=causal, window=window,
                                    diffusion=diffusion))

    return nqt, q_tile


def _k_step(iq, jk, nk, *, block_q, block_k, causal, window, diffusion=None):
    """``(kt, last_k)`` of step ``(iq, jk)`` of the forward's and dq's grids:
    the k tile the step stands on (``jk`` counts from the first tile of q
    tile ``iq``'s band) and the last tile that contributes to ``iq``. The step
    is live iff ``kt <= last_k``. Python ints give ints (the census), program
    ids scalars (the kernels).

    Under block diffusion (tiles of the row's length, so none crosses the
    middle of the stream) a noised q tile stands first on the noised tile
    that holds its own blocks and then on the clean tiles ``0 ..`` that hold
    an EARLIER block than its last one; a clean q tile on the clean tiles up
    to the one that holds its last block."""
    if diffusion is not None:
        block, length = diffusion
        half = nk // 2
        q0 = iq * block_q
        is_clean = q0 >= length
        clean = _pick(is_clean, 1, 0)
        qp0 = q0 - length * clean
        # the last clean key a query of the tile sees, as a tile of its half
        seen = (qp0 + block_q - 1 - block * (1 - clean)) // block_k
        own = qp0 // block_k
        kt = _pick(is_clean, half + jk,
                   _pick(jk == 0, own, half + jk - 1))
        return kt, _pick(seen < 0, own, half + seen)
    kt = _first_k_tile(iq, block_q=block_q, block_k=block_k,
                       window=window) + jk
    return kt, _last_k_tile(iq, nk, block_q=block_q, block_k=block_k,
                            causal=causal, window=window)


def _q_step(jk, iq, nq, *, block_q, block_k, causal, window, diffusion=None):
    """``(qt, last_q)`` of step ``(jk, iq)`` of dk/dv's grid: the transposed
    mirror of :func:`_k_step`, live iff ``qt <= last_q``. Under block
    diffusion a noised k tile is seen by the noised q tiles over its own rows
    and no other; a clean k tile by the noised q tiles from the first that
    holds a LATER block than its first one, then by the clean q tiles from
    the one over its first row."""
    if diffusion is not None:
        block, length = diffusion
        half = nq // 2
        k0 = jk * block_k
        is_clean = k0 >= length
        kp0 = k0 - length * _pick(is_clean, 1, 0)
        over = kp0 // block_q
        later = (kp0 + block) // block_q       # may be `half`: no noised tile
        qt = _pick(is_clean,
                   _pick(iq < half - later, later + iq,
                         half + over + iq - (half - later)),
                   over + iq)
        return qt, _pick(is_clean, nq - 1, over + block_k // block_q - 1)
    qt = _first_q_tile(jk, block_q=block_q, block_k=block_k, causal=causal,
                       window=window) + iq
    return qt, _last_q_tile(jk, nq, block_q=block_q, block_k=block_k,
                            window=window)


def _grid_steps(L, tiles, causal, window, transposed=False, diffusion=None):
    """``(qt, kt, live)`` of every step one head's grid takes at length ``L``,
    in the grid's order: the forward's and dq's (k innermost), or dk/dv's
    (``transposed``: q innermost), from the axes and the step rule the
    kernels themselves run."""
    bq, bk = tiles
    nq, nk = L // bq, L // bk
    kw = dict(block_q=bq, block_k=bk, causal=causal, window=window,
              diffusion=diffusion)
    if transposed:
        nqt, _ = _restricted_q_axis(nq, bq, bk, causal, window, diffusion)
        for jk in range(nk):
            for i in range(nqt):
                qt, last = (int(x) for x in _q_step(jk, i, nq, **kw))
                yield qt, jk, qt <= last
    else:
        nkt, _ = _restricted_k_axis(nk, bq, bk, causal, window, diffusion)
        for iq in range(nq):
            for j in range(nkt):
                kt, last = (int(x) for x in _k_step(iq, j, nk, **kw))
                yield iq, kt, kt <= last


# A per-row statistic (the log-sum-exp, the backward's ``delta``) is a column
# ``[rows, 1]`` in a kernel and, as a ``[B·H, L, 1]`` array, 128 times its
# size in HBM (the tiling pads the last dimension to a lane tile): 128 MB an
# array at 8 x 8 heads x 4096, which the band's callers have room for, and
# 512 MB at the block-diffusion cell's 4 x 32 heads x 8192, which that step
# has not. So under block diffusion, and where q / k and v differ in width
# (``_stat_rows``), the forward and dq take and give them as ROWS,
# ``[B·H, 1, L]`` (as dk/dv always has), and turn a q tile's row into its
# column once, on the tile's first step.


def _as_col(row):
    """``[1, n]`` → ``[n, 1]`` in a kernel (``n`` a multiple of 128)."""
    return jnp.transpose(jnp.broadcast_to(row, (128, row.shape[1])))[:, :1]


def _as_row(col):
    """``[n, 1]`` → ``[1, n]`` in a kernel (``n`` a multiple of 128)."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], 128)))[:1, :]


def _stat_rows(diffusion, Dk, Dv) -> bool:
    """Whether a call's per-row statistics travel as rows ``[B·H, 1, L]``:
    under block diffusion and wherever q / k and v differ in width (both came
    with callers of 128 heads x 8192 positions, where a column array is 512
    MB). The band's callers of one width keep their columns, and the programs
    they had."""
    return diffusion is not None or Dk != Dv


def _fa_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
               plan, window, nk, diffusion=None, stat_rows=False):
    """One (bh, iq, jk) step: fold the step's [bq, bk] score tile, piece by
    piece (:func:`_run_band`), into the online softmax state; finalize on
    this q block's last contributing k step.

    The grid's k axis is restricted to the band (the BlockSpec index map only
    loads in-band tiles), so ``jk`` counts tiles from the band start: the
    real k tile is ``first_k + jk``."""
    if len(rest) == 6:
        km_ref, o_ref, lse_ref, m_s, l_s, acc = rest
    else:
        km_ref, (o_ref, lse_ref, m_s, l_s, acc) = None, rest
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc[:] = jnp.zeros_like(acc)

    # under causal/window masking, k tiles outside the band contribute
    # nothing — the restricted grid never visits tiles below the band, and
    # the guard skips the steps past its end (≈2× at long causal context)
    kt, last_k = _k_step(iq, jk, nk, block_q=block_q, block_k=block_k,
                         causal=causal, window=window, diffusion=diffusion)
    q0, k0 = iq * block_q, kt * block_k

    def fold(r, c, rows, cols, edge):
        rs, cs = pl.ds(r, rows), pl.ds(c, cols)
        q = q_ref[0, rs, :].astype(jnp.float32) * scale  # [rows, Dk]
        k = k_ref[0, cs, :].astype(jnp.float32)          # [cols, Dk]
        v = v_ref[0, cs, :].astype(jnp.float32)          # [cols, Dv]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [rows, cols]
        valid = _piece_valid(
            _band_valid(q0 + r, k0 + c, rows, cols, causal, window, diffusion,
                        edge)
            if edge else None,
            None if km_ref is None else km_ref[0, :, cs],     # [1, cols]
            s.shape)
        if valid is not None:
            s = jnp.where(valid, s, _NEG)

        m_prev = m_s[rs, :]                              # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                   # [rows, 1]
        l_s[rs, :] = l_s[rs, :] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[rs, :] = acc[rs, :] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_s[rs, :] = m_new

    _run_band(fold, _chunk(q0, k0, block_q, block_k, causal, window,
                           diffusion), kt <= last_k, plan)

    @pl.when(kt == last_k)
    def _():
        l = jnp.maximum(l_s[:], 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        lse = m_s[:] + jnp.log(l)
        lse_ref[0] = _as_row(lse) if stat_rows else lse


def _pick_block_q(L):
    """q tile height: 512 rows at every ``L >= 1024`` that 512 divides, else
    ``BLOCK_Q``. A grid step costs about a microsecond whatever it computes
    (v5e, PR 28: a step whose body is guarded off takes 0.8-1.3 us), so tall
    tiles win: at 8 x 2048 x 16 heads of 64, not causal, forward + dq + dk/dv
    take 7.65 ms a call at 512 x 1024 and 12.46 ms at 256 x 512. 1024 rows
    gain another 5 % where they fit and do not fit the scoped VMEM at head
    128 or without a band (the whole-tile body's float32 [rows, keys]
    temporaries), so 512 it stays. L = 512 keeps the default ladder, as do
    lengths that aren't 512-multiples (tile rule)."""
    return 512 if L >= 1024 and L % 512 == 0 else BLOCK_Q


def _pick_block_k(L):
    """k tile width: the largest of 2048, 1024, ``BLOCK_K``, 384, 256, 128
    that divides L (128 always does). Wider is fewer grid steps, and since
    the band is cut inside a step (:func:`_band_plan`) a wide tile no longer
    computes what lies outside it. v5e, PR 28, ms a call forward + dq + dk/dv,
    causal: 8 x 2048 x 16 heads of 64: 7.24 at the parent (whole 512 x 1024
    tiles under the mask), 5.97 with the band cut inside 1024-key steps, 4.98
    at 2048 keys; 8 x 4096 x 8 heads of 128 over 2 key-value heads: 10.98,
    9.97, 8.92. Not causal 7.65 -> 7.18 and 14.96 -> 14.19. The 1024-key
    figures are a sweep's (``chip_smoke.py``'s kernels leg on this tree reads
    the others); in that sweep dq and dk/dv ran bodies as wide as the tile
    and head 128 read 9.09 at 2048 keys, before their bodies were held to
    ``_WIDEST`` keys. 4096 keys a step fit no better (dk/dv four times slower
    at head 128). Every (bq, bk) combination keeps bk % bq == 0 or
    bq % bk == 0, which the tile index math relies on."""
    return next(c for c in (2048, 1024, BLOCK_K, 384, 256, 128) if L % c == 0)


def band_census(L, causal=False, window=None, masked=False,
                block_diffusion=None):
    """What the three kernels do at length ``L``, for one head, counted from
    the index arithmetic they run (:func:`_grid_steps`: their grids' axes and
    step rule; their :func:`_band_plan`) and from nothing measured:
    per kernel a dict of

    - ``steps``, ``steps_idle``: grid steps, and those of them the guard
      turns off (they fetch nothing either: the index maps are clamped);
    - ``bodies_unmasked`` / ``bodies_masked`` and ``pairs_unmasked`` /
      ``pairs_masked``: the bodies that run without and with a mask (every
      body is masked under a key mask: ``masked``) and the (query, key)
      pairs they compute; ``pairs_skipped``: the rest of the grid's tiles;
    - ``pairs_band``: the pairs :func:`band_predicate` admits, and
      ``computed_over_band``: computed pairs over those (1.0 is the floor).

    Causal, at the 512 x 2048 tiles of L = 2048 and 4096: dq and dk/dv
    compute 1.125 and 1.062 times the band, 78 % and 88 % of it with no
    mask; the forward 1.25 and 1.125 times, none and 44 % of it with no mask
    (whole 512 x 1024 tiles under the mask before PR 28: 1.50 and 1.25).

    ``block_diffusion=G``: the block-diffusion mask over a stream of ``L``
    positions (``L`` is the call's length, twice the rows': a noised and a
    clean copy). At ``L = 8192`` (rows of 4096), ``G = 4``, tiles 512 x 2048:
    the forward computes 1.25 times the visible pairs, dq and dk/dv 1.125
    times; 0.125 and 0.0625 of that is the noised copy's own diagonal, where
    a 512 x 512 body (256 x 256 in dq and dk/dv) holds 4 x 4 blocks."""
    import numpy as np

    diffusion = _canonical_diffusion(block_diffusion, L, causal, window)
    window = _canonical_window(window, L)
    tiles = bq, bk = _tiles(L if diffusion is None else L // 2)
    mask = (causal, window, diffusion)
    pairs_band = L * L
    if causal or window is not None or diffusion:   # a q tile's rows at a time
        pairs_band = sum(int(band_predicate(
            np.arange(q0, q0 + bq)[:, None], np.arange(L)[None, :],
            *mask).sum()) for q0 in range(0, L, bq))

    def count(transposed, one_body):
        plan = {key: pieces for key, pieces, _ in
                _band_plan(L, tiles, causal, window, one_body, diffusion)}
        out = dict(steps=0, steps_idle=0, bodies_unmasked=0, bodies_masked=0,
                   pairs_unmasked=0, pairs_masked=0)
        for qt, kt, live in _grid_steps(L, tiles, causal, window, transposed,
                                        diffusion):
            out["steps"] += 1
            out["steps_idle"] += not live
            key = _chunk(qt * bq, kt * bk, bq, bk, *mask)[0]
            pieces = plan.get(key, ()) if live else ()
            for _, _, rows, cols, edge in pieces:
                kind = "masked" if edge or masked else "unmasked"
                out["bodies_" + kind] += 1
                out["pairs_" + kind] += rows * cols
        computed = out["pairs_unmasked"] + out["pairs_masked"]
        out["pairs_skipped"] = out["steps"] * bq * bk - computed
        out["pairs_band"] = pairs_band
        out["computed_over_band"] = computed / pairs_band
        return out

    return {"flash_fwd": count(False, True), "flash_dq": count(False, False),
            "flash_dkv": count(True, False)}


def _gqa_groups(q, k, qk_major=False):
    """Validated GQA group size: q heads per shared k/v head (1 = MHA);
    ``qk_major``: q and k are head-major, ``[B·heads, L, D]``."""
    axis = 0 if qk_major else 2
    H, Hkv = q.shape[axis], k.shape[axis]
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


def _tiles(L):
    """The grid's ``(block_q, block_k)`` at length ``L``."""
    return _pick_block_q(L), _pick_block_k(L)


def _fa_forward(q, k, v, key_mask, *, scale, causal, interpret,
                window=None, diffusion=None, qk_major=False, heads=None):
    """q [B, L, H, Dk], k [B, L, Hkv, Dk], v [B, L, Hkv, Dv] with Hkv | H
    (grouped-query attention reads shared K/V heads straight from the index
    maps — no repeated-KV materialization), + key_mask [B, L] →
    (out [B, L, H, Dv], lse). ``qk_major``: q and k come as the kernels walk
    them, ``[B·H, L, D]`` and ``[B·Hkv, L, D]``; with ``heads`` (= H) v too,
    ``[B·Hkv, L, Dv]`` (see :func:`flash_attention`)."""
    L = q.shape[1]
    _gqa_groups(q, k, qk_major)
    if L % BLOCK_Q:
        raise ValueError(
            f"sequence length {L} must be a multiple of {BLOCK_Q}"
        )
    tiles = _tiles(L if diffusion is None else L // 2)
    return _fwd_call(q, k, v, key_mask, tiles=tiles, scale=scale,
                     causal=causal, interpret=interpret, window=window,
                     diffusion=diffusion, qk_major=qk_major, heads=heads)


# The two launchers are jitted on their own: a model calls them once a layer
# with the same shapes, and tracing and lowering the kernels' bodies (a score
# of them in a causal kernel) is then paid once a program, not once a layer:
# v5e, PR 28, XGLM-564M's 24 layers trace and lower in 14.2 s so and in 92.7 s
# without (23.2 s when a kernel had one body). It is not free: under it the
# TPU compiler keeps dk and not dv in its fast memory space, and the fusion
# that reads dq, dk and dv takes 0.2 ms a layer more (PERF.md section 6).
# The tiles are an argument so that the choice is part of the cache's key;
# the band's grain (``_FINE``, ``_WIDEST``) is read when a launcher traces.
_STATIC = ("tiles", "scale", "causal", "interpret", "window", "diffusion",
           "qk_major", "heads")


def _sizes(q, k, v, qk_major, heads):
    """``(B, L, H, Hkv, Dk, Dv)`` of a launcher's operands: from ``v [B, L,
    Hkv, Dv]``, or, where all three come head-major (``heads`` = H, which
    three head-major operands do not say), from it and their rows."""
    Dk, Dv = q.shape[-1], v.shape[-1]
    groups = _gqa_groups(q, k, qk_major)
    if heads is None:
        B, L, Hkv, _ = v.shape
        return B, L, Hkv * groups, Hkv, Dk, Dv
    return q.shape[0] // heads, q.shape[1], heads, heads // groups, Dk, Dv


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(q, k, v, key_mask, *, tiles, scale, causal, interpret, window,
              diffusion=None, qk_major=False, heads=None):
    B, L, H, Hkv, Dk, Dv = _sizes(q, k, v, qk_major, heads)
    bq, bk = tiles
    rows = _stat_rows(diffusion, Dk, Dv)

    def bh(x):  # [B, L, h, d] → [B·h, L, d]
        h, d = x.shape[2:]
        return jnp.moveaxis(x, 2, 1).reshape(B * h, L, d)

    qb, kb = (q, k) if qk_major else (bh(q), bh(k))

    nk = L // bk
    nkt, k_tile = _restricted_k_axis(nk, bq, bk, causal, window, diffusion)
    grid = (B * H, L // bq, nkt)
    qspec = pl.BlockSpec((1, bq, Dk), lambda b, i, j: (b, i, 0))
    kspec, vspec = (pl.BlockSpec(
        (1, bk, d), lambda b, i, j: (_kv_row(b, H, Hkv), k_tile(i, j), 0)
    ) for d in (Dk, Dv))
    ospec = pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0))
    # lse carries a trailing singleton so its block obeys the (8, 128)
    # tile rule (last dim equal to the array dim is allowed); where the
    # statistics are rows (_stat_rows) it is one (see _as_col)
    if not rows:
        lspec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
        lshape = (B * H, L, 1)
    else:
        lspec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
        lshape = (B * H, 1, L)
    out_shape = [
        jax.ShapeDtypeStruct((B * H, L, Dv), q.dtype),
        jax.ShapeDtypeStruct(lshape, jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((bq, 1), jnp.float32),   # running max m
        pltpu.VMEM((bq, 1), jnp.float32),   # running denom l
        pltpu.VMEM((bq, Dv), jnp.float32),  # running numerator acc
    ]
    in_specs = [qspec, kspec, vspec]
    args = [qb, kb, v if heads else bh(v)]
    if key_mask is not None:
        # mask ships as [B, 1, L] so its block obeys the (8, 128) tile rule
        in_specs.append(
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b // H, 0,
                                                      k_tile(i, j)))
        )
        args.append(key_mask.astype(jnp.float32)[:, None, :])
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        plan=_band_plan(L, tiles, causal, window, True, diffusion),
        window=window, nk=nk, diffusion=diffusion, stat_rows=rows,
    )

    o, lse = pl.pallas_call(
        kernel, grid=grid,
        in_specs=in_specs,
        out_specs=[ospec, lspec],
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    out = jnp.moveaxis(o.reshape(B, H, L, Dv), 1, 2)
    return out, lse[:, 0] if rows else lse[..., 0]


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref, *rest,
                      scale, causal, block_q, block_k, plan, window, nk,
                      diffusion=None, stat_rows=False):
    """One (bh, iq, jk) step: rebuild the step's [bq, bk] probability tile
    from the saved lse, piece by piece (:func:`_run_band`), and fold
    ``ds @ k`` into the dq accumulator; write on this q block's last
    contributing k step."""
    lse_s = d_s = None
    if stat_rows:                 # the statistics came as rows: see _as_col
        *rest, lse_s, d_s = rest
    if len(rest) == 3:
        km_ref, dq_ref, acc = rest
    else:
        km_ref, (dq_ref, acc) = None, rest
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        if stat_rows:
            lse_s[:] = _as_col(lse_ref[0])
            d_s[:] = _as_col(d_ref[0])

    def stat(ref, col, rs):       # a statistic's [rows, 1] piece
        return col[rs, :] if stat_rows else ref[0, rs, :]

    kt, last_k = _k_step(iq, jk, nk, block_q=block_q, block_k=block_k,
                         causal=causal, window=window, diffusion=diffusion)
    q0, k0 = iq * block_q, kt * block_k

    def fold(r, c, rows, cols, edge):
        rs, cs = pl.ds(r, rows), pl.ds(c, cols)
        qs = q_ref[0, rs, :].astype(jnp.float32) * scale  # [rows, Dk]
        kk = k_ref[0, cs, :].astype(jnp.float32)          # [cols, Dk]
        vv = v_ref[0, cs, :].astype(jnp.float32)          # [cols, Dv]
        gg = g_ref[0, rs, :].astype(jnp.float32)          # [rows, Dv]
        s = jax.lax.dot_general(
            qs, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # [rows, cols]
        valid = _piece_valid(
            _band_valid(q0 + r, k0 + c, rows, cols, causal, window, diffusion,
                        edge)
            if edge else None,
            None if km_ref is None else km_ref[0, :, cs],     # [1, cols]
            s.shape)
        if valid is not None:
            s = jnp.where(valid, s, _NEG)
        lse = stat(lse_ref, lse_s, rs)                    # [rows, 1]
        p = jnp.exp(s - lse)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        dp = jax.lax.dot_general(
            gg, vv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # [rows, cols]
        ds = p * (dp - stat(d_ref, d_s, rs))              # delta [rows, 1]
        acc[rs, :] += jax.lax.dot_general(
            ds, kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    _run_band(fold, _chunk(q0, k0, block_q, block_k, causal, window,
                           diffusion), kt <= last_k, plan)

    @pl.when(kt == last_k)
    def _():
        dq_ref[0] = acc[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref, *rest,
                       scale, causal, block_q, block_k, plan, window, nq,
                       gqa_groups=None, diffusion=None):
    """One (bh, jk, iq) step — or (b·hkv, jk, gg, iq) under grouped-query
    attention, where the extra ``gg`` axis walks the q heads sharing this
    k/v head and the dk/dv accumulators run across the whole group:
    rebuild the step's transposed [bk, bq] probability tile, piece by piece
    (:func:`_run_band`), and fold ``pᵀ @ dO`` / ``dsᵀ @ q`` into the
    dv/dk accumulators; write on the group's last contributing q step.
    ``iq`` counts q tiles from the first that sees this k tile."""
    if len(rest) == 5:
        km_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        km_ref = None
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    jk = pl.program_id(1)
    if gqa_groups is None:
        last_g = None
        iq = pl.program_id(2)
        first_step = iq == 0
    else:
        grp = pl.program_id(2)  # in-group q head (gg names the dO tile)
        iq = pl.program_id(3)
        first_step = (grp == 0) & (iq == 0)
        last_g = grp == gqa_groups - 1

    @pl.when(first_step)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    qt, last_q = _q_step(jk, iq, nq, block_q=block_q, block_k=block_k,
                         causal=causal, window=window, diffusion=diffusion)
    q0, k0 = qt * block_q, jk * block_k

    def fold(r, c, rows, cols, edge):
        rs, cs = pl.ds(r, rows), pl.ds(c, cols)
        qs = q_ref[0, rs, :].astype(jnp.float32) * scale  # [rows, Dk]
        kk = k_ref[0, cs, :].astype(jnp.float32)          # [cols, Dk]
        vv = v_ref[0, cs, :].astype(jnp.float32)          # [cols, Dv]
        gg = g_ref[0, rs, :].astype(jnp.float32)          # [rows, Dv]
        st = jax.lax.dot_general(
            kk, qs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # [cols, rows]
        valid = _piece_valid(
            _band_valid_t(q0 + r, k0 + c, rows, cols, causal, window,
                          diffusion, edge)
            if edge else None,
            None if km_ref is None else km_ref[0, cs, :],     # [cols, 1]
            st.shape)
        if valid is not None:
            st = jnp.where(valid, st, _NEG)
        pt = jnp.exp(st - lse_ref[0, :, rs])              # lse [1, rows]
        if valid is not None:
            pt = jnp.where(valid, pt, 0.0)
        dv_acc[cs, :] += jax.lax.dot_general(
            pt, gg, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            vv, gg, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # [cols, rows]
        dst = pt * (dpt - d_ref[0, :, rs])                # delta [1, rows]
        dk_acc[cs, :] += jax.lax.dot_general(
            dst, qs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _run_band(fold, _chunk(q0, k0, block_q, block_k, causal, window,
                           diffusion), qt <= last_q, plan)

    write = qt == last_q if last_g is None else ((qt == last_q) & last_g)

    @pl.when(write)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _fa_backward(q, k, v, key_mask, out, lse, g, *, scale, causal,
                 interpret, window=None, diffusion=None, qk_major=False,
                 heads=None):
    """Blockwise flash-attention backward: (dq, dk, dv) via two Pallas
    kernels, ``O(block_q · block_k)`` on-chip — no [B, H, L, L] tensors.
    Under grouped-query attention (k/v hold Hkv < H heads) dq reads the
    shared heads through the index maps and the dkv grid gains a group
    axis whose accumulators sum the whole group — dk/dv come out
    Hkv-wide, no repeated-KV tensors anywhere. ``qk_major``: q and k come,
    and dq and dk go, head-major; with ``heads`` v and dv too."""
    L = q.shape[1]
    tiles = _tiles(L if diffusion is None else L // 2)
    return _bwd_call(q, k, v, key_mask, out, lse, g, tiles=tiles,
                     scale=scale, causal=causal, interpret=interpret,
                     window=window, diffusion=diffusion, qk_major=qk_major,
                     heads=heads)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(q, k, v, key_mask, out, lse, g, *, tiles, scale, causal,
              interpret, window, diffusion=None, qk_major=False, heads=None):
    B, L, H, Hkv, Dk, Dv = _sizes(q, k, v, qk_major, heads)
    groups = H // Hkv
    bq, bk = tiles  # the forward's: one ladder
    plan = _band_plan(L, tiles, causal, window, False, diffusion)
    rows = _stat_rows(diffusion, Dk, Dv)

    def bh(x):  # [B, L, h, d] → [B·h, L, d]
        h, d = x.shape[2:]
        return jnp.moveaxis(x, 2, 1).reshape(B * h, L, d)

    qb, kb = (q, k) if qk_major else (bh(q), bh(k))
    vb, gb = v if heads else bh(v), bh(g)
    # delta = rowsum(dO · O): one elementwise pass, [B·H, L]
    delta = jnp.sum(gb.astype(jnp.float32) * bh(out).astype(jnp.float32),
                    axis=-1)
    lse_row, d_row = lse[:, None, :], delta[:, None, :]    # [B·H, 1, L]
    nk, nq = L // bk, L // bq
    # same restricted band axes as the forward (one shared builder, so the
    # forward and backward grids cannot drift apart)
    nkt, k_tile = _restricted_k_axis(nk, bq, bk, causal, window, diffusion)
    nqt, q_tile = _restricted_q_axis(nq, bq, bk, causal, window, diffusion)

    # q, k, dq and dk are Dk wide; v, dO and dv Dv
    qspec, gspec = (pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
                    for d in (Dk, Dv))
    kspec_q, vspec_q = (pl.BlockSpec(
        (1, bk, d), lambda b, i, j: (_kv_row(b, H, Hkv), k_tile(i, j), 0)
    ) for d in (Dk, Dv))
    if not rows:                  # columns [B·H, L, 1]
        statspec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
        stats, stat_scratch = [lse[..., None], delta[..., None]], []
    else:                         # rows, turned in the kernel: see _as_col
        statspec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
        stats = [lse_row, d_row]
        stat_scratch = [pltpu.VMEM((bq, 1), jnp.float32)] * 2

    dq_specs = [qspec, kspec_q, vspec_q, gspec, statspec, statspec]
    dq_args = [qb, kb, vb, gb] + stats
    if key_mask is not None:
        dq_specs.append(
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b // H, 0,
                                                      k_tile(i, j)))
        )
        dq_args.append(key_mask.astype(jnp.float32)[:, None, :])
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, plan=plan, window=window,
                          nk=nk, diffusion=diffusion, stat_rows=rows),
        grid=(B * H, nq, nkt),
        in_specs=dq_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B * H, L, Dk), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, Dk), jnp.float32)] + stat_scratch,
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
        kspec, vspec = (pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
                        for d in (Dk, Dv))
        qspec2, gspec2 = (pl.BlockSpec(
            (1, bq, d), lambda b, j, i: (b, q_tile(j, i), 0)
        ) for d in (Dk, Dv))
        rowspec = pl.BlockSpec(
            (1, 1, bq), lambda b, j, i: (b, 0, q_tile(j, i))
        )
        kmspec = pl.BlockSpec((1, bk, 1), lambda b, j, i: (b // H, j, 0))
    else:
        grid = (B * Hkv, nk, groups, nqt)
        kspec, vspec = (pl.BlockSpec(
            (1, bk, d), lambda b, j, gg, i: (b, j, 0)) for d in (Dk, Dv))
        qspec2, gspec2 = (pl.BlockSpec(
            (1, bq, d),
            lambda b, j, gg, i: (q_row_of(b, gg), q_tile(j, i), 0),
        ) for d in (Dk, Dv))
        rowspec = pl.BlockSpec(
            (1, 1, bq),
            lambda b, j, gg, i: (q_row_of(b, gg), 0, q_tile(j, i)),
        )
        kmspec = pl.BlockSpec(
            (1, bk, 1), lambda b, j, gg, i: (b // Hkv, j, 0)
        )
    dkv_specs = [qspec2, kspec, vspec, gspec2, rowspec, rowspec]
    dkv_args = [qb, kb, vb, gb, lse_row, d_row]
    if key_mask is not None:
        dkv_specs.append(kmspec)
        dkv_args.append(key_mask.astype(jnp.float32)[..., None])
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, plan=plan, window=window,
                          nq=nq, gqa_groups=None if groups == 1 else groups,
                          diffusion=diffusion),
        grid=grid,
        in_specs=dkv_specs,
        out_specs=[kspec, vspec],
        out_shape=[jax.ShapeDtypeStruct((B * Hkv, L, Dk), k.dtype),
                   jax.ShapeDtypeStruct((B * Hkv, L, Dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, Dk), jnp.float32),
                        pltpu.VMEM((bk, Dv), jnp.float32)],
        interpret=interpret,
        name="flash_dkv",
    )(*dkv_args)

    def unbh(x):  # [B·h, L, d] → [B, L, h, d]
        h, d = x.shape[0] // B, x.shape[-1]
        return jnp.moveaxis(x.reshape(B, h, L, d), 1, 2)

    if qk_major:
        return dq, dk, dv if heads else unbh(dv)
    return unbh(dq), unbh(dk), unbh(dv)


def _attention_bwd_math(q, k, v, key_mask, lse, g, *, scale, causal,
                        window=None, diffusion=None):
    """Recompute-based backward (plain XLA): p from saved lse, then the
    standard flash-attention gradient identities. GQA: k/v may hold
    Hkv < H heads — expanded here, with dk/dv group-summed back. ``v``
    and ``g`` may be another width than ``q`` and ``k``."""
    B, L, H, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[-1]
    groups = _gqa_groups(q, k)
    if groups > 1:
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    qf = q.astype(jnp.float32) * scale
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    band = band_predicate(jnp.arange(L)[:, None], jnp.arange(L)[None, :],
                          causal, window, diffusion)
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
        dv = dv.reshape(B, L, Hkv, groups, Dv).sum(axis=3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# A Mosaic kernel cannot be partitioned by the compiler (see
# ``ops.on_each_device``), and attention is independent across the batch: the
# forward and the backward each run through it, every array batch-major in
# dim 0 — q/k/v/out/g ``[B, …]``, the mask ``[B, L]``, lse ``[B·H, L]``.


def _forward(q, k, v, key_mask, scale, causal, interpret, window, diffusion,
             qk_major, heads):
    return ops.on_each_device(
        functools.partial(_fa_forward, scale=scale, causal=causal,
                          interpret=interpret, window=window,
                          diffusion=diffusion, qk_major=qk_major,
                          heads=heads),
        q, k, v, key_mask,
    )


def _backward(q, k, v, key_mask, out, lse, g, scale, causal, interpret,
              window, diffusion, qk_major, heads):
    return ops.on_each_device(
        functools.partial(_fa_backward, scale=scale, causal=causal,
                          interpret=interpret, window=window,
                          diffusion=diffusion, qk_major=qk_major,
                          heads=heads),
        q, k, v, key_mask, out, lse, g,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_core(q, k, v, key_mask, causal, scale, interpret, window,
                diffusion, qk_major, heads):
    out, _ = _forward(q, k, v, key_mask, scale, causal, interpret, window,
                      diffusion, qk_major, heads)
    return out


def _fa_fwd(q, k, v, key_mask, causal, scale, interpret, window, diffusion,
            qk_major, heads):
    out, lse = _forward(q, k, v, key_mask, scale, causal, interpret, window,
                        diffusion, qk_major, heads)
    # Without remat, saving `out` adds no memory: it aliases the primal
    # output. Under a rematted block the two names are what the block's
    # policy keeps (``ops.REMAT_SAVED``): ``B·L·H·Dv`` of q's dtype and one
    # float32 a row more a layer, and the block's backward does not run this
    # kernel a second time. The NAMED values are the primal output and the
    # residuals alike: a use of an un-named one brings the kernel back.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, key_mask, out, lse)


def _fa_bwd(causal, scale, interpret, window, diffusion, qk_major, heads, res,
            g):
    q, k, v, key_mask, out, lse = res
    dq, dk, dv = _backward(q, k, v, key_mask, out, lse, g, scale, causal,
                           interpret, window, diffusion, qk_major, heads)
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


def _canonical_diffusion(block, L, causal, window):
    """``(block, rows' length)`` for ``block_diffusion=block`` over a stream
    of ``L`` positions, or None without one; a named error for what the
    block-diffusion mask cannot be combined with or cut into tiles."""
    if block is None:
        return None
    block = int(block)
    if causal or window is not None:
        raise ValueError(
            "block_diffusion is a mask of its own: it cannot be combined "
            "with causal=True or window"
        )
    if block < 1 or block & (block - 1) or block > BLOCK_Q:
        raise ValueError(
            f"block_diffusion must be a power of two from 1 to {BLOCK_Q}, "
            f"got {block}"
        )
    if L % (2 * block):
        raise ValueError(
            f"block_diffusion={block} over {L} positions: the stream is a "
            f"noised and a clean copy of one row of whole blocks, so its "
            f"length must be a multiple of {2 * block}"
        )
    return block, L // 2


def flash_attention(q, k, v, causal: bool = False, scale=None, key_mask=None,
                    interpret: bool | None = None, window: int | None = None,
                    block_diffusion: int | None = None,
                    qk_major: bool = False, heads: int | None = None):
    """Pallas flash attention; same contract as ``attention_reference``.

    ``q/k/v`` [B, L, H, D] → [B, L, H, D]; ``v`` may be another width than
    ``q`` and ``k`` (latent attention's 192 / 128: ``q``, ``k`` and their
    gradients are ``Dk`` wide, ``v``, the result, dO and dv ``Dv``, nothing is
    padded, and the default ``scale`` is ``Dk ** -0.5``). Optional
    ``key_mask`` [B, L] (1 = attend). Gradients flow to q/k/v (the mask gets zero cotangent, as
    with the hard mask in the reference). ``window`` enables sliding-window
    (local) attention: query ``i`` sees keys ``(i-window, i]`` when causal,
    ``|i-j| < window`` otherwise; the kernel grid only visits in-band tiles,
    so compute AND k/v DMA scale as O(L·window). ``block_diffusion=G`` is
    the block-diffusion training mask (:func:`band_predicate`): the ``L``
    positions are a noised copy of a row of ``L // 2`` tokens followed by its
    clean copy, in blocks of ``G``; not with ``causal`` or ``window``, and
    ``L // 2`` must be a multiple of 128. No mask array is built: a tile the
    mask empties is neither computed nor fetched, a wholly visible one runs
    with no mask, and only tiles a diagonal crosses are masked.
    ``qk_major``: ``q`` and ``k`` come head-major, ``[B·H, L, D]`` and
    ``[B·Hkv, L, D]`` (what ``ops.qk_prep`` writes: the layout the kernels'
    grids walk, which any other caller's q and k are copied into), and their
    gradients go back so; ``v`` and the result keep ``[B, L, heads, D]``.
    With ``heads`` (the count of query heads a batch row has, which three
    head-major operands do not say) ``v`` too comes head-major, ``[B·Hkv, L,
    Dv]`` (what ``ops.mla_prep`` writes), and ``dv`` goes back so; the result
    and its cotangent keep ``[B, L, heads, Dv]``. Both are decided while
    tracing: a caller that asks for neither gets the copies it always got.
    """
    L = q.shape[1]
    if q.shape[-1] != k.shape[-1]:
        raise ValueError(
            f"q and k must be one width (their product is the score); got "
            f"{q.shape[-1]} and {k.shape[-1]}"
        )
    if v.shape[-1] != q.shape[-1] and block_diffusion is not None:
        raise ValueError(
            f"v of another width ({v.shape[-1]}) than q and k "
            f"({q.shape[-1]}) runs under the causal band, a window or no "
            f"mask; with block_diffusion it is not written"
        )
    if (heads is not None) != (v.ndim == 3) or (
            heads is not None and (not qk_major or q.shape[0] % heads)):
        raise ValueError(
            f"heads says that q, k and v ALL come head-major, [B·heads, L, "
            f"D]: it goes with qk_major=True, v of three dimensions and rows "
            f"that are whole batch rows of heads, and with nothing else; got "
            f"heads={heads}, qk_major={qk_major}, q {q.shape}, v {v.shape}"
        )
    diffusion = _canonical_diffusion(block_diffusion, L, causal, window)
    if diffusion is not None and (L // 2) % BLOCK_Q:
        raise ValueError(
            f"block_diffusion: the rows' length {L // 2} (half the stream) "
            f"must be a multiple of {BLOCK_Q}"
        )
    return _flash_core(
        q, k, v, key_mask, bool(causal),
        float(scale if scale is not None else q.shape[-1] ** -0.5),
        ops.interpreted(interpret),
        _canonical_window(window, L),
        diffusion,
        bool(qk_major),
        None if heads is None else int(heads),
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
              impl: str = "auto", window: int | None = None,
              block_diffusion: int | None = None):
    """Dispatch between the Pallas kernel and the XLA reference.

    ``impl``: ``"flash"`` forces the kernel (requires ``L % 128 == 0``),
    ``"reference"`` the XLA path, ``"auto"`` is decided by
    :func:`attention_impl`. ``key_mask`` is treated as a static-presence
    argument (its values are traced, its presence is not). ``window``:
    sliding-window (local) attention span, ``block_diffusion``: the
    block-diffusion training mask over a noised and a clean copy of each row
    — see :func:`flash_attention`.
    """
    from distkeras_tpu.parallel.sequence import attention_reference

    # under block diffusion the tiles are cut from the rows' length
    tiled = q.shape[1] if block_diffusion is None else q.shape[1] // 2
    if attention_impl(impl, L=tiled) == "reference":
        return attention_reference(q, k, v, causal=causal, scale=scale,
                                   key_mask=key_mask, window=window,
                                   block_diffusion=block_diffusion)
    return flash_attention(q, k, v, causal, scale, key_mask, window=window,
                           block_diffusion=block_diffusion)
