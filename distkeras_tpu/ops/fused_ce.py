"""Chunked fused linear + softmax cross-entropy — large-vocab LM training
without the ``[B, L, V]`` logits tensor.

The plain causal-LM loss path materializes the full logits
(``hidden @ lm_head`` → ``[B, L, V]``) and then reduces them to one scalar;
at serious vocab sizes that buffer dominates training memory (B=8, L=2048,
V=64k in f32 is ~4.3 GB — before the backward doubles it with dlogits).
Only three reductions of the logits are ever needed: the per-row
log-sum-exp, the picked label logit, and (in the backward) the softmax
row. So this op computes the loss **in row chunks** inside a ``lax.scan``:
each chunk's ``[chunk, V]`` logits live only for one scan step, XLA fuses
the matmul with the log-sum-exp that consumes it, and the full logits
tensor never exists in HBM — forward *or* backward.

The backward is a :func:`jax.custom_vjp` that walks the **vocabulary**,
not the rows. The forward saves each row's log-sum-exp (``[N]`` f32), so
the backward needs no softmax reduction of its own: for a tile of ``Vb``
vocabulary columns it recomputes ``hidden @ kernel[:, tile]`` (the
flash-attention trade: FLOPs for HBM), forms
``dlogits = (exp(logits − lse) − onehot) · mask · g/Σmask`` tile-locally,
writes ``d_kernel[:, tile] = hiddenᵀ @ dlogits`` (and ``d_bias[tile]``)
**once, in the kernel's dtype, as the loop's output**, and adds
``dlogits @ kernel[:, tile]ᵀ`` into the loop's only carry, the ``[N, D]``
f32 hidden gradient. A loop over row chunks would have to carry the
kernel's gradient instead — an f32 ``[D, V]`` array read and written once
a chunk, which at V = 256k is most of the step's memory traffic.

The tile width follows from what a tile costs. Every tile reads and
writes the whole carry, ``8 · N · D`` bytes, to add a product of
``2 · N · D · Vb`` FLOPs to it, so below ``Vb = 4 ·`` (the chip's FLOPs a
byte) — 962 columns on a v5e, whatever ``N`` and ``D`` are — the
``d_hidden`` product waits for HBM and not for the matrix unit. The width
aimed at is therefore the larger of the forward's budget spread over all
rows (``128 · ⌈⌈V / steps⌉ / 128⌉`` with ``steps = ⌈N / chunk⌉`` row
chunks: ``chunk`` still widens the tile where it asks for more) and
:data:`_BWD_TILE_COLUMNS`; then the tiles are evened out so that padding
stays under 128 columns a tile: ``tiles = ⌈V / width⌉``,
``Vb = 128 · ⌈⌈V / tiles⌉ / 128⌉`` (:func:`_vocab_tiles`, whose docstring
has the sweep measured on the chip). The kernel is zero-padded to
``tiles · Vb`` columns and the padded columns are masked to ``p = 0``, so
they get no gradient and give none. When one row chunk holds every row
(``N ≤ chunk``), or ``V`` is no wider than the width aimed at, there is
one tile of width ``V`` and no padding.

Peak extra memory: ``O(chunk · V)`` activations in the forward; in the
backward ``O(N · max(Vb_budget, _BWD_TILE_COLUMNS))`` — a tile's bf16
``dlogits`` (``N · Vb · 2`` bytes: 243 MB at 32,768 rows of 3,712
columns, 134 MB at 16,384 of 4,096; three times that where the compiler
keeps a float32 tile beside it) — plus the ``[N, D]`` f32 carry, instead
of ``O(N · V)``. The backward's share no longer shrinks with ``chunk``
below the floor: a caller with far more rows than these pays in
proportion (a row-blocked inner loop would bound it, at the price of a
kernel-shaped carry; not built).

This is a compiler-level fusion, not a Pallas kernel, on purpose: the
chunk matmul ``[chunk, D] · [D, V]`` is exactly MXU-shaped, and XLA already
fuses the elementwise softmax/log-sum-exp chain into its epilogue — a
hand-written kernel would re-derive what the scan structure already
guarantees (the O(chunk·V) ceiling).

Surfaced on the LM family as ``transformer_lm(fused_ce=True)`` (see
``models/lm.py``) via the ``ModelSpec.fused_losses`` seam — consumed by
the six collective/PS trainers, ``MeshTrainer(strategy="spmd")`` (any
``parameter_sharding``), and the ``validation_data`` evaluator. The
pipeline/sequence/expert strategy engines rebuild their forwards
mesh-specialized and train unfused (``MeshTrainer`` warns). The reference has no analogue (its largest head was an IMDB LSTM
classifier, SURVEY.md §5.7); this exists so the rebuild's beyond-parity LM
family trains at real vocab sizes on one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _chunk_rows(n: int, chunk: int) -> tuple[int, int]:
    """Number of scan steps and padded row count."""
    steps = max(1, _cdiv(n, chunk))
    return steps, steps * chunk


#: Least width (columns) the backward aims at for a vocabulary tile. A tile
#: moves the ``[N, D]`` float32 carry through HBM once each way, ``8 N D``
#: bytes, for a ``d_hidden`` product of ``2 N D Vb`` FLOPs: the two take
#: the same time at ``Vb = 4 x`` the chip's FLOPs a byte, which is
#: ``4 x 197e12 / 819e9 = 962`` columns on a TPU v5e for any ``N`` and
#: ``D``. 4096, about four times that, leaves the carry's traffic under a
#: quarter of the product's time, and is the width measured at 91-97 % of peak
#: (:func:`_vocab_tiles` has the sweep). A multiple of 128 lanes.
_BWD_TILE_COLUMNS = 4096


def _vocab_tiles(n: int, v: int, chunk: int) -> tuple[int, int]:
    """Number of backward tiles and their width ``Vb`` (module docstring).

    The width aimed at is the larger of the forward's budget,
    ``128 · ⌈⌈V / steps⌉ / 128⌉`` with ``steps = ⌈N / chunk⌉``, and
    :data:`_BWD_TILE_COLUMNS`; ``tiles = ⌈V / width⌉`` and the tiles are
    evened, ``Vb = 128 · ⌈⌈V / tiles⌉ / 128⌉``, so that fewer than 128
    columns a tile are padding and ``Vb`` is over half the width aimed at.
    One tile of width ``V`` when the width aimed at is ``V`` or more.

    v5e, PR 30: the loss's ``value_and_grad`` alone (bf16, chunk 256) with
    the width forced, ms a call by operation from a profiler trace: the
    product added into the carry / logits to ``dlogits`` / ``d_kernel`` /
    the whole call. 32768 x 2048 x 32784 (ZAYA1's cut, where the budget
    alone gave the first)::

        86 x   384   70.19  24.10  24.48   150.5
        33 x  1024   27.67  24.53  25.83   109.7
        17 x  2048   28.11  25.25  27.36   112.4
        12 x  2816   24.53  27.56  27.27   111.0
         9 x  3712   24.66  24.92  25.57   106.8   <- the rule
         8 x  4224   30.38  25.82  27.25   115.1
         5 x  6656   27.04  24.09  25.54   108.3
         3 x 10944   28.41  23.81  23.65   107.1

    16384 x 1024 x 256008 (XGLM)::

        63 x  4096   47.56  45.65  47.45   249.1   <- the rule, and before
        32 x  8064   46.58  46.53  45.87   243.4

    A product is 4.43-4.48 TFLOP at the first shape (22.5 ms at the 197
    TFLOP/s peak) and 8.66 at the second (44.0 ms); the forward's 28.58 and
    80.7 ms are in the whole call. 384 columns pay for the carry's traffic
    (46 GB a call); from 1,024 up the three products lie within a few ms
    of one another and of the peak, and which width is quickest follows how
    the compiler tiles each product more than the carry: the rule's
    9 x 3712 read the least of the eight, the next wider the most. The
    ``exp``, one-hot and weights stay in the logits product's fusion, whose
    output is the bf16 ``dlogits`` tile (the compiled step's text at 3,712;
    that operation's time at every width). XGLM's 8,064 read 2.3 % under
    its 4,096, 3.9 ms of it in the forward, which this rule does not touch:
    not taken, that cell is the control.
    """
    steps, _ = _chunk_rows(n, chunk)
    width = max(128 * _cdiv(_cdiv(v, steps), 128), _BWD_TILE_COLUMNS)
    if width >= v:
        return 1, v
    tiles = _cdiv(v, width)
    return tiles, 128 * _cdiv(_cdiv(v, tiles), 128)


def _pad_to(x, size, axis=0):
    n = x.shape[axis]
    if n == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - n)
    return jnp.pad(x, pad)


def _logits(h, kernel, bias):
    """``h @ kernel (+ bias)`` in f32: ``[rows, D] @ [D, cols]``.

    The matmul runs in the params' dtype (bf16 on TPU → MXU) with f32
    accumulation; the softmax math downstream is all f32.
    """
    logits = jnp.dot(h, kernel, preferred_element_type=jnp.float32)
    logits = logits.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    return logits


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused_ce(hidden, kernel, bias, labels, mask, chunk):
    loss, _ = _fused_ce_fwd(hidden, kernel, bias, labels, mask, chunk)
    return loss


def _fused_ce_fwd(hidden, kernel, bias, labels, mask, chunk):
    n = hidden.shape[0]
    steps, rows = _chunk_rows(n, chunk)
    h = _pad_to(hidden, rows).reshape(steps, chunk, hidden.shape[1])
    lab = _pad_to(labels, rows).reshape(steps, chunk)
    m = _pad_to(mask, rows).reshape(steps, chunk)

    def body(total, args):
        with jax.named_scope("fused_ce_fwd"):
            h_c, lab_c, m_c = args
            logits = _logits(h_c, kernel, bias)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, lab_c[:, None], axis=-1)[:, 0]
            nll = lse - picked
            return total + jnp.sum(nll * m_c), (lse, nll)

    total, (lse, nll) = jax.lax.scan(
        body, jnp.zeros((), jnp.float32), (h, lab, m))
    msum = jnp.sum(mask)
    denom = jnp.maximum(msum, 1.0)
    res = (hidden, kernel, bias, labels, mask, total, msum,
           lse.reshape(rows)[:n], nll.reshape(rows)[:n])
    return total / denom, res


def _fused_ce_bwd(chunk, res, g):
    hidden, kernel, bias, labels, mask, total, msum, lse, nll = res
    n, d = hidden.shape
    v = kernel.shape[1]
    tiles, vb = _vocab_tiles(n, v, chunk)
    denom = jnp.maximum(msum, 1.0)
    weight = mask * (g / denom)
    padded = tiles * vb > v
    # [D, V] → [tiles, D, Vb]; zero columns past V, masked out of p below
    k = _pad_to(kernel, tiles * vb, axis=1).reshape(d, tiles, vb)
    xs = (
        jnp.arange(tiles, dtype=jnp.int32) * vb,
        k.transpose(1, 0, 2),
        None if bias is None else _pad_to(bias, tiles * vb).reshape(tiles, vb),
    )

    def body(dh, args):
        with jax.named_scope("fused_ce_bwd"):
            first, k_b, bias_b = args
            col = first + jnp.arange(vb, dtype=jnp.int32)
            logits = _logits(hidden, k_b, bias_b)
            p = jnp.exp(logits - lse[:, None])
            if padded:
                p = jnp.where(col < v, p, 0.0)
            onehot = (labels[:, None] == col).astype(p.dtype)
            dlogits = (p - onehot) * weight[:, None]
            # both products in the hidden dtype (bf16 → MXU), f32 accumulation
            dl = dlogits.astype(hidden.dtype)
            dh = dh + jnp.dot(dl, k_b.T, preferred_element_type=jnp.float32)
            dk_b = jnp.dot(
                hidden.T, dl, preferred_element_type=jnp.float32,
            ).astype(kernel.dtype)
            # no [Vb] reduction for bias-free heads
            db_b = (None if bias is None
                    else jnp.sum(dlogits, axis=0).astype(bias.dtype))
            return dh, (dk_b, db_b)

    dh, (dk, db) = jax.lax.scan(body, jnp.zeros((n, d), jnp.float32), xs)
    dk = dk.transpose(1, 0, 2).reshape(d, tiles * vb)[:, :v]
    dbias = None if bias is None else db.reshape(tiles * vb)[:v]
    # loss = T/D with T = Σ nll_i·m_i, D = max(Σm, 1):
    # ∂loss/∂m_i = nll_i/D − T·[Σm > 1]/D² — the same weights a caller
    # differentiating the unfused masked mean would get
    ddenom = jnp.where(msum > 1.0, 1.0, 0.0)
    dmask = g * (nll / denom - total * ddenom / denom**2)
    return (
        dh.astype(hidden.dtype),
        dk,
        dbias,
        np.zeros(labels.shape, dtype=jax.dtypes.float0),
        dmask.astype(mask.dtype),
    )


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def chunked_softmax_cross_entropy(hidden, labels, kernel, bias=None, *,
                                  mask=None, chunk: int = 256,
                                  denominator=None):
    """Mean sparse softmax cross-entropy of ``hidden @ kernel (+ bias)``
    against integer ``labels``, computed ``chunk`` rows at a time.

    Equivalent to ``sparse_softmax_cross_entropy(labels, logits)`` (or its
    masked form when ``mask`` is given) with the logits accumulated in f32 —
    but the full ``[N, V]`` logits tensor is never materialized in either
    the forward or the backward pass (see module docstring).

    Args:
      hidden: ``[N, D]`` final hidden states (callers flatten ``[B, L, D]``).
      labels: ``[N]`` integer class ids.
      kernel: ``[D, V]`` head weight (any float dtype; bf16 hits the MXU).
      bias: optional ``[V]`` head bias.
      mask: optional ``[N]`` validity weights; loss is
        ``sum(nll · mask) / max(sum(mask), 1)``. Default: all rows valid.
      chunk: rows per scan step — peak logits memory is ``chunk × V`` f32
        (the backward's ``[N, Vb]`` vocabulary tile is cut from the same
        budget where that is wider than what hides the carry's traffic:
        module docstring).
      denominator: what the weighted sum is divided by in place of
        ``max(sum(mask), 1)``: a loss whose weights are no 0/1 validity and
        whose normaliser is its own (block diffusion's ``1 / t`` at masked
        positions, over every position). A scalar; the loss is
        ``sum(nll · mask) / denominator``, rescaled from the masked mean by
        two scalars, with no second pass over the logits.
    """
    hidden = jnp.asarray(hidden)
    if hidden.ndim != 2:
        raise ValueError(f"hidden must be [rows, dim], got {hidden.shape}")
    labels = jnp.asarray(labels, jnp.int32).reshape(hidden.shape[0])
    if mask is None:
        mask = jnp.ones((hidden.shape[0],), jnp.float32)
    else:
        mask = jnp.asarray(mask, jnp.float32).reshape(hidden.shape[0])
    if int(chunk) < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    loss = _fused_ce(hidden, kernel, bias, labels, mask, int(chunk))
    if denominator is None:
        return loss
    return loss * (jnp.maximum(jnp.sum(mask), 1.0) / denominator)
