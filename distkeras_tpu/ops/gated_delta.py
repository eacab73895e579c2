"""The gated delta rule, Gated DeltaNet's recurrence (Yang, Kautz and
Hatamizadeh 2024), in its chunked form.

Per value head, with a float32 state ``S [dk, dv]`` that starts at 0, for each
token ``t``::

    S̄ = exp(g_t) S_{t-1}
    S_t = S̄ + beta_t k_t (v_t - S̄ᵀ k_t)ᵀ
    o_t = S_tᵀ q_t

``g_t <= 0`` is the token's log-decay and ``beta_t`` its write strength. The
per-token form (:func:`gated_delta_tokens`) runs ``T`` dependent steps on
``[dk, dv]`` matrices; the chunked form (:func:`gated_delta`) turns the work
inside a chunk of ``C`` tokens into matrix products of all chunks at once and
leaves only the state's hand-over from chunk to chunk to a scan. Inside a
chunk, index ``r``, let ``Γ_r = g_1 + ... + g_r`` and ``D[r, s] = exp(Γ_r -
Γ_s)`` for ``s <= r`` (:func:`_prepare`):

- ``A = strict_lower(diag(beta) K Kᵀ ⊙ D)``, ``T = (I + A)^-1``;
- ``W = T diag(beta exp(Γ)) K``, ``U = T diag(beta) V``;
- then chunk by chunk: ``V' = U - W S``; ``O = diag(exp(Γ)) Q S + ((Q Kᵀ) ⊙
  D) V'``; ``S <- exp(Γ_C) S + (diag(exp(Γ_C - Γ)) K)ᵀ V'``.

Every decay is ``exp`` of a masked DIFFERENCE ``Γ_r - Γ_s`` with ``s <= r``
(or of ``Γ`` itself, which is at most 0), never ``exp(-Γ)``: over a chunk
``Γ`` passes -88, where ``exp(-Γ)`` overflows float32. The inverse of the
unit lower-triangular ``I + A`` is built from its diagonal blocks upwards
(blocks of 1, 2, 4, ... tokens, :func:`unit_lower_inverse`), a few batched
products in place of ``C`` dependent steps.

A product's operands are in the inputs' dtype (bf16 in a bf16 model: what the
published kernels do) and its sums float32; the state, the decays and the
inverse are float32. The gradient is written out (a ``custom_vjp``): the
forward keeps its inputs and the state at the start of every chunk, and the
backward runs the chunks in reverse, each one's products made again from its
own inputs, so that no array of all chunks' intermediate products is alive
in the backward.

Which form runs where (:func:`gated_delta_impl`, ``ops.kernel_impl(
"gated_delta", …)``): on a TPU, where the key and value heads are multiples
of 128 lanes, the forward is one Pallas kernel, ``gated_delta_fwd``, that
walks each row's chunks in order with every chunk's products, its inverse and
the state in VMEM and writes only ``o`` and the state at each chunk's start;
elsewhere (other widths, the CPU) the forward is XLA's, every chunk's
products at once (:func:`_prepare`) and a scan over chunks
(:func:`_scan_chunks`). The arithmetic is the same in both; the written-out
backward is XLA's in both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: tokens a chunk: the default of :func:`gated_delta` (an implementation's
#: choice, not a size of the model)
CHUNK = 64

_F32 = jnp.float32


def _mm(a, b, dtype):
    """``a @ b`` on operands of ``dtype``, summed in float32."""
    return jnp.matmul(a.astype(dtype), b.astype(dtype), preferred_element_type=_F32)


def _t(a):
    return jnp.swapaxes(a, -1, -2)


def _iotas(C: int):
    """Row and column index of a ``[C, C]`` matrix (2-D: what a kernel can
    make)."""
    return (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0),
            jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _decays(col, row, seen=None):
    """``D [..., C, C]``: ``exp(Γ_r - Γ_s)`` where ``seen`` (by default ``s <=
    r``), else 0, of ``Γ`` as a column ``col [..., C, 1]`` and a row ``row
    [..., 1, C]``; the difference is masked BEFORE ``exp``, so nothing
    overflows."""
    if seen is None:
        r, s = _iotas(col.shape[-2])
        seen = r >= s
    return jnp.where(seen, jnp.exp(jnp.where(seen, col - row, 0.0)), 0.0)


def unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular float32 ``a [..., C, C]``,
    ``C`` a power of two, by doubling the diagonal blocks it inverts: with
    ``T_m`` the inverse of ``I + a`` cut to its diagonal blocks of ``m``, and
    ``X_m`` the part of ``a`` that joins two such blocks into one of ``2 m``
    (``(I + T_m X_m)^-1 = I - T_m X_m``, as ``X_m T_m X_m = 0``): ``T_2m = T_m -
    T_m X_m T_m``, from ``T_1 = I``. Every product is ``[C, C]`` (no padded
    small blocks), at 3-pass bf16 precision. Row ``r`` and column ``s`` lie
    in one block of ``2 m`` and in two of ``m`` exactly when ``m <= r ^ s < 2
    m``."""
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGH)
    return _inverses([a], mm, a.shape[-1])[0]


def _inverses(arrays, mm, block: int):
    """:func:`unit_lower_inverse` of each of ``arrays`` (``[..., W, W]``, all
    of one shape) cut to its diagonal blocks of ``block``, a power of two that
    divides ``W``: the arrays round by round in step, so that one array's
    products stand beside the others' for the scheduler to overlap. Where a
    block is less than ``W``, the blocks are laid side by side, ``[...,
    block, W]``, and each product's right operand block-diagonal again: the
    blocks' products come out side by side from ``block`` of the MXU's rows
    rather than ``W``. Returns the inverses ``[..., W, W]``, 0 across
    blocks."""
    W = arrays[0].shape[-1]
    per = W // block
    row = jax.lax.broadcasted_iota(jnp.int32, (block, W), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block, W), 1)
    x = row ^ (lane % block)
    if per == 1:
        diag, sides = (lambda c: c), list(arrays)
    else:
        blocks = [lane // block == p for p in range(per)]

        def diag(c):                      # side by side -> block-diagonal
            return jnp.concatenate([jnp.where(b, c, 0.0) for b in blocks], axis=-2)

        sides = [sum(a[..., p * block:(p + 1) * block, :] for p in range(per))
                 for a in arrays]
    ts = [jnp.where(x == 0, 1.0, 0.0) - jnp.where(x == 1, c, 0.0) for c in sides]
    m = 2
    while m < block:
        band = (x >= m) & (x < 2 * m)
        ys = [mm(jnp.where(band, c, 0.0), diag(t)) for c, t in zip(sides, ts)]
        ts = [t - mm(t, diag(y)) for t, y in zip(ts, ys)]
        m *= 2
    return [diag(t) for t in ts]


def _prepare(q, k, v, g, beta):
    """What a chunk's state hand-over needs, for chunks ``[..., C, d]``
    (``g``, ``beta`` ``[..., C]``): ``(W, U, P, Qg, Kg, last)`` with ``P =
    (Q Kᵀ) ⊙ D``, ``Qg = diag(exp(Γ)) Q``, ``Kg = diag(exp(Γ_C - Γ)) K``,
    ``last = exp(Γ_C)``. ``U`` and ``last`` are float32, the others in the
    inputs' dtype. ``q`` and ``k`` may hold fewer heads (axis -3) than ``v``:
    value head ``j`` reads key head ``j // (Hv // Hk)``."""
    cd = q.dtype
    q, k = (jnp.repeat(a, v.shape[-3] // a.shape[-3], axis=-3) for a in (q, k))
    gam = jnp.cumsum(g, axis=-1)
    decay = _decays(gam[..., :, None], gam[..., None, :])
    C = g.shape[-1]
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    a = jnp.where(strict, beta[..., :, None] * _mm(k, _t(k), cd) * decay, 0.0)
    t = unit_lower_inverse(a)
    w = _mm(t, (beta * jnp.exp(gam))[..., None] * k, cd)
    u = _mm(t, beta[..., None] * v, cd)
    p = _mm(q, _t(k), cd) * decay
    qg = jnp.exp(gam)[..., None] * q
    kg = jnp.exp(gam[..., -1:] - gam)[..., None] * k
    return (w.astype(cd), u, p.astype(cd), qg.astype(cd), kg.astype(cd),
            jnp.exp(gam[..., -1]))


def _scan_chunks(w, u, p, qg, kg, last):
    """The forward hand-over over axis 0 (chunks): ``(O, S at each chunk's
    start)``, both in the inputs' dtype (a product reads ``S`` so)."""
    cd = w.dtype

    def step(s, xs):
        w, u, p, qg, kg, last = xs
        vn = u - _mm(w, s, cd)
        o = _mm(qg, s, cd) + _mm(p, vn, cd)
        return last[..., None, None] * s + _mm(_t(kg), vn, cd), (o.astype(cd), s.astype(cd))

    shape = w.shape[1:-2] + (w.shape[-1], u.shape[-1])
    _, (o, starts) = jax.lax.scan(step, jnp.zeros(shape, _F32), (w, u, p, qg, kg, last))
    return o, starts


@jax.custom_vjp
def _chunked(q, k, v, g, beta):
    """The rule over chunks on axis 0: ``q``, ``k`` ``[N, B, Hk, C, dk]``, ``v``
    ``[N, B, Hv, C, dv]``, ``g``, ``beta`` ``[N, B, Hv, C]``; ``O`` in ``v``'s
    dtype."""
    return _scan_chunks(*_prepare(q, k, v, g, beta))[0]


def _chunked_fwd(q, k, v, g, beta):
    o, starts = _scan_chunks(*_prepare(q, k, v, g, beta))
    return o, (q, k, v, g, beta, starts)


def _chunked_bwd(res, do):
    """The chunks in reverse: each one's ``_prepare`` made again and pulled
    back, the state's gradient handed from chunk to chunk."""
    q, k, v, g, beta, starts = res
    cd = q.dtype

    def step(ds, xs):                    # ds: the gradient into this chunk's END state
        q, k, v, g, beta, s, do = xs
        (w, u, p, qg, kg, last), pull = jax.vjp(_prepare, q, k, v, g, beta)
        vn = u - _mm(w, s, cd)
        dvn = _mm(_t(p), do, cd) + _mm(kg, ds, cd)
        d_start = _mm(_t(qg), do, cd) + last[..., None, None] * ds - _mm(_t(w), dvn, cd)
        grads = pull((-_mm(dvn, _t(s), cd).astype(cd), dvn, _mm(do, _t(vn), cd).astype(cd),
                      _mm(do, _t(s), cd).astype(cd), _mm(vn, _t(ds), cd).astype(cd),
                      jnp.sum(s.astype(_F32) * ds, axis=(-2, -1))))
        return d_start, grads

    # under a scope of its own: its operations inherit the name of the pass
    # that ran the forward (remat's, in a rematted block), not the backward's
    with jax.named_scope("gated_delta_bwd"):
        _, grads = jax.lax.scan(step, jnp.zeros(starts.shape[1:], _F32),
                                (q, k, v, g, beta, starts, do), reverse=True)
    return grads


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def _chunks(a, chunk: int, dtype):
    """``[B, T, H, ...]`` to ``[N, B, H, C, ...]`` in ``dtype``, the tail padded
    with zeros: tokens that neither decay nor write."""
    B, T = a.shape[:2]
    pad = -T % chunk
    a = jnp.pad(a.astype(dtype), [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
    a = a.reshape((B, (T + pad) // chunk, chunk) + a.shape[2:])
    return jnp.moveaxis(a, (1, 3), (0, 2))


def _in_chunks(q, k, v, g, beta, chunk: int):
    """The rule's five inputs as :func:`_chunked` takes them."""
    return tuple(_chunks(a, chunk, dtype) for a, dtype in (
        (q, v.dtype), (k, v.dtype), (v, v.dtype), (g, _F32), (beta, _F32)))


# -- the forward as one Pallas kernel ----------------------------------------------
#
# The grid walks (batch row, group of key heads) and, in order, the sequence in
# steps of a few blocks of 128 tokens; a step takes its key heads' q and k
# columns and their value heads' v columns straight from the ``[B, T, H·d]``
# layout and writes ``o`` back into it, so q and k are read once for all of
# their value heads and nothing is laid out chunk-major on the way in or out.
# Each value head's float32 state lives in a VMEM scratch from the first step
# of a row to its last. The arithmetic is ``_prepare``'s and
# ``_scan_chunks``': the chunks of a block of 128 tokens (two of 64) are made
# together, as the diagonal blocks of ``[128, 128]`` arrays (the decays, ``A``,
# the inverse, ``P``) that are 0 across chunks, so that the products are as
# wide as the MXU. A step first makes every (block, value head)'s products,
# none of which reads the state, side by side; then hands each value head's
# state from chunk to chunk, the value heads side by side. A product's time
# on the MXU is mostly latency at these sizes, and independent products beside
# each other hide it. Nothing of a chunk is written to HBM but ``o`` and the
# state at the chunk's start (the backward's residual).

_LANES = 128
#: tokens a grid step walks (blocks of 128; at most all of the call) and key
#: heads it takes (at most; a divisor of the call's), with their value heads:
#: 2 x 4 x 2 (block, value head)s side by side at the linear-attention cell's
#: call, 5.05 ms a call on a v5e where 2 key heads took 5.51 and 8 4.79
_TOKENS_A_STEP, _KEY_HEADS_A_STEP = 256, 4
#: scoped VMEM a grid step may hold: its blocks twice over, each (block, value
#: head)'s products and what the compiler keeps of the values in between
_VMEM_LIMIT = 64 * 2 ** 20


def gated_delta_impl(impl: str = "auto", *, T: int, dk: int, dv: int, Hk: int,
                     Hv: int, dtype="bfloat16") -> str:
    """``"pallas"`` or ``"xla"``: how the forward of :func:`gated_delta` runs
    over ``T`` tokens of ``Hk`` key heads of ``dk`` and ``Hv`` value heads of
    ``dv`` in ``dtype`` (``ops.kernel_impl("gated_delta", …)`` is the public
    door; the backward is XLA either way). ``"xla"`` is returned as asked;
    ``"pallas"`` is the kernel where its tiles fit (``dk`` and ``dv`` multiples
    of 128 lanes, ``Hv`` a multiple of ``Hk``, bf16 or float32 operands; any
    ``T``: the tail is padded) and falls back to ``"xla"`` where they do not;
    ``"auto"`` is the kernel only when it also compiles natively."""
    if impl not in ("pallas", "xla", "auto"):
        raise ValueError(
            f"unknown gated_delta impl {impl!r}; use 'pallas', 'xla', or 'auto'"
        )
    fits = (T > 0 and dk % _LANES == 0 and dv % _LANES == 0 and Hk > 0
            and Hv % Hk == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)))
    if impl == "xla" or not fits:
        return "xla"
    from distkeras_tpu import ops

    return "pallas" if impl == "pallas" or ops.native_kernels() else "xla"


def _mm_high(a, b):
    """``a @ b`` of float32 operands at ``Precision.HIGH``, written out as
    Mosaic has no such setting: each operand split into a bf16 part and the
    bf16 rounding of what that leaves, three one-pass products of the parts
    summed in float32 (the one of the two small parts dropped), as XLA's
    3-pass bf16 does it."""
    bf = jnp.bfloat16
    a_hi, b_hi = a.astype(bf), b.astype(bf)
    a_lo, b_lo = (a - a_hi.astype(_F32)).astype(bf), (b - b_hi.astype(_F32)).astype(bf)
    dot = functools.partial(jnp.dot, preferred_element_type=_F32)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def _dot_tn(a, b):
    """``aᵀ b`` summed in float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=_F32)


def _dot_nt(a, b):
    """``a bᵀ`` summed in float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=_F32)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s0_ref, state, w_s, u_s, qg_s,
                kg_s, p_s, *, chunk):
    """One (batch row, key heads, step of the sequence) grid step: ``q_ref``,
    ``k_ref [1, L, hk·dk]``, ``v_ref`` and ``o_ref [1, L, hk·G·dv]`` (each key
    head's ``G`` value heads side by side), ``g_ref``, ``b_ref [1, hk, G, L]``,
    ``s0_ref [L / chunk, 1, hk·G, dk, dv]``; scratch: ``state [hk·G, dk, dv]``
    float32 and, for each (block of ``W`` tokens, value head) of the step, its
    ``W``, ``U``, ``Qg``, ``Kg`` and ``P``.

    First every block's products for every value head, each independent of
    the others and of the state (the inverses round by round in step), then
    the state handed from chunk to chunk, the value heads side by side."""
    _, hk, G, L = g_ref.shape
    H, dk, dv = state.shape
    W = p_s.shape[-1]
    nb, per = L // W, W // chunk
    cd = v_ref.dtype
    dot = functools.partial(jnp.dot, preferred_element_type=_F32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, _F32)

    r, s = _iotas(W)
    seen = (r // chunk == s // chunk) & (s <= r)      # s up to r in r's chunk
    diagonal = r == s
    ends = s == r // chunk * chunk + chunk - 1        # the last token of r's chunk
    made = []                                         # per (block, value head)
    for blk in range(nb):
        toks = slice(blk * W, (blk + 1) * W)
        for h in range(hk):
            q, k = q_ref[0, toks, h * dk:(h + 1) * dk], k_ref[0, toks, h * dk:(h + 1) * dk]
            kk, qk = _dot_nt(k, k), _dot_nt(q, k)
            for j in range(G):
                gam = jnp.sum(jnp.where(seen, g_ref[0, h, j:j + 1, toks], 0.0),
                              axis=1, keepdims=True)                      # Γ [W, 1]
                # a column made a row and back through the diagonal: exact
                gam_row = jnp.sum(jnp.where(diagonal, gam, 0.0), axis=0, keepdims=True)
                gam_end = jnp.sum(jnp.where(ends, gam_row, 0.0), axis=1, keepdims=True)
                beta = jnp.sum(jnp.where(diagonal, b_ref[0, h, j:j + 1, toks], 0.0),
                               axis=1, keepdims=True)
                decay = _decays(gam, gam_row, seen)
                made.append(dict(toks=toks, q=q, k=k, qk=qk, gam=gam, gam_end=gam_end,
                                 beta=beta, decay=decay, v=(h * G + j) * dv,
                                 a=jnp.where(seen & ~diagonal, beta * kk * decay, 0.0)))
    ts = _inverses([m["a"] for m in made], _mm_high, chunk)
    for i, (m, t) in enumerate(zip(made, ts)):
        t, gam, beta = t.astype(cd), m["gam"], m["beta"]
        w_s[i] = dot(t, (beta * jnp.exp(gam) * m["k"]).astype(cd)).astype(cd)
        u_s[i] = dot(t, (beta * v_ref[0, m["toks"], m["v"]:m["v"] + dv]).astype(cd))
        qg_s[i] = (jnp.exp(gam) * m["q"]).astype(cd)
        kg_s[i] = (jnp.exp(m["gam_end"] - gam) * m["k"]).astype(cd)
        p_s[i] = (m["qk"] * m["decay"]).astype(cd)
        m["last"] = jnp.exp(m["gam_end"])

    held = [state[i] for i in range(H)]
    for blk in range(nb):
        toks = slice(blk * W, (blk + 1) * W)
        inter, vns = [[] for _ in range(H)], [[] for _ in range(H)]
        for c in range(per):
            rows = slice(c * chunk, (c + 1) * chunk)
            for i in range(H):
                n = blk * H + i
                start = held[i].astype(cd)
                s0_ref[blk * per + c, 0, i] = start
                vn = (u_s[n, rows] - dot(w_s[n, rows], start)).astype(cd)
                inter[i].append(dot(qg_s[n, rows], start))
                vns[i].append(vn)
                held[i] = (made[n]["last"][c * chunk:c * chunk + 1] * held[i]
                           + _dot_tn(kg_s[n, rows], vn))
        for i in range(H):
            # every chunk's ((Q Kᵀ) ⊙ D) V' at once: D is 0 across chunks
            o = (jnp.concatenate(inter[i], axis=0)
                 + dot(p_s[blk * H + i], jnp.concatenate(vns[i], axis=0)))
            o_ref[0, toks, i * dv:(i + 1) * dv] = o.astype(o_ref.dtype)
    for i in range(H):
        state[i] = held[i]


def _plan(T: int, chunk: int, Hk: int, tokens: int, key_heads: int):
    """``(tokens a block, tokens a grid step, tokens of the padded call, key
    heads a grid step)``."""
    W = max(chunk, _LANES)
    L = min(-(-T // W), max(tokens // W, 1)) * W
    hk = max(d for d in range(1, key_heads + 1) if Hk % d == 0)
    return W, L, -(-T // L) * L, hk


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "tokens", "key_heads"))
def _kernel_fwd(q, k, v, g, beta, *, chunk, interpret, tokens=_TOKENS_A_STEP,
                key_heads=_KEY_HEADS_A_STEP):
    """``(o [B, T, Hv, dv], S at each chunk's start [N, B, Hv, dk, dv])``, both
    in ``v``'s dtype: what ``_scan_chunks(*_prepare(...))`` gives, from the
    inputs as :func:`gated_delta` takes them; ``tokens`` and ``key_heads`` a
    grid step (:func:`_plan`) change how the work is cut, not the result."""
    B, T, Hk, dk = q.shape
    Hv, dv = v.shape[2:]
    G, cd = Hv // Hk, v.dtype
    W, L, Tp, hk = _plan(T, chunk, Hk, tokens, key_heads)

    def flat(a):                           # [B, Tp, H·d] in the products' dtype
        a = jnp.pad(a.astype(cd), [(0, 0), (0, Tp - T), (0, 0), (0, 0)])
        return a.reshape(B, Tp, -1)

    def heads(a):                          # [B, Hk, G, Tp] float32
        a = jnp.pad(a.astype(_F32), [(0, 0), (0, Tp - T), (0, 0)])
        return jnp.moveaxis(a, 1, 2).reshape(B, Hk, G, Tp)

    qk = pl.BlockSpec((1, L, hk * dk), lambda b, h, t: (b, t, h))
    vo = pl.BlockSpec((1, L, hk * G * dv), lambda b, h, t: (b, t, h))
    gb = pl.BlockSpec((1, hk, G, L), lambda b, h, t: (b, h, 0, t))
    made = L // W * hk * G
    o, starts = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(B, Hk // hk, Tp // L),
        in_specs=[qk, qk, vo, gb, gb],
        out_specs=[vo, pl.BlockSpec((L // chunk, 1, hk * G, dk, dv),
                                    lambda b, h, t: (t, b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, Hv * dv), cd),
                   jax.ShapeDtypeStruct((Tp // chunk, B, Hv, dk, dv), cd)],
        scratch_shapes=[pltpu.VMEM((hk * G, dk, dv), _F32),
                        pltpu.VMEM((made, W, dk), cd), pltpu.VMEM((made, W, dv), _F32),
                        pltpu.VMEM((made, W, dk), cd), pltpu.VMEM((made, W, dk), cd),
                        pltpu.VMEM((made, W, W), cd)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gated_delta_fwd",
    )(flat(q), flat(k), flat(v), heads(g), heads(beta))
    return o.reshape(B, Tp, Hv, dv)[:, :T], starts[:-(-T // chunk)]


def _on_each_device(q, k, v, g, beta, chunk, interpret):
    # independent across the batch, every input and ``o`` batch-major in dim
    # 0 and the starts in dim 1: under ``ops.kernel_mesh`` each device runs
    # its own rows
    from distkeras_tpu import ops

    return ops.on_each_device(
        functools.partial(_kernel_fwd, chunk=chunk, interpret=interpret),
        q, k, v, g, beta, out_dims=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel_rule(q, k, v, g, beta, chunk, interpret):
    return _on_each_device(q, k, v, g, beta, chunk, interpret)[0]


def _kernel_rule_fwd(q, k, v, g, beta, chunk, interpret):
    o, starts = _on_each_device(q, k, v, g, beta, chunk, interpret)
    return o, (q, k, v, g, beta, starts)


def _kernel_rule_bwd(chunk, interpret, res, do):
    """:func:`_chunked_bwd` on the chunk-major inputs it reads, made here
    from the residuals, and its gradients laid back out."""
    q, k, v, g, beta, starts = res
    with jax.named_scope("gated_delta_bwd"):
        xs, back = jax.vjp(functools.partial(_in_chunks, chunk=chunk), q, k, v, g, beta)
        return back(_chunked_bwd(xs + (starts,), _chunks(do, chunk, v.dtype)))


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


def gated_delta(q, k, v, g, beta, chunk: int = CHUNK, impl: str = "auto",
                interpret: bool | None = None):
    """The gated delta rule over a sequence, chunk by chunk.

    ``q``, ``k`` ``[B, T, Hk, dk]``; ``v`` ``[B, T, Hv, dv]``; ``g`` (log-decay,
    ``<= 0``) and ``beta`` ``[B, T, Hv]``, float32. Value head ``j`` reads key
    head ``j // (Hv // Hk)``. Products take their operands in ``v``'s dtype.
    Returns ``o [B, T, Hv, dv]`` in ``v``'s dtype. ``T`` need not be a multiple
    of ``chunk`` (a power of two): the tail is padded with tokens that neither
    decay nor write. The state starts at 0 in every row. The forward is the
    ``gated_delta_fwd`` kernel or XLA's as :func:`gated_delta_impl` says for
    ``impl``, the backward XLA's."""
    B, T, Hk, dk = q.shape
    Hv, dv = v.shape[2:]
    use = gated_delta_impl(impl, T=T, dk=dk, dv=dv, Hk=Hk, Hv=Hv, dtype=v.dtype)
    with jax.named_scope("gated_delta"):
        if use == "pallas":
            from distkeras_tpu import ops

            return gated_delta_kernel(q, k, v, g, beta, chunk, ops.interpreted(interpret))
        o = _chunked(*_in_chunks(q, k, v, g, beta, chunk))
        o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, -1, Hv, dv)[:, :T]
        return o.astype(v.dtype)


def gated_delta_kernel(q, k, v, g, beta, chunk: int = CHUNK, interpret: bool | None = None):
    """:func:`gated_delta` with its forward in the ``gated_delta_fwd`` kernel
    whatever the shape (in the interpreter any shape runs; natively the
    shapes :func:`gated_delta_impl` admits)."""
    from distkeras_tpu import ops

    return _kernel_rule(q, k, v, g, beta, chunk, ops.interpreted(interpret))


def gated_delta_tokens(q, k, v, g, beta):
    """The same rule token by token (a scan over ``T``), in float32: what the
    chunked form is tested against, and what ``chip_smoke.py`` measures its
    error by. Shapes as :func:`gated_delta`'s."""
    Hk, Hv = q.shape[2], v.shape[2]
    q, k = (jnp.repeat(a.astype(_F32), Hv // Hk, axis=2) for a in (q, k))
    hi = jax.lax.Precision.HIGHEST

    def token(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None, None] * s
        e = vt - jnp.einsum("...de,...d->...e", s, kt, precision=hi)
        s = s + jnp.einsum("...d,...e->...de", bt[..., None] * kt, e, precision=hi)
        return s, jnp.einsum("...de,...d->...e", s, qt, precision=hi)

    B, _, _, dk = q.shape
    xs = tuple(jnp.moveaxis(a.astype(_F32), 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((B, Hv, dk, v.shape[-1]), _F32), xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)
