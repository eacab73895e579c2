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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: tokens a chunk: the default of :func:`gated_delta` (an implementation's
#: choice, not a size of the model)
CHUNK = 64

_F32 = jnp.float32


def _mm(a, b, dtype):
    """``a @ b`` on operands of ``dtype``, summed in float32."""
    return jnp.matmul(a.astype(dtype), b.astype(dtype), preferred_element_type=_F32)


def _t(a):
    return jnp.swapaxes(a, -1, -2)


def _decays(gam):
    """``D [..., C, C]``: ``exp(Γ_r - Γ_s)`` where ``s <= r``, else 0; the
    difference is masked BEFORE ``exp``, so nothing overflows."""
    C = gam.shape[-1]
    seen = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    diff = jnp.where(seen, gam[..., :, None] - gam[..., None, :], 0.0)
    return jnp.where(seen, jnp.exp(diff), 0.0)


def unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular float32 ``a [..., C, C]``,
    ``C`` a power of two, by doubling the diagonal blocks it inverts: with
    ``T_m`` the inverse of ``I + a`` cut to its diagonal blocks of ``m``, and
    ``X_m`` the part of ``a`` that joins two such blocks into one of ``2 m``
    (``(I + T_m X_m)^-1 = I - T_m X_m``, as ``X_m T_m X_m = 0``): ``T_2m = T_m -
    T_m X_m T_m``, from ``T_1 = I``. Every product is ``[C, C]`` (no padded
    small blocks), at 3-pass bf16 precision."""
    C = a.shape[-1]
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGH)
    r = jnp.arange(C)
    t = jnp.broadcast_to(jnp.eye(C, dtype=a.dtype), a.shape)
    m = 1
    while m < C:
        joins = (r[:, None] // (2 * m) == r[None, :] // (2 * m)) \
            & (r[:, None] // m != r[None, :] // m)
        x = jnp.where(joins, a, 0.0)
        t = t - (x if m == 1 else mm(t, mm(x, t)))
        m *= 2
    return t


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
    decay = _decays(gam)
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


def gated_delta(q, k, v, g, beta, chunk: int = CHUNK):
    """The gated delta rule over a sequence, chunk by chunk.

    ``q``, ``k`` ``[B, T, Hk, dk]``; ``v`` ``[B, T, Hv, dv]``; ``g`` (log-decay,
    ``<= 0``) and ``beta`` ``[B, T, Hv]``, float32. Value head ``j`` reads key
    head ``j // (Hv // Hk)``. Products take their operands in ``v``'s dtype.
    Returns ``o [B, T, Hv, dv]`` in ``v``'s dtype. ``T`` need not be a multiple
    of ``chunk`` (a power of two): the tail is padded with tokens that neither
    decay nor write. The state starts at 0 in every row."""
    with jax.named_scope("gated_delta"):
        B, T = q.shape[:2]
        Hv, dv = v.shape[2:]
        pad = -T % chunk
        N = (T + pad) // chunk

        def chunks(a, dtype):             # [B, T, H, ...] -> [N, B, H, C, ...]
            a = jnp.pad(a.astype(dtype), [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            a = a.reshape((B, N, chunk) + a.shape[2:])
            return jnp.moveaxis(a, (1, 3), (0, 2))

        o = _chunked(chunks(q, v.dtype), chunks(k, v.dtype), chunks(v, v.dtype),
                     chunks(g, _F32), chunks(beta, _F32))
        o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, N * chunk, Hv, dv)[:, :T]
        return o.astype(v.dtype)


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
