"""From latent attention's two projections to the flash kernels' operands in
one pass.

A latent-attention sublayer (:class:`distkeras_tpu.models.lm.LatentAttention`)
has, between its projections and ``flash_fwd``: ``q [B, S, H·(dn + dr)]`` as
``Wq`` left it, ``kv [B, S, H·(dn + dv)]`` as ``Wkvb`` left it (a head's key
part with no position, then its value) and ONE rotary key a token for all
heads, ``k_rope [B, S, dr]``. The kernels want ``q`` and ``k`` ``[B·H, S,
dn + dr]`` and ``v [B·H, S, dv]``, head-major, the last ``dr`` columns of
every q head and the shared key rotated over pairs ``(2i, 2i+1)``. As ``jnp``
operations (``models.lm.latent_qkv``) that is a slice, a cast to float32, two
strided slices, a ``stack``, a cast back and a concatenation for q, the
shared key broadcast to ``H`` heads and concatenated for k, and the launcher's
three moves into the head-major layout; a 64-column piece of a 192-wide head
is half a lane tile at an offset that is a tile boundary for every other head
only, so each piece is padded to 128 lanes and merged, forward and backward.

:func:`mla_prep` is that chain as Pallas kernels (``mla_prep_fwd`` in a
device trace: one call for q, one for k and v; ``mla_prep_bwd`` likewise):
float32 only in registers, the arithmetic of ``apply_rope`` in its order, one
rounding at the end; the shared key is rotated once a grid step and stored
beside every head of the step, never broadcast in HBM, and its gradient is
the float32 sum over heads rotated back. The map is linear: the backward's
only residuals are the tables.

Two heads of ``dn + 64`` columns are whole lane tiles, so the kernels walk
q's flat layout in pairs of heads: the rotation runs on whole 128-lane tiles
under a table laid over the pair (``cos`` 1 and ``sin`` 0 on the columns
with no position: pairs never straddle a tile, and ``sin`` 0 kills what the
roll carries across), and the second head of a pair is moved 64 lanes by a
roll and a select. Every load and store starts on a tile boundary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu import ops
from distkeras_tpu.ops.qk_prep import (_LANES, _ROW_TILES, _even_lanes,
                                       _rotated, _row_tile)

#: the rotary columns of a head: half a lane tile
_ROPE = _LANES // 2
#: most heads of the same rows a grid step holds (always whole pairs)
_HEADS_A_TILE = 8


def _heads_a_tile(heads: int) -> int:
    return max(h for h in range(2, _HEADS_A_TILE + 1, 2) if heads % h == 0)


def mla_prep_impl(impl: str = "auto", *, S: int, nope: int, rope: int,
                  v: int, heads: int = 2) -> str:
    """``"pallas"`` or ``"xla"``: what a latent-attention sublayer runs
    between its projections and the flash kernels for ``S`` rows and ``heads``
    heads of ``nope + rope`` (q, k) and ``v`` columns
    (``ops.kernel_impl("mla_prep", …)`` is the public door). ``"xla"`` is
    returned as asked; ``"pallas"`` is the kernels where their tiles fit
    (``nope`` and ``v`` multiples of 128 lanes, ``rope`` 64, an even count of
    heads, ``S`` a multiple of a row tile) and falls back to ``"xla"`` where
    they do not; ``"auto"`` is the kernels only when they also compile
    natively."""
    if impl not in ("pallas", "xla", "auto"):
        raise ValueError(
            f"unknown mla_prep impl {impl!r}; use 'pallas', 'xla', or 'auto'"
        )
    fits = (nope > 0 and nope % _LANES == 0 and v > 0 and v % _LANES == 0
            and rope == _ROPE and heads % 2 == 0 and _row_tile(S) is not None)
    if impl == "xla" or not fits:
        return "xla"
    return "pallas" if impl == "pallas" or ops.native_kernels() else "xla"


def _low_lanes(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) < _ROPE


def _swapped(y):
    """The two halves of a lane tile exchanged."""
    return pltpu.roll(y, _ROPE, 1)


# q: heads a and b of a pair lie in 2·nope/128 + 1 lane tiles of the flat
# layout: a's tiles with no position; ONE tile of a's rotary columns and b's
# first 64; then b's columns from its 64th on, the last tile ending with its
# rotary columns. ``cos`` / ``sin [rows, 256]`` hold the two tiles' tables.


def _q_fwd_kernel(x_ref, cos_ref, sin_ref, o_ref, *, nope):
    """One (row tile, batch row, head group) step: ``x_ref [1, rows,
    hb·(nope + 64)]`` (the product as it left ``Wq``) to ``o_ref [hb, rows,
    nope + 64]``, a pair of heads at a time."""
    hb, rows, W = o_ref.shape
    f32, L, nt = jnp.float32, _LANES, nope // _LANES
    even, low = _even_lanes((rows, L)), _low_lanes((rows, L))

    def rotated(y, t):
        return _rotated(y, cos_ref[:, t * L:(t + 1) * L],
                        sin_ref[:, t * L:(t + 1) * L], even)

    for a in range(0, hb, 2):
        def tile(i, base=a * W):
            return x_ref[0, :, base + i * L:base + (i + 1) * L]

        for i in range(nt):
            o_ref[a, :, i * L:(i + 1) * L] = tile(i)
        joint = rotated(tile(nt).astype(f32), 0)
        o_ref[a, :, nope:] = joint[:, :_ROPE].astype(o_ref.dtype)
        before = _swapped(joint)
        for i in range(nt):
            y = tile(nt + 1 + i).astype(f32)
            after = _swapped(rotated(y, 1) if i == nt - 1 else y)
            o_ref[a + 1, :, i * L:(i + 1) * L] = jnp.where(
                low, before, after).astype(o_ref.dtype)
            before = after
        o_ref[a + 1, :, nope:] = before[:, :_ROPE].astype(o_ref.dtype)


def _q_bwd_kernel(g_ref, cos_ref, sin_ref, dx_ref, wide, *, nope):
    """The same step backward: the cotangent ``g_ref [hb, rows, nope + 64]``
    (as ``flash_dq`` wrote it) to ``dx_ref [1, rows, hb·(nope + 64)]``.
    ``wide [rows, 128]`` float32 is where a head's 64 rotary columns become a
    lane tile."""
    hb, rows, W = g_ref.shape
    f32, L, nt = jnp.float32, _LANES, nope // _LANES
    even, low = _even_lanes((rows, L)), _low_lanes((rows, L))

    def rotated(y, t):
        return _rotated(y, cos_ref[:, t * L:(t + 1) * L],
                        sin_ref[:, t * L:(t + 1) * L], even, transposed=True)

    def widened(h):
        wide[:, :_ROPE] = g_ref[h, :, nope:].astype(f32)
        return wide[...]

    wide[...] = jnp.zeros_like(wide)
    for a in range(0, hb, 2):
        base = a * W
        for i in range(nt):
            dx_ref[0, :, base + i * L:base + (i + 1) * L] = \
                g_ref[a, :, i * L:(i + 1) * L]
        after = _swapped(g_ref[a + 1, :, :L].astype(f32))
        joint = rotated(jnp.where(low, widened(a), after), 0)
        dx_ref[0, :, base + nope:base + nope + L] = joint.astype(dx_ref.dtype)
        for i in range(nt):
            before = after
            if i == nt - 1:
                after = _swapped(widened(a + 1))
            else:
                after = _swapped(
                    g_ref[a + 1, :, (i + 1) * L:(i + 2) * L].astype(f32))
            y = jnp.where(low, before, after)
            if i == nt - 1:
                y = rotated(y, 1)
            at = base + nope + (i + 1) * L
            dx_ref[0, :, at:at + L] = y.astype(dx_ref.dtype)


# k and v: every load is aligned (a head of ``kv`` is nope + v columns, both
# multiples of 128); only the shared key's 64 columns are half a tile.


def _kv_fwd_kernel(kv_ref, kr_ref, cos_ref, sin_ref, k_ref, v_ref, wide, *,
                   nope):
    """One step: ``kv_ref [1, rows, hb·(nope + dv)]`` and the shared rotary
    key ``kr_ref [1, rows, 64]`` to ``k_ref [hb, rows, nope + 64]`` and
    ``v_ref [hb, rows, dv]``; the key is rotated once and stored beside every
    head's part."""
    hb, rows, dv = v_ref.shape
    wide[...] = jnp.zeros_like(wide)
    wide[:, :_ROPE] = kr_ref[0].astype(jnp.float32)
    shared = _rotated(wide[...], cos_ref[...], sin_ref[...],
                      _even_lanes(wide.shape))[:, :_ROPE].astype(k_ref.dtype)
    for h in range(hb):
        base = h * (nope + dv)
        k_ref[h, :, :nope] = kv_ref[0, :, base:base + nope]
        k_ref[h, :, nope:] = shared
        v_ref[h] = kv_ref[0, :, base + nope:base + nope + dv]


def _kv_bwd_kernel(dk_ref, dv_ref, cos_ref, sin_ref, dkv_ref, dkr_ref, acc, *,
                   nope):
    """The same step backward: ``dk_ref`` and ``dv_ref`` (as ``flash_dkv``
    wrote them) to ``dkv_ref [1, rows, hb·(nope + dv)]``; the shared key's
    gradient is the float32 sum over ALL heads of dk's rotary columns (``acc``
    carries it over the head groups, the innermost grid axis) rotated by the
    opposite angle, written with the last group."""
    hb, rows, dv = dv_ref.shape
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    for h in range(hb):
        base = h * (nope + dv)
        dkv_ref[0, :, base:base + nope] = dk_ref[h, :, :nope]
        dkv_ref[0, :, base + nope:base + nope + dv] = dv_ref[h]
        acc[:, :_ROPE] += dk_ref[h, :, nope:].astype(jnp.float32)

    @pl.when(g == pl.num_programs(2) - 1)
    def _():
        back = _rotated(acc[...], cos_ref[...], sin_ref[...],
                        _even_lanes(acc.shape), transposed=True)
        dkr_ref[0] = back[:, :_ROPE].astype(dkr_ref.dtype)


def _plan(B, S, heads, flat_width, major_widths, table_width):
    """The grid all four kernels walk (row tiles outermost, so that the
    tables' tile is fetched once for every batch row and head; head groups
    innermost) and its specs: the flat block of ``flat_width`` columns a
    head, a head-major block for each of ``major_widths``, the tables', the
    shared rotary key's; and the float32 scratch in which 64 rotary columns
    become a lane tile."""
    rows, hb = _row_tile(S), _heads_a_tile(heads)
    groups = heads // hb
    grid = (S // rows, B, groups)
    flat = pl.BlockSpec((1, rows, hb * flat_width), lambda s, b, g: (b, s, g))
    major = [pl.BlockSpec((hb, rows, w),
                          lambda s, b, g: (b * groups + g, s, 0))
             for w in major_widths]
    table = pl.BlockSpec((rows, table_width), lambda s, b, g: (s, 0))
    shared = pl.BlockSpec((1, rows, _ROPE), lambda s, b, g: (b, s, 0))
    return (grid, flat, major, table, shared,
            pltpu.VMEM((rows, _LANES), jnp.float32))


def _tables(angles, units):
    """``cos`` and ``sin [S, 64·len(units)]`` of ``angles [S, 32]``, a pair's
    angle in both its lanes, over the 64-lane units that ``units`` marks; on
    the others the angle is 0: ``cos`` 1 and ``sin`` 0, a rotation that leaves
    a column as it is. (Tiled and masked, not concatenated: the compiler
    makes a pad and a merge of every concatenation.)"""
    if angles.shape[-1] * 2 != _ROPE:
        raise ValueError(f"angles {angles.shape} are not [S, {_ROPE // 2}]")
    angles = jax.lax.stop_gradient(jnp.asarray(angles, jnp.float32))
    laid = jnp.tile(jnp.repeat(angles, 2, axis=-1), (1, len(units)))
    laid = jnp.where(np.repeat(np.asarray(units, bool), _ROPE), laid, 0.0)
    return jnp.cos(laid), jnp.sin(laid)


_STATIC = ("heads", "nope", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _q_fwd(x, cos, sin, *, heads, nope, interpret):
    B, S, width = x.shape
    W = width // heads
    grid, flat, (major,), table, _, _ = _plan(B, S, heads, W, (W,),
                                              2 * _LANES)
    return pl.pallas_call(
        functools.partial(_q_fwd_kernel, nope=nope),
        grid=grid,
        in_specs=[flat, table, table],
        out_specs=major,
        out_shape=jax.ShapeDtypeStruct((B * heads, S, W), x.dtype),
        interpret=interpret,
        name="mla_prep_fwd",
    )(x, cos, sin)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _q_bwd(g, cos, sin, *, heads, nope, interpret):
    BH, S, W = g.shape
    B = BH // heads
    grid, flat, (major,), table, _, wide = _plan(B, S, heads, W, (W,),
                                                 2 * _LANES)
    return pl.pallas_call(
        functools.partial(_q_bwd_kernel, nope=nope),
        grid=grid,
        in_specs=[major, table, table],
        out_specs=flat,
        out_shape=jax.ShapeDtypeStruct((B, S, heads * W), g.dtype),
        scratch_shapes=[wide],
        interpret=interpret,
        name="mla_prep_bwd",
    )(g, cos, sin)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _kv_fwd(kv, kr, cos, sin, *, heads, nope, interpret):
    B, S, width = kv.shape
    dv = width // heads - nope
    grid, flat, major, table, shared, wide = _plan(
        B, S, heads, nope + dv, (nope + _ROPE, dv), _LANES)
    return pl.pallas_call(
        functools.partial(_kv_fwd_kernel, nope=nope),
        grid=grid,
        in_specs=[flat, shared, table, table],
        out_specs=major,
        out_shape=[
            jax.ShapeDtypeStruct((B * heads, S, nope + _ROPE), kv.dtype),
            jax.ShapeDtypeStruct((B * heads, S, dv), kv.dtype)],
        scratch_shapes=[wide],
        interpret=interpret,
        name="mla_prep_fwd",
    )(kv, kr, cos, sin)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _kv_bwd(dk, dv, cos, sin, *, heads, nope, interpret):
    BH, S, width = dv.shape
    B = BH // heads
    grid, flat, major, table, shared, acc = _plan(
        B, S, heads, nope + width, (nope + _ROPE, width), _LANES)
    return pl.pallas_call(
        functools.partial(_kv_bwd_kernel, nope=nope),
        grid=grid,
        in_specs=major + [table, table],
        out_specs=[flat, shared],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, heads * (nope + width)), dk.dtype),
            jax.ShapeDtypeStruct((B, S, _ROPE), dk.dtype)],
        scratch_shapes=[acc],
        interpret=interpret,
        name="mla_prep_bwd",
    )(dk, dv, cos, sin)


# Like ``qk_prep``: independent across the batch, every array but the tables
# batch-major in dim 0, so under ``ops.kernel_mesh`` each device runs its own
# rows. Linear maps: the tables are all a backward needs.


def _each_device(kernel, *arrays, tables, heads, nope, interpret):
    return ops.on_each_device(
        functools.partial(kernel, heads=heads, nope=nope,
                          interpret=interpret), *arrays, whole=tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _q_core(x, cos, sin, heads, nope, interpret):
    return _each_device(_q_fwd, x, tables=(cos, sin), heads=heads, nope=nope,
                        interpret=interpret)


def _q_core_fwd(x, cos, sin, heads, nope, interpret):
    return _q_core(x, cos, sin, heads, nope, interpret), (cos, sin)


def _q_core_bwd(heads, nope, interpret, tables, g):
    dx = _each_device(_q_bwd, g, tables=tables, heads=heads, nope=nope,
                      interpret=interpret)
    return (dx,) + tuple(map(jnp.zeros_like, tables))


_q_core.defvjp(_q_core_fwd, _q_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _kv_core(kv, kr, cos, sin, heads, nope, interpret):
    return tuple(_each_device(_kv_fwd, kv, kr, tables=(cos, sin), heads=heads,
                              nope=nope, interpret=interpret))


def _kv_core_fwd(kv, kr, cos, sin, heads, nope, interpret):
    return _kv_core(kv, kr, cos, sin, heads, nope, interpret), (cos, sin)


def _kv_core_bwd(heads, nope, interpret, tables, g):
    dkv, dkr = _each_device(_kv_bwd, *g, tables=tables, heads=heads,
                            nope=nope, interpret=interpret)
    return (dkv, dkr) + tuple(map(jnp.zeros_like, tables))


_kv_core.defvjp(_kv_core_fwd, _kv_core_bwd)


def mla_prep(q, kv, k_rope, angles, *, heads: int, nope: int,
             interpret: bool | None = None):
    """``q [B, S, heads·(nope + 64)]`` (a query projection's result), ``kv
    [B, S, heads·(nope + dv)]`` (the second key-value projection's: a head's
    key part with no position, then its value) and ``k_rope [B, S, 64]`` (the
    one rotary key a token) to the flash kernels' operands ``(q, k, v)``:
    ``[B·heads, S, nope + 64]`` twice and ``[B·heads, S, dv]``, in their
    dtypes; the last 64 columns of every q head and ``k_rope`` rotated by
    ``angles [S, 32]`` over pairs ``(2i, 2i+1)``, ``k_rope`` laid beside every
    head's key part. Differentiable in ``q``, ``kv`` and ``k_rope``. Raises
    for a shape :func:`mla_prep_impl` sends to ``"xla"``."""
    S, width = q.shape[1:]
    if width % heads or kv.shape[-1] % heads:
        raise ValueError(
            f"{width} and {kv.shape[-1]} columns are not {heads} heads")
    rope, dv = width // heads - nope, kv.shape[-1] // heads - nope
    if mla_prep_impl("pallas", S=S, nope=nope, rope=rope, v=dv,
                     heads=heads) != "pallas" or k_rope.shape[-1] != rope:
        raise ValueError(
            f"mla_prep needs an even count of heads of a multiple of {_LANES} "
            f"columns with no position, {_ROPE} rotary ones and values of a "
            f"multiple of {_LANES}, and rows of a multiple of "
            f"{_ROW_TILES[-1]}; got heads={heads}, nope={nope}, rope={rope} "
            f"(the shared key's {k_rope.shape[-1]}), v={dv}, S={S}"
        )
    interpret = ops.interpreted(interpret)
    # a pair's two rotary tiles: [a's rotary columns | b's first 64], then
    # [b's last 64 with no position | b's rotary columns]
    q = _q_core(q, *_tables(angles, (1, 0, 0, 1)), int(heads), int(nope),
                interpret)
    k, v = _kv_core(kv, k_rope, *_tables(angles, (1, 0)), int(heads),
                    int(nope), interpret)
    return q, k, v
