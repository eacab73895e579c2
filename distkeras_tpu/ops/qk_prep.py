"""From a projection's result to the flash kernels' operand in one pass.

A block whose queries and keys take an RMSNorm over each head and rotary
embeddings (:class:`distkeras_tpu.models.lm.QKNormAttention`) does, between
its q / k projection and ``flash_fwd``: a cast to float32, the mean of squares
over each head's lanes, ``rsqrt``, the norm's weight, the rotation of feature
pairs ``(2i, 2i+1)``, a cast back, and the move from ``[B, S, heads·D]`` to
the head-major ``[B·heads, S, D]`` the kernels' grids walk. As ``jnp``
operations each of those is a pass over HBM (the reduction over 128 lanes, the
float32 intermediate and the transpose are each a fusion boundary; the strided
slices of the pairs lower to gathers and, backward, scatter-adds): twelve
times the bytes of reading the projection's result and writing the operand.

:func:`qk_prep` is that chain as ONE Pallas kernel each way (``qk_prep_fwd``,
``qk_prep_bwd`` in a device trace): float32 only in registers, the same
arithmetic in the same order as the ``jnp`` chain (``head_norm_rope`` in
``models/lm.py``, which stays as what runs at any other shape and as what the
tests compare with), one rounding at the end. The backward reads the
projection's result again (the only residual) and the cotangent in the
head-major layout ``flash_dq`` / ``flash_dkv`` write.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu import ops

_LANES = 128
#: row tiles, largest first: a call's rows must be a multiple of one
_ROW_TILES = (512, 256, 128)
#: most lanes of the same rows a tile holds: 8 heads of 128 (the table's
#: tile is read once for all of them; the backward's three tiles of 512 rows
#: and their second buffers come to 6 MiB of the 16 a kernel may use)
_LANES_A_TILE = 1024


def _row_tile(S: int):
    return next((t for t in _ROW_TILES if S % t == 0), None)


def _heads_a_tile(heads: int, D: int) -> int:
    most = max(1, _LANES_A_TILE // D)
    return max(h for h in range(1, most + 1) if heads % h == 0)


def qk_prep_impl(impl: str = "auto", *, S: int, D: int) -> str:
    """``"pallas"`` or ``"xla"``: what a block runs between its q / k
    projection and the flash kernels for ``S`` rows and heads of ``D``
    (``ops.kernel_impl("qk_prep", …)`` is the public door). ``"xla"`` is
    returned as asked; ``"pallas"`` is the kernel where its tiles fit (``D`` a
    multiple of 128 lanes, ``S`` of a row tile) and falls back to ``"xla"``
    where they do not; ``"auto"`` is the kernel only when it also compiles
    natively."""
    if impl not in ("pallas", "xla", "auto"):
        raise ValueError(
            f"unknown qk_prep impl {impl!r}; use 'pallas', 'xla', or 'auto'"
        )
    fits = D % _LANES == 0 and _row_tile(S) is not None
    if impl == "xla" or not fits:
        return "xla"
    return "pallas" if impl == "pallas" or ops.native_kernels() else "xla"


def _even_lanes(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) % 2 == 0


def _rotated(y, cos, sin, even, transposed=False):
    """Rotary over lane pairs ``(2i, 2i+1)`` of ``y [rows, D]`` (``cos`` and
    ``sin`` hold a pair's angle in both its lanes): an even lane takes
    ``y·cos − next·sin``, an odd one ``y·cos + previous·sin``;
    ``transposed`` is the rotation by the opposite angle, its transpose."""
    D = y.shape[-1]
    nxt = pltpu.roll(y, D - 1, 1)          # lane j holds y[j + 1]
    prv = pltpu.roll(y, 1, 1)              # lane j holds y[j - 1]
    other = (jnp.where(even, nxt, -prv) if transposed
             else jnp.where(even, -nxt, prv))
    return y * cos + other * sin


# A head's whole row tile goes through each kernel as one array: cut into
# pieces of 16, 64, 128 and 256 rows by a loop inside the step, the forward
# took 3.4, 2.6, 1.7 and 1.2 times as long (v5e, PR 32, at bf16 [4, 8192,
# 4096]: 3.00 / 4.11 ms forward / backward at 16 rows, 0.89 / 1.29 at all 512).


def _fwd_kernel(x_ref, w_ref, cos_ref, sin_ref, o_ref, *, eps):
    """One (row tile, batch row, head group) step: ``x_ref [1, rows,
    hb·D]`` (the product as it left the projection) to ``o_ref [hb, rows,
    D]``, a head at a time."""
    hb, rows, D = o_ref.shape
    f32 = jnp.float32
    w, cos, sin = w_ref[...], cos_ref[...], sin_ref[...]
    even = _even_lanes((rows, D))
    for h in range(hb):
        x = x_ref[0, :, h * D:(h + 1) * D].astype(f32)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w
        o_ref[h] = _rotated(y, cos, sin, even).astype(o_ref.dtype)


def _bwd_kernel(x_ref, g_ref, w_ref, cos_ref, sin_ref, dx_ref, dw_ref, *,
                eps):
    """The same step backward: from ``x_ref`` again and the cotangent
    ``g_ref [hb, rows, D]``, the projection result's gradient ``dx_ref [1,
    rows, hb·D]`` and this step's share of the weight's, ``dw_ref [1, 8, D]``
    (rows folded onto 8 sublanes; summed outside)."""
    hb, rows, D = g_ref.shape
    f32 = jnp.float32
    w, cos, sin = w_ref[...], cos_ref[...], sin_ref[...]
    even = _even_lanes((rows, D))
    dw = jnp.zeros((8, D), f32)
    for h in range(hb):
        hs = slice(h * D, (h + 1) * D)
        x = x_ref[0, :, hs].astype(f32)
        r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        n = x * r
        dy = _rotated(g_ref[h].astype(f32), cos, sin, even, transposed=True)
        dw = dw + jnp.sum((dy * n).reshape(rows // 8, 8, D), 0)
        dn = dy * w
        dx = r * (dn - n * jnp.mean(dn * n, -1, keepdims=True))
        dx_ref[0, :, hs] = dx.astype(dx_ref.dtype)
    dw_ref[0] = dw


def _plan(x, heads):
    """The grid both kernels walk and their specs: row tiles outermost, so
    that the table's tile is fetched once for every batch row and head."""
    B, S, width = x.shape
    D = width // heads
    rows, hb = _row_tile(S), _heads_a_tile(heads, D)
    groups = heads // hb
    grid = (S // rows, B, groups)
    flat = pl.BlockSpec((1, rows, hb * D), lambda s, b, g: (b, s, g))
    major = pl.BlockSpec((hb, rows, D),
                         lambda s, b, g: (b * groups + g, s, 0))
    weight = pl.BlockSpec((1, D), lambda s, b, g: (0, 0))
    table = pl.BlockSpec((rows, D), lambda s, b, g: (s, 0))
    return grid, flat, major, weight, table


def _tables(angles, D):
    """``cos`` and ``sin [S, D]`` of ``angles [S, D // 2]``, a pair's angle
    in both its lanes."""
    if angles.shape[-1] * 2 != D:
        raise ValueError(f"angles {angles.shape} are not [S, {D // 2}]")
    angles = jax.lax.stop_gradient(jnp.asarray(angles, jnp.float32))
    return (jnp.repeat(jnp.cos(angles), 2, axis=-1),
            jnp.repeat(jnp.sin(angles), 2, axis=-1))


@functools.partial(jax.jit, static_argnames=("heads", "eps", "interpret"))
def _prep_fwd(x, w, cos, sin, *, heads, eps, interpret):
    B, S, width = x.shape
    D = width // heads
    grid, flat, major, weight, table = _plan(x, heads)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[flat, weight, table, table],
        out_specs=major,
        out_shape=jax.ShapeDtypeStruct((B * heads, S, D), x.dtype),
        interpret=interpret,
        name="qk_prep_fwd",
    )(x, w, cos, sin)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "interpret"))
def _prep_bwd(x, g, w, cos, sin, *, heads, eps, interpret):
    B, S, width = x.shape
    D = width // heads
    grid, flat, major, weight, table = _plan(x, heads)
    tiles, groups = grid[0], grid[2]
    # a grid step's share of the weight's gradient, batch row outermost
    share = pl.BlockSpec(
        (1, 8, D), lambda s, b, g: ((b * tiles + s) * groups + g, 0, 0))
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=grid,
        in_specs=[flat, major, weight, table, table],
        out_specs=[flat, share],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B * tiles * groups, 8, D),
                                        jnp.float32)],
        interpret=interpret,
        name="qk_prep_bwd",
    )(x, g, w, cos, sin)
    return dx, dw


# Like the flash kernels (``flash_attention._forward``): independent across
# the batch, every array but the weight and the tables batch-major in dim 0,
# so under ``ops.kernel_mesh`` each device runs its own rows.


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _core(x, w, cos, sin, heads, eps, interpret):
    return ops.on_each_device(
        functools.partial(_prep_fwd, heads=heads, eps=eps,
                          interpret=interpret),
        x, whole=(w, cos, sin))


def _core_fwd(x, w, cos, sin, heads, eps, interpret):
    return _core(x, w, cos, sin, heads, eps, interpret), (x, w, cos, sin)


def _core_bwd(heads, eps, interpret, res, g):
    x, w, cos, sin = res
    dx, dw = ops.on_each_device(
        functools.partial(_prep_bwd, heads=heads, eps=eps,
                          interpret=interpret),
        x, g, whole=(w, cos, sin))
    return (dx, jnp.sum(dw, (0, 1)).reshape(w.shape), jnp.zeros_like(cos),
            jnp.zeros_like(sin))


_core.defvjp(_core_fwd, _core_bwd)


def qk_prep(x, w, angles, *, heads: int, eps: float,
            interpret: bool | None = None):
    """``x [B, S, heads·D]`` (a q or k projection's result) to the flash
    kernels' operand ``[B·heads, S, D]`` in ``x.dtype``: an RMSNorm over each
    head with weight ``w [D]`` and ``eps``, then rotary by ``angles [S,
    D // 2]`` (row ``s`` of every batch row stands at ``angles[s]``: any
    positions, repeats among them) over pairs ``(2i, 2i+1)``. Differentiable
    in ``x`` and ``w``. Raises for a shape :func:`qk_prep_impl` sends to
    ``"xla"``."""
    B, S, width = x.shape
    if width % heads:
        raise ValueError(f"{width} columns are not {heads} heads")
    D = width // heads
    if qk_prep_impl("pallas", S=S, D=D) != "pallas":
        raise ValueError(
            f"qk_prep needs heads of a multiple of {_LANES} and rows of a "
            f"multiple of {_ROW_TILES[-1]}; got D={D}, S={S}"
        )
    cos, sin = _tables(angles, D)
    return _core(x, w.astype(jnp.float32).reshape(1, D), cos, sin,
                 int(heads), float(eps), ops.interpreted(interpret))
