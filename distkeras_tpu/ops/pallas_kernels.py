"""Pallas TPU kernels for hot elementwise paths.

The reference had no native kernels at all — its compute lived in Theano/TF1
(SURVEY.md §2b.4) — so nothing here is a port; it is TPU-native surplus.

``fused_adam`` fuses the whole Adam step — both moment updates, bias
correction, and the parameter update — into ONE Pallas kernel, i.e. one pass
over HBM per leaf instead of the several reads/writes a chain of unfused
elementwise ops would make. At communication-window boundaries every parameter
is touched by the optimizer, so this path is HBM-bandwidth bound; fusing it is
the classic TPU win (XLA usually fuses these too — the kernel makes the
schedule explicit and guaranteed, and serves as the repo's template for
writing Pallas kernels against the engine).

The kernel runs on real TPUs; everywhere else (the 8-fake-device CPU mesh in
CI) it executes in Pallas interpret mode, so the SAME code path is unit-tested
against the optax oracle without TPU hardware. Select it with
``worker_optimizer="fused_adam"`` on any trainer.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu import ops

_LANES = 128          # TPU lane width (last dim of every tile)
_BLOCK_ROWS = 256     # rows per grid step: 256×128 f32 = 128 KiB/buffer in VMEM


def _adam_kernel(bc_ref, g_ref, m_ref, v_ref, m_out, v_out, u_out,
                 *, lr, b1, b2, eps):
    """One block: new moments + bias-corrected update, single VMEM round."""
    g = g_ref[:]
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    m_out[:] = m
    v_out[:] = v
    # bc holds [1/(1-b1^t), 1/(1-b2^t)] — computed once per step on the host
    # side of the trace (t is a traced scalar, so it can't be closed over)
    mhat = m * bc_ref[0, 0]
    vhat = v * bc_ref[0, 1]
    u_out[:] = (-lr) * mhat / (jnp.sqrt(vhat) + eps)


def _adam_leaf(g, m, v, bc, *, lr, b1, b2, eps, interpret):
    """Apply the kernel to one (arbitrary-shape) leaf via 1D→(rows,128) tiling."""
    shape, dtype = g.shape, g.dtype
    n = g.size
    rows = max(1, -(-n // _LANES))
    rows_p = -(-rows // _BLOCK_ROWS) * _BLOCK_ROWS
    total = rows_p * _LANES

    def prep(x):
        flat = x.reshape(-1)
        return jnp.pad(flat, (0, total - n)).reshape(rows_p, _LANES)

    blk = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))
    scal = pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM)
    kernel = functools.partial(_adam_kernel, lr=lr, b1=b1, b2=b2, eps=eps)
    out = jax.ShapeDtypeStruct((rows_p, _LANES), dtype)
    m_new, v_new, u = pl.pallas_call(
        kernel,
        grid=(rows_p // _BLOCK_ROWS,),
        in_specs=[scal, blk, blk, blk],
        out_specs=[blk, blk, blk],
        out_shape=[out, out, out],
        interpret=interpret,
        name="fused_adam",
    )(bc, prep(g), prep(m), prep(v))

    def unprep(x):
        return x.reshape(-1)[:n].reshape(shape)

    return unprep(m_new), unprep(v_new), unprep(u)


class FusedAdamState(NamedTuple):
    count: Any
    mu: Any
    nu: Any


def fused_adam(learning_rate: float, b1: float = 0.9, b2: float = 0.999,
               eps: float = 1e-8,
               interpret: bool | None = None) -> optax.GradientTransformation:
    """Adam as a single fused Pallas kernel per leaf (optax-compatible).

    Semantics match ``optax.adam`` exactly (same bias correction, same eps
    placement); the unit tests pin the two against each other. ``interpret``
    defaults to "kernel on TPU, interpreter elsewhere".
    """
    lr = float(learning_rate)

    def init(params):
        zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
        return FusedAdamState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree.map(zeros, params),
            nu=jax.tree.map(zeros, params),
        )

    def update(updates, state, params=None):
        del params
        count = state.count + 1
        t = count.astype(jnp.float32)
        bc = jnp.stack([
            1.0 / (1.0 - jnp.power(b1, t)),
            1.0 / (1.0 - jnp.power(b2, t)),
        ]).astype(jnp.float32).reshape(1, 2)

        g_leaves, treedef = jax.tree.flatten(updates)
        m_leaves = treedef.flatten_up_to(state.mu)
        v_leaves = treedef.flatten_up_to(state.nu)
        interp = ops.interpreted(interpret)
        new_m, new_v, u = [], [], []
        for g, m, v in zip(g_leaves, m_leaves, v_leaves):
            mi, vi, ui = _adam_leaf(
                g.astype(jnp.float32), m, v, bc,
                lr=lr, b1=b1, b2=b2, eps=eps, interpret=interp,
            )
            new_m.append(mi)
            new_v.append(vi)
            u.append(ui.astype(g.dtype))
        return (
            jax.tree.unflatten(treedef, u),
            FusedAdamState(
                count=count,
                mu=jax.tree.unflatten(treedef, new_m),
                nu=jax.tree.unflatten(treedef, new_v),
            ),
        )

    return optax.GradientTransformation(init, update)
