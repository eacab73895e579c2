"""Fused LSTM scan — a Pallas TPU kernel for the recurrent hot loop.

Parity+perf: the reference's newest model was a Keras LSTM trained step-by-
step on CPU executors (reference ``distkeras/examples`` IMDB config —
SURVEY.md §2b #19 / BASELINE config 5). The rebuild's XLA ``lax.scan`` path
(:mod:`distkeras_tpu.models.lstm`) is bounded not by matmul FLOPs but by
per-step overheads: each of the T sequential steps round-trips the h/c
carries through HBM and launches a tiny [B,H]·[H,4H] contraction.
This kernel runs
the WHOLE scan as one Pallas grid:

- grid ``(T/K,)`` with ``K`` timesteps unrolled per grid step — TPU grid
  steps execute sequentially, which is exactly a recurrence: the carries
  (h, c) live in VMEM scratch across grid steps and never touch HBM, and
  the K-unroll amortizes the per-grid-step pipeline overhead that
  dominates at [B,H]-sized blocks;
- the recurrent weight ``wh [H, 4H]`` has a constant index map, so Mosaic
  keeps it resident in VMEM for the whole scan (one HBM fetch total);
- per timestep, one MXU contraction ``h @ wh`` plus the VPU gate math; the
  step's ``h`` and ``c`` tiles (both in the model dtype — the f32 carry
  inside the kernel keeps the recurrence itself full-precision) stream out
  double-buffered while the next chunk computes.

Backward is the reverse-time kernel with the same structure: carries
``dc``/``dh`` and the ``dwh`` accumulator in VMEM scratch, per step one
recompute of the gate pre-activations from the saved ``h`` sequence (no
saved probabilities — same recompute philosophy as
:mod:`distkeras_tpu.ops.flash_attention`), and two MXU contractions
(``dz @ whᵀ`` for the carried gradient, ``h_prevᵀ @ dz`` folded into the
``dwh`` accumulator). The t-1 states come from the saved sequences via a
previous-chunk block view — no shifted HBM copies.

Gate math matches ``models.lstm.LSTMClassifier`` exactly: forget bias +1.0,
cell state f32 in-kernel, gates/hidden in the model dtype. On TPU the
kernel compiles natively; elsewhere it runs in Pallas interpret mode so the
same code path is oracle-tested in CI (tests/test_recurrent.py pins values
AND gradients against the ``lax.scan`` reference).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu import ops

#: timesteps unrolled per grid step (largest divisor of T from this ladder)
CHUNK = 8

#: per-core scoped VMEM budget for a kernel's blocks (v5e limit is 16 MiB;
#: leave headroom for scratch, wh, and Mosaic's own allocations)
_VMEM_BUDGET = 10 * 1024 * 1024


def _pick_chunk(T, per_t_bytes):
    """Largest ladder divisor of T whose double-buffered blocks fit VMEM."""
    for k in (CHUNK, 5, 4, 2, 1):
        if T % k == 0 and 2 * k * per_t_bytes <= _VMEM_BUDGET:
            return k
    return 1


def _gates(z):
    """z [B, 4H] f32 → (i_s, f_s, g_t, o_s) activated gates, H-wide each."""
    H = z.shape[-1] // 4
    i, f, g, o = (z[:, k * H:(k + 1) * H] for k in range(4))
    return (jax.nn.sigmoid(i), jax.nn.sigmoid(f + 1.0), jnp.tanh(g),
            jax.nn.sigmoid(o))


def _lstm_fwd_kernel(gx_ref, wh_ref, hs_ref, *rest, K):
    """One grid step = K timesteps: z = gx_t + h @ wh; gate math; stream
    out h_t (and c_t when training needs the residual); carries stay in
    VMEM scratch."""
    if len(rest) == 3:
        cs_ref, h_s, c_s = rest
    else:
        cs_ref, (h_s, c_s) = None, rest
    t0 = pl.program_id(0)

    @pl.when(t0 == 0)
    def _():
        h_s[:] = jnp.zeros_like(h_s)
        c_s[:] = jnp.zeros_like(c_s)

    wh = wh_ref[:].astype(h_s.dtype)
    for k in range(K):
        z = (
            gx_ref[k].astype(jnp.float32)
            + jax.lax.dot_general(
                h_s[:], wh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        i_s, f_s, g_t, o_s = _gates(z)
        c = f_s * c_s[:] + i_s * g_t
        h = (o_s * jnp.tanh(c)).astype(h_s.dtype)
        c_s[:] = c
        h_s[:] = h
        hs_ref[k] = h.astype(hs_ref.dtype)
        if cs_ref is not None:
            cs_ref[k] = c.astype(cs_ref.dtype)


def _lstm_bwd_kernel(gx_ref, wh_ref, hs_ref, hsp_ref, cs_ref, csp_ref,
                     dh_ref, dgx_ref, dwh_ref, dc_s, dhr_s, dwh_s, *, K):
    """One grid step = K reverse timesteps: recompute gates from h_{t-1},
    fold gradients. ``hsp_ref``/``csp_ref`` are the PREVIOUS chunk's saved
    h/c blocks (clamped at chunk 0); the global first timestep's zero
    initial state is imposed in-kernel."""
    s = pl.program_id(0)          # s = 0 … T/K-1, visiting chunks in reverse
    n = pl.num_programs(0)

    @pl.when(s == 0)
    def _():
        dc_s[:] = jnp.zeros_like(dc_s)
        dhr_s[:] = jnp.zeros_like(dhr_s)
        dwh_s[:] = jnp.zeros_like(dwh_s)

    wh = wh_ref[:].astype(hs_ref.dtype)
    for k in range(K - 1, -1, -1):
        if k > 0:
            h_prev = hs_ref[k - 1]
            c_prev = cs_ref[k - 1].astype(jnp.float32)
        else:
            # hsp/csp are single-timestep views of the previous chunk's
            # last step (clamped); zero them at the global first timestep
            first_t = (s == n - 1)   # global t == 0
            h_prev = jnp.where(
                first_t, 0.0, hsp_ref[0].astype(jnp.float32)
            ).astype(hs_ref.dtype)
            c_prev = jnp.where(
                first_t, 0.0, csp_ref[0].astype(jnp.float32)
            )
        z = (
            gx_ref[k].astype(jnp.float32)
            + jax.lax.dot_general(
                h_prev, wh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        i_s, f_s, g_t, o_s = _gates(z)
        c = cs_ref[k].astype(jnp.float32)
        tc = jnp.tanh(c)

        dh_total = dh_ref[k].astype(jnp.float32) + dhr_s[:]
        do_pre = dh_total * tc * o_s * (1.0 - o_s)
        dc_tot = dh_total * o_s * (1.0 - tc * tc) + dc_s[:]
        di_pre = dc_tot * g_t * i_s * (1.0 - i_s)
        df_pre = dc_tot * c_prev * f_s * (1.0 - f_s)
        dg_pre = dc_tot * i_s * (1.0 - g_t * g_t)
        dc_s[:] = dc_tot * f_s

        dz = jnp.concatenate([di_pre, df_pre, dg_pre, do_pre], axis=-1)
        dgx_ref[k] = dz.astype(dgx_ref.dtype)
        dz_c = dz.astype(hs_ref.dtype)
        dhr_s[:] = jax.lax.dot_general(
            dz_c, wh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dwh_s[:] += jax.lax.dot_general(
            h_prev, dz_c, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(s == n - 1)
    def _():
        dwh_ref[:] = dwh_s[:].astype(dwh_ref.dtype)


def _fwd(gx_t, wh, interpret, save_c: bool = True):
    """gx_t [T, B, 4H] (time-major), wh [H, 4H] → (hs [T, B, H], cs|None).

    ``save_c=False`` (the eval/primal path) skips streaming the c sequence
    to HBM entirely — it is only the backward's residual. When saved, cs is
    stored in the model dtype (halves its HBM traffic for bf16 training);
    the f32 carry inside the kernel keeps the recurrence full-precision.
    """
    T, B, H4 = gx_t.shape
    H = H4 // 4
    # streamed blocks per timestep: gx [B,4H] in, hs(+cs) [B,H] out
    K = _pick_chunk(T, (H4 + (2 if save_c else 1) * H) * B
                    * gx_t.dtype.itemsize)
    seq_spec = pl.BlockSpec((K, B, H), lambda t: (t, 0, 0))
    seq_shape = jax.ShapeDtypeStruct((T, B, H), gx_t.dtype)
    out = pl.pallas_call(
        functools.partial(_lstm_fwd_kernel, K=K), grid=(T // K,),
        in_specs=[
            pl.BlockSpec((K, B, H4), lambda t: (t, 0, 0)),
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
        ],
        out_specs=[seq_spec, seq_spec] if save_c else [seq_spec],
        out_shape=[seq_shape, seq_shape] if save_c else [seq_shape],
        scratch_shapes=[
            pltpu.VMEM((B, H), gx_t.dtype),   # h carry
            pltpu.VMEM((B, H), jnp.float32),  # c carry
        ],
        interpret=interpret,
        name="lstm_scan_fwd",
    )(gx_t, wh)
    return (out[0], out[1]) if save_c else (out[0], None)


def _bwd(gx_t, wh, hs, cs, dhs, interpret):
    """Reverse-time gradients → (dgx_t [T, B, 4H], dwh [H, 4H])."""
    T, B, H4 = gx_t.shape
    H = H4 // 4
    # streamed blocks per timestep: gx+dgx [B,4H], hs/hsp/cs/csp/dh [B,H]
    K = _pick_chunk(T, (2 * H4 + 5 * H) * B * gx_t.dtype.itemsize)
    n = T // K

    rev = lambda t: (n - 1 - t, 0, 0)       # visit chunks in reverse time
    # single-timestep view of the previous chunk's LAST step (clamped;
    # kernel zeroes t==0) — streams 1 row, not a whole spare chunk
    rev_prev = lambda t: (jnp.maximum((n - 1 - t) * K - 1, 0), 0, 0)
    dgx, dwh = pl.pallas_call(
        functools.partial(_lstm_bwd_kernel, K=K), grid=(n,),
        in_specs=[
            pl.BlockSpec((K, B, H4), rev),              # gx
            pl.BlockSpec((H, H4), lambda t: (0, 0)),    # wh
            pl.BlockSpec((K, B, H), rev),               # hs chunk
            pl.BlockSpec((1, B, H), rev_prev),          # h_{chunk-1} view
            pl.BlockSpec((K, B, H), rev),               # cs chunk
            pl.BlockSpec((1, B, H), rev_prev),          # c_{chunk-1} view
            pl.BlockSpec((K, B, H), rev),               # dh
        ],
        out_specs=[
            pl.BlockSpec((K, B, H4), rev),
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H4), gx_t.dtype),
            jax.ShapeDtypeStruct((H, H4), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),   # dc carry
            pltpu.VMEM((B, H), jnp.float32),   # dh carried from t+1
            pltpu.VMEM((H, H4), jnp.float32),  # dwh accumulator
        ],
        interpret=interpret,
        name="lstm_scan_bwd",
    )(gx_t, wh, hs, hs, cs, cs, dhs)
    return dgx, dwh


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lstm_core(gx_t, wh, interpret):
    hs, _ = _fwd(gx_t, wh, interpret, save_c=False)
    return hs


def _lstm_core_fwd(gx_t, wh, interpret):
    hs, cs = _fwd(gx_t, wh, interpret)
    return hs, (gx_t, wh, hs, cs)


def _lstm_core_bwd(interpret, res, dhs):
    gx_t, wh, hs, cs = res
    dgx, dwh = _bwd(gx_t, wh, hs, cs, dhs, interpret)
    return dgx, dwh.astype(wh.dtype)


_lstm_core.defvjp(_lstm_core_fwd, _lstm_core_bwd)


def lstm_scan_reference(gates_x, wh):
    """The XLA ``lax.scan`` oracle (identical math, batch-major I/O).

    ``gates_x`` [B, T, 4H] (model dtype), ``wh`` [H, 4H] → hs [B, T, H].
    """
    H = wh.shape[0]
    dtype = gates_x.dtype

    def step(carry, gx_t):
        c, h = carry
        z = (gx_t + h @ wh.astype(dtype)).astype(jnp.float32)
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = (jax.nn.sigmoid(o) * jnp.tanh(c)).astype(dtype)
        return (c, h), h

    B = gates_x.shape[0]
    c0 = jnp.zeros((B, H), jnp.float32)
    h0 = jnp.zeros((B, H), dtype)
    _, outs = jax.lax.scan(step, (c0, h0), jnp.moveaxis(gates_x, 1, 0))
    return jnp.moveaxis(outs, 0, 1)


def lstm_impl(impl: str = "auto", *, B: int, H: int) -> str:
    """``"pallas"`` or ``"xla"``: what :func:`lstm_scan` runs for batch
    ``B`` and hidden width ``H`` (``ops.kernel_impl("lstm_scan", …)`` is the
    public door). A named implementation is returned as asked; ``"auto"``
    is the kernel only when it compiles natively with tile-friendly shapes
    (H a multiple of 128, B of 8)."""
    if impl not in ("pallas", "xla", "auto"):
        raise ValueError(
            f"unknown lstm impl {impl!r}; use 'pallas', 'xla', or 'auto'"
        )
    if impl != "auto":
        return impl
    return "xla" if H % 128 or B % 8 or not ops.native_kernels() else "pallas"


def lstm_scan(gates_x, wh, impl: str = "auto",
              interpret: bool | None = None):
    """Run the LSTM recurrence over pre-projected gate inputs.

    ``gates_x`` [B, T, 4H] (``x @ W_x + b`` for every step — hoisted out of
    the recurrence as one big matmul), ``wh`` [H, 4H] recurrent weights →
    ``hs`` [B, T, H] in ``gates_x.dtype``. Differentiable in both arguments.

    ``impl``: ``"pallas"`` forces the fused kernel, ``"xla"`` the
    ``lax.scan`` reference, ``"auto"`` is decided by :func:`lstm_impl`.
    """
    B, T, H4 = gates_x.shape
    if lstm_impl(impl, B=B, H=H4 // 4) == "xla":
        return lstm_scan_reference(gates_x, wh)
    hs = _lstm_core(
        jnp.moveaxis(gates_x, 1, 0), wh,
        ops.interpreted(interpret),
    )
    return jnp.moveaxis(hs, 0, 1)
