#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU chip: drives the main path once through the entry points
a user calls, at the full width of models the repo supports, and checks what
comes out by the repo's own means. Run it through the chip tool from the root
of a checkout:

    python3 chip_smoke.py              # one chip: kernels, loss, adag, lm, moe, serve
    python3 chip_smoke.py --chips 4    # four chips: adag4, lm4 (and no other)

Each phase prints one JSON line (its name, seconds, what it checked); the last
line of stdout is ``{"ok": true, "device": {...}}`` and the exit code 0 only
if every check of every phase passed. A device that is not a TPU, a failed
check or an exception means a non-zero exit and no such line — nothing here
falls back to the CPU, and no switch turns the device check off. The phases
are plain functions of their sizes, so tests/test_chip_smoke.py calls them
tiny on the CPU mesh; the sizes ``main`` passes are the defaults below.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import threading
import time

import numpy as np

SEED = 0
#: normalized max error ``max|got - want| / max|want|`` a kernel may show
#: against its XLA reference, by dtype — the repo's interpret-mode tests' own
#: (tests/test_recurrent.py bf16 gradients; tests/test_pallas_kernels.py f32)
TOL = {"bfloat16": 2e-2, "float32": 1e-5}
#: held-out accuracy of the ``adag`` job in the CPU rehearsal (synthetic
#: MNIST stand-in, seed 0: 1024 of 1024, loss 2.49 -> 0.002 over 32
#: windows); the chip must come within 0.02 of it
ADAG_CPU_ACCURACY = 1.0
#: how one natively compiled Pallas kernel call reads in a compiled program
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


class SmokeFailure(RuntimeError):
    """A check of what came out failed."""


def _check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _report(phase: str, t0: float, **checked) -> dict:
    line = {"phase": phase, "seconds": round(time.perf_counter() - t0, 1),
            **checked}
    print(json.dumps(line), flush=True)
    return line


def _norm_err(got, want) -> float:
    """``max|got - want| / max|want|`` in float32, NaN-propagating."""
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def _device_set(tree) -> set:
    import jax

    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def _peak_bytes(devices) -> list:
    """Per device ``[peak_bytes_in_use, peak_bytes_reserved]`` — live buffers,
    and what running programs reserved for their temporaries (None where
    the backend reports no memory stats)."""
    stats = [d.memory_stats() or {} for d in devices]
    return [[st.get("peak_bytes_in_use"), st.get("peak_bytes_reserved")]
            for st in stats]


# ---------------------------------------------------------------------------
# kernels: each Pallas kernel once, against its own XLA reference
# ---------------------------------------------------------------------------


def _device_ms_by_op(run, calls: int) -> dict:
    """``{operation: ms a call}`` on the chip, from a profiler trace of
    ``run()`` (which makes ``calls`` calls): every operation's own time, what
    it contains taken out (a ``while`` is not charged its body's)."""
    import tempfile

    import jax

    from benchmark import xplane

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            run()
        planes = xplane.device_planes(xplane.read_planes(xplane.trace_file(d)))
    ns: dict[str, float] = {}
    for lines in planes.values():
        for event, _, _, own in xplane.self_times(lines[xplane.OPS_LINE]):
            name = xplane.short_name(event)
            ns[name] = ns.get(name, 0.0) + own
    return {k: v / 1e6 / calls for k, v in ns.items()}


def _device_ms_by_kernel(run, names, calls: int) -> dict:
    """``{name: ms a call}`` for the kernels whose operations' names start
    with one of ``names``; None where the trace holds none."""
    ms = dict.fromkeys(names, 0.0)
    for op, v in _device_ms_by_op(run, calls).items():
        stem = op.split(".")[0]
        if stem in ms:
            ms[stem] += v
    return {k: round(v, 4) if v else None for k, v in ms.items()}


def _flash_times(timed, interpret, reps=3) -> list:
    """The three flash kernels at each ``(B, L, H, D, Hkv)`` of ``timed``,
    causal and not — or, for an entry ``(B, L, H, D, Hkv, G)``, under the
    block-diffusion mask over blocks of ``G`` (``L`` the stream's length: a
    noised and a clean copy of rows of ``L // 2``); ``D`` a pair ``(Dk, Dv)``
    is latent attention's call, q and k ``Dk`` wide and v ``Dv``, causal: ms a
    call on the chip
    (None in interpret mode: a CPU gives no device time) beside what
    ``band_census`` counts for that length: grid steps a head and how many
    are idle, computed over band pairs, and the share of the computed pairs
    that run with no mask."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.flash_attention import band_census, flash_attention

    names = ("flash_fwd", "flash_dq", "flash_dkv")
    out = []
    for B, L, H, D, Hkv, *block in timed:
        Dk, Dv = D if isinstance(D, (tuple, list)) else (D, D)
        keys = jax.random.split(jax.random.PRNGKey(SEED + L), 4)
        q, g, k, v = (jax.random.normal(key, (B, L, heads, d), jnp.bfloat16)
                      for key, heads, d in zip(
                          keys, (H, H, Hkv, Hkv), (Dk, Dv, Dk, Dv)))
        # one mask an entry: the block-diffusion one, or causal (and, at one
        # width, none)
        for mask in ([dict(block_diffusion=block[0])] if block
                     else [dict(causal=True)] if Dk != Dv
                     else [dict(causal=True), dict(causal=False)]):
            def fwd_bwd(q, k, v, g):
                o, vjp = jax.vjp(lambda q, k, v: flash_attention(
                    q, k, v, interpret=interpret, **mask), q, k, v)
                return (o,) + vjp(g)
            step = jax.jit(fwd_bwd)
            jax.block_until_ready(step(q, k, v, g))      # compiles
            ms = dict.fromkeys(names)
            if not interpret:
                ms = _device_ms_by_kernel(
                    lambda: jax.block_until_ready(
                        [step(q, k, v, g) for _ in range(reps)]),
                    names, reps)
            census = band_census(L, **mask)
            out.append({"shape": [B, L, H, D, Hkv], **mask, **{
                n: {"ms": ms[n], "steps": c["steps"],
                    "steps_idle": c["steps_idle"],
                    "computed_over_band": round(c["computed_over_band"], 4),
                    "unmasked_share": round(c["pairs_unmasked"] / (
                        c["pairs_unmasked"] + c["pairs_masked"]), 4)}
                for n, c in census.items()}})
    return out


def _kernel_beside_chain(names, floor, fused, plain, args, interpret,
                         reps) -> dict:
    """Part of a ``*_prep_times`` entry: for each kernel of ``names`` ms a
    call of ``fused(*args)`` on the chip, its ``floor`` of bytes and the GB/s
    they come to, and under ``chain_ms`` the ms of every operation of
    ``plain(*args)``; no times in interpret mode."""
    import jax

    ms, chain_ms = dict.fromkeys(names), None
    if not interpret:
        ms = _device_ms_by_kernel(
            lambda: jax.block_until_ready(
                [fused(*args) for _ in range(reps)]), names, reps)
        chain_ms = round(sum(_device_ms_by_op(
            lambda: jax.block_until_ready(
                [plain(*args) for _ in range(reps)]), reps).values()), 4)
    return {**{n: {"ms": ms[n], "floor_bytes": floor[n],
                   "gb_per_s": ms[n] and round(floor[n] / ms[n] / 1e6, 1)}
               for n in names}, "chain_ms": chain_ms}


def _qk_prep_times(shapes, interpret, reps=3, eps=1e-6) -> list:
    """``ops.qk_prep`` forward and backward at each ``(B, S, heads, D)`` of
    ``shapes`` (bf16; the block-diffusion cell's q and k projections by
    default; rows at ``0 .. S/2 - 1`` twice) beside the ``jnp`` chain it
    replaces (``head_norm_rope``, the cast, the move to head-major): ms a call
    of each kernel on the chip and the GB/s its floor's bytes come to (the
    forward reads the projection's result and writes the operand; the backward
    reads both and writes the gradient; the chip moves 819), the chain's ms
    for the same two passes (None in interpret mode: a CPU gives no device
    time), and the kernel's error against it."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.lm import head_norm_rope, rope_angles_at
    from distkeras_tpu.ops.qk_prep import qk_prep

    names = ("qk_prep_fwd", "qk_prep_bwd")
    out = []
    for B, S, heads, D in shapes:
        ks = jax.random.split(jax.random.PRNGKey(SEED + heads), 3)
        x = jax.random.normal(ks[0], (B, S, heads * D), jnp.bfloat16)
        g = jax.random.normal(ks[1], (B * heads, S, D), jnp.bfloat16)
        w = 1.0 + 0.1 * jax.random.normal(ks[2], (D,), jnp.float32)
        row = np.arange(S // 2)
        angles = jnp.asarray(rope_angles_at(np.concatenate([row, row]), D,
                                            1e6))

        def chain(x, w):
            y = head_norm_rope(x, w, angles, heads, eps).astype(x.dtype)
            return jnp.moveaxis(y, 2, 1).reshape(B * heads, S, D)

        def fwd_bwd(fn):
            def run(x, w, g):
                o, vjp = jax.vjp(fn, x, w)
                return (o,) + vjp(g)
            return jax.jit(run)

        kernel = fwd_bwd(lambda x, w: qk_prep(
            x, w, angles, heads=heads, eps=eps, interpret=interpret))
        plain = fwd_bwd(chain)
        got, want = kernel(x, w, g), plain(x, w, g)        # compiles
        entry = {"shape": [B, S, heads, D], "norm_err": {
            part: _norm_err(a, b)
            for part, a, b in zip(("out", "dx", "dw"), got, want)}}
        floor = {"qk_prep_fwd": 2 * x.nbytes, "qk_prep_bwd": 3 * x.nbytes}
        out.append({**entry, **_kernel_beside_chain(
            names, floor, kernel, plain, (x, w, g), interpret, reps)})
    return out


def _mla_prep_times(shapes, interpret, reps=3) -> list:
    """``ops.mla_prep`` forward and backward at each ``(B, S, heads, nope,
    rope, v)`` of ``shapes`` (bf16; the latent-attention cell's projections by
    default), q's kernels and k / v's apart, beside the ``jnp`` chain each
    replaces (``latent_qkv`` and the move to head-major): ms a call of each
    kernel on the chip and the GB/s its floor's bytes come to (each operand
    read and each result written once; the chip moves 819), the chain's ms
    for the same two passes (None in interpret mode: a CPU gives no device
    time), and the kernel's error against it."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.lm import latent_qkv, rope_angles
    from distkeras_tpu.ops.mla_prep import mla_prep

    names = ("mla_prep_fwd", "mla_prep_bwd")
    out = []
    for B, S, heads, nope, rope, v in shapes:
        ks = jax.random.split(jax.random.PRNGKey(SEED + heads), 6)
        bf16 = jnp.bfloat16
        x = [jax.random.normal(k, (B, S, w), bf16) for k, w in zip(
            ks, (heads * (nope + rope), heads * (nope + v), rope))]
        cot = [jax.random.normal(k, (B * heads, S, w), bf16) for k, w in zip(
            ks[3:], (nope + rope, nope + rope, v))]
        angles = jnp.asarray(rope_angles(S, rope, 1e6))

        def kernel(*x):
            return mla_prep(*x, angles, heads=heads, nope=nope,
                            interpret=interpret)

        def chain(*x):
            return tuple(jnp.moveaxis(a, 2, 1).reshape(B * heads, S, -1)
                         for a in latent_qkv(*x, angles, heads, nope))

        # q alone, then k and v: (results, the arguments they depend on)
        for part, res, args in (("q", (0,), (0,)), ("kv", (1, 2), (1, 2))):
            def fwd_bwd(fn):
                def picked(*x):
                    results = fn(*x)                  # ONE call
                    return tuple(results[i] for i in res)

                def run(x, g):
                    o, vjp = jax.vjp(picked, *x)
                    grads = vjp(tuple(g))
                    return o + tuple(grads[i] for i in args)
                return jax.jit(run)

            fused, plain = fwd_bwd(kernel), fwd_bwd(chain)
            g = [cot[i] for i in res]
            got, want = fused(x, g), plain(x, g)            # compiles
            parts = {"q": ("q", "dq"), "kv": ("k", "v", "dkv", "dk_rope")}
            entry = {"part": part, "shape": [B, S, heads, nope, rope, v],
                     "norm_err": {n: _norm_err(a, b) for n, a, b in zip(
                         parts[part], got, want)}}
            floor = dict.fromkeys(names, sum(x[i].nbytes for i in args)
                                  + sum(cot[i].nbytes for i in res))
            out.append({**entry, **_kernel_beside_chain(
                names, floor, fused, plain, (x, g), interpret, reps)})
    return out


def kernels(*, attn=(8, 2048, 8, 128),
            qmm=((8, 2048, 8192), (1024, 8192, 2048)),
            adam=(16384, 1024), lstm=(64, 200, 512), interpret=False,
            timed=((8, 2048, 16, 64, 16), (8, 4096, 8, 128, 2),
                   (4, 8192, 32, 128, 4, 4), (4, 8192, 32, (192, 128), 32)),
            block=4, prep=((4, 8192, 32, 128), (4, 8192, 4, 128)),
            wide=(1, 8192, 2, 192, 128),
            latent=((4, 8192, 32, 128, 64, 128),)):
    """flash attention fwd+bwd (causal, and under the block-diffusion mask
    over blocks of ``block``; bf16), ``q_matmul`` (bf16 × int8), fused Adam
    (f32) and the fused LSTM scan fwd+bwd (bf16), each at a real call shape
    with ``interpret`` EXPLICIT, against ``attention_reference``,
    ``_q_matmul_xla``, ``optax.adam`` and ``lstm_scan_reference``. Then the
    three flash kernels timed on the chip at ``timed``, the benchmark cells'
    ``(B, L, H, D, Hkv)`` (the third with its block length: the
    block-diffusion call): what a computed pair costs with and without the
    mask, beside what ``band_census`` says they compute; the fourth is latent
    attention's call (q and k 192 wide, v 128), whose error against the
    ``jnp`` path is taken at ``wide``, ``(B, L, H, Dk, Dv)``: the same length
    at few enough heads for the path's ``[L, L]`` scores. Then ``qk_prep``
    each way at ``prep``, the block-diffusion cell's q and k projections
    ``(B, S, heads, D)``, beside the ``jnp`` chain it replaces
    (:func:`_qk_prep_times`), and ``mla_prep`` each way at ``latent``, the
    latent-attention cell's projections ``(B, S, heads, nope, rope, v)``,
    beside the chain it replaces (:func:`_mla_prep_times`)."""
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.ops import kernel_impl
    from distkeras_tpu.ops.flash_attention import flash_attention
    from distkeras_tpu.ops.pallas_kernels import fused_adam
    from distkeras_tpu.ops.quant import _q_matmul_xla, q_matmul, quantize
    from distkeras_tpu.ops.recurrent import lstm_scan, lstm_scan_reference
    from distkeras_tpu.parallel.sequence import attention_reference

    t0 = time.perf_counter()
    bf16 = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 32))
    errs: dict[str, float] = {}

    def fwd_bwd(fn, args, cot):
        """(out, *grads) of ``fn`` at ``args`` under cotangent ``cot`` — an
        ARGUMENT of the program: closed over, its tens of megabytes would be
        a constant in the HLO and in the compile-cache entry."""
        def run(cot, *a):
            out, vjp = jax.vjp(fn, *a)
            return (out,) + vjp(cot.astype(out.dtype))
        return jax.jit(run)(cot, *args)

    def compare(name, got, want, parts):
        for part, g, w in zip(parts, got, want):
            errs[f"{name}.{part}"] = (_norm_err(g, w), str(w.dtype))

    L = attn[1]
    q, k, v, g = (jax.random.normal(next(keys), attn, bf16) for _ in range(4))
    compare(
        "flash",
        fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=interpret), (q, k, v), g),
        fwd_bwd(lambda q, k, v: attention_reference(
            q, k, v, causal=True), (q, k, v), g),
        ("out", "dq", "dk", "dv"),
    )
    if (L // 2) % 128 == 0:      # the same arrays as a noised and a clean copy
        compare(
            "flash_bd",
            fwd_bwd(lambda q, k, v: flash_attention(
                q, k, v, block_diffusion=block, interpret=interpret),
                (q, k, v), g),
            fwd_bwd(lambda q, k, v: attention_reference(
                q, k, v, block_diffusion=block), (q, k, v), g),
            ("out", "dq", "dk", "dv"),
        )

    if wide:
        Bw, Lw, Hw, Dk, Dv = wide
        qw, kw, vw, gw = (jax.random.normal(next(keys), (Bw, Lw, Hw, d), bf16)
                          for d in (Dk, Dk, Dv, Dv))
        compare(
            f"flash_{Dk}_{Dv}",
            fwd_bwd(lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=interpret), (qw, kw, vw), gw),
            fwd_bwd(lambda q, k, v: attention_reference(
                q, k, v, causal=True), (qw, kw, vw), gw),
            ("out", "dq", "dk", "dv"),
        )

    for m, kk, n in qmm:
        x = jax.random.normal(next(keys), (m, kk), bf16)
        qt = quantize(jax.random.normal(next(keys), (kk, n), jnp.float32))
        got = jax.jit(lambda x, qt: q_matmul(
            x, qt, impl="pallas", interpret=interpret))(x, qt)
        want = jax.jit(lambda x, qt: _q_matmul_xla(x, qt, x.dtype))(x, qt)
        compare(f"q_matmul[{m},{kk}]x[{kk},{n}]", (got,), (want,), ("out",))

    # two updates, so the second runs on non-zero moments and a bias
    # correction that is not 1/(1-b)
    p = {"w": jax.random.normal(next(keys), adam, jnp.float32)}
    g1, g2 = ({"w": jax.random.normal(next(keys), adam, jnp.float32)}
              for _ in range(2))

    def two_updates(tx):
        def run(p, g1, g2):
            u1, s = tx.update(g1, tx.init(p), p)
            u2, s = tx.update(g2, s, optax.apply_updates(p, u1))
            return u1["w"], u2["w"], s
        return jax.jit(run)(p, g1, g2)

    fu1, fu2, fs = two_updates(fused_adam(1e-3, interpret=interpret))
    ou1, ou2, os_ = two_updates(optax.adam(1e-3))
    compare("fused_adam", (fu1, fu2, fs.mu["w"], fs.nu["w"]),
            (ou1, ou2, os_[0].mu["w"], os_[0].nu["w"]),
            ("update1", "update2", "mu", "nu"))

    Bl, T, Hl = lstm
    gx = (0.5 * jax.random.normal(next(keys), (Bl, T, 4 * Hl))).astype(bf16)
    wh = jax.random.normal(next(keys), (Hl, 4 * Hl)) / math.sqrt(Hl)
    probe = jax.random.normal(next(keys), (Bl, T, Hl))
    compare(
        "lstm",
        fwd_bwd(lambda gx, wh: lstm_scan(
            gx, wh, impl="pallas", interpret=interpret), (gx, wh), probe),
        fwd_bwd(lstm_scan_reference, (gx, wh), probe),
        ("hs", "dgates", "dwh"),
    )

    # what "auto" resolves to for these shapes: natively it must be the
    # kernel everywhere, or the main path would run the references
    auto = {
        "attention": kernel_impl("attention", L=L),
        "lstm_scan": kernel_impl("lstm_scan", B=Bl, H=Hl),
        "q_matmul": kernel_impl("q_matmul", k=qmm[0][1], n=qmm[0][2]),
        **({"qk_prep": kernel_impl("qk_prep", S=prep[0][1], D=prep[0][3])}
           if prep else {}),
        **({"mla_prep": kernel_impl("mla_prep", **dict(zip(
            ("S", "heads", "nope", "rope", "v"), latent[0][1:])))}
           if latent else {}),
    }
    preps = _qk_prep_times(prep, interpret)
    for entry in preps:
        for part, e in entry["norm_err"].items():
            # the weight's gradient is f32 but sums products of bf16 rows
            errs[f"qk_prep{entry['shape'][2]}.{part}"] = (e, "bfloat16")
    latents = _mla_prep_times(latent, interpret)
    for entry in latents:
        for part, e in entry["norm_err"].items():
            errs[f"mla_prep{entry['shape'][2]}.{part}"] = (e, "bfloat16")
    line = _report("kernels", t0, interpret=bool(interpret), auto=auto,
                   norm_err={k: e for k, (e, _) in errs.items()},
                   flash=_flash_times(timed, interpret), qk_prep=preps,
                   mla_prep=latents)
    for name, (e, dtype) in errs.items():
        # wh's gradient is f32 but flows through the bf16 recurrence
        tol = TOL["bfloat16"] if name.startswith("lstm") else TOL[dtype]
        _check(e <= tol, f"kernels: {name} off its reference by {e:.3g} "
                         f"(normalized), tolerance {tol:g}")
    if not interpret:
        _check(set(auto.values()) <= {"flash", "pallas"},
               f"kernels: 'auto' does not pick the kernels here: {auto}")
    return line


# ---------------------------------------------------------------------------
# loss: the fused cross-entropy alone, at the benchmark cells' shapes
# ---------------------------------------------------------------------------


def loss(*, shapes=((32768, 2048, 32784, 256), (16384, 1024, 256008, 256)),
         timed=True, reps=3, check_rows=128):
    """``value_and_grad`` of ``chunked_softmax_cross_entropy`` alone at each
    ``(N, D, V, chunk)`` of ``shapes`` (bf16 hidden and head; the two
    benchmark cells' by default): the static count of what its backward runs
    (``_vocab_tiles``: tiles, their width, padded columns, bytes of the
    float32 ``[N, D]`` carry read and written a call), ms a call and the four
    longest operations on the chip (None where ``timed`` is off: a CPU gives
    no device time), and the hidden gradient of the first ``check_rows``
    rows against the plain softmax formula on those rows."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops import fused_ce

    t0 = time.perf_counter()
    out = []
    for n, d, v, chunk in shapes:
        kh, kw, ky = jax.random.split(jax.random.PRNGKey(SEED + v), 3)
        h = jax.random.normal(kh, (n, d), jnp.bfloat16)
        w = (0.02 * jax.random.normal(kw, (d, v))).astype(jnp.bfloat16)
        y = jax.random.randint(ky, (n,), 0, v, jnp.int32)
        step = jax.jit(jax.value_and_grad(
            lambda h, w, y: fused_ce.chunked_softmax_cross_entropy(
                h, y, w, chunk=chunk), argnums=(0, 1)))
        value, (dh, _) = jax.block_until_ready(step(h, w, y))   # compiles

        def plain_dh(h, w, y):
            logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
            p = jax.nn.softmax(logits, axis=-1)
            dl = (p - jax.nn.one_hot(y, v, dtype=p.dtype)) / n
            return jnp.dot(dl.astype(h.dtype), w.T,
                           preferred_element_type=jnp.float32)

        err = _norm_err(dh[:check_rows], jax.jit(plain_dh)(
            h[:check_rows], w, y[:check_rows]))
        ms = ops = None
        if timed:
            by_op = _device_ms_by_op(
                lambda: jax.block_until_ready(
                    [step(h, w, y) for _ in range(reps)]), reps)
            ms = round(sum(by_op.values()), 3)
            ops = {k: round(by_op[k], 3)
                   for k in sorted(by_op, key=by_op.get, reverse=True)[:4]}
        tiles, vb = fused_ce._vocab_tiles(n, v, chunk)
        out.append({"shape": [n, d, v, chunk], "tiles": tiles, "vb": vb,
                    "padded_columns": tiles * vb - v,
                    "carry_bytes_moved": 8 * n * d * tiles,
                    "loss": float(value), "dh_norm_err": err,
                    "ms": ms, "ops": ops})
    line = _report("loss", t0, timed=bool(timed), shapes=out)
    for o in out:
        _check(math.isfinite(o["loss"]), f"loss: {o['loss']} at {o['shape']}")
        _check(o["dh_norm_err"] <= TOL["bfloat16"],
               f"loss: d_hidden off the plain formula by "
               f"{o['dh_norm_err']:.3g} at {o['shape']}")
    return line


# ---------------------------------------------------------------------------
# adag: the paper's path
# ---------------------------------------------------------------------------


def _adag_trainer(num_workers, mesh, batch_size, window, epochs,
                  optimizer=("adam", 1e-3)):
    from distkeras_tpu import ADAG
    from distkeras_tpu.models import lenet

    return ADAG(lenet(), loss="sparse_softmax_cross_entropy",
                worker_optimizer=optimizer[0], learning_rate=optimizer[1],
                batch_size=batch_size, communication_window=window,
                num_epoch=epochs, num_workers=num_workers, mesh=mesh)


def _accuracy(trainer, params, test) -> float:
    import jax

    spec = trainer.spec
    out, _ = jax.jit(lambda p, n, x: spec.apply(p, n, x, False))(
        params, trainer.trained_nt_, test["features"])
    return float(np.mean(np.argmax(np.asarray(out), -1) == test["label"]))


def adag(*, n_train=8192, n_test=1024, batch_size=128, window=4, epochs=2,
         num_workers=None, min_accuracy=ADAG_CPU_ACCURACY - 0.02):
    """``ADAG(lenet()).train`` on the MNIST stand-in, as the verify skill
    drives it: the loss must fall by more than half, held-out accuracy must
    reach the CPU rehearsal's (less 0.02), and the state the trainer's
    engine builds must live on the devices of its mesh."""
    import jax

    from distkeras_tpu.datasets import is_synthetic, mnist

    t0 = time.perf_counter()
    train, test = mnist(n_train=n_train, n_test=n_test)
    t = _adag_trainer(num_workers, None, batch_size, window, epochs)
    params = t.train(train, shuffle=True)
    losses = t.get_history().losses()
    acc = _accuracy(t, params, test)
    state = t._build_engine().init_state(*t.spec.init_np(t.seed))
    placed = _device_set((state.center, state.workers))
    line = _report(
        "adag", t0, synthetic_data=is_synthetic("mnist"),
        windows=len(losses), loss_first=losses[0], loss_last=losses[-1],
        accuracy=acc, min_accuracy=min_accuracy,
        state_devices=sorted(str(d) for d in placed),
    )
    _check(all(math.isfinite(x) for x in losses), "adag: non-finite loss")
    _check(losses[-1] < 0.5 * losses[0],
           f"adag: loss fell {losses[0]:.4f} -> {losses[-1]:.4f}, not by half")
    _check(acc >= min_accuracy,
           f"adag: held-out accuracy {acc:.4f} < {min_accuracy:.4f}")
    _check(placed == set(t.mesh.devices.flat)
           and placed <= set(jax.devices()),
           f"adag: trainer state on {placed}, mesh {t.mesh.devices}")
    return line


# ---------------------------------------------------------------------------
# lm: MeshTrainer on the config-9 decoder
# ---------------------------------------------------------------------------


def _lm_spec(vocab, maxlen, dim, heads, depth, ce_chunk, plain=False):
    """The decoder on its kernel path (bf16, flash attention, fused chunked
    cross-entropy) or, ``plain``, its float32 twin on the reference path —
    one parameter tree serves both."""
    import jax.numpy as jnp

    from distkeras_tpu.models import transformer_lm

    kw = dict(vocab=vocab, maxlen=maxlen, dim=dim, heads=heads, depth=depth,
              pos_embedding="rope")
    if plain:
        return transformer_lm(dtype=jnp.float32, attn_impl="reference",
                              fused_ce=False, **kw)
    return transformer_lm(dtype=jnp.bfloat16, attn_impl="flash",
                          fused_ce=True, ce_chunk=ce_chunk, remat=False, **kw)


def _token_dataset(vocab, maxlen, rows):
    from distkeras_tpu.data import Dataset

    toks = np.random.default_rng(SEED).integers(
        0, vocab, size=(rows, maxlen + 1)).astype(np.int32)
    return Dataset({"features": toks[:, :-1], "label": toks[:, 1:]})


def _lm_trainer(spec, mesh_shape, sharding, batch, epochs):
    from distkeras_tpu.trainers import MeshTrainer

    return MeshTrainer(
        spec, loss="sparse_softmax_cross_entropy", worker_optimizer="adam",
        learning_rate=1e-4, mesh_shape=mesh_shape,
        parameter_sharding=sharding, batch_size=batch, num_epoch=epochs,
        input_mode="resident", seed=SEED,
    )


def _compiled_step(trainer, init, ds, batch):
    """The trainer's own step program, compiled for its mesh on the state
    its engine builds from ``init = (params, nt)``: ``(compiled, params as
    placed)``."""
    engine, to_engine, _ = trainer._build_engine()
    params, nt, opt = engine.init_state(to_engine(init[0]), init[1])
    first = engine.place_batch((ds["features"][:batch], ds["label"][:batch]))
    return engine._step.lower(params, nt, opt, first).compile(), params


def lm(*, vocab=16384, maxlen=2048, dim=1024, heads=8, depth=8, ce_chunk=512,
       batch=8, steps=4, epochs=2, kernel_calls=24):
    """``MeshTrainer(...).train`` on ``transformer_lm`` with flash attention,
    RoPE and the fused cross-entropy in bf16: the compiled step must hold
    ``kernel_calls`` Pallas kernel calls, every loss must be finite and the
    first within 1.0 of ln(vocab), and that first loss must equal the plain
    model's (reference attention, unfused loss, true float32) on the same
    batch and parameters to bf16 tolerance."""
    import jax

    from distkeras_tpu.ops import get_loss

    t0 = time.perf_counter()
    model = (vocab, maxlen, dim, heads, depth, ce_chunk)
    spec, plain = _lm_spec(*model), _lm_spec(*model, plain=True)
    ds = _token_dataset(vocab, maxlen, batch * steps)
    trainer = _lm_trainer(spec, {"dp": 1}, "megatron", batch, epochs)
    trainer.train(ds)
    losses = trainer.get_history().losses()
    peak = _peak_bytes(trainer.mesh.devices.flat)

    p0, nt0 = spec.init_np(SEED)
    compiled, _ = _compiled_step(trainer, (p0, nt0), ds, batch)
    calls = compiled.as_text().count(KERNEL_CALL)
    mem = compiled.memory_analysis()

    # the plain twin shares the parameter tree; HIGHEST makes its float32
    # matmuls real float32 on a TPU too (the default rounds through bf16)
    x, y = ds["features"][:batch], ds["label"][:batch]
    loss_fn = get_loss("sparse_softmax_cross_entropy")
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(
            lambda p, x, y: loss_fn(y, plain.apply(p, nt0, x, False)[0])
        )(p0, x, y))

    line = _report(
        "lm", t0, steps=len(losses), losses=[round(v, 4) for v in losses],
        ln_vocab=round(math.log(vocab), 4), plain_f32_first_loss=want,
        kernel_calls_in_step=calls,
        step_temp_bytes=getattr(mem, "temp_size_in_bytes", None),
        peak_bytes_in_use=peak,
    )
    _check(len(losses) == steps * epochs,
           f"lm: {len(losses)} losses for {steps * epochs} steps")
    _check(all(math.isfinite(v) for v in losses), "lm: non-finite loss")
    # unit-variance logits at initialisation put the loss near ln V + 1/2
    _check(abs(losses[0] - math.log(vocab)) <= 1.0,
           f"lm: first loss {losses[0]:.4f} not near ln(vocab)")
    _check(abs(losses[0] - want) <= 1e-2 * abs(want),
           f"lm: first loss {losses[0]:.5f} vs plain float32 {want:.5f}")
    _check(calls == kernel_calls,
           f"lm: {calls} Pallas kernel calls in the compiled step, "
           f"expected {kernel_calls}")
    return line


# ---------------------------------------------------------------------------
# moe: MeshTrainer on the ZAYA1 block (CCA + dropless routed experts)
# ---------------------------------------------------------------------------


def _zaya_spec(vocab, maxlen, dim, heads, kv_heads, depth, zaya, ce_chunk,
               plain=False):
    """``_lm_spec`` with ZAYA1 blocks: the kernel path under remat, or its
    float32 twin (reference attention, unfused loss)."""
    import jax.numpy as jnp

    from distkeras_tpu.models import transformer_lm

    kw = dict(vocab=vocab, maxlen=maxlen, dim=dim, heads=heads,
              kv_heads=kv_heads, depth=depth, pos_embedding="rope",
              tie_embeddings=True, zaya=zaya)
    if plain:
        return transformer_lm(dtype=jnp.float32, attn_impl="reference",
                              fused_ce=False, **kw)
    return transformer_lm(dtype=jnp.bfloat16, attn_impl="flash",
                          fused_ce=True, ce_chunk=ce_chunk, remat=True, **kw)


def moe(*, vocab=8192, maxlen=1024, dim=512, heads=4, kv_heads=2, depth=2,
        head_dim=128, router_dim=128, experts=8, experts_held=(0, 4),
        expert_dim=512, ce_chunk=256, batch=4, steps=4, epochs=2,
        kernel_calls=28, top_k=(4096, 512, 256, 32, 8, 8)):
    """``MeshTrainer(...).train`` on ``transformer_lm(zaya=...)`` in bf16 with
    flash attention, the fused cross-entropy and remat, holding half the
    router's experts: every loss finite, the first equal to the plain float32
    model's on the same batch and parameters to bf16 tolerance, the compiled
    step holding ``kernel_calls`` kernel calls (flash attention's and the
    grouped expert products'), and the counters the trainer fetched adding
    up to every token once a layer. Then ``dropless_experts`` alone at
    ``top_k = (tokens, dim, expert_dim, experts, held, k)``: the ``k``
    largest of a random router renormalised, the first ``held`` experts held,
    in chunks of ``held_rows``, against every held expert applied to every
    token under its one-hot weight."""
    import jax

    from distkeras_tpu.models import ZayaDims
    from distkeras_tpu.models.lm import moe_tokens
    from distkeras_tpu.ops import get_loss

    t0 = time.perf_counter()
    dims = ZayaDims(head_dim=head_dim, router_dim=router_dim, experts=experts,
                    experts_held=tuple(experts_held), expert_dim=expert_dim)
    model = (vocab, maxlen, dim, heads, kv_heads, depth, dims, ce_chunk)
    spec, plain = _zaya_spec(*model), _zaya_spec(*model, plain=True)
    ds = _token_dataset(vocab, maxlen, batch * steps)
    from distkeras_tpu.trainers import MeshTrainer

    # the benchmark cell's path: streamed batches, the loss and the
    # counters fetched at each epoch's end
    trainer = MeshTrainer(
        spec, loss="sparse_softmax_cross_entropy", worker_optimizer="adam",
        learning_rate=1e-4, mesh_shape={"dp": 1}, batch_size=batch,
        num_epoch=epochs, input_mode="stream", log_metrics=True, seed=SEED)
    trainer.train(ds)
    losses = trainer.get_history().losses()
    routed = moe_tokens(trainer.counters_)

    p0, nt0 = spec.init_np(SEED)
    compiled, _ = _compiled_step(trainer, (p0, nt0), ds, batch)
    calls = compiled.as_text().count(KERNEL_CALL)
    x, y = ds["features"][:batch], ds["label"][:batch]
    loss_fn = get_loss("sparse_softmax_cross_entropy")
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(
            # in training mode, as the step: the routers balance their bias
            lambda p, nt, x, y: loss_fn(y, plain.apply(p, nt, x, True)[0])
        )(p0, nt0, x, y))

    first, count = experts_held
    top = _top_k_experts(*top_k)
    line = _report(
        "moe", t0, steps=len(losses), losses=[round(v, 4) for v in losses],
        plain_f32_first_loss=want, kernel_calls_in_step=calls,
        tokens_by_layer_and_expert=routed.tolist(),
        held_share=float(routed[:, first:first + count].sum() / routed.sum()),
        top_k=top,
    )
    _check(top["norm_err"] <= TOL["bfloat16"],
           f"moe: top-{top_k[-1]} dropless experts off the one-hot sum by "
           f"{top['norm_err']:.3g} (normalized)")
    _check(top["pairs"] == top_k[0] * top_k[-1],
           f"moe: top-k counted {top['pairs']} pairs")
    _check(len(losses) == steps * epochs,
           f"moe: {len(losses)} losses for {steps * epochs} steps")
    _check(all(math.isfinite(v) for v in losses), "moe: non-finite loss")
    _check(abs(losses[0] - want) <= 1e-2 * abs(want),
           f"moe: first loss {losses[0]:.5f} vs plain float32 {want:.5f}")
    _check(calls == kernel_calls,
           f"moe: {calls} kernel calls in the compiled step, expected "
           f"{kernel_calls}")
    _check(routed.sum(1).tolist() == [batch * steps * epochs * maxlen] * depth,
           f"moe: the counters hold {routed.sum(1).tolist()} tokens a layer")
    return line


def _top_k_experts(tokens, dim, expert_dim, experts, held, k) -> dict:
    """``dropless_experts`` on ``[tokens, k]`` pairs (bf16) against the plain
    one-hot sum in float32: the error, the pairs counted and held, and the
    rows a chunk holds."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models import SdarDims
    from distkeras_tpu.models.lm import held_rows
    from distkeras_tpu.parallel.expert import dropless_experts

    ks = jax.random.split(jax.random.PRNGKey(SEED + k), 4)
    x = jax.random.normal(ks[0], (tokens, dim), jnp.bfloat16)
    w_in = jax.random.normal(ks[1], (held, dim, 2 * expert_dim)) * dim ** -0.5
    w_out = jax.random.normal(ks[2], (held, expert_dim, dim)) \
        * expert_dim ** -0.5
    top, chosen = jax.lax.top_k(
        jax.nn.softmax(jax.random.normal(ks[3], (tokens, experts))), k)
    weight = top / top.sum(-1, keepdims=True)
    rows = held_rows(tokens, SdarDims(experts=experts, experts_per_token=k,
                                      experts_held=(0, held)))
    got, pairs = jax.jit(lambda x, a, b: dropless_experts(
        x, chosen.astype(jnp.int32), weight, a, b, experts=(0, held),
        total=experts, rows=rows))(x, w_in, w_out)

    def plain(x, w_in, w_out):
        y = jnp.zeros(x.shape, jnp.float32)
        for j in range(held):
            gu = x @ w_in[j]
            out = (jax.nn.silu(gu[:, :expert_dim]) * gu[:, expert_dim:]) \
                @ w_out[j]
            y = y + jnp.sum(jnp.where(chosen == j, weight, 0.0), -1,
                            keepdims=True) * out
        return y

    with jax.default_matmul_precision("highest"):
        want = jax.jit(plain)(x.astype(jnp.float32), w_in, w_out)
    return {"k": k, "rows_a_chunk": list(rows), "pairs": int(pairs.sum()),
            "pairs_held": int(pairs[:held].sum()),
            "norm_err": _norm_err(got, want)}


# ---------------------------------------------------------------------------
# serve: GenerationEngine behind GenerationServer, four concurrent clients
# ---------------------------------------------------------------------------


def serve(*, vocab=16384, maxlen=1024, dim=2048, heads=16, depth=8,
          kv_heads=1, prompt_lens=(37, 96, 128, 256), new_tokens=32,
          max_batch=8):
    """The 400M MQA decoder in a ``GenerationEngine`` behind a
    ``GenerationServer`` on loopback; one ``GenerationClient`` per prompt
    sends concurrently, greedy. Every request must answer with exactly the
    tokens dense ``models.generate`` emits for that prompt (the repo's
    pinned bit-identity oracle), and the server must stop cleanly."""
    import socket

    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models import generate, transformer_lm
    from distkeras_tpu.serving import (GenerationClient, GenerationEngine,
                                       GenerationServer)

    t0 = time.perf_counter()
    spec = transformer_lm(vocab=vocab, maxlen=maxlen, dim=dim, heads=heads,
                          depth=depth, kv_heads=kv_heads, dtype=jnp.bfloat16,
                          attn_impl="flash", pos_embedding="rope")
    params = jax.device_put(spec.init_np(SEED)[0])
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
               for n in prompt_lens]

    server = GenerationServer(GenerationEngine(spec, params,
                                               max_batch=max_batch))
    server.start()
    answers: list = [None] * len(prompts)

    def ask(i):
        try:
            client = GenerationClient(server.host, server.port)
            try:
                answers[i] = client.generate(prompts[i],
                                             max_new_tokens=new_tokens)
            finally:
                client.close()
        except Exception as e:  # re-raised on the main thread below
            answers[i] = e

    threads = [threading.Thread(target=ask, args=(i,), daemon=True)
               for i in range(len(prompts))]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=900)
    finally:
        server.stop()
    stats = server.stats()
    for a in answers:
        if isinstance(a, Exception):
            raise a
    _check(not any(th.is_alive() for th in threads)
           and all(a is not None for a in answers),
           "serve: a request never answered")

    first_diff = []
    for p, a in zip(prompts, answers):
        oracle = np.asarray(generate(spec, params, p[None], new_tokens))
        oracle = oracle[0, len(p):]
        diff = np.nonzero(np.asarray(a) != oracle)[0] \
            if len(a) == len(oracle) else np.array([0])
        first_diff.append(int(diff[0]) if len(diff) else None)
    try:
        socket.create_connection((server.host, server.port), 1).close()
        stopped = False
    except OSError:
        stopped = True          # nobody listens there any more
    line = _report(
        "serve", t0, requests=len(prompts), prompt_lens=list(prompt_lens),
        new_tokens=new_tokens, completed=stats["completed"],
        mean_batch_occupancy=round(stats["mean_batch_occupancy"], 2),
        first_token_differing_from_dense_generate=first_diff,
        server_stopped=stopped, blocks_in_use=stats["blocks_in_use"],
        params_on=sorted(str(d) for d in _device_set(params)),
    )
    _check(stats["completed"] == len(prompts),
           f"serve: {stats['completed']} of {len(prompts)} completed")
    _check(first_diff == [None] * len(prompts),
           f"serve: token streams differ from dense greedy generate at "
           f"{first_diff} (index of first differing new token per request)")
    _check(stopped and stats["blocks_in_use"] == 0,
           "serve: server did not stop cleanly or leaked cache blocks")
    return line


# ---------------------------------------------------------------------------
# four chips: one SPMD replica per chip, against its one-device twin
# ---------------------------------------------------------------------------


def _rel_l2(tree, ref) -> float:
    """``|tree - ref|_2 / |ref|_2`` over all leaves as one vector."""
    import jax

    flat = lambda t: np.concatenate([np.ravel(x) for x in jax.tree.leaves(t)])
    a, b = flat(tree), flat(ref)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


#: adag4's optimizers: (learning rate, worst relative loss difference from
#: the one-device run, relative L2 of the centers from it or None = not held)
ADAG4_OPTIMIZERS = {"adam": (1e-3, 2e-2, None), "sgd": (1e-2, 2e-3, 2e-3)}


def adag4(*, n_train=8192, n_test=1024, batch_size=128, window=4, epochs=2,
          workers=4, optimizers=("adam", "sgd"),
          min_accuracy=ADAG_CPU_ACCURACY - 0.02):
    """The ``adag`` job with one worker per chip on ``get_mesh(workers)``
    against the same job with the workers stacked on ONE device, and the
    stacked-worker state must really span ``workers`` distinct devices.

    The collective backend is deterministic (one program twice is bitwise
    equal), but these are two programs — ``workers`` replicas vmapped on a
    chip, one replica per chip — and on a TPU at default precision they
    differ in the last bits of a gradient. Adam's first steps are sign-like
    (``lr * g / |g|``), so such a bit in a near-zero gradient becomes a
    whole ``lr`` in the weight: under Adam only the loss curve and the
    accuracy are held equal. The same job under plain SGD, whose response to
    rounding is linear, is what holds the CENTERS to float tolerance."""
    import jax

    from distkeras_tpu.datasets import mnist
    from distkeras_tpu.parallel.mesh import get_mesh

    t0 = time.perf_counter()
    train, test = mnist(n_train=n_train, n_test=n_test)
    meshes = {"spread": get_mesh(workers),
              "stacked": get_mesh(workers, devices=jax.devices()[:1])}
    runs = {}
    for opt in optimizers:
        for name, mesh in meshes.items():
            t = _adag_trainer(workers, mesh, batch_size, window, epochs,
                              (opt, ADAG4_OPTIMIZERS[opt][0]))
            params = t.train(train, shuffle=True)
            runs[opt, name] = (t, params, t.get_history().losses())
    t, params, _ = runs[optimizers[0], "spread"]
    acc = _accuracy(t, params, test)
    loss_err, center_err = {}, {}
    for opt in optimizers:
        (_, p, l), (_, p1, l1) = runs[opt, "spread"], runs[opt, "stacked"]
        loss_err[opt] = max(abs(a - b) / abs(b) for a, b in zip(l, l1))
        center_err[opt] = _rel_l2(p, p1)
    state = t._build_engine().init_state(*t.spec.init_np(t.seed))
    shards = [
        sorted((str(s.device), tuple(s.data.shape))
               for s in leaf.addressable_shards)
        for leaf in jax.tree.leaves(state.workers)
    ]
    line = _report(
        "adag4", t0, workers=workers, accuracy=acc,
        losses={f"{opt}.{name}": [round(v, 5) for v in runs[opt, name][2]]
                for opt in optimizers for name in meshes},
        worst_rel_loss_diff_vs_stacked=loss_err,
        center_rel_l2_vs_stacked=center_err,
        worker_state_shards=shards[0],
    )
    _check(acc >= min_accuracy, f"adag4: accuracy {acc:.4f}")
    for opt in optimizers:
        _, loss_tol, center_tol = ADAG4_OPTIMIZERS[opt]
        _check(loss_err[opt] <= loss_tol,
               f"adag4: {opt} loss curve differs from the one-device run "
               f"by {loss_err[opt]:.3g} (relative), tolerance {loss_tol:g}")
        _check(center_tol is None or center_err[opt] <= center_tol,
               f"adag4: {opt} centers differ from the one-device run by "
               f"{center_err[opt]:.3g} (relative L2), tolerance {center_tol}")
    for sh in shards:
        _check(len({dev for dev, _ in sh}) == workers
               and all(shape[0] == 1 for _, shape in sh),
               f"adag4: stacked-worker leaf not one worker per device: {sh}")
    return line


def lm4(*, vocab=16384, maxlen=2048, dim=1024, heads=8, depth=8, ce_chunk=512,
        batch=8, steps=4, dp=4):
    """The ``lm`` model under ``MeshTrainer(mesh_shape={"dp": dp},
    parameter_sharding="fsdp")`` against ``{"dp": 1}`` on the same batches:
    losses must agree to bf16 tolerance, every sharded parameter's shards
    must sit on ``dp`` devices, and the compiled step must gather the
    parameters and scatter the gradients."""
    import jax

    t0 = time.perf_counter()
    spec = _lm_spec(vocab, maxlen, dim, heads, depth, ce_chunk)
    ds = _token_dataset(vocab, maxlen, batch * steps)
    sharded = _lm_trainer(spec, {"dp": dp}, "fsdp", batch, 1)
    sharded.train(ds)
    losses = sharded.get_history().losses()
    peak = _peak_bytes(sharded.mesh.devices.flat)
    single = _lm_trainer(spec, {"dp": 1}, "megatron", batch, 1)
    single.train(ds)
    losses1 = single.get_history().losses()

    compiled, params = _compiled_step(sharded, spec.init_np(SEED), ds, batch)
    text = compiled.as_text()
    collectives = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                   for op in ("all-gather", "reduce-scatter", "all-reduce")}
    n_sharded, small = 0, 0
    for leaf in jax.tree.leaves(params):
        devs = {s.device for s in leaf.addressable_shards}
        _check(len(devs) == dp, f"lm4: a parameter sits on {len(devs)} devices")
        if leaf.addressable_shards[0].data.size * dp == leaf.size:
            n_sharded += 1
        else:
            small += 1          # below fsdp's size floor: replicated
    line = _report(
        "lm4", t0, dp=dp, losses=[round(v, 4) for v in losses],
        losses_one_device=[round(v, 4) for v in losses1],
        params_sharded=n_sharded, params_replicated_small=small,
        collectives_in_step=collectives,
        kernel_calls_in_step=text.count(KERNEL_CALL),
        peak_bytes_in_use=peak,
    )
    _check(len(losses) == len(losses1) == steps
           and all(math.isfinite(v) for v in losses), "lm4: bad loss count")
    worst = max(abs(a - b) / abs(b) for a, b in zip(losses, losses1))
    _check(worst <= 1e-2, f"lm4: losses differ from one device by {worst:.3g}")
    _check(n_sharded > 0 and collectives["all-gather"] > 0
           and collectives["reduce-scatter"] + collectives["all-reduce"] > 0,
           f"lm4: no sharded parameter or no collective: {collectives}")
    return line


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phases (adag4, lm4)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} x {first.platform} ({first.device_kind})",
              file=sys.stderr)
        return 2

    from distkeras_tpu.utils import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    from distkeras_tpu.observability import trace

    for phase in ((adag4, lm4) if args.chips == 4
                  else (kernels, loss, adag, lm, moe, serve)):
        phase()
    counts = trace.jax_counts()
    print(json.dumps({"phase": "cache", "dir": cache_dir,
                      "hits": counts["cache_hits"],
                      "misses": counts["cache_misses"]}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
