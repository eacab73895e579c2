"""The plain reference for kanana-2-30b-a3b's layer (``model_type``
``deepseek_v3`` without a query latent), trained on next tokens: float32
``jax.numpy``, written from ISSUE 33's equations.

Multi-head latent attention: ``q = n Wq`` as heads of ``qk_nope_dim +
qk_rope_dim``; ``(c, k_rope) = split(n Wkva)``, ``k_rope`` ONE vector a token
for all heads; ``RMSNorm(c) Wkvb`` as heads of ``qk_nope_dim + v_dim``; rotary
(pairs ``(2i, 2i+1)``, theta from the configuration) over q's rotary columns
and over ``k_rope``; the score of a head is the SUM ``q_nope . k_nope + q_rope .
k_rope`` (no key is ever built ``qk_nope_dim + qk_rope_dim`` wide) over
``sqrt(qk_nope_dim + qk_rope_dim)``, causal. Layer 0 (the leading
``dense_layers``) has a dense SwiGLU; the others a float32 sigmoid router
whose ``experts_per_token`` largest of ``s + b`` are chosen and weighted
``route_scale * s_e / (sum of the chosen s + 1e-20)``, SwiGLU experts of which
only the held ones add to the result, and one shared SwiGLU on every token.
An untied head and the shifted next-token cross-entropy (the batch's labels).
After a step every router's bias moves by ``bias_rate * sign(mean(n) - n)``,
``n`` the step's (token, expert) pairs by expert over ALL experts.

No kernel, no sort, no grouped product, no chunks of pairs (each held expert
is applied to every position under a one-hot weight), nothing imported from
the program. Every matrix product runs at ``HIGHEST`` precision unless
``precision="fp8"`` (both operands of every matrix product rounded to float8
e4m3, as in ``benchmark/reference.py``).

Layers are a Python loop over per-layer leaves, each under ``jax.checkpoint``;
attention goes a block of queries at a time and a step's rows a block of rows
at a time, their gradients added into one accumulator in place; Adam's two
moments rest on the host while a step's gradients are added up.

``fault`` plants the faults only this layer can hide (the cell's controls; no
run uses them): ``"no_rope"`` (``q_rope . k_rope`` left out of the score),
``"scale_128"`` (the scale ``1 / sqrt(qk_nope_dim)``), ``"biased_weights"``
(the weights taken from ``s + b``), ``"unscaled"`` (``route_scale`` left
out), ``"no_shared"`` (the shared expert left out).
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp

from benchmark import weights_kanana
from benchmark.reference import ADAM_B1, ADAM_B2, ADAM_EPS, _chunks, _mm
from benchmark.reference_sdar import _einsum, _rms

FAULTS = ("no_rope", "scale_128", "biased_weights", "unscaled", "no_shared")


def _rope(x, base):
    """Rotate pairs (2i, 2i+1) of ``x [B, S, ..., dr]`` by the position ``0 ..
    S - 1`` along axis 1."""
    S, dr = x.shape[1], x.shape[-1]
    inv = base ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (dr // 2,))
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1).reshape(x.shape)


def _swiglu(n, w_in, w_out, precision):
    """``(silu(n Wg) * (n Wu)) Wd``, gate and up side by side in ``w_in``."""
    gu = _mm(n, w_in, precision)
    f = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w_out, precision)


def attention(m, precision, x, w, queries=1024, fault=None):
    """The latent attention sublayer on ``x [B, S, D]``, a block of
    ``queries`` queries at a time."""
    B, S, _ = x.shape
    H, R = m["heads"], m["kv_rank"]
    dn, dr, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_dim"]
    n = _rms(x, w["ln1_g"], m["norm_eps"])
    q = _mm(n, w["wq"], precision).reshape(B, S, H, dn + dr)
    kva = _mm(n, w["wkva"], precision)
    c = _rms(kva[..., :R], w["kvn_g"], m["norm_eps"])
    kv = _mm(c, w["wkvb"], precision).reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], m["rope_base"])
    k_rope = _rope(kva[..., R:], m["rope_base"])                 # [B, S, dr]: every head's
    scale = (dn if fault == "scale_128" else dn + dr) ** -0.5
    qb = _chunks(S, queries)
    keys = jnp.arange(S)[None, :]

    @jax.checkpoint
    def some(args):
        qn, qr, at = args                  # [B, qb, H, dn], [B, qb, H, dr], [qb]
        s = _einsum("blhd,bmhd->bhlm", qn, k_nope, precision)
        if fault != "no_rope":
            s = s + _einsum("blhd,bmd->bhlm", qr, k_rope, precision)
        see = keys <= at[:, None]
        p = jax.nn.softmax(jnp.where(see, s * scale, -jnp.inf), axis=-1)
        return _einsum("bhlm,bmhd->blhd", p, v, precision)

    blocks = lambda a: a.reshape(B, S // qb, qb, H, -1).swapaxes(0, 1)
    o = jax.lax.map(some, (blocks(q_nope), blocks(q_rope),
                           jnp.arange(S).reshape(S // qb, qb)))
    o = o.swapaxes(0, 1).reshape(B, S, H * dv)
    return x + _mm(o, w["wo"], precision)


def dense(m, precision, x, w):
    """A leading layer's second sublayer: a dense SwiGLU."""
    n = _rms(x, w["lnd_g"], m["norm_eps"])
    return x + _swiglu(n, w["dn_in"], w["dn_out"], precision)


def route(m, precision, n, w, bias, fault=None):
    """``(chosen [B, S, k], weight [B, S, k])`` of normed states ``n``."""
    s = jax.nn.sigmoid(_mm(n, w["wr"], precision))
    _, chosen = jax.lax.top_k(s + bias, m["experts_per_token"])
    top = jnp.take_along_axis(s + bias if fault == "biased_weights" else s, chosen, -1)
    scale = 1.0 if fault == "unscaled" else m["route_scale"]
    return chosen, scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)


def experts(m, precision, x, w, bias, held=None, fault=None):
    """The expert sublayer on ``x [B, S, D]``: the held experts' part of the
    routed sum, and the shared expert. Returns the new ``x`` and every
    position's chosen experts ``[B, S, k]``."""
    first, count = held or weights_kanana.held(m)
    n = _rms(x, w["ln2_g"], m["norm_eps"])
    chosen, weight = route(m, precision, n, w, bias, fault)
    y = jnp.zeros_like(x)
    for j in range(count):
        out = _swiglu(n, w["ex_in"][j], w["ex_out"][j], precision)
        y = y + jnp.sum(jnp.where(chosen == first + j, weight, 0.0), -1, keepdims=True) * out
    if fault != "no_shared":
        y = y + _swiglu(n, w["sh_in"], w["sh_out"], precision)
    return x + y, chosen


def moved_bias(m, bias, chosen):
    """A router's bias after a step that chose ``chosen`` (any shape of expert
    numbers): ``b + bias_rate * sign(mean(n) - n)``."""
    n = jnp.bincount(chosen.reshape(-1), length=m["experts"]).astype(jnp.float32)
    return bias + m["bias_rate"] * jnp.sign(jnp.mean(n) - n)


def hidden(m, w, bias, tokens, precision="float32", held=None, queries=1024, fault=None):
    """Final hidden states ``[B, S, D]`` after the head's norm, and the chosen
    experts of every position in every EXPERT layer ``[expert layers, B, S,
    k]``. ``bias``: each expert layer's balancing bias, in order."""
    x = w["embed"][tokens]

    @jax.checkpoint
    def dense_block(x, wl):
        return dense(m, precision, attention(m, precision, x, wl, queries, fault), wl)

    @jax.checkpoint
    def expert_block(x, wl, b):
        return experts(m, precision, attention(m, precision, x, wl, queries, fault), wl, b,
                       held, fault)

    chosen = []
    for i in range(m["depth"]):
        wl = weights_kanana.layer_of(m, w, i)
        if i < m["dense_layers"]:
            x = dense_block(x, wl)
        else:
            x, c = expert_block(x, wl, bias[i - m["dense_layers"]])
            chosen.append(c)
    return _rms(x, w["lnf_g"], m["norm_eps"]), jnp.stack(chosen)


def nll_sum(m, w, bias, x, y, precision="float32", chunk=512, held=None, queries=1024,
            fault=None):
    """Sum over positions of ``-log softmax(head(hidden))[y]``, and the chosen
    experts."""
    h, chosen = hidden(m, w, bias, x, precision, held, queries, fault)
    h = h.reshape(-1, m["dim"])
    c = _chunks(h.shape[0], chunk)

    @jax.checkpoint
    def one(args):
        hc, yc = args
        z = _mm(hc, w["head"], precision)
        return jnp.sum(jax.nn.logsumexp(z, -1) - jnp.take_along_axis(z, yc[:, None], -1)[:, 0])

    parts = jax.lax.map(one, (h.reshape(-1, c, m["dim"]), y.reshape(-1, c)))
    return jnp.sum(parts), chosen


def train_steps(m, seed, batches, learning_rate, precision="float32", rows_per_block=1,
                queries_per_block=1024, held=None, fault=None):
    """The first ``len(batches)`` Adam steps from the seed's weights and bias,
    with ``benchmark/reference.py``'s constants; a batch is ``(x, y)``, ``y``
    the next tokens. Returns each step's loss, the first step's per-leaf
    gradient norms, the per-leaf norm of the parameters' change after the last
    step, the first step's chosen experts ``[expert layers, rows, L, k]``
    (sorted within a position) and the routers' bias after the last step
    ``[expert layers, experts]``. ``held`` (other experts than the
    configuration's, with their own weights), ``precision="fp8"`` and ``fault``
    plant the controls' faults; no run uses them."""
    mh = dict(m, experts_held=list(held)) if held else m
    with jax.default_matmul_precision("highest"):
        key = weights_kanana.seed_key(seed)
        make = jax.jit(lambda key: (weights_kanana.layered(mh, key),
                                    jnp.stack(weights_kanana.router_bias(mh, key))))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def add_grads(acc, w, bias, x, y):
            (l, chosen), g = jax.value_and_grad(
                lambda w_: nll_sum(mh, w_, bias, x, y, precision, queries=queries_per_block,
                                   fault=fault), has_aux=True)(w)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), jnp.sort(chosen, -1)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def adam(w, mu, nu, g, t):
            mu = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, mu, g)
            nu = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, nu, g)
            w = jax.tree.map(
                lambda p, a, b: p - learning_rate * (a / (1 - ADAM_B1 ** t))
                / (jnp.sqrt(b / (1 - ADAM_B2 ** t)) + ADAM_EPS), w, mu, nu)
            return w, mu, nu

        move = jax.jit(lambda bias, chosen: jnp.stack(
            [moved_bias(mh, b, c) for b, c in zip(bias, chosen)]))
        norms = jax.jit(lambda tree: weights_kanana.leaf_norms(mh, tree))
        change = jax.jit(lambda w, key: weights_kanana.leaf_norms(mh, jax.tree.map(
            jnp.subtract, w, weights_kanana.layered(mh, key))))
        zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w))
        mean = jax.jit(lambda g, n: jax.tree.map(lambda a: a / n, g), donate_argnums=0)
        clock, spent = time.perf_counter(), {}

        def lap(what, *ready):
            nonlocal clock
            jax.block_until_ready(ready)
            now = time.perf_counter()
            spent[what] = spent.get(what, 0.0) + now - clock
            clock = now

        w, bias = make(key)
        lap("weights", w)
        mu = nu = None             # between steps the moments rest on the host
        losses, grad_norms, routes = [], None, None
        for t, (x, y) in enumerate(batches, 1):
            x, y = jnp.asarray(x), jnp.asarray(y)
            rb = _chunks(x.shape[0], rows_per_block)
            acc, chosen = (jnp.zeros((), jnp.float32), zeros(w)), []
            for lo in range(0, x.shape[0], rb):
                acc, c = add_grads(acc, w, bias, x[lo:lo + rb], y[lo:lo + rb])
                chosen.append(c)
            chosen = jnp.concatenate(chosen, axis=1)
            bias = move(bias, chosen)
            g = mean(acc[1], jnp.float32(x.size))
            losses.append(float(acc[0]) / x.size)
            lap(f"gradients {t}", g)
            if grad_norms is None:
                grad_norms = jax.device_get(norms(g))
                routes = jax.device_get(chosen)
                lap("norms and routes")
            del chosen
            mu, nu = (zeros(w), zeros(w)) if mu is None else jax.device_put((mu, nu))
            lap("moments to the chip", mu, nu)
            w, mu, nu = adam(w, mu, nu, g, jnp.float32(t))
            del g
            lap("adam", w)
            mu, nu = jax.device_get((mu, nu)) if t < len(batches) else (None, None)
            lap("moments to the host")
        delta = jax.device_get(change(w, key))
        lap("norms and routes")
        print("reference_kanana.train_steps, seconds: "
              + ", ".join(f"{k} {v:.1f}" for k, v in spent.items()), file=sys.stderr)
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta,
            "routes": routes, "bias": jax.device_get(bias)}
