"""The plain reference for ZAYA1: its layer in float32 ``jax.numpy``.

ISSUE 27's section 1, equation by equation (the points it marks [assumed] are
in ``benchmark/configs/zaya1-8b.json`` under ``assumed``): RMSNorm, compressed
convolutional attention (projections into the latent, two causal convolutions
on q and on k, a value whose second half is the previous token's, the q-k
mean, L2-normalised q and k with a temperature a key head, rotary on the
leading part of each head), learned scaling of the residual stream, a router
MLP whose state passes from layer to layer, top-1 of ALL the router's experts
(after a bias that every training step first balances on its own tokens,
``balance``) and SwiGLU experts of which only the held ones add to the result. No kernel,
no sort, no grouped product (each held expert is applied to every token under
a mask), nothing imported from the program. Every matrix product runs at
``HIGHEST`` precision unless ``precision="fp8"``, the cell's control (both
operands of every matrix product rounded to float8 e4m3, as in
``benchmark/reference.py``).

Layers are a Python loop over per-layer leaves, each under ``jax.checkpoint``,
and a step's rows go through in blocks whose gradients are added into one
accumulator in place: beside the weights and the accumulator only one layer's
gradient is ever held, and Adam's two moments rest on the host while a step's
gradients are added up (at the cell's size the four trees and a block's
temporaries are more than one chip holds).
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp

from benchmark import weights_zaya
from benchmark.reference import ADAM_B1, ADAM_B2, ADAM_EPS, _HI, _chunks, _fp8, _gelu, _mm


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _delay(x, n):
    """``x [B, S, C]`` moved ``n`` positions later, zeros in front."""
    return jnp.concatenate([jnp.zeros_like(x[:, :n]), x[:, :x.shape[1] - n]], 1) if n else x


def _einsum(spec, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=_HI)


def _convs(x, w0, w1, precision):
    """A causal depthwise convolution (``w0 [k0, C]``: one filter a channel),
    then a causal convolution grouped by head (``w1 [k1, heads, dh, dh]``);
    ``out[t]`` reads ``x[t - (k-1) + j]`` through ``w[j]``."""
    B, S, _ = x.shape
    k0, (k1, heads, dh, _) = w0.shape[0], w1.shape
    c1 = sum(_delay(x, k0 - 1 - j) * w0[j] for j in range(k0))
    return sum(_einsum("bshd,hde->bshe", _delay(c1, k1 - 1 - j).reshape(B, S, heads, dh),
                       w1[j], precision) for j in range(k1))          # [B, S, heads, dh]


def _rope_part(x, rot, base):
    """Rotate pairs (2i, 2i+1) of the first ``rot`` of ``x [B, S, H, dh]``."""
    S = x.shape[1]
    inv = base ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([turned.reshape(x.shape[:-1] + (rot,)), x[..., rot:]], -1)


def _unit(a, dh):
    return a / jnp.sqrt(jnp.sum(jnp.square(a), -1, keepdims=True)) * dh ** 0.5


def cca(m, precision, x, w):
    """Sublayer A on ``x [B, S, D]``; ``w`` one layer's leaves."""
    B, S, _ = x.shape
    H, K, dh = m["heads"], m["kv_heads"], m["head_dim"]
    G = H // K
    h = _rms(x, w["ln1_g"], m["norm_eps"])
    q0, k0 = _mm(h, w["wq"], precision), _mm(h, w["wk"], precision)
    v = jnp.concatenate([_mm(h, w["wv_a"], precision),
                         _delay(_mm(h, w["wv_b"], precision), 1)], -1).reshape(B, S, K, dh)
    qh, kh = q0.reshape(B, S, K, G, dh), k0.reshape(B, S, K, 1, dh)
    q = _convs(q0, w["cq0"], w["cq1"], precision).reshape(B, S, K, G, dh) + (qh + kh) / 2
    k = _convs(k0, w["ck0"], w["ck1"], precision) + (kh[:, :, :, 0] + jnp.mean(qh, 3)) / 2
    rot = int(dh * m["rotary_fraction"])
    q = _rope_part(_unit(q, dh).reshape(B, S, H, dh), rot, m["rope_base"])
    k = _rope_part(_unit(k, dh) * w["tau"][:, None], rot, m["rope_base"])
    s = _einsum("blkgd,bmkd->bkglm", q.reshape(B, S, K, G, dh), k, precision) * dh ** -0.5
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
    o = _einsum("bkglm,bmkd->blkgd", p, v, precision).reshape(B, S, H * dh)
    return (w["a1"] * x + w["b1"]) + (w["g1"] * _mm(o, w["wo"], precision) + w["e1"])


def route(m, precision, h, r, w):
    """The router: its new state, every expert's probability and the chosen one."""
    r = _mm(h, w["wd"], precision) + w["gamma"] * r
    s = _gelu(_mm(_rms(r, w["lnr_g"], m["norm_eps"]), w["w1"], precision))
    s = _mm(_gelu(_mm(s, w["w2"], precision)), w["w3"], precision)
    p = jax.nn.softmax(s, axis=-1)
    return r, p, jnp.argmax(p + w["rbias"], axis=-1)


BALANCE_SWEEPS = 2


def balance(p, bias, sweeps=BALANCE_SWEEPS):
    """``bias [E]`` after ``sweeps`` sweeps over the experts: in turn each
    expert's bias is set where exactly ``T // E`` of ``p [T, E]``'s rows
    prefer it to the best of the others (half-way between the two rows at
    the cut), the others' held; at the end the mean is taken off."""
    T, E = p.shape
    k = T // E

    def one(i, bias):
        e = i % E
        best_other = jnp.max(jnp.where(jnp.arange(E) == e, -jnp.inf, p + bias), -1)
        margin = jnp.sort(best_other - p[:, e])
        return bias.at[e].set(0.5 * (margin[k - 1] + margin[k]))

    bias = jax.lax.fori_loop(0, sweeps * E, one, bias)
    return bias - jnp.mean(bias)


def step_balancer(m, precision="float32", held=None):
    """``f(w, tokens) -> [bias of layer 0, ...]``: what a training step on
    ``tokens [rows, S]`` makes of the routers' balancing bias ``w["rbias"]``
    before it routes. Layer by layer over ALL the step's rows (a layer's bias
    is balanced on every token's probabilities at once, the layers before it
    already routed with theirs), the attention a row at a time."""
    rows = lambda f, *xs: jax.lax.map(lambda a: f(*(b[None] for b in a))[0], xs)
    attend = jax.jit(lambda x, wl: rows(lambda x: cca(m, precision, x, wl), x))
    probs = jax.jit(lambda x, r, wl: route(
        m, precision, _rms(x, wl["ln2_g"], m["norm_eps"]), r, wl)[1])
    finish = jax.jit(lambda x, r, wl: experts(m, precision, x, r, wl, held)[:2])
    solve = jax.jit(lambda p, b: balance(p.reshape(-1, p.shape[-1]), b))

    def f(w, tokens):
        x = w["embed"][jnp.asarray(tokens)]
        r = jnp.zeros(x.shape[:2] + (m["router_dim"],), jnp.float32)
        out = []
        for i in range(m["depth"]):
            wl = _layer(m, w, i)
            x = attend(x, wl)
            out.append(solve(probs(x, r, wl), wl["rbias"]))
            x, r = finish(x, r, dict(wl, rbias=out[-1]))
        return out

    return f


def experts(m, precision, x, r, w, held=None):
    """Sublayer B on ``x [B, S, D]`` with router state ``r [B, S, R]``. Returns
    the new ``x``, the new ``r`` and the chosen expert of every token."""
    first, count = held or weights_zaya.held(m)
    F = m["expert_dim"]
    h = _rms(x, w["ln2_g"], m["norm_eps"])
    r, p, chosen = route(m, precision, h, r, w)
    weight = jnp.take_along_axis(p, chosen[..., None], -1)
    y = jnp.zeros_like(x)
    for j in range(count):
        gu = _mm(h, w["ex_in"][j], precision)
        out = _mm(jax.nn.silu(gu[..., :F]) * gu[..., F:], w["ex_out"][j], precision)
        y = y + jnp.where(chosen[..., None] == first + j, weight * out, 0.0)
    return (w["a2"] * x + w["b2"]) + (w["g2"] * y + w["e2"]), r, chosen


def _layer(m, w, i):
    return {n: w[n][i] for n in weights_zaya.block_leaves(m)}


def hidden(m, w, tokens, precision="float32", held=None):
    """Final hidden states ``[B, S, D]`` after the head's norm, and the chosen
    expert of every token in every layer ``[depth, B, S]``."""
    x = w["embed"][tokens]
    r = jnp.zeros(x.shape[:2] + (m["router_dim"],), jnp.float32)

    @jax.checkpoint
    def block(x, r, wl):
        return experts(m, precision, cca(m, precision, x, wl), r, wl, held)

    chosen = []
    for i in range(m["depth"]):
        x, r, c = block(x, r, _layer(m, w, i))
        chosen.append(c)
    return _rms(x, w["lnf_g"], m["norm_eps"]), jnp.stack(chosen)


def logits(m, w, tokens, precision="float32", held=None):
    with jax.default_matmul_precision("highest"):
        return _mm(hidden(m, w, tokens, precision, held)[0], w["embed"].T, precision)


def nll_sum(m, w, tokens, labels, precision="float32", chunk=512, held=None):
    """Sum over all positions of -log softmax(head(hidden))[label], and the
    chosen experts."""
    h, chosen = hidden(m, w, tokens, precision, held)
    h = h.reshape(-1, m["dim"])
    c = _chunks(h.shape[0], chunk)
    head = w["embed"].T

    @jax.checkpoint
    def one(hy):
        hc, yc = hy
        z = _mm(hc, head, precision)
        return jnp.sum(jax.nn.logsumexp(z, -1) - jnp.take_along_axis(z, yc[:, None], -1)[:, 0])

    return jnp.sum(jax.lax.map(one, (h.reshape(-1, c, m["dim"]), labels.reshape(-1, c)))), chosen


def train_steps(m, seed, batches, learning_rate, precision="float32", rows_per_block=1,
                half_batch=False, held=None):
    """The first ``len(batches)`` Adam steps from the seed's weights, with
    ``benchmark/reference.py``'s constants. Returns each step's mean loss, the
    first step's per-leaf gradient norms, the per-leaf norm of the parameters'
    change after the last step, and the first step's chosen experts
    ``[depth, rows, S]``. ``half_batch`` (a step that drops the second half of
    its rows) and ``held`` (other experts than the configuration's, with their
    own weights) plant the controls' faults; no run uses them. The routers'
    balancing bias starts at the seed's and, as in the program, each step
    first balances it on its own tokens (``step_balancer``: one more forward
    pass, over all the step's rows at once) and routes with the result."""
    mh = dict(m, experts_held=list(held)) if held else m
    with jax.default_matmul_precision("highest"):
        key = weights_zaya.seed_key(seed)
        make = jax.jit(lambda key: weights_zaya.layered(mh, key))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def add_grads(acc, w, x, y):
            (l, chosen), g = jax.value_and_grad(
                lambda w_: nll_sum(mh, w_, x, y, precision), has_aux=True)(w)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), chosen

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def adam(w, mu, nu, g, t):
            mu = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, mu, g)
            nu = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, nu, g)
            w = jax.tree.map(
                lambda p, a, b: p - learning_rate * (a / (1 - ADAM_B1 ** t))
                / (jnp.sqrt(b / (1 - ADAM_B2 ** t)) + ADAM_EPS), w, mu, nu)
            return w, mu, nu

        norms = jax.jit(lambda tree: weights_zaya.leaf_norms(mh, tree))
        change = jax.jit(lambda w, key: weights_zaya.leaf_norms(mh, jax.tree.map(
            jnp.subtract, w, weights_zaya.layered(mh, key))))
        zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w))

        balanced = step_balancer(mh, precision, held)
        mean = jax.jit(lambda g, n: jax.tree.map(lambda a: a / n, g), donate_argnums=0)
        clock, spent = time.perf_counter(), {}

        def lap(what, *ready):
            nonlocal clock
            jax.block_until_ready(ready)
            now = time.perf_counter()
            spent[what] = spent.get(what, 0.0) + now - clock
            clock = now

        w = make(key)
        lap("weights", w)
        mu = nu = None             # between steps the moments rest on the host
        losses, grad_norms, routes = [], None, None
        for t, (x, y) in enumerate(batches, 1):
            if half_batch:
                x, y = x[: len(x) // 2], y[: len(y) // 2]
            rb = _chunks(x.shape[0], rows_per_block)
            # no gradient reaches the bias, so Adam leaves it where this puts it
            w = dict(w, rbias=balanced(w, x))
            lap("balancing", w)
            acc, chosen = (jnp.zeros((), jnp.float32), zeros(w)), []
            for lo in range(0, x.shape[0], rb):
                acc, c = add_grads(acc, w, jnp.asarray(x[lo:lo + rb]), jnp.asarray(y[lo:lo + rb]))
                chosen.append(c)
            n = x.shape[0] * x.shape[1]
            g = mean(acc[1], jnp.float32(n))
            losses.append(float(acc[0]) / n)
            lap(f"gradients {t}", g)
            chosen = jnp.concatenate(chosen, axis=1)
            if grad_norms is None:
                grad_norms = jax.device_get(norms(g))
                routes = jax.device_get(chosen)
                lap("norms and routes")
            mu, nu = (zeros(w), zeros(w)) if mu is None else jax.device_put((mu, nu))
            lap("moments to the chip", mu, nu)
            w, mu, nu = adam(w, mu, nu, g, jnp.float32(t))
            del g
            lap("adam", w)
            mu, nu = jax.device_get((mu, nu)) if t < len(batches) else (None, None)
            lap("moments to the host")
        delta = jax.device_get(change(w, key))
        lap("norms and routes")
        print("reference_zaya.train_steps, seconds: "
              + ", ".join(f"{k} {v:.1f}" for k, v in spent.items()), file=sys.stderr)
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta,
            "routes": routes}
