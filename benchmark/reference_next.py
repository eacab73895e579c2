"""The plain reference for Qwen3-Next-80B-A3B's layers (``model_type``
``qwen3_next``), trained on next tokens: float32 ``jax.numpy``, written from
the layer's equations below.

Norms are zero-centred, ``x / sqrt(mean(x^2) + eps) * (1 + w)``. Layer ``i`` is
gated full attention where ``(i + 1) % full_attention_interval == 0``, Gated
DeltaNet otherwise; every layer then has the expert sublayer.

Gated DeltaNet: ``n Wqkvz`` and ``n Wba`` with columns grouped by key head
``g`` (q, k, the v of value heads ``2g`` and ``2g + 1``, their z; b then a of
those two heads); a causal depthwise convolution of ``conv_kernel`` taps and
SiLU over the channels ``[q (all key heads); k; v (all value heads)]``;
``q = l2(q) / sqrt(dk)``, ``k = l2(k)``, ``l2(u) = u / sqrt(sum(u^2) +
1e-6)``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; the
gated delta rule TOKEN BY TOKEN on a float32 ``[dk, dv]`` state a value head
(value head ``j`` reads key head ``j // 2``): ``S̄ = exp(g) S``, ``S = S̄ + beta
k (v - S̄ᵀ k)ᵀ``, ``o = Sᵀ q``, its scan checkpointed in segments of 64
tokens; ``o / sqrt(mean(o^2) + eps) * w_n * silu(z)``; the output projection.
Gated attention: ``n Wq`` as heads of a query and its gate, key and value
heads, zero-centred norms a q and k head, rotary (pairs ``(2i, 2i+1)``, theta
from the configuration) over a head's first ``rotary_dim`` columns, causal
softmax at ``head_dim ** -0.5``, ``(o * sigmoid(gate)) Wo``. Experts: a
float32 softmax router over all ``experts``, the ``experts_per_token``
largest renormalised over themselves, SwiGLU experts of which only the held
ones add to the result, and ``sigmoid(n w_sg) * SwiGLU_shared(n)`` on every
token. An untied head and the shifted next-token cross-entropy.

No kernel, no chunk of the rule, no sort, no grouped product (each held expert
is applied to every position under a one-hot weight), nothing imported from
the program. Every matrix product runs at ``HIGHEST`` precision unless
``precision="fp8"`` (both operands of every matrix product rounded to float8
e4m3, as in ``benchmark/reference.py``; the delta rule's too: the state it
reads, ``k``, ``q``, the write ``beta k`` and the error ``v - S̄ᵀ k``).
Layers are a Python loop over per-layer leaves, each under
``jax.checkpoint``; attention goes a block of queries at a time and a step's
rows a block of rows at a time.

``fault`` plants the faults only these layers can hide (the cell's controls;
no run uses them): ``"bf16_state"`` (the rule's state rounded to bfloat16
after every token), ``"no_output_gate"`` (attention's ``sigmoid(gate)`` left
out), ``"no_l2"`` (q and k not L2-normed), ``"decay_after_write"`` (``S =
exp(g) (S + beta k (v - Sᵀ k)ᵀ)``), ``"shared_ungated"`` (the shared expert
added without its sigmoid gate).
"""

from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp

from benchmark import weights_next
from benchmark.reference import ADAM_B1, ADAM_B2, ADAM_EPS, _chunks, _mm
from benchmark.reference_kanana import _swiglu
from benchmark.reference_sdar import _einsum

FAULTS = ("bf16_state", "no_output_gate", "no_l2", "decay_after_write", "shared_ungated")
SEGMENT = 64          # tokens a checkpointed piece of the rule's scan


def _rms0(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, dr, base):
    """Rotate pairs (2i, 2i+1) of the first ``dr`` columns of ``x [B, S, H,
    dh]`` by the position ``0 .. S - 1``; the other columns as they are."""
    S = x.shape[1]
    inv = base ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0:dr:2], x[..., 1:dr:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape[:-1] + (dr,))
    return jnp.concatenate([rot, x[..., dr:]], -1)


def delta_rule(q, k, v, g, beta, fault=None, precision="float32"):
    """The gated delta rule token by token: ``q``, ``k`` ``[B, S, Hv, dk]``
    (already one a value head), ``v`` ``[B, S, Hv, dv]``, ``g``, ``beta``
    ``[B, S, Hv]``; returns ``o [B, S, Hv, dv]``. ``precision="fp8"`` rounds
    both operands of each of a token's three products."""
    B, S, Hv, dk = q.shape
    dv = v.shape[-1]

    def token(s, x):
        qt, kt, vt, gt, bt = x
        decay = jnp.exp(gt)[..., None, None]
        if fault != "decay_after_write":
            s = decay * s
        e = vt - _einsum("bhde,bhd->bhe", s, kt, precision)
        s = s + _einsum("bhd,bhe->bhde", bt[..., None] * kt, e, precision)
        if fault == "decay_after_write":
            s = decay * s
        if fault == "bf16_state":       # a cast there and back the TPU compiler drops
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, _einsum("bhde,bhd->bhe", s, qt, precision)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs)

    seg = _chunks(S, SEGMENT)
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((S // seg, seg) + a.shape[:1] + a.shape[2:])
               for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(segment, jnp.zeros((B, Hv, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((S,) + o.shape[2:]), 0, 1)


def gdn(m, precision, x, w, fault=None):
    """The Gated DeltaNet sublayer on ``x [B, S, D]``."""
    B, S, _ = x.shape
    Hk, Hv, dk, dv = (m["linear_key_heads"], m["linear_value_heads"], m["linear_key_dim"],
                      m["linear_value_dim"])
    G, K, eps = Hv // Hk, m["conv_kernel"], m["norm_eps"]
    n = _rms0(x, w["g_ln"], eps)
    qkvz = _mm(n, w["g_qkvz"], precision).reshape(B, S, Hk, -1)
    ba = _mm(n, w["g_ba"], precision).reshape(B, S, Hk, 2, G)
    q, k, v, z = (qkvz[..., :dk], qkvz[..., dk:2 * dk], qkvz[..., 2 * dk:2 * dk + G * dv],
                  qkvz[..., 2 * dk + G * dv:])
    mixed = jnp.concatenate([a.reshape(B, S, -1) for a in (q, k, v)], -1)
    padded = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(w["g_conv"][j] * padded[:, j:j + S] for j in range(K)))
    q = mixed[..., :Hk * dk].reshape(B, S, Hk, dk)
    k = mixed[..., Hk * dk:2 * Hk * dk].reshape(B, S, Hk, dk)
    v = mixed[..., 2 * Hk * dk:].reshape(B, S, Hv, dv)
    if fault != "no_l2":
        l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)
        q, k = l2(q), l2(k)
    q, k = jnp.repeat(q * dk ** -0.5, G, axis=2), jnp.repeat(k, G, axis=2)
    beta = jax.nn.sigmoid(ba[..., 0, :].reshape(B, S, Hv))
    g = -jnp.exp(w["g_alog"]) * jax.nn.softplus(ba[..., 1, :].reshape(B, S, Hv) + w["g_dtb"])
    o = delta_rule(q, k, v, g, beta, fault, precision)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps) * w["g_norm"]
    o = o * jax.nn.silu(z.reshape(B, S, Hv, dv))
    return x + _mm(o.reshape(B, S, Hv * dv), w["g_out"], precision)


def attention(m, precision, x, w, queries=1024, fault=None):
    """The gated attention sublayer on ``x [B, S, D]``, a block of ``queries``
    queries at a time."""
    B, S, _ = x.shape
    H, K, dh, eps = m["heads"], m["kv_heads"], m["head_dim"], m["norm_eps"]
    n = _rms0(x, w["a_ln"], eps)
    qg = _mm(n, w["a_q"], precision).reshape(B, S, H, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = _mm(n, w["a_k"], precision).reshape(B, S, K, dh)
    v = _mm(n, w["a_v"], precision).reshape(B, S, K, dh)
    q = _rope(_rms0(q, w["a_qn"], eps), m["rotary_dim"], m["rope_base"])
    k = _rope(_rms0(k, w["a_kn"], eps), m["rotary_dim"], m["rope_base"])
    qb = _chunks(S, queries)
    keys = jnp.arange(S)[None, :]

    @jax.checkpoint
    def some(args):
        qs, at = args                      # [B, qb, K, H // K, dh], [qb]
        s = _einsum("blkgd,bmkd->bkglm", qs, k, precision) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(keys <= at[:, None], s, -jnp.inf), axis=-1)
        return _einsum("bkglm,bmkd->blkgd", p, v, precision)

    qs = q.reshape(B, S // qb, qb, K, H // K, dh).swapaxes(0, 1)
    o = jax.lax.map(some, (qs, jnp.arange(S).reshape(S // qb, qb)))
    o = o.swapaxes(0, 1).reshape(B, S, H, dh)
    if fault != "no_output_gate":
        o = o * jax.nn.sigmoid(gate)
    return x + _mm(o.reshape(B, S, H * dh), w["a_out"], precision)


def route(m, precision, n, w):
    """``(chosen [B, S, k], weight [B, S, k])`` of normed states ``n``."""
    p = jax.nn.softmax(_mm(n, w["wr"], precision), axis=-1)
    top, chosen = jax.lax.top_k(p, m["experts_per_token"])
    return chosen, top / jnp.sum(top, -1, keepdims=True)


def experts(m, precision, x, w, held=None, fault=None):
    """The expert sublayer on ``x [B, S, D]``: the held experts' part of the
    routed sum, and the gated shared expert. Returns the new ``x`` and every
    position's chosen experts ``[B, S, k]``."""
    first, count = held or weights_next.held(m)
    n = _rms0(x, w["ln2_g"], m["norm_eps"])
    chosen, weight = route(m, precision, n, w)

    @jax.checkpoint
    def one(y, args):
        w_in, w_out, e = args
        share = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1, keepdims=True)
        return y + share * _swiglu(n, w_in, w_out, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["ex_in"], w["ex_out"], first + jnp.arange(count)))
    shared = _swiglu(n, w["sh_in"], w["sh_out"], precision)
    if fault != "shared_ungated":
        shared = shared * jax.nn.sigmoid(_mm(n, w["sgate"], precision))
    return x + y + shared, chosen


def hidden(m, w, tokens, precision="float32", held=None, queries=1024, fault=None):
    """Final hidden states ``[B, S, D]`` after the head's norm, and the chosen
    experts of every position in every layer ``[depth, B, S, k]``."""
    x = w["embed"][tokens]

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def block(x, wl, full):
        mixer = attention(m, precision, x, wl, queries, fault) if full \
            else gdn(m, precision, x, wl, fault)
        return experts(m, precision, mixer, wl, held, fault)

    chosen = []
    for i in range(m["depth"]):
        x, c = block(x, weights_next.layer_of(m, w, i), (i + 1) % m["full_attention_interval"] == 0)
        chosen.append(c)
    return _rms0(x, w["lnf_g"], m["norm_eps"]), jnp.stack(chosen)


def nll_sum(m, w, x, y, precision="float32", chunk=512, held=None, queries=1024, fault=None):
    """Sum over positions of ``-log softmax(head(hidden))[y]``, and the chosen
    experts."""
    h, chosen = hidden(m, w, x, precision, held, queries, fault)
    h = h.reshape(-1, m["dim"])
    c = _chunks(h.shape[0], chunk)

    @jax.checkpoint
    def one(args):
        hc, yc = args
        z = _mm(hc, w["head"], precision)
        return jnp.sum(jax.nn.logsumexp(z, -1) - jnp.take_along_axis(z, yc[:, None], -1)[:, 0])

    parts = jax.lax.map(one, (h.reshape(-1, c, m["dim"]), y.reshape(-1, c)))
    return jnp.sum(parts), chosen


@functools.lru_cache(maxsize=None)
def _leaf_programs(sizes: str):
    """The jitted weights, norms and change of a configuration (``sizes`` its
    ``model`` group as sorted JSON), made once a process: the controls run
    the reference again and again at one configuration."""
    m = json.loads(sizes)
    return (jax.jit(lambda key: weights_next.layered(m, key)),
            jax.jit(lambda tree: weights_next.leaf_norms(m, tree)),
            jax.jit(lambda w, key: weights_next.leaf_norms(m, jax.tree.map(
                jnp.subtract, w, weights_next.layered(m, key)))))


def train_steps(m, seed, batches, learning_rate, precision="float32", rows_per_block=1,
                queries_per_block=1024, held=None, fault=None):
    """The first ``len(batches)`` Adam steps from the seed's weights, with
    ``benchmark/reference.py``'s constants; a batch is ``(x, y)``, ``y`` the
    next tokens. Returns each step's loss, the first step's per-leaf gradient
    norms, the per-leaf norm of the parameters' change after the last step
    and the first step's chosen experts ``[depth, rows, L, k]`` (sorted
    within a position). ``held`` (other experts than the configuration's,
    with their own weights), ``precision="fp8"`` and ``fault`` plant the
    controls' faults; no run uses them."""
    mh = dict(m, experts_held=list(held)) if held else m
    with jax.default_matmul_precision("highest"):
        key = weights_next.seed_key(seed)
        make, norms, change = _leaf_programs(json.dumps(mh, sort_keys=True))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def add_grads(acc, w, x, y):
            (l, chosen), g = jax.value_and_grad(
                lambda w_: nll_sum(mh, w_, x, y, precision, queries=queries_per_block,
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

        zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w))
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
            x, y = jnp.asarray(x), jnp.asarray(y)
            rb = _chunks(x.shape[0], rows_per_block)
            acc, chosen = (jnp.zeros((), jnp.float32), zeros(w)), []
            for lo in range(0, x.shape[0], rb):
                acc, c = add_grads(acc, w, x[lo:lo + rb], y[lo:lo + rb])
                chosen.append(c)
            g = mean(acc[1], jnp.float32(x.size))
            losses.append(float(acc[0]) / x.size)
            lap(f"gradients {t}", g)
            if grad_norms is None:
                grad_norms = jax.device_get(norms(g))
                routes = jax.device_get(jnp.concatenate(chosen, axis=1))
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
        print("reference_next.train_steps, seconds: "
              + ", ".join(f"{k} {v:.1f}" for k, v in spent.items()), file=sys.stderr)
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta, "routes": routes}
