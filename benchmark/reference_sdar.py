"""The plain reference for SDAR trained by diffusion over blocks: its layer
and its objective in float32 ``jax.numpy``.

ISSUE 31's section 1, equation by equation (what it marks *assumed* is in
``benchmark/configs/sdar-30b-a3b.json`` under ``assumed``): RMSNorm, bias-free
grouped-query attention with an RMSNorm a head on q and on k and rotary over
the whole head at the token's position in its row, a linear softmax router
whose 8 largest of ALL the router's experts are renormalised, SwiGLU experts of
which only the held ones add to the result, an untied head; a row runs as a
noised copy followed by its clean copy under the dense ``[2 L, 2 L]`` mask of
the issue's table (:func:`visible`), and the loss reads the noised copy's
masked positions, unshifted, weighted ``1 / t`` over all ``R L`` positions. No
kernel, no sort, no grouped product (each held expert is applied to every
position under a one-hot weight), nothing imported from the program: the
noise is drawn again with the same ``jax.random`` calls from the same key and
step count (:func:`noise`). Every matrix product runs at ``HIGHEST`` precision
unless ``precision="fp8"`` (both operands of every matrix product rounded to
float8 e4m3, as in ``benchmark/reference.py``).

Layers are a Python loop over per-layer leaves, each under ``jax.checkpoint``;
attention goes a block of queries at a time and a step's rows a block of rows
at a time, their gradients added into one accumulator in place; Adam's two
moments rest on the host while a step's gradients are added up.

``fault`` plants the two faults only this objective can hide (the cell's
controls; no run uses them): ``"causal_in_block"``, a noised query sees only
the keys up to itself inside its own block, and ``"unweighted"``, the
``1 / t`` left out of the loss.
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp

from benchmark import weights_sdar
from benchmark.reference import ADAM_B1, ADAM_B2, ADAM_EPS, _HI, _chunks, _fp8, _mm


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _einsum(spec, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=_HI)


def _rope(x, positions, base):
    """Rotate pairs (2i, 2i+1) of ``x [B, S, H, dh]`` by ``positions [S]``."""
    dh = x.shape[-1]
    inv = base ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


def visible(a, c, length, block, fault=None):
    """The issue's table: may the query at stream index ``a`` see the key at
    stream index ``c``? Indices below ``length`` are the noised copy."""
    a_noised, c_noised = a < length, c < length
    ab, cb = (a % length) // block, (c % length) // block
    own = cb == ab
    if fault == "causal_in_block":
        own = own & (c <= a)
    return jnp.where(a_noised,
                     jnp.where(c_noised, own, cb < ab),
                     jnp.where(c_noised, False, cb <= ab))


def noise(m, key, step, tokens):
    """One step's noise on ``tokens [R, L]``: the noised rows, each position's
    level ``t`` and whether it was masked; the program's draws, call for call."""
    R, L = tokens.shape
    G, floor = m["block_length"], m["noise_floor"]
    level, each = jax.random.split(jax.random.fold_in(key, step))
    u = jax.random.uniform(level, (R, L // G), jnp.float32)
    t = jnp.repeat(floor + (1.0 - floor) * u, G, axis=1)
    masked = jax.random.uniform(each, (R, L), jnp.float32) < t
    return jnp.where(masked, m["vocab"] - 1, tokens), t, masked


def attention(m, precision, x, w, queries=1024, fault=None):
    """The attention sublayer on a stream ``x [B, 2 L, D]``, a block of
    ``queries`` queries at a time."""
    B, S, _ = x.shape
    H, K, dh, L = m["heads"], m["kv_heads"], m["head_dim"], S // 2
    n = _rms(x, w["ln1_g"], m["norm_eps"])
    pos = jnp.concatenate([jnp.arange(L), jnp.arange(L)])
    q = _rms(_mm(n, w["wq"], precision).reshape(B, S, H, dh), w["qn_g"], m["norm_eps"])
    k = _rms(_mm(n, w["wk"], precision).reshape(B, S, K, dh), w["kn_g"], m["norm_eps"])
    q, k = _rope(q, pos, m["rope_base"]), _rope(k, pos, m["rope_base"])
    v = _mm(n, w["wv"], precision).reshape(B, S, K, dh)
    qb = _chunks(S, queries)
    c = jnp.arange(S)[None, :]

    @jax.checkpoint
    def some(args):
        qs, a = args                     # [B, qb, K, G, dh], [qb]
        s = _einsum("blkgd,bmkd->bkglm", qs, k, precision) * dh ** -0.5
        see = visible(a[:, None], c, L, m["block_length"], fault)
        p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return _einsum("bkglm,bmkd->blkgd", p, v, precision)

    qs = q.reshape(B, S // qb, qb, K, H // K, dh).swapaxes(0, 1)
    o = jax.lax.map(some, (qs, jnp.arange(S).reshape(S // qb, qb)))
    o = o.swapaxes(0, 1).reshape(B, S, H * dh)
    return x + _mm(o, w["wo"], precision)


def first_block(m, w, noised, clean, precision="float32", queries=1024, fault=None):
    """The first layer's attention sublayer at the first row's first noised
    block, ``[block_length, D]``: those queries see their own block's noised
    keys and nothing else, so the mask inside a block alone decides them."""
    x = w["embed"][jnp.concatenate([noised[:1], clean[:1]], 1)]
    return attention(m, precision, x, _layer(m, w, 0), queries, fault)[0, :m["block_length"]]


def experts(m, precision, x, w, held=None):
    """The expert sublayer on ``x [B, S, D]``. Returns the new ``x`` and every
    position's chosen experts ``[B, S, k]``."""
    first, count = held or weights_sdar.held(m)
    F = m["expert_dim"]
    n = _rms(x, w["ln2_g"], m["norm_eps"])
    p = jax.nn.softmax(_mm(n, w["wr"], precision), axis=-1)
    top, chosen = jax.lax.top_k(p, m["experts_per_token"])
    weight = top / jnp.sum(top, -1, keepdims=True)
    y = jnp.zeros_like(x)
    for j in range(count):
        gu = _mm(n, w["ex_in"][j], precision)
        out = _mm(jax.nn.silu(gu[..., :F]) * gu[..., F:], w["ex_out"][j], precision)
        y = y + jnp.sum(jnp.where(chosen == first + j, weight, 0.0), -1, keepdims=True) * out
    return x + y, chosen


def _layer(m, w, i):
    return {n: w[n][i] for n in weights_sdar.block_leaves(m)}


def hidden(m, w, stream, precision="float32", held=None, queries=1024, fault=None):
    """Final hidden states ``[B, 2 L, D]`` of a stream after the head's norm,
    and the chosen experts of every position in every layer
    ``[depth, B, 2 L, k]``."""
    x = w["embed"][stream]

    @jax.checkpoint
    def block(x, wl):
        return experts(m, precision, attention(m, precision, x, wl, queries, fault), wl, held)

    chosen = []
    for i in range(m["depth"]):
        x, c = block(x, _layer(m, w, i))
        chosen.append(c)
    return _rms(x, w["lnf_g"], m["norm_eps"]), jnp.stack(chosen)


def weighted_nll_sum(m, w, noised, clean, weight, precision="float32", chunk=512, held=None,
                     queries=1024, fault=None):
    """Sum over positions of ``weight * -log softmax(head(hidden of the noised
    copy))[clean token]``, and the chosen experts."""
    L = clean.shape[1]
    h, chosen = hidden(m, w, jnp.concatenate([noised, clean], 1), precision, held, queries,
                       fault)
    h = h[:, :L].reshape(-1, m["dim"])
    c = _chunks(h.shape[0], chunk)

    @jax.checkpoint
    def one(args):
        hc, yc, wc = args
        z = _mm(hc, w["head"], precision)
        nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(z, yc[:, None], -1)[:, 0]
        return jnp.sum(wc * nll)

    parts = jax.lax.map(one, (h.reshape(-1, c, m["dim"]), clean.reshape(-1, c),
                              weight.reshape(-1, c)))
    return jnp.sum(parts), chosen


def train_steps(m, seed, batches, learning_rate, precision="float32", rows_per_block=1,
                queries_per_block=1024, half_batch=False, held=None, fault=None):
    """The first ``len(batches)`` Adam steps from the seed's weights, with
    ``benchmark/reference.py``'s constants; a batch is ``(x, y)`` with ``y``
    the same clean rows. Returns each step's loss, the first step's per-leaf
    gradient norms, the per-leaf norm of the parameters' change after the last
    step, the first step's chosen experts ``[depth, rows, 2 L, k]`` (sorted
    within a position), the first step's :func:`first_block` and each step's
    count of masked positions.
    ``half_batch`` (a step that drops the second half of its rows), ``held``
    (other experts than the configuration's, with their own weights),
    ``precision="fp8"`` and ``fault`` plant the controls' faults; no run uses
    them."""
    mh = dict(m, experts_held=list(held)) if held else m
    with jax.default_matmul_precision("highest"):
        key = weights_sdar.seed_key(seed)
        nkey = weights_sdar.noise_key(key)
        make = jax.jit(lambda key: weights_sdar.layered(mh, key))
        draw = jax.jit(lambda step, x: noise(mh, nkey, step, x))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def add_grads(acc, w, noised, clean, weight):
            (l, chosen), g = jax.value_and_grad(
                lambda w_: weighted_nll_sum(mh, w_, noised, clean, weight, precision,
                                            queries=queries_per_block, fault=fault),
                has_aux=True)(w)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), jnp.sort(chosen, -1)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def adam(w, mu, nu, g, t):
            mu = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, mu, g)
            nu = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, nu, g)
            w = jax.tree.map(
                lambda p, a, b: p - learning_rate * (a / (1 - ADAM_B1 ** t))
                / (jnp.sqrt(b / (1 - ADAM_B2 ** t)) + ADAM_EPS), w, mu, nu)
            return w, mu, nu

        block_one = jax.jit(lambda w, noised, clean: first_block(
            mh, w, noised, clean, precision, queries_per_block, fault))
        norms = jax.jit(lambda tree: weights_sdar.leaf_norms(mh, tree))
        change = jax.jit(lambda w, key: weights_sdar.leaf_norms(mh, jax.tree.map(
            jnp.subtract, w, weights_sdar.layered(mh, key))))
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
        losses, masked_counts, grad_norms, routes, first = [], [], None, None, None
        for t, (x, _) in enumerate(batches, 1):
            noised, level, masked = draw(jnp.int32(t - 1), jnp.asarray(x))
            weight = jnp.where(masked, 1.0 if fault == "unweighted" else 1.0 / level, 0.0)
            clean = jnp.asarray(x)
            if half_batch:
                noised, clean, weight, masked = (a[: a.shape[0] // 2]
                                                 for a in (noised, clean, weight, masked))
            masked_counts.append(int(jnp.sum(masked)))
            if first is None:
                first = jax.device_get(block_one(w, noised, clean))
            rb = _chunks(clean.shape[0], rows_per_block)
            acc, chosen = (jnp.zeros((), jnp.float32), zeros(w)), []
            for lo in range(0, clean.shape[0], rb):
                acc, c = add_grads(acc, w, noised[lo:lo + rb], clean[lo:lo + rb],
                                   weight[lo:lo + rb])
                chosen.append(c)
            n = clean.shape[0] * clean.shape[1]
            g = mean(acc[1], jnp.float32(n))
            losses.append(float(acc[0]) / n)
            lap(f"gradients {t}", g)
            if grad_norms is None:
                grad_norms = jax.device_get(norms(g))
                routes = jax.device_get(jnp.concatenate(chosen, axis=1))
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
        print("reference_sdar.train_steps, seconds: "
              + ", ".join(f"{k} {v:.1f}" for k, v in spent.items()), file=sys.stderr)
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta,
            "routes": routes, "first_block": first, "masked": masked_counts}
