"""The plain reference: a pre-LayerNorm decoder in float32 ``jax.numpy``.

Pre-LN blocks with biases, tanh GELU, a 4x (or the configured) MLP, grouped
K/V heads, sinusoidal or rotary positions and a tied output head; its loss,
its gradients and three steps of Adam. No kernel, no cache, no batching, and
nothing imported from the program. Every matrix product runs at ``HIGHEST``
precision (true float32 on a TPU) unless ``precision="fp8"``, the training
cells' control: both operands of every matrix product, attention's included,
rounded to float8 e4m3 with one scale a tensor, the step below bfloat16 that
a later change might be tempted to take. (The serve cells' control is the
program's own int8 path, not the reference: ``drivers/serve.py``.)

Computed a block of rows and a layer at a time so that it fits beside nothing
else: layers under ``lax.scan`` with ``jax.checkpoint``, the loss in chunks of
tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

_HI = jax.lax.Precision.HIGHEST
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8     # optax.adam's defaults
LN_EPS = 1e-6                                     # flax LayerNorm's default
ROPE_BASE = 10000.0                               # the program's constant


def _ln(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _fp8(x):
    """float8 e4m3 with one scale a tensor (its largest magnitude to 448, the
    format's), gradient passed straight through."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, precision):
    """``a [..., K] @ b [K, N]``."""
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=_HI)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _sincos(length, dim):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    i = jnp.arange(dim // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2.0 * i / dim)
    return jnp.stack([jnp.sin(angle), jnp.cos(angle)], -1).reshape(length, dim)


def _rope(x, length, dh):
    """Rotate pairs (2i, 2i+1) of ``x [B, L, H, dh]`` by position."""
    inv = ROPE_BASE ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


def _block(m, precision, x, w):
    """One pre-LN block on ``x [B, L, D]``; ``w`` one layer's leaves."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    B, L, D = x.shape
    H, dh = m["heads"], m["dim"] // m["heads"]
    K = m["kv_heads"] or H
    qkv = _mm(_ln(x, w["ln1_g"], w["ln1_b"]), w["qkv_w"], precision) + w["qkv_b"]
    q = qkv[..., :H * dh].reshape(B, L, H, dh)
    k = qkv[..., H * dh:(H + K) * dh].reshape(B, L, K, dh)
    v = qkv[..., (H + K) * dh:].reshape(B, L, K, dh)
    if m["pos_embedding"] == "rope":
        q, k = _rope(q, L, dh), _rope(k, L, dh)
    q = q.reshape(B, L, K, H // K, dh)            # query head h reads K/V head h // group
    if precision == "fp8":
        q, k = _fp8(q), _fp8(k)
    s = jnp.einsum("blkgd,bmkd->bkglm", q, k, precision=_HI) * dh ** -0.5
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    ok = j <= i
    if m["attn_window"]:
        ok &= i - j < m["attn_window"]
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    if precision == "fp8":
        p, v = _fp8(p), _fp8(v)
    o = jnp.einsum("bkglm,bmkd->blkgd", p, v, precision=_HI).reshape(B, L, H * dh)
    x = x + _mm(o, w["out_w"], precision) + w["out_b"]
    u = _gelu(_mm(_ln(x, w["ln2_g"], w["ln2_b"]), w["up_w"], precision) + w["up_b"])
    return x + _mm(u, w["down_w"], precision) + w["down_b"]


def hidden(m, w, tokens, precision="float32"):
    """Final hidden states ``[B, L, D]`` after the head's LayerNorm."""
    L = tokens.shape[1]
    x = w["embed"].astype(jnp.float32)[tokens]
    if m["pos_embedding"] == "sincos":
        x = x + _sincos(L, m["dim"])[None]
    layers = {k: w[k] for k in weights.BLOCK_NAMES}
    step = jax.checkpoint(lambda x, wl: (_block(m, precision, x, wl), None))
    x, _ = jax.lax.scan(step, x, layers)
    return _ln(x, w["lnf_g"].astype(jnp.float32), w["lnf_b"].astype(jnp.float32))


def _chunks(n, chunk):
    chunk = min(chunk, n)
    while n % chunk:
        chunk -= 1
    return chunk


def nll_sum(m, w, tokens, labels, precision="float32", chunk=512):
    """Sum over all positions of -log softmax(head(hidden))[label]."""
    h = hidden(m, w, tokens, precision).reshape(-1, m["dim"])
    c = _chunks(h.shape[0], chunk)
    head = w["embed"].astype(jnp.float32).T

    @jax.checkpoint
    def one(hy):
        hc, yc = hy
        logits = _mm(hc, head, precision)
        return jnp.sum(jax.nn.logsumexp(logits, -1)
                       - jnp.take_along_axis(logits, yc[:, None], -1)[:, 0])

    return jnp.sum(jax.lax.map(one, (h.reshape(-1, c, m["dim"]),
                                     labels.reshape(-1, c))))


def train_steps(m, seed, batches, learning_rate, precision="float32",
                rows_per_block=1, half_batch=False):
    """The first ``len(batches)`` Adam steps from the seed's weights.

    ``batches``: ``[(tokens [B, L], labels [B, L]), ...]``. Returns each step's
    mean loss, the first step's per-leaf gradient norms and the per-leaf norm of
    the parameters' change after the last step. ``half_batch`` plants the
    fault of a step that drops the second half of its rows and takes the mean
    over the rest (read on the chip to place the limits; never used in a run).
    """
    with jax.default_matmul_precision("highest"):
        key = weights.seed_key(seed)
        make = jax.jit(lambda key: weights.stacked(m, key, "float32"))

        @jax.jit
        def grads(w, X, Y):
            def body(acc, xy):
                l, g = jax.value_and_grad(
                    lambda w_: nll_sum(m, w_, xy[0], xy[1], precision))(w)
                return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

            zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
            (l, g), _ = jax.lax.scan(body, zero, (X, Y))
            n = X.shape[0] * X.shape[1] * X.shape[2]
            return l / n, jax.tree.map(lambda a: a / n, g)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def adam(w, mu, nu, g, t):
            mu = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, mu, g)
            nu = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, nu, g)
            w = jax.tree.map(
                lambda p, a, b: p - learning_rate * (a / (1 - ADAM_B1 ** t))
                / (jnp.sqrt(b / (1 - ADAM_B2 ** t)) + ADAM_EPS), w, mu, nu)
            return w, mu, nu

        norms = jax.jit(lambda tree: weights.leaf_norms(m, tree))
        # the change is taken against weights made again from the key, inside
        # the jit, so that no second copy is held through the steps
        change = jax.jit(lambda w, key: weights.leaf_norms(m, jax.tree.map(
            jnp.subtract, w, weights.stacked(m, key, "float32"))))
        w = make(key)
        mu = jax.tree.map(jnp.zeros_like, w)
        nu = jax.tree.map(jnp.zeros_like, w)
        losses, grad_norms = [], None
        for t, (x, y) in enumerate(batches, 1):
            if half_batch:
                x, y = x[: len(x) // 2], y[: len(y) // 2]
            rb = _chunks(x.shape[0], rows_per_block)
            X = jnp.asarray(x).reshape(-1, rb, x.shape[1])
            Y = jnp.asarray(y).reshape(-1, rb, y.shape[1])
            loss, g = grads(w, X, Y)
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = jax.device_get(norms(g))
            w, mu, nu = adam(w, mu, nu, g, jnp.float32(t))
        delta = jax.device_get(change(w, key))
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}


def next_logits(m, w, tokens, precision="float32"):
    """Logits ``[n-1, V]`` of what follows each position but the last of one
    sequence ``tokens [n]``; ``w`` stacked weights in the types they are served
    in (each layer is taken to float32 as it is used)."""
    with jax.default_matmul_precision("highest"):
        h = hidden(m, w, tokens[None], precision)[0, :-1]
        return _mm(h, w["embed"].astype(jnp.float32).T, precision)


def gap_below_best(logits, chosen):
    """How far the chosen token's logit lies below the best, at each position."""
    return jnp.max(logits, -1) - jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]
