"""SRU sequence classifier — the recurrence that isn't latency-bound.

An LSTM's sequential chain has a latency floor: every timestep needs
``h_{t-1}`` through a matmul, so a T=200 sequence is 200 dependent MXU
dispatches no kernel can parallelize away. The levers left are
architectural (QRNN/SRU-style recurrences that break the dependency), and
this module is that lever.

The Simple Recurrent Unit (Lei et al. 2018, "Simple Recurrent Units for
Highly Parallelizable Recurrence") moves ALL matmuls out of the recurrence:

    x̃_t, f_t, r_t  =  split(x_t @ W)          (one [B·T, E]·[E, 3H] matmul)
    c_t  =  f_t ⊙ c_{t-1} + (1 − f_t) ⊙ x̃_t   (elementwise, linear in c)
    h_t  =  r_t ⊙ g(c_t) + (1 − r_t) ⊙ x_t    (highway output)

The cell update is a FIRST-ORDER LINEAR recurrence, and linear recurrences
compose associatively: ``(f₁,g₁)∘(f₂,g₂) = (f₁f₂, f₂g₁+g₂)``. On TPU that
means ``jax.lax.associative_scan`` evaluates all T steps in O(log T)
parallel depth on the VPU — one fused program, no per-step dispatch, no
h→matmul dependency — while the MXU sees a single big time-parallel
projection. Same classifier interface as ``models.lstm`` (padded tokens +
mask, masked-mean pooling), so it drops into the IMDB BASELINE config
unchanged; its throughput against the LSTM is not measured on the chip.

No reference counterpart (the Spark-era reference topped out at a Keras
LSTM — SURVEY.md §2b.2); this is the beyond-parity answer to its slowest
benchmark config rather than a port of anything.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from distkeras_tpu.model import ModelSpec, from_flax


def sru_recurrence(gates, impl: str = "assoc"):
    """Run the SRU cell update over time.

    ``gates``: ``[B, T, 3H]`` packed ``(x̃, pre_f, pre_r)`` projections.
    Returns ``h``-ready pieces ``(c [B,T,H] f32, r [B,T,H] f32)``.

    ``impl="assoc"`` evaluates the linear recurrence with
    ``jax.lax.associative_scan`` (O(log T) depth — the TPU path);
    ``impl="scan"`` is the sequential ``lax.scan`` oracle the tests pin
    against (identical math, different evaluation order).
    """
    H = gates.shape[-1] // 3
    xt = gates[..., :H].astype(jnp.float32)
    f = jax.nn.sigmoid(gates[..., H: 2 * H].astype(jnp.float32))
    r = jax.nn.sigmoid(gates[..., 2 * H:].astype(jnp.float32))
    g = (1.0 - f) * xt  # the additive term of c_t = f·c_{t-1} + g_t

    if impl == "assoc":
        def combine(a, b):
            fa, ga = a
            fb, gb = b
            return fa * fb, fb * ga + gb

        _, c = jax.lax.associative_scan(combine, (f, g), axis=1)
    elif impl == "scan":
        def step(c_prev, fg):
            f_t, g_t = fg
            c_t = f_t * c_prev + g_t
            return c_t, c_t

        f_tm = jnp.moveaxis(f, 1, 0)  # scan over time-major
        g_tm = jnp.moveaxis(g, 1, 0)
        _, c = jax.lax.scan(step, jnp.zeros_like(f[:, 0]), (f_tm, g_tm))
        c = jnp.moveaxis(c, 0, 1)
    else:
        raise ValueError(f"unknown SRU impl {impl!r}; use 'assoc' or 'scan'")
    return c, r


class SRUClassifier(nn.Module):
    """Token sequence → class logits through ``depth`` SRU layers."""

    vocab: int = 20000
    embed_dim: int = 128
    hidden_dim: int = 128
    num_classes: int = 2
    depth: int = 1
    dtype: jnp.dtype = jnp.bfloat16
    impl: str = "assoc"

    @nn.compact
    def __call__(self, tokens, mask=None, training: bool = False):
        if mask is None:
            mask = jnp.ones(tokens.shape, jnp.float32)
        H = self.hidden_dim
        x = nn.Embed(self.vocab, self.embed_dim, dtype=self.dtype)(tokens)
        for layer in range(self.depth):
            # all three gates of every timestep in one MXU matmul
            gates = nn.Dense(3 * H, dtype=self.dtype,
                             name=f"w_{layer}")(x)            # [B, T, 3H]
            c, r = sru_recurrence(gates, impl=self.impl)
            # highway: project x once per layer if widths differ
            skip = x.astype(jnp.float32)
            if skip.shape[-1] != H:
                skip = nn.Dense(H, dtype=self.dtype,
                                name=f"skip_{layer}")(x).astype(jnp.float32)
            h = r * jnp.tanh(c) + (1.0 - r) * skip             # [B, T, H] f32
            x = h.astype(self.dtype)
        m = mask.astype(jnp.float32)[..., None]
        pooled = jnp.sum(x.astype(jnp.float32) * m, axis=1) / jnp.maximum(
            jnp.sum(m, axis=1), 1.0
        )
        logits = nn.Dense(self.num_classes, dtype=self.dtype)(
            pooled.astype(self.dtype)
        )
        return logits.astype(jnp.float32)


def sru_classifier(vocab=20000, maxlen=200, embed_dim=128, hidden_dim=128,
                   num_classes=2, depth=1, dtype=jnp.bfloat16,
                   impl="assoc") -> ModelSpec:
    """Drop-in alternative to :func:`models.lstm.lstm_classifier` whose
    recurrence parallelizes over time (module docstring) — same
    ``(tokens, mask)`` inputs and BASELINE-config column layout."""
    module = SRUClassifier(
        vocab=vocab, embed_dim=embed_dim, hidden_dim=hidden_dim,
        num_classes=num_classes, depth=depth, dtype=dtype, impl=impl,
    )
    example = (
        jnp.zeros((1, maxlen), jnp.int32),
        jnp.ones((1, maxlen), jnp.float32),
    )
    return from_flax(module, example, name="sru_classifier")
