"""LSTM sequence classifier (IMDB sentiment, BASELINE config 5).

Variable-length sequences arrive pre-padded to a static length with a mask
column (see ``distkeras_tpu.datasets.imdb`` / ``SequencePadTransformer``) —
XLA traces one static-shape program, no recompiles per length bucket
(SURVEY.md §7.3 hard part 3). Classification reads a mask-weighted mean over
valid timesteps, which avoids a gather on the last-valid index and fuses into
the final matmul.

TPU note — hoisted input projection: the input half of the LSTM's gate math
(``x_t @ W_x`` for every t) has no sequential dependence, so it runs as ONE
big ``[B·T, E] @ [E, 4H]`` matmul before the scan (MXU-friendly), leaving
only the recurrent ``h @ W_h`` inside the ``lax.scan``. Kept for the
simpler code; no benchmark cell times it against
``nn.RNN(OptimizedLSTMCell)``. Cell state stays f32; gates/hidden compute
in ``dtype``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from distkeras_tpu.model import ModelSpec, from_flax


class LSTMClassifier(nn.Module):
    vocab: int = 20000
    embed_dim: int = 128
    hidden_dim: int = 128
    num_classes: int = 2
    dtype: jnp.dtype = jnp.bfloat16
    #: recurrence implementation: "pallas" (the fused VMEM-carry kernel in
    #: ops.recurrent — forget bias +1.0, same gate math), "xla" (lax.scan),
    #: "auto" (kernel natively on TPU with tile-friendly shapes)
    scan_impl: str = "auto"

    @nn.compact
    def __call__(self, tokens, mask=None, training: bool = False):
        from distkeras_tpu.ops.recurrent import lstm_scan

        if mask is None:
            mask = jnp.ones(tokens.shape, jnp.float32)
        H = self.hidden_dim
        x = nn.Embed(self.vocab, self.embed_dim, dtype=self.dtype)(tokens)
        # all timesteps' input projections in one matmul (bias lives here)
        gates_x = nn.Dense(4 * H, dtype=self.dtype, name="wx")(x)  # [B,T,4H]
        wh = self.param("wh", nn.initializers.orthogonal(), (H, 4 * H),
                        jnp.float32)
        # ys in `dtype`: the [B, T, H] buffer (and its saved-for-backward
        # copy) stays bf16; the mask-mean below accumulates in f32
        outs = lstm_scan(gates_x, wh, impl=self.scan_impl)  # [B, T, H]
        m = mask.astype(jnp.float32)[..., None]
        pooled = jnp.sum(outs.astype(jnp.float32) * m, axis=1) / jnp.maximum(
            jnp.sum(m, axis=1), 1.0
        )
        logits = nn.Dense(self.num_classes, dtype=self.dtype)(
            pooled.astype(self.dtype)
        )
        return logits.astype(jnp.float32)


def lstm_classifier(vocab=20000, maxlen=200, embed_dim=128, hidden_dim=128,
                    num_classes=2, dtype=jnp.bfloat16,
                    scan_impl="auto") -> ModelSpec:
    module = LSTMClassifier(
        vocab=vocab, embed_dim=embed_dim, hidden_dim=hidden_dim,
        num_classes=num_classes, dtype=dtype, scan_impl=scan_impl,
    )
    example = (
        jnp.zeros((1, maxlen), jnp.int32),
        jnp.ones((1, maxlen), jnp.float32),
    )
    return from_flax(module, example, name="lstm_classifier")
