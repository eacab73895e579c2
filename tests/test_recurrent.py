"""Fused LSTM scan kernel vs the lax.scan oracle (values AND gradients).

Same testing philosophy as tests/test_flash_attention.py: the kernel runs in
Pallas interpret mode on CPU so CI pins the exact code path that compiles
natively on the chip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu.ops.recurrent import (
    lstm_scan,
    lstm_scan_reference,
    _lstm_core,
)

B, T, H = 8, 7, 128


def make_inputs(rng, b=B, t=T, h=H, dtype=jnp.float32):
    gx = rng.normal(0, 0.5, size=(b, t, 4 * h)).astype(np.float32)
    wh = (rng.normal(0, 1.0, size=(h, 4 * h)) / np.sqrt(h)).astype(np.float32)
    return jnp.asarray(gx).astype(dtype), jnp.asarray(wh)


def pallas_scan(gx, wh):
    return jnp.moveaxis(
        _lstm_core(jnp.moveaxis(gx, 1, 0), wh, True), 0, 1
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_matches_reference(rng, dtype):
    """bf16 is the production default path (LSTMClassifier dtype). In f32
    the kernel matches the XLA scan to float tolerance; in bf16 the two
    agree to the bf16 rounding floor here (``chip_smoke.py``'s ``kernels``
    phase compares them on the chip)."""
    gx, wh = make_inputs(rng, dtype=dtype)
    out = pallas_scan(gx, wh)
    ref = lstm_scan_reference(gx, wh)
    assert out.dtype == ref.dtype == dtype
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 \
        else dict(rtol=5e-2, atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol
    )


def test_bf16_gradients_match_reference(rng):
    """The bf16 backward (downcast cs residual, bf16 recompute) stays at
    the cast-chain noise floor vs the XLA scan's bf16 gradients."""
    gx, wh = make_inputs(rng, t=16, dtype=jnp.bfloat16)
    probe = jnp.asarray(rng.normal(size=(B, 16, H)).astype(np.float32))

    def loss(fn):
        return lambda gx, wh: jnp.sum(
            fn(gx, wh).astype(jnp.float32) * probe
        )

    gk = jax.grad(loss(pallas_scan), argnums=(0, 1))(gx, wh)
    gr = jax.grad(loss(lstm_scan_reference), argnums=(0, 1))(gx, wh)
    for a, b in zip(gk, gr):
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        denom = np.max(np.abs(b32)) + 1e-9
        assert np.max(np.abs(a32 - b32)) / denom < 2e-2


@pytest.mark.parametrize("t", [T, 16])
def test_gradients_match_reference(rng, t):
    """t=7 forces chunk K=1; t=16 runs the K=8 chunked backward (the
    previous-chunk boundary views and cross-chunk dc/dh carry handoff)."""
    gx, wh = make_inputs(rng, t=t)
    probe = jnp.asarray(rng.normal(size=(B, t, H)).astype(np.float32))

    def loss(fn):
        return lambda gx, wh: jnp.sum(fn(gx, wh) * probe)

    gk = jax.grad(loss(pallas_scan), argnums=(0, 1))(gx, wh)
    gr = jax.grad(loss(lstm_scan_reference), argnums=(0, 1))(gx, wh)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_vmap_matches_reference(rng):
    """The stacked-worker engine vmaps the model over W — the kernel must
    batch correctly (carries independent per worker)."""
    W = 2
    gxs, whs = zip(*(make_inputs(rng, b=8, t=5) for _ in range(W)))
    gxs = jnp.stack(gxs)
    whs = jnp.stack(whs)
    out = jax.vmap(pallas_scan)(gxs, whs)
    for w in range(W):
        ref = lstm_scan_reference(gxs[w], whs[w])
        np.testing.assert_allclose(np.asarray(out[w]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_auto_dispatch_and_validation(rng):
    gx, wh = make_inputs(rng, b=4, t=3, h=16)
    # off-TPU / tiny shapes: auto takes the XLA path (identical by def)
    np.testing.assert_array_equal(
        np.asarray(lstm_scan(gx, wh, impl="auto")),
        np.asarray(lstm_scan_reference(gx, wh)),
    )
    with pytest.raises(ValueError, match="lstm impl"):
        lstm_scan(gx, wh, impl="warp")


def test_model_through_kernel_matches_xla_model(rng):
    """LSTMClassifier(scan_impl='pallas') == scan_impl='xla' end to end."""
    from distkeras_tpu.models import lstm_classifier
    from distkeras_tpu.ops import recurrent

    toks = rng.integers(0, 100, size=(8, 12)).astype(np.int32)
    mask = np.ones((8, 12), np.float32)
    mask[:, 9:] = 0.0
    kw = dict(vocab=100, maxlen=12, embed_dim=32, hidden_dim=128,
              num_classes=2, dtype=jnp.float32)
    xla = lstm_classifier(scan_impl="xla", **kw)
    pal = lstm_classifier(scan_impl="pallas", **kw)
    params, nt = xla.init_np(0)
    out_x, _ = xla.apply(params, nt, (toks, mask), False)
    out_p, _ = pal.apply(params, nt, (toks, mask), False)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               rtol=1e-5, atol=1e-5)
