"""Pallas flash attention vs the XLA oracle (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops.flash_attention import attention, flash_attention
from distkeras_tpu.parallel.sequence import attention_reference

B, L, H, D = 2, 256, 2, 64


def qkv(rng, L=L):
    mk = lambda: rng.normal(0, 1, size=(B, L, H, D)).astype(np.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(rng, causal):
    q, k, v = qkv(rng)
    out = flash_attention(q, k, v, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_forward_with_key_mask(rng):
    q, k, v = qkv(rng)
    mask = np.ones((B, L), np.float32)
    mask[:, L - 40:] = 0.0
    out = flash_attention(q, k, v, key_mask=mask)
    ref = attention_reference(q, k, v, key_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_fully_masked_rows_give_zeros(rng):
    q, k, v = qkv(rng)
    mask = np.zeros((B, L), np.float32)  # nothing to attend to
    out = np.asarray(flash_attention(q, k, v, key_mask=mask))
    np.testing.assert_allclose(out, np.zeros_like(out), atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(rng, causal):
    q, k, v = qkv(rng)
    cot = rng.normal(size=(B, L, H, D)).astype(np.float32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) * cot)

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) * cot)

    g = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    r = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for name, gg, rr in zip("qkv", g, r):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rr),
                                   rtol=5e-3, atol=5e-4, err_msg=name)


def test_masked_gradients_match_reference(rng):
    q, k, v = qkv(rng)
    mask = np.ones((B, L), np.float32)
    mask[:, L - 64:] = 0.0
    cot = rng.normal(size=(B, L, H, D)).astype(np.float32)

    g = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, key_mask=mask) * cot
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    r = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, key_mask=mask) * cot
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, gg, rr in zip("qkv", g, r):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rr),
                                   rtol=5e-3, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_multi_k_tile_online_softmax(rng, causal, monkeypatch):
    """Multiple k tiles per q block (nk=2): exercises the cross-tile corr
    rescaling of (m, l, acc) and the causal last_k early finalization that
    single-tile shapes never touch. BLOCK_K is shrunk so the multi-tile
    path runs at CI-friendly sizes."""
    from distkeras_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "BLOCK_K", 128)
    q, k, v = qkv(rng)                       # L=256 → nk=2
    mask = np.ones((B, L), np.float32)
    mask[:, L - 60:] = 0.0
    out = fa.flash_attention(q, k, v, causal=causal, key_mask=mask)
    ref = attention_reference(q, k, v, causal=causal, key_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    # and the gradient path across tiles
    cot = rng.normal(size=(B, L, H, D)).astype(np.float32)
    g = jax.grad(
        lambda q: jnp.sum(
            fa.flash_attention(q, k, v, causal=causal, key_mask=mask) * cot
        )
    )(q)
    r = jax.grad(
        lambda q: jnp.sum(
            attention_reference(q, k, v, causal=causal, key_mask=mask) * cot
        )
    )(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                               rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_bwd_kernel_matches_bwd_math(rng, causal):
    """Pin the Pallas backward kernels directly against the plain-XLA
    gradient identities (same saved lse), causal x key_mask."""
    from distkeras_tpu.ops import flash_attention as fa

    q, k, v = qkv(rng)
    mask = np.ones((B, L), np.float32)
    mask[:, L - 48:] = 0.0
    scale = D ** -0.5
    out, lse = fa._fa_forward(q, k, v, mask, scale=scale, causal=causal,
                              interpret=True)
    g = rng.normal(size=(B, L, H, D)).astype(np.float32)
    dq, dk, dv = fa._fa_backward(q, k, v, mask, out, lse, g,
                                 scale=scale, causal=causal, interpret=True)
    rq, rk, rv = fa._attention_bwd_math(q, k, v, mask, lse, g,
                                        scale=scale, causal=causal)
    for name, got, want in (("dq", dq, rq), ("dk", dk, rk), ("dv", dv, rv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_multi_tile_bwd_all_grads(rng, causal, monkeypatch):
    """Grads wrt q AND k AND v with 2 k tiles per q block: exercises the
    dkv kernel's cross-q accumulation and the causal first_q skip."""
    from distkeras_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "BLOCK_K", 128)
    q, k, v = qkv(rng)                       # L=256 → 2 tiles each way
    mask = np.ones((B, L), np.float32)
    mask[:, L - 60:] = 0.0
    cot = rng.normal(size=(B, L, H, D)).astype(np.float32)
    g = jax.grad(
        lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, causal=causal, key_mask=mask) * cot
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    r = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=causal, key_mask=mask) * cot
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, gg, rr in zip("qkv", g, r):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rr),
                                   rtol=5e-3, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_asymmetric_tiles_bwd(rng, causal, monkeypatch):
    """Production tiling has block_k > block_q (512 vs 128); exercise the
    asymmetric causal skip bounds (last_k/first_q stride by bk/bq = 2 here)
    that the symmetric-tile tests never reach."""
    from distkeras_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "BLOCK_K", 256)
    L2 = 512                                  # 4 q blocks x 2 k blocks
    q, k, v = qkv(rng, L=L2)
    mask = np.ones((B, L2), np.float32)
    mask[:, L2 - 50:] = 0.0
    cot = rng.normal(size=(B, L2, H, D)).astype(np.float32)
    g = jax.grad(
        lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, causal=causal, key_mask=mask) * cot
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    r = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=causal, key_mask=mask) * cot
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, gg, rr in zip("qkv", g, r):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rr),
                                   rtol=5e-3, atol=5e-4, err_msg=name)


def test_fully_masked_rows_zero_grads(rng):
    """All-masked rows must give finite (zero) dq and contribute nothing
    to dk/dv — the exp(s - lse) recompute must not NaN."""
    q, k, v = qkv(rng)
    mask = np.zeros((B, L), np.float32)
    cot = np.ones((B, L, H, D), np.float32)
    g = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, key_mask=mask) * cot
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, gg in zip("qkv", g):
        arr = np.asarray(gg)
        assert np.isfinite(arr).all(), name
        np.testing.assert_allclose(arr, np.zeros_like(arr), atol=1e-6,
                                   err_msg=name)


def test_length_guard_raises_below_block(rng):
    mk = lambda: rng.normal(size=(B, 96, H, D)).astype(np.float32)
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention(mk(), mk(), mk())


def test_under_jit_with_traced_mask(rng):
    q, k, v = qkv(rng)
    mask = np.ones((B, L), np.float32)

    @jax.jit
    def f(q, k, v, mask):
        return flash_attention(q, k, v, key_mask=mask)

    out = f(q, k, v, mask)
    ref = attention_reference(q, k, v, key_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_auto_dispatch_falls_back_on_ragged_length(rng):
    Lr = 100  # not a multiple of the q block
    mk = lambda: rng.normal(size=(B, Lr, H, D)).astype(np.float32)
    q, k, v = mk(), mk(), mk()
    out = attention(q, k, v, impl="auto")
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_block_ladders_scale_with_length():
    """Blocks scale with L: 512 rows by the widest of 2048 | 1024 | 512 keys
    that divides L from L = 1024 up (v5e, PR 28: a grid step costs about a
    microsecond, and the band is cut inside a step, so a wide tile computes
    nothing outside it), (128, 512|384|256|128) below and at lengths 512
    does not divide — keeping the tile index math's divisibility assumption
    (bk % bq == 0 or bq % bk == 0) true by construction."""
    from distkeras_tpu.ops.flash_attention import _pick_block_k, _pick_block_q

    assert (_pick_block_q(1024), _pick_block_k(1024)) == (512, 1024)
    assert (_pick_block_q(1536), _pick_block_k(1536)) == (512, 512)
    assert (_pick_block_q(2048), _pick_block_k(2048)) == (512, 2048)
    assert (_pick_block_q(3072), _pick_block_k(3072)) == (512, 1024)
    assert (_pick_block_q(4096), _pick_block_k(4096)) == (512, 2048)
    assert (_pick_block_q(8192), _pick_block_k(8192)) == (512, 2048)
    assert (_pick_block_q(16384), _pick_block_k(16384)) == (512, 2048)
    # non-512-multiples keep the small-tile fallbacks
    assert (_pick_block_q(4480), _pick_block_k(4480)) == (128, 128)
    assert (_pick_block_q(256), _pick_block_k(256)) == (128, 256)
    # L = 512 is BELOW the measured range: a 512-row tile there would be a
    # single-tile config no measurement covered, so the gate keeps the
    # default ladder
    assert (_pick_block_q(512), _pick_block_k(512)) == (128, 512)
    for L in (512, 1024, 2048, 4096, 4480, 8192, 8320, 16384):
        bq, bk = _pick_block_q(L), _pick_block_k(L)
        assert L % bq == 0 and L % bk == 0
        assert bk % bq == 0 or bq % bk == 0


@pytest.mark.parametrize("causal", [False, True])
def test_large_block_path_matches_reference(rng, causal):
    """The L = 4096 (512, 2048) tile path, end to end in interpret mode:
    forward and all three gradients vs the XLA oracle (``chip_smoke.py``'s
    ``kernels`` phase holds the native kernels to XLA on the chip; this pins
    the same code path in CI)."""
    Lbig = 4096
    q = rng.normal(0, 1, size=(1, Lbig, 1, 64)).astype(np.float32)
    k = rng.normal(0, 1, size=(1, Lbig, 1, 64)).astype(np.float32)
    v = rng.normal(0, 1, size=(1, Lbig, 1, 64)).astype(np.float32)
    cot = rng.normal(size=(1, Lbig, 1, 64)).astype(np.float32)

    out = flash_attention(q, k, v, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=causal) * cot),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=causal) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_wide_k_tile_bk_over_bq_path(rng, causal, monkeypatch):
    """The L>=8192 ladder's (bq=512, bk=1024) combo — bk wider than bq —
    exercises the backward's first_q/last_k skip math on the bk > bq side.
    The ladders are monkeypatched so the combo runs at a CI-friendly
    L=2048 (the tile arithmetic only sees bq/bk, never L itself)."""
    from distkeras_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_pick_block_q", lambda L: 512)
    monkeypatch.setattr(fa, "_pick_block_k", lambda L: 1024)
    Lw = 2048
    q = rng.normal(0, 1, size=(1, Lw, 1, 64)).astype(np.float32)
    k = rng.normal(0, 1, size=(1, Lw, 1, 64)).astype(np.float32)
    v = rng.normal(0, 1, size=(1, Lw, 1, 64)).astype(np.float32)
    cot = rng.normal(size=(1, Lw, 1, 64)).astype(np.float32)

    out = fa.flash_attention(q, k, v, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, causal=causal) * cot),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=causal) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


# ---------------------------------------------------------------------------
# Sliding-window (local) attention
# ---------------------------------------------------------------------------
#
# The windowed comparisons pin matmul precision: this host's XLA:CPU runs
# f32 dots at reduced precision (~1e-2 abs on L=256 scores), and a windowed
# softmax has few enough terms that the noise no longer averages out of the
# normalized output (full-row softmax comparisons above absorb it).


@pytest.mark.parametrize("causal", [False, True])
def test_windowed_forward_matches_reference(rng, causal):
    q, k, v = qkv(rng)
    with jax.default_matmul_precision("highest"):
        for w in (1, 17, 128, 200):
            out = flash_attention(q, k, v, causal=causal, window=w)
            ref = attention_reference(q, k, v, causal=causal, window=w)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=f"window={w}")


@pytest.mark.parametrize("causal", [False, True])
def test_windowed_gradients_match_reference(rng, causal):
    q, k, v = qkv(rng)
    cot = rng.normal(size=(B, L, H, D)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        for w in (17, 200):
            g = jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, causal=causal, window=w) * cot
                ),
                argnums=(0, 1, 2),
            )(q, k, v)
            r = jax.grad(
                lambda q, k, v: jnp.sum(
                    attention_reference(q, k, v, causal=causal, window=w)
                    * cot
                ),
                argnums=(0, 1, 2),
            )(q, k, v)
            for name, gg, rr in zip("qkv", g, r):
                np.testing.assert_allclose(
                    np.asarray(gg), np.asarray(rr), rtol=5e-3, atol=5e-4,
                    err_msg=f"window={w} {name}")


@pytest.mark.parametrize("causal", [False, True])
def test_windowed_restricted_grid_multi_tile(rng, causal, monkeypatch):
    """nk > 1 with a window smaller than the sequence: the kernel's k axis
    is RESTRICTED (first_k > 0 for late q blocks, index-map clamping at the
    band edges) — the path the single-tile shapes never reach."""
    from distkeras_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "BLOCK_K", 128)
    Lw = 512                                  # 4 q blocks × 4 k tiles
    mk = lambda: rng.normal(0, 1, size=(1, Lw, 2, D)).astype(np.float32)
    q, k, v = mk(), mk(), mk()
    cot = rng.normal(size=(1, Lw, 2, D)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        for w in (64, 130):
            g = jax.grad(
                lambda q, k, v: jnp.sum(
                    fa.flash_attention(q, k, v, causal=causal, window=w)
                    * cot
                ),
                argnums=(0, 1, 2),
            )(q, k, v)
            r = jax.grad(
                lambda q, k, v: jnp.sum(
                    attention_reference(q, k, v, causal=causal, window=w)
                    * cot
                ),
                argnums=(0, 1, 2),
            )(q, k, v)
            for name, gg, rr in zip("qkv", g, r):
                np.testing.assert_allclose(
                    np.asarray(gg), np.asarray(rr), rtol=5e-3, atol=5e-4,
                    err_msg=f"window={w} {name}")


def test_windowed_with_key_mask_band_fully_masked(rng):
    """Queries whose whole BAND is key-masked must yield zeros and finite
    zero gradients in both the kernel and the reference (the reference's
    zeroing convention combines the band with the key mask)."""
    q, k, v = qkv(rng)
    mask = np.ones((B, L), np.float32)
    mask[:, L - 100:] = 0.0                    # last 100 keys invalid
    w = 40                                     # queries >= L-61 see nothing
    cot = rng.normal(size=(B, L, H, D)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, key_mask=mask, window=w)
        ref = attention_reference(q, k, v, key_mask=mask, window=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        dead = np.asarray(out)[:, L - 61:]
        np.testing.assert_allclose(dead, np.zeros_like(dead), atol=1e-6)
        g = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, key_mask=mask, window=w) * cot
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        r = jax.grad(
            lambda q, k, v: jnp.sum(
                attention_reference(q, k, v, key_mask=mask, window=w) * cot
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for name, gg, rr in zip("qkv", g, r):
            assert np.isfinite(np.asarray(gg)).all(), name
            np.testing.assert_allclose(np.asarray(gg), np.asarray(rr),
                                       rtol=5e-3, atol=5e-4, err_msg=name)


def test_window_validation_and_degenerate(rng):
    q, k, v = qkv(rng)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, window=0)
    with pytest.raises(ValueError, match="window"):
        attention_reference(q, k, v, window=-3)
    # window >= L is exactly the unwindowed program
    a = flash_attention(q, k, v, causal=True, window=L + 7)
    b = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)


def test_attention_dispatch_passes_window(rng):
    q, k, v = qkv(rng)
    with jax.default_matmul_precision("highest"):
        out = attention(q, k, v, causal=True, window=50, impl="flash")
        ref = attention_reference(q, k, v, causal=True, window=50)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        # reference dispatch honors it too
        out = attention(q, k, v, causal=True, window=50, impl="reference")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=0)


# ---------------------------------------------------------------------------
# Grouped-query attention (kv heads < q heads) — kernel-native
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hkv", [2, 1])
@pytest.mark.parametrize("causal", [False, True])
def test_gqa_flash_matches_reference(rng, hkv, causal):
    """k/v with Hkv shared heads go straight into the kernels (index-map
    head grouping, grouped dk/dv accumulation) — forward and all three
    gradients equal the expanded-KV reference, with Hkv-shaped dk/dv."""
    Hq = 4
    q = rng.normal(0, 1, size=(B, L, Hq, D)).astype(np.float32)
    k = rng.normal(0, 1, size=(B, L, hkv, D)).astype(np.float32)
    v = rng.normal(0, 1, size=(B, L, hkv, D)).astype(np.float32)
    cot = rng.normal(size=(B, L, Hq, D)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, causal=causal)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        g = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=causal) * cot),
            argnums=(0, 1, 2))(q, k, v)
        r = jax.grad(
            lambda q, k, v: jnp.sum(
                attention_reference(q, k, v, causal=causal) * cot),
            argnums=(0, 1, 2))(q, k, v)
        assert g[1].shape == (B, L, hkv, D)  # dk stays Hkv-wide
        for name, gg, rr in zip("qkv", g, r):
            np.testing.assert_allclose(np.asarray(gg), np.asarray(rr),
                                       rtol=5e-3, atol=1e-3, err_msg=name)


def test_gqa_flash_with_window_and_mask(rng):
    """GQA × sliding window × key mask, all three in one kernel program."""
    Hq, hkv = 4, 2
    q = rng.normal(0, 1, size=(B, L, Hq, D)).astype(np.float32)
    k = rng.normal(0, 1, size=(B, L, hkv, D)).astype(np.float32)
    v = rng.normal(0, 1, size=(B, L, hkv, D)).astype(np.float32)
    mask = np.ones((B, L), np.float32)
    mask[:, L - 48:] = 0.0
    cot = rng.normal(size=(B, L, Hq, D)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        g = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True, window=40,
                                key_mask=mask) * cot),
            argnums=(0, 1, 2))(q, k, v)
        r = jax.grad(
            lambda q, k, v: jnp.sum(
                attention_reference(q, k, v, causal=True, window=40,
                                    key_mask=mask) * cot),
            argnums=(0, 1, 2))(q, k, v)
        for name, gg, rr in zip("qkv", g, r):
            np.testing.assert_allclose(np.asarray(gg), np.asarray(rr),
                                       rtol=5e-3, atol=1e-3, err_msg=name)


def test_gqa_head_divisibility_validated(rng):
    q = rng.normal(size=(1, 128, 4, 32)).astype(np.float32)
    kv = rng.normal(size=(1, 128, 3, 32)).astype(np.float32)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, kv, kv)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        attention_reference(q, kv, kv)


# -- one kernel per device under a mesh (ops.kernel_mesh) ----------------------


@pytest.mark.parametrize("masked,stacked", [(False, False), (True, False),
                                            (False, True)])
def test_kernel_runs_per_device_under_a_declared_mesh(rng, masked, stacked):
    """A Mosaic kernel inside a jit over several chips is refused by the
    compiler unless it sits in a shard_map — which a virtual CPU mesh never
    shows. Under ``kernel_mesh`` the forward AND the backward each become a
    shard_map over the batch split (every other mesh axis replicated), with
    the same values and gradients as the reference; with no mesh declared
    the program holds no shard_map at all. ``stacked`` adds a vmapped
    worker axis on top."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distkeras_tpu.ops import kernel_mesh

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    Bm = 4
    lead = (2,) if stacked else ()
    mk = lambda h: jnp.asarray(
        rng.normal(size=lead + (Bm, 128, h, 32)).astype(np.float32))
    q, k, v, cot = mk(4), mk(2), mk(2), mk(4)
    mask = None
    if masked:
        mask = np.ones((Bm, 128), np.float32)
        mask[:, 100:] = 0.0

    def loss(fn):
        def one(q, k, v, cot):
            return jnp.sum(fn(q, k, v, causal=True, key_mask=mask) * cot)
        f = jax.vmap(one) if stacked else one
        return lambda q, k, v: jnp.sum(f(q, k, v, cot))

    def declared(q, k, v):
        with kernel_mesh(mesh, "dp"):
            return jax.value_and_grad(loss(flash_attention), (0, 1, 2))(
                q, k, v)

    spec = P(None, "dp") if stacked else P("dp")
    put = lambda x: jax.device_put(x, NamedSharding(mesh, spec))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(declared)(put(q), put(k), put(v))
        want = jax.value_and_grad(loss(attention_reference), (0, 1, 2))(
            q, k, v)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-3, atol=1e-3)
    assert str(jax.make_jaxpr(declared)(q, k, v)).count("shard_map") >= 2
    plain = jax.make_jaxpr(jax.value_and_grad(loss(flash_attention),
                                              (0, 1, 2)))(q, k, v)
    assert "shard_map" not in str(plain) and "pallas_call" in str(plain)


# ---------------------------------------------------------------------------
# The band's grain: what a step runs is decided from its tile's offset
# ---------------------------------------------------------------------------

# (L, causal, window, key mask) -> for forward and for dq = dk/dv: computed
# over band pairs, the share of the computed pairs with no mask, idle steps
CENSUS = {
    "causal-2048": ((2048, True, None, False),
                    (1.2494, 0.0, 0), (1.1245, 0.7778, 0)),
    "causal-4096": ((4096, True, None, False),
                    (1.1247, 0.4444, 4), (1.0622, 0.8824, 4)),
    "full-2048": ((2048, False, None, False), (1.0, 1.0, 0), (1.0, 1.0, 0)),
    "causal-window512-2048": ((2048, True, 512, False),
                              (1.9994, 0.0, 0), (1.4996, 0.3333, 0)),
    "bidirectional-window512-2048": ((2048, False, 512, False),
                                     (1.4298, 0.0, 0), (1.2153, 0.6471, 0)),
    "full-keymask-2048": ((2048, False, None, True),
                          (1.0, 0.0, 0), (1.0, 0.0, 0)),
    "causal-keymask-2048": ((2048, True, None, True),
                            (1.2494, 0.0, 0), (1.1245, 0.0, 0)),
    # the fallback ladders: (128, 256) cut no finer, (128, 128) tiles
    "causal-256": ((256, True, None, False),
                   (1.9922, 0.0, 0), (1.9922, 0.0, 0)),
    "causal-4480": ((4480, True, None, False),
                    (1.0283, 0.9444, 595), (1.0283, 0.9444, 595)),
}


@pytest.mark.parametrize("case", sorted(CENSUS))
def test_band_census_counts_what_the_kernels_run(case):
    """The static counter, from the kernels' own index arithmetic: at the
    benchmark's two lengths no kernel computes more than 1.25 / 1.125 times
    the causal band (1.50 / 1.25 when whole tiles ran under the mask), dq
    and dk/dv run 78 % / 88 % of their pairs with no mask, no tile runs a
    mask without a band or key mask, every body runs one under a key mask,
    and the grid's pairs are all accounted for."""
    from distkeras_tpu.ops.flash_attention import _tiles, band_census

    (L, causal, window, masked), fwd, bwd = CENSUS[case]
    census = band_census(L, causal=causal, window=window, masked=masked)
    assert sorted(census) == ["flash_dkv", "flash_dq", "flash_fwd"]
    bq, bk = _tiles(L)
    for name, c in census.items():
        ratio, unmasked, idle = fwd if name == "flash_fwd" else bwd
        computed = c["pairs_unmasked"] + c["pairs_masked"]
        assert computed + c["pairs_skipped"] == c["steps"] * bq * bk, name
        assert computed >= c["pairs_band"], name
        assert c["computed_over_band"] == pytest.approx(ratio, abs=1e-4), name
        assert c["pairs_unmasked"] / computed == pytest.approx(
            unmasked, abs=1e-4), name
        assert c["steps_idle"] == idle, name
        assert (c["bodies_unmasked"] == 0) == (unmasked == 0.0), name
    if case in ("causal-2048", "causal-4096"):
        limit = 1.25 if L == 2048 else 1.125
        assert all(c["computed_over_band"] <= limit for c in census.values())


def _band_case(rng, case):
    """(q, k, v, kwargs) at L = 1024 for one of the band cases below."""
    heads, kv_heads, D = (2, 2, 64) if case.startswith("mha64") else (4, 1, 128)
    Lb = 1024
    mk = lambda h: rng.normal(0, 1, size=(1, Lb, h, D)).astype(np.float32)
    q, k, v = mk(heads), mk(kv_heads), mk(kv_heads)
    kind = case.split("-", 1)[1]
    kw = {"causal": kind not in ("bidirectional-window", "blocks",
                                 "blocks-keymask")}
    if "window" in kind:
        kw["window"] = 300
    if "blocks" in kind:         # a noised and a clean copy of rows of 512
        kw["block_diffusion"] = 4
    if "keymask" in kind:
        mask = np.ones((1, Lb), np.float32)
        mask[:, :40] = 0.0       # queries 0..39 see no key at all
        mask[:, 700:760] = 0.0
        kw["key_mask"] = mask
    return q, k, v, kw


BAND_CASES = [f"{h}-{k}" for h in ("mha64", "gqa128")
              for k in ("causal", "causal-window", "bidirectional-window",
                        "keymask", "blocks", "blocks-keymask")]


@pytest.fixture
def small_band_tiles(monkeypatch):
    """256 x 512 tiles cut down to 128, bodies of dq and dk/dv 256 keys wide
    at most: at L = 1024 a causal call then holds, in its first grid step
    AND its last, a piece left out, a piece with no mask and a piece under
    the mask (and tiles wholly inside and wholly outside the band between)."""
    from distkeras_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_pick_block_q", lambda L: 256)
    monkeypatch.setattr(fa, "_pick_block_k", lambda L: 512)
    monkeypatch.setattr(fa, "_FINE", 128)
    monkeypatch.setattr(fa, "_WIDEST", 256)
    plan = {d: pieces for d, pieces, _ in
            fa._band_plan(1024, fa._tiles(1024), True, None)}
    first, last = plan[0], plan[256]     # steps (0, 0) and (3, 1)
    for pieces in (first, last):
        edges = [edge for *_, edge in pieces]
        assert True in edges and False in edges
        assert sum(r * c for _, _, r, c, _ in pieces) < 256 * 512
    # the launchers read the grain when they trace: nothing traced at
    # another grain may answer for these tiles, here or after
    for launcher in (fa._fwd_call, fa._bwd_call):
        launcher.clear_cache()
    yield fa
    for launcher in (fa._fwd_call, fa._bwd_call):
        launcher.clear_cache()


@pytest.mark.parametrize("case", BAND_CASES)
def test_band_census_is_what_the_kernels_run(rng, case, small_band_tiles,
                                             monkeypatch):
    """The census against the kernels at run time: every body a kernel's grid
    steps actually execute (interpret mode, counted through ``_run_band``'s
    ``fold``) is a body ``band_census`` counted, by kind and by pairs, for the
    forward, dq and dk/dv alike: a guard or an index map changed in a kernel
    and not in the census fails here."""
    fa = small_band_tiles
    q, k, v, kw = _band_case(rng, case)
    ran = []
    real = fa._run_band

    def counting(fold, *rest):
        bodies = []           # one list a kernel, in the order they trace
        ran.append(bodies)

        def counted(r, c, rows, cols, edge):
            jax.debug.callback(lambda: bodies.append((rows * cols, edge)))
            fold(r, c, rows, cols, edge)
        real(counted, *rest)

    monkeypatch.setattr(fa, "_run_band", counting)
    jax.block_until_ready(jax.grad(
        lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, **kw)),
        argnums=(0, 1, 2))(q, k, v))
    jax.effects_barrier()
    census = fa.band_census(q.shape[1], causal=kw["causal"],
                            window=kw.get("window"),
                            masked="key_mask" in kw,
                            block_diffusion=kw.get("block_diffusion"))
    heads = q.shape[0] * q.shape[2]
    assert len(ran) == 3
    for name, bodies in zip(("flash_fwd", "flash_dq", "flash_dkv"), ran):
        c = census[name]
        masked = [n for n, edge in bodies if edge or "key_mask" in kw]
        assert len(bodies) - len(masked) == heads * c["bodies_unmasked"], name
        assert len(masked) == heads * c["bodies_masked"], name
        assert sum(n for n, _ in bodies) == heads * (
            c["pairs_unmasked"] + c["pairs_masked"]), name
        assert c["steps_idle"] > 0 or "window" in kw, name


@pytest.mark.parametrize("case", BAND_CASES)
def test_band_pieces_forward_and_lse_match_reference(rng, case,
                                                     small_band_tiles):
    fa = small_band_tiles
    q, k, v, kw = _band_case(rng, case)
    scale = q.shape[-1] ** -0.5
    with jax.default_matmul_precision("highest"):
        diffusion = fa._canonical_diffusion(
            kw.get("block_diffusion"), q.shape[1], kw["causal"],
            kw.get("window"))
        out, lse = fa._fa_forward(
            q, k, v, kw.get("key_mask"), scale=scale, causal=kw["causal"],
            interpret=True, window=kw.get("window"), diffusion=diffusion)
        ref = attention_reference(q, k, v, **kw)
        groups = q.shape[2] // k.shape[2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q * scale,
                       jnp.repeat(k, groups, axis=2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    Lb = q.shape[1]
    valid = fa.band_predicate(np.arange(Lb)[:, None], np.arange(Lb)[None, :],
                              kw["causal"], kw.get("window"), diffusion)
    valid = np.broadcast_to(valid, s.shape)
    if "key_mask" in kw:
        valid = valid & (kw["key_mask"][:, None, None, :] > 0.5)
    want = jax.scipy.special.logsumexp(jnp.where(valid, s, -jnp.inf), axis=-1)
    seen = valid.any(-1)                     # rows with a key to attend to
    got = np.asarray(lse).reshape(want.shape)
    np.testing.assert_allclose(got[seen], np.asarray(want)[seen],
                               rtol=2e-5, atol=2e-5)
    if "key_mask" in kw:
        assert not seen.all()
        # causal: queries 0..39 see no key; under blocks only the first four
        # (later noised blocks see earlier clean ones, which are not masked)
        dead = np.asarray(out)[:, :40 if kw["causal"] else 4]
        np.testing.assert_allclose(dead, np.zeros_like(dead), atol=1e-6)


@pytest.mark.parametrize("case", BAND_CASES)
def test_band_pieces_gradients_match_reference(rng, case, small_band_tiles):
    fa = small_band_tiles
    q, k, v, kw = _band_case(rng, case)
    cot = rng.normal(size=q.shape).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        g = jax.grad(
            lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, **kw) * cot),
            argnums=(0, 1, 2))(q, k, v)
        r = jax.grad(
            lambda q, k, v: jnp.sum(attention_reference(q, k, v, **kw) * cot),
            argnums=(0, 1, 2))(q, k, v)
    assert g[1].shape == k.shape and g[2].shape == v.shape
    for name, gg, rr in zip(("dq", "dk", "dv"), g, r):
        assert np.isfinite(np.asarray(gg)).all(), name
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rr),
                                   rtol=5e-3, atol=1e-3, err_msg=name)
