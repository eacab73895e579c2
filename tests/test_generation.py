"""Causal LM + KV-cached autoregressive decoding (models/lm.py).

The load-bearing oracle: decoding one token at a time against the KV cache
must produce exactly the same logits as re-running the full causal forward
on the growing sequence — cache decode is an optimization, never a
different model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import generate, next_token_dataset, transformer_lm
from distkeras_tpu.models.lm import TransformerLM

VOCAB, MAXLEN, DIM, HEADS, DEPTH = 64, 32, 32, 4, 2


@pytest.fixture(scope="module")
def lm():
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                          depth=DEPTH, dtype=jnp.float32)
    params, _ = spec.init_np(0)
    return spec, params


def _assert_cached_decode_matches_full(module, params, toks, lp, *,
                                       check_prefill_logits=True,
                                       rtol=2e-4, atol=2e-4):
    """Prefill on ``toks[:, :lp]`` + jitted cached decode over the rest must
    match ONE full forward over the whole sequence, position by position:
    causal attention makes ``full[:, pos]`` the prediction after consuming
    exactly ``toks[:, :pos+1]`` (causality of the full forward itself is
    pinned in test_decode_step_matches_full_forward). Returns the final
    caches."""
    full = np.asarray(module.apply({"params": params}, toks))
    logits_pre, caches = module.apply(
        {"params": params}, toks[:, :lp], method=TransformerLM.prefill
    )
    if check_prefill_logits:
        np.testing.assert_allclose(
            np.asarray(logits_pre), full[:, :lp], rtol=rtol, atol=atol
        )
    step = jax.jit(
        lambda tok, caches, pos: module.apply(
            {"params": params}, tok, caches, pos,
            method=TransformerLM.decode_step,
        )
    )
    for pos in range(lp, toks.shape[1]):
        step_logits, caches = step(toks[:, pos], caches, pos)
        np.testing.assert_allclose(
            np.asarray(step_logits), full[:, pos],
            rtol=rtol, atol=atol, err_msg=f"pos={pos}",
        )
    return caches


def test_decode_step_matches_full_forward(lm):
    """Prefill + N cached decode steps == full forward logits, position by
    position (f32, exact math path).

    The oracle is ONE full forward over the whole sequence: causal
    attention makes ``full[:, pos]`` the model's prediction after
    consuming exactly ``toks[:, :pos+1]`` — verified directly below by a
    prefix re-run — so every decode position checks against it without
    re-running a growing-prefix forward per step."""
    spec, params = lm
    module = spec.module
    rng = np.random.default_rng(0)
    toks = rng.integers(0, VOCAB, size=(3, 12)).astype(np.int32)

    full = np.asarray(module.apply({"params": params}, toks))
    # causality of the oracle itself: a prefix re-run reproduces its rows
    lp = 5
    prefix = module.apply({"params": params}, toks[:, :lp])
    np.testing.assert_allclose(np.asarray(prefix), full[:, :lp],
                               rtol=2e-4, atol=2e-4)

    logits_pre, caches = module.apply(
        {"params": params}, toks[:, :lp], method=TransformerLM.prefill
    )
    np.testing.assert_allclose(
        np.asarray(logits_pre), full[:, :lp], rtol=2e-4, atol=2e-4
    )
    step = jax.jit(
        lambda tok, caches, pos: module.apply(
            {"params": params}, tok, caches, pos,
            method=TransformerLM.decode_step,
        )
    )
    for pos in range(lp, toks.shape[1]):
        step_logits, caches = step(toks[:, pos], caches, pos)
        np.testing.assert_allclose(
            np.asarray(step_logits), full[:, pos],
            rtol=2e-4, atol=2e-4,
        )


def test_greedy_generation_matches_uncached_argmax(lm):
    """generate(temperature=0) equals the uncached greedy stream — the
    cache changes cost, not output. Greedy self-consistency needs one
    full forward on the emitted sequence: token t+1 must be the argmax of
    the full model's logits at position t given the emitted prefix (the
    causal forward's row t sees exactly that prefix)."""
    spec, params = lm
    module = spec.module
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, VOCAB, size=(2, 6)).astype(np.int32)
    out = generate(spec, params, prompt, max_new_tokens=8)
    assert out.shape == (2, 14)
    assert np.array_equal(out[:, :6], prompt)

    full = np.asarray(module.apply({"params": params}, jnp.asarray(out)))
    want = np.argmax(full[:, 5:-1], axis=-1)
    np.testing.assert_array_equal(out[:, 6:], want)


def test_sampled_generation_reproducible_and_valid(lm):
    spec, params = lm
    prompt = np.zeros((4, 4), np.int32)
    a = generate(spec, params, prompt, max_new_tokens=6, temperature=1.0,
                 top_k=8, seed=7)
    b = generate(spec, params, prompt, max_new_tokens=6, temperature=1.0,
                 top_k=8, seed=7)
    c = generate(spec, params, prompt, max_new_tokens=6, temperature=1.0,
                 top_k=8, seed=8)
    np.testing.assert_array_equal(a, b)  # same seed → same tokens
    assert not np.array_equal(a, c)      # different seed → different draw
    assert a.min() >= 0 and a.max() < VOCAB


def test_top_k_restricts_support(lm):
    """With top_k=1, sampling at any temperature degenerates to greedy."""
    spec, params = lm
    prompt = np.ones((2, 5), np.int32)
    greedy = generate(spec, params, prompt, max_new_tokens=5)
    k1 = generate(spec, params, prompt, max_new_tokens=5, temperature=2.0,
                  top_k=1, seed=3)
    np.testing.assert_array_equal(greedy, k1)


def test_top_p_restricts_support(lm):
    """Sampled tokens stay inside the numpy-computed nucleus; a tiny top_p
    degenerates to greedy; top_p=1.0 is a no-op filter."""
    spec, params = lm
    module = spec.module
    prompt = np.ones((2, 5), np.int32)

    greedy = generate(spec, params, prompt, max_new_tokens=5)
    p_tiny = generate(spec, params, prompt, max_new_tokens=5,
                      temperature=2.0, top_p=1e-6, seed=3)
    np.testing.assert_array_equal(greedy, p_tiny)

    plain = generate(spec, params, prompt, max_new_tokens=6,
                     temperature=1.0, seed=11)
    p_one = generate(spec, params, prompt, max_new_tokens=6,
                     temperature=1.0, top_p=1.0, seed=11)
    np.testing.assert_array_equal(plain, p_one)

    # every sampled first token lies in the nucleus of its own distribution
    top_p = 0.6
    logits = np.asarray(
        module.apply({"params": params}, jnp.asarray(prompt))
    )[:, -1]
    out = generate(spec, params, prompt, max_new_tokens=1, temperature=1.0,
                   top_p=top_p, seed=5)
    for row, tok in enumerate(out[:, -1]):
        order = np.argsort(-logits[row])
        probs = np.exp(logits[row][order] - logits[row][order].max())
        probs /= probs.sum()
        before = np.cumsum(probs) - probs
        nucleus = set(order[before < top_p])
        assert int(tok) in nucleus


def test_generate_rejects_bad_top_p(lm):
    spec, params = lm
    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="top_p"):
            generate(spec, params, np.zeros((1, 4), np.int32),
                     max_new_tokens=2, temperature=1.0, top_p=bad)


def test_generate_validates_inputs(lm):
    spec, params = lm
    with pytest.raises(ValueError, match="maxlen"):
        generate(spec, params, np.zeros((1, 30), np.int32), max_new_tokens=5)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(spec, params, np.zeros((1, 4), np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="batch, length"):
        generate(spec, params, np.zeros((4,), np.int32), max_new_tokens=2)
    with pytest.raises(TypeError, match="TransformerLM"):
        from distkeras_tpu.models import mlp

        generate(mlp(), params, np.zeros((1, 4), np.int32), max_new_tokens=2)


def test_lm_trains_next_token_with_trainer():
    """The LM is a first-class trainer citizen: ADAG on the 8-device mesh
    drives next-token loss down on a deterministic-cycle language, and the
    trained model then generates the cycle greedily."""
    from distkeras_tpu import ADAG

    period = 8
    rows, length = 512, 16
    rng = np.random.default_rng(0)
    starts = rng.integers(0, period, size=(rows, 1))
    grid = (starts + np.arange(length + 1)[None]) % period  # token = pos%8
    ds = next_token_dataset(grid)
    assert ds["features"].shape == (rows, length)
    assert np.array_equal(ds["features"][:, 1:], ds["label"][:, :-1])

    spec = transformer_lm(vocab=period, maxlen=32, dim=32, heads=4, depth=2,
                          dtype=jnp.float32)
    t = ADAG(spec, loss="sparse_softmax_cross_entropy",
             worker_optimizer="adam", learning_rate=5e-3, num_workers=4,
             batch_size=32, communication_window=2, num_epoch=6)
    t.train(ds, shuffle=True)
    losses = [float(l) for l in t.get_history().losses()]
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < 0.5 * np.mean(losses[:4])

    prompt = np.tile(np.arange(6) % period, (2, 1)).astype(np.int32)
    out = generate(spec, t.trained_params_, prompt, max_new_tokens=8)
    expect = (np.arange(6, 14) % period)[None].repeat(2, axis=0)
    assert np.array_equal(out[:, 6:], expect)


def test_generator_predictor_appends_column(lm):
    """GeneratorPredictor chunks prompts to a static batch and appends the
    generated-token column; equal to calling generate() directly."""
    from distkeras_tpu.data import Dataset
    from distkeras_tpu.predictors import GeneratorPredictor

    spec, params = lm
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, VOCAB, size=(11, 6)).astype(np.int32)  # 11 % 4 != 0
    ds = Dataset({"features": prompts})
    p = GeneratorPredictor(spec, params, max_new_tokens=5, batch_size=4)
    out = p.predict(ds)
    assert out["generated"].shape == (11, 5)
    direct = generate(spec, params, prompts, max_new_tokens=5)
    np.testing.assert_array_equal(out["generated"], direct[:, 6:])

    with pytest.raises(TypeError, match="TransformerLM"):
        from distkeras_tpu.models import mlp

        GeneratorPredictor(mlp(), params)


def test_generate_eos_id_stops_rows_and_pads(lm):
    """eos_id: each row matches the eos-free greedy stream up to and
    including its first eos, then pads with eos_id — static output shape,
    mask-and-carry done flags (the serving tier's retire rule)."""
    spec, params = lm
    rng = np.random.default_rng(9)
    prompts = rng.integers(0, VOCAB, size=(3, 6)).astype(np.int32)
    free = generate(spec, params, prompts, max_new_tokens=10)
    # pick the token row 0 emits at step 3 as eos: row 0 must stop there
    eos = int(free[0, 6 + 3])
    out = generate(spec, params, prompts, max_new_tokens=10, eos_id=eos)
    assert out.shape == free.shape
    cuts = []
    for b in range(3):
        new = free[b, 6:]
        hits = np.where(new == eos)[0]
        cut = hits[0] + 1 if hits.size else 10
        cuts.append(cut)
        np.testing.assert_array_equal(out[b, :6 + cut], free[b, :6 + cut])
        assert (out[b, 6 + cut:] == eos).all()
    assert min(cuts) < 10, "eos token never fired — test is vacuous"

    from distkeras_tpu.serving import per_row_new_token_counts

    np.testing.assert_array_equal(
        per_row_new_token_counts(out[:, 6:], eos), cuts
    )

    with pytest.raises(ValueError, match="eos_id"):
        generate(spec, params, prompts, 4, eos_id=VOCAB)


def test_generate_eos_id_sampled_path(lm):
    """eos works with temperature/top_k sampling and stays deterministic
    per seed (its own fold_in key schedule)."""
    spec, params = lm
    prompt = np.ones((2, 5), np.int32)
    a = generate(spec, params, prompt, 12, temperature=0.9, top_k=12,
                 seed=4, eos_id=3)
    b = generate(spec, params, prompt, 12, temperature=0.9, top_k=12,
                 seed=4, eos_id=3)
    np.testing.assert_array_equal(a, b)
    for row in a[:, 5:]:
        hits = np.where(row == 3)[0]
        if hits.size:
            assert (row[hits[0]:] == 3).all()


def test_generator_predictor_eos_and_per_row_counts(lm):
    """Satellite: eos_id now rides the sampling path (beams=1) instead of
    raising, and per_row_new_tokens adds the serving-tier count column."""
    from distkeras_tpu.data import Dataset
    from distkeras_tpu.predictors import GeneratorPredictor
    from distkeras_tpu.serving import per_row_new_token_counts

    spec, params = lm
    rng = np.random.default_rng(5)
    prompts = rng.integers(0, VOCAB, size=(6, 6)).astype(np.int32)
    free = generate(spec, params, prompts, max_new_tokens=8)
    eos = int(free[0, 6])  # row 0's first new token → count 1 for row 0
    p = GeneratorPredictor(spec, params, max_new_tokens=8, batch_size=4,
                           eos_id=eos, per_row_new_tokens=True)
    out = p.predict(Dataset({"features": prompts}))
    assert out["generated"].shape == (6, 8)
    np.testing.assert_array_equal(
        out["generated_new_tokens"],
        per_row_new_token_counts(out["generated"], eos),
    )
    assert out["generated_new_tokens"][0] == 1
    # length_penalty stays beam-only
    with pytest.raises(ValueError, match="length_penalty"):
        GeneratorPredictor(spec, params, length_penalty=0.5)


def test_generate_single_token_and_program_reuse(lm):
    """max_new_tokens=1 (zero-length scan) works, and repeated generate()
    calls with one decode config reuse one compiled program."""
    from distkeras_tpu.models.lm import _generate_program

    spec, params = lm
    prompt = np.zeros((2, 4), np.int32)
    out = generate(spec, params, prompt, max_new_tokens=1)
    assert out.shape == (2, 5)
    full = spec.module.apply({"params": params}, jnp.asarray(prompt))
    np.testing.assert_array_equal(
        out[:, -1], np.asarray(jnp.argmax(full[:, -1], -1)))
    assert _generate_program(spec.module, 1, 0.0, None) is \
        _generate_program(spec.module, 1, 0.0, None)


@pytest.mark.slow  # bf16 dtype-path variant; the f32 cache-parity oracle stays fast
def test_decode_matches_full_forward_bf16():
    """The decode step follows attention_reference's exact dtype path, so
    cache-vs-full parity holds in the default bf16 too (logit differences at
    the bf16 resolution floor, not a different math path)."""
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                          depth=DEPTH, dtype=jnp.bfloat16)
    params, _ = spec.init_np(0)
    module = spec.module
    rng = np.random.default_rng(3)
    toks = rng.integers(0, VOCAB, size=(2, 10)).astype(np.int32)
    _, caches = module.apply(
        {"params": params}, toks[:, :9], method=TransformerLM.prefill
    )
    step_logits, _ = module.apply(
        {"params": params}, toks[:, 9], caches, 9,
        method=TransformerLM.decode_step,
    )
    full = module.apply({"params": params}, toks)
    np.testing.assert_allclose(
        np.asarray(step_logits), np.asarray(full[:, -1]), rtol=0, atol=1e-3
    )


def test_generate_rejects_bad_top_k(lm):
    spec, params = lm
    prompt = np.zeros((1, 4), np.int32)
    for bad in (0, -3, VOCAB + 1):
        with pytest.raises(ValueError, match="top_k"):
            generate(spec, params, prompt, max_new_tokens=2,
                     temperature=1.0, top_k=bad)


def test_windowed_lm_decode_matches_full_forward():
    """Sliding-window LM: prefill + cached decode (cache masked to the band)
    equals the full windowed forward at every position."""
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                          depth=DEPTH, dtype=jnp.float32, attn_window=6)
    params, _ = spec.init_np(0)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, VOCAB, size=(2, 14)).astype(np.int32)
    _assert_cached_decode_matches_full(spec.module, params, toks, lp=4)


def test_windowed_lm_generates(lm):
    """generate() runs end-to-end on a windowed LM and differs from the
    unwindowed model's continuation (the window actually binds)."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, VOCAB, size=(2, 10)).astype(np.int32)
    specw = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                           depth=DEPTH, dtype=jnp.float32, attn_window=3)
    params, _ = specw.init_np(0)
    outw = generate(specw, params, prompt, max_new_tokens=8)
    assert outw.shape == (2, 18)
    assert (outw[:, :10] == prompt).all()
    spec_full = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM,
                               heads=HEADS, depth=DEPTH, dtype=jnp.float32)
    out_full = generate(spec_full, params, prompt, max_new_tokens=8)
    assert (outw != out_full).any()


def test_flash_lm_takes_the_kernel_on_any_backend():
    """attn_impl='flash' is the Pallas kernel whenever the length is a tile
    multiple — also here on the CPU (interpret mode). The backend never
    swaps it for the reference: only a ragged length does."""
    module = transformer_lm(vocab=VOCAB, maxlen=128, dim=DIM, heads=HEADS,
                            depth=1, dtype=jnp.float32,
                            attn_impl="flash").module
    fwd = lambda L: str(jax.make_jaxpr(
        lambda t: module.init(jax.random.PRNGKey(0), t)
    )(jnp.zeros((1, L), jnp.int32)))
    assert "pallas_call" in fwd(128)
    assert "pallas_call" not in fwd(100)


def test_flash_lm_accepts_ragged_prompt():
    """attn_impl='flash' on the LM family: a prompt whose length is not a
    tile multiple must prefill (on the XLA reference path) instead of
    erroring."""
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                          depth=DEPTH, dtype=jnp.float32, attn_impl="flash")
    params, _ = spec.init_np(0)
    prompt = np.arange(10, dtype=np.int32)[None].repeat(2, axis=0)
    out = generate(spec, params, prompt, max_new_tokens=4)
    assert out.shape == (2, 14)


def test_gqa_kv_heads_equal_heads_is_mha():
    """kv_heads == heads is EXACTLY the MHA model: same parameter tree,
    same logits (the fused qkv split reduces to thirds)."""
    spec_mha = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM,
                              heads=HEADS, depth=DEPTH, dtype=jnp.float32)
    spec_gqa = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM,
                              heads=HEADS, depth=DEPTH, dtype=jnp.float32,
                              kv_heads=HEADS)
    params, _ = spec_mha.init_np(0)
    pg, _ = spec_gqa.init_np(0)
    assert jax.tree.structure(params) == jax.tree.structure(pg)
    toks = np.arange(8, dtype=np.int32)[None].repeat(2, axis=0)
    a = spec_mha.module.apply({"params": params}, toks)
    b = spec_gqa.module.apply({"params": params}, toks)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gqa_decode_matches_full_forward():
    """GQA (2 kv heads under 4 query heads): prefill + cached decode against
    the Hkv-wide cache equals the full grouped forward at every position."""
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                          depth=DEPTH, dtype=jnp.float32, kv_heads=2)
    params, _ = spec.init_np(0)
    module = spec.module
    rng = np.random.default_rng(3)
    toks = rng.integers(0, VOCAB, size=(2, 12)).astype(np.int32)

    _, caches = module.apply(
        {"params": params}, toks[:, :4], method=TransformerLM.prefill
    )
    kc, vc = caches[0]
    assert kc.shape == (2, MAXLEN, 2, DIM // HEADS)  # Hkv-wide cache
    _assert_cached_decode_matches_full(module, params, toks, lp=4)


@pytest.mark.slow  # mqa train+generate integration; gqa decode parity pin stays fast
def test_mqa_trains_and_generates():
    """MQA (kv_heads=1) end to end: the LM learns a deterministic next-token
    rule through the trainer API and continues it at decode time."""
    import jax.numpy as jnp2

    from distkeras_tpu.trainers import ADAG

    rng = np.random.default_rng(0)
    V, Lp1 = 32, 17
    start = rng.integers(0, V, size=(512, 1))
    rows = (start + np.arange(Lp1)) % V
    spec = transformer_lm(vocab=V, maxlen=64, dim=32, heads=4, depth=1,
                          dtype=jnp2.float32, kv_heads=1)
    ds = next_token_dataset(rows.astype(np.int32))
    t = ADAG(spec, loss="sparse_softmax_cross_entropy",
             worker_optimizer="adam", learning_rate=5e-3, batch_size=64,
             communication_window=2, num_epoch=6, num_workers=2,
             label_col="label")
    params = t.train(ds)
    losses = t.get_history().losses()
    assert losses[-1] < losses[0] / 3
    out = generate(spec, params, rows[:4, :6].astype(np.int32),
                   max_new_tokens=8)
    expect = (rows[:4, :1] + np.arange(14)) % V
    assert (out == expect).mean() > 0.8


def test_gqa_validates_head_divisibility():
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=4,
                          depth=1, dtype=jnp.float32, kv_heads=3)
    with pytest.raises(ValueError, match="multiple of kv_heads"):
        spec.init_np(0)


def test_rope_decode_matches_full_forward():
    """RoPE LM: prefill + cached decode (cache holds pre-rotated keys)
    equals the full rotary forward at every position."""
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                          depth=DEPTH, dtype=jnp.float32,
                          pos_embedding="rope")
    params, _ = spec.init_np(0)
    rng = np.random.default_rng(4)
    toks = rng.integers(0, VOCAB, size=(2, 12)).astype(np.int32)
    _assert_cached_decode_matches_full(spec.module, params, toks, lp=4)


def test_rope_is_relative():
    """The defining RoPE property: rotating q and k at positions (p+s, p+s)
    gives the same attention scores as (p, p) — verify via apply_rope
    directly: <R(p+s)q, R(k+s)k> == <R(p)q, R(k)k> for aligned shifts."""
    from distkeras_tpu.models.lm import apply_rope, rope_angles

    rng = np.random.default_rng(5)
    dh, L, s = 16, 6, 9
    q = rng.normal(size=(1, L, 1, dh)).astype(np.float32)
    k = rng.normal(size=(1, L, 1, dh)).astype(np.float32)
    table = jnp.asarray(rope_angles(64, dh))
    q0, k0 = apply_rope(q, table[:L]), apply_rope(k, table[:L])
    qs, ks = apply_rope(q, table[s:s + L]), apply_rope(k, table[s:s + L])
    s0 = np.einsum("blhd,bmhd->blm", np.asarray(q0), np.asarray(k0))
    s1 = np.einsum("blhd,bmhd->blm", np.asarray(qs), np.asarray(ks))
    np.testing.assert_allclose(s1, s0, rtol=1e-4, atol=1e-4)


@pytest.mark.slow  # rope x gqa x window training composition; each part pinned separately in the fast tier
def test_rope_gqa_window_compose_and_train():
    """The modern-LM combo — RoPE + GQA + sliding window — trains through
    the trainer API and the cached decode continues the learned rule."""
    import jax.numpy as jnp2

    from distkeras_tpu.trainers import ADAG

    rng = np.random.default_rng(0)
    V, Lp1 = 32, 17
    start = rng.integers(0, V, size=(512, 1))
    rows = (start + np.arange(Lp1)) % V
    spec = transformer_lm(vocab=V, maxlen=64, dim=32, heads=4, depth=1,
                          dtype=jnp2.float32, kv_heads=2, attn_window=8,
                          pos_embedding="rope")
    ds = next_token_dataset(rows.astype(np.int32))
    t = ADAG(spec, loss="sparse_softmax_cross_entropy",
             worker_optimizer="adam", learning_rate=5e-3, batch_size=64,
             communication_window=2, num_epoch=6, num_workers=2,
             label_col="label")
    params = t.train(ds)
    losses = t.get_history().losses()
    assert losses[-1] < losses[0] / 3
    out = generate(spec, params, rows[:4, :6].astype(np.int32),
                   max_new_tokens=8)
    expect = (rows[:4, :1] + np.arange(14)) % V
    assert (out == expect).mean() > 0.8


def test_pos_embedding_validation():
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                          depth=1, dtype=jnp.float32, pos_embedding="learned")
    with pytest.raises(ValueError, match="pos_embedding"):
        spec.init_np(0)


def test_rope_requires_even_head_dim():
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=36, heads=4,
                          depth=1, dtype=jnp.float32, pos_embedding="rope")
    with pytest.raises(ValueError, match="even head dim"):
        spec.init_np(0)


def test_extend_matches_sequential_decode_steps(lm):
    """The multi-token cached forward (speculative decoding's verify pass)
    equals the same positions decoded one step at a time — logits and the
    caches it leaves behind."""
    spec, params = lm
    module = spec.module
    rng = np.random.default_rng(1)
    toks = rng.integers(0, VOCAB, size=(3, 11)).astype(np.int32)
    lp, T = 4, 5

    _, caches = module.apply(
        {"params": params}, toks[:, :lp], method=TransformerLM.prefill
    )
    ext_logits, ext_caches = module.apply(
        {"params": params}, toks[:, lp : lp + T], caches, lp,
        method=TransformerLM.extend,
    )
    step_caches = caches
    step_logits = []
    for pos in range(lp, lp + T):
        lg, step_caches = module.apply(
            {"params": params}, toks[:, pos], step_caches, pos,
            method=TransformerLM.decode_step,
        )
        step_logits.append(np.asarray(lg))
    np.testing.assert_allclose(
        np.asarray(ext_logits), np.stack(step_logits, axis=1),
        rtol=2e-4, atol=2e-4,
    )
    for (ka, va), (kb, vb) in zip(ext_caches, step_caches):
        np.testing.assert_allclose(np.asarray(ka), np.asarray(kb),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(va), np.asarray(vb),
                                   rtol=2e-4, atol=2e-4)


def test_speculative_matches_greedy_any_draft(lm):
    """Speculative output is EXACTLY the target's greedy stream no matter
    how bad the draft is — an unrelated random draft only costs rounds."""
    from distkeras_tpu.models import speculative_generate

    spec, params = lm
    draft = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=16, heads=2,
                           depth=1, dtype=jnp.float32)
    dparams, _ = draft.init_np(99)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, VOCAB, size=(3, 6)).astype(np.int32)

    greedy = generate(spec, params, prompt, max_new_tokens=9)
    out, stats = speculative_generate(
        spec, params, draft, dparams, prompt, 9, spec_tokens=3
    )
    np.testing.assert_array_equal(out, greedy)
    assert stats["rounds"] >= 1
    # proposals are clamped to the emission budget: the final round may
    # overhang max_new_tokens, and those proposals don't count; stats are
    # per-row sums (B=3 rows, K=3)
    assert 0 < stats["proposed"] <= 3 * 3 * stats["rounds"]
    assert 0 <= stats["accepted"] <= stats["proposed"]
    assert 0.0 <= stats["acceptance"] <= 1.0


def test_speculative_self_draft_accepts_everything(lm):
    """With draft == target every proposal is accepted: K+1 tokens per
    verify pass, so rounds collapse ~(K+1)x vs one-at-a-time decode."""
    from distkeras_tpu.models import speculative_generate

    spec, params = lm
    prompt = np.ones((2, 5), np.int32)
    new, K = 12, 3
    greedy = generate(spec, params, prompt, max_new_tokens=new)
    out, stats = speculative_generate(
        spec, params, spec, params, prompt, new, spec_tokens=K
    )
    np.testing.assert_array_equal(out, greedy)
    assert stats["accepted"] == stats["proposed"]
    assert stats["acceptance"] == 1.0
    # 1 prefill token + rounds * (K+1) emissions must cover `new`
    assert stats["rounds"] == -(-(new - 1) // (K + 1))


@pytest.mark.slow  # spec x gqa x rope composition; spec exactness pin stays fast
def test_speculative_composes_with_gqa_and_rope():
    """The verify forward rides the same block machinery as decode — GQA
    cache layouts and RoPE offsets included."""
    from distkeras_tpu.models import speculative_generate

    spec = transformer_lm(vocab=32, maxlen=48, dim=32, heads=4, depth=2,
                          kv_heads=2, pos_embedding="rope",
                          dtype=jnp.float32)
    params, _ = spec.init_np(3)
    draft = transformer_lm(vocab=32, maxlen=48, dim=16, heads=2, depth=1,
                           kv_heads=1, pos_embedding="rope",
                           dtype=jnp.float32)
    dparams, _ = draft.init_np(4)
    prompt = np.arange(10, dtype=np.int32).reshape(2, 5) % 32

    greedy = generate(spec, params, prompt, max_new_tokens=8)
    out, _ = speculative_generate(
        spec, params, draft, dparams, prompt, 8, spec_tokens=4
    )
    np.testing.assert_array_equal(out, greedy)


def test_speculative_stats_clamped_to_budget(lm):
    """The final verify round's proposals that overhang max_new_tokens are
    excluded from proposed/accepted, so a perfect draft still reports
    acceptance == 1.0 (not >1 or a deflated proposed count)."""
    from distkeras_tpu.models import speculative_generate

    spec, params = lm
    prompt = np.ones((2, 5), np.int32)
    # new=9, K=4: with self-draft every round emits K+1=5, so the second
    # round overhangs (n=6, room=3) and only 3 of its 4 proposals count
    out, stats = speculative_generate(
        spec, params, spec, params, prompt, 9, spec_tokens=4
    )
    np.testing.assert_array_equal(
        out, generate(spec, params, prompt, max_new_tokens=9)
    )
    assert stats["rounds"] == 2
    # per-row sums over B=2 rows: each row proposes 4 + min(4, room=3)
    assert stats["proposed"] == 14
    assert stats["accepted"] == 14
    assert stats["acceptance"] == 1.0


def test_speculative_sampled_reproducible_and_valid(lm):
    """temperature>0 speculative decoding: same seed → same stream, tokens
    in-vocab, stats well-formed."""
    from distkeras_tpu.models import speculative_generate

    spec, params = lm
    draft = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=16, heads=2,
                           depth=1, dtype=jnp.float32)
    dparams, _ = draft.init_np(99)
    prompt = np.ones((3, 5), np.int32)
    a, sa = speculative_generate(spec, params, draft, dparams, prompt, 8,
                                 spec_tokens=3, temperature=1.0, seed=5)
    b, _ = speculative_generate(spec, params, draft, dparams, prompt, 8,
                                spec_tokens=3, temperature=1.0, seed=5)
    c, _ = speculative_generate(spec, params, draft, dparams, prompt, 8,
                                spec_tokens=3, temperature=1.0, seed=6)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.shape == (3, 13) and a.min() >= 0 and a.max() < VOCAB
    assert np.array_equal(a[:, :5], prompt)
    assert 0 <= sa["accepted"] <= sa["proposed"] <= 3 * 3 * sa["rounds"]


def test_speculative_sampled_topk1_degenerates_to_greedy(lm):
    """top_k=1 makes both warped distributions one-hot: any-temperature
    sampled speculation must emit exactly the target's greedy stream."""
    from distkeras_tpu.models import speculative_generate

    spec, params = lm
    draft = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=16, heads=2,
                           depth=1, dtype=jnp.float32)
    dparams, _ = draft.init_np(7)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, VOCAB, size=(2, 6)).astype(np.int32)
    greedy = generate(spec, params, prompt, max_new_tokens=9)
    out, _ = speculative_generate(spec, params, draft, dparams, prompt, 9,
                                  spec_tokens=3, temperature=2.0, top_k=1,
                                  seed=11)
    np.testing.assert_array_equal(out, greedy)


def test_speculative_sampled_self_draft_accepts_everything(lm):
    """draft == target ⇒ p == q at every position ⇒ min(1, p/q) == 1:
    acceptance is ~1.0. (Not asserted exact: q comes from decode_step and
    p from the extend verify pass — different XLA programs whose logits
    differ at f32 epsilon, and a top-k/top-p warp can flip a boundary
    token between the two truncated supports. Pure-temperature warps keep
    the ratio within e^±ε, so acceptance stays at 1.0 up to measure-zero
    draws; truncation makes the rare boundary rejection possible.)"""
    from distkeras_tpu.models import speculative_generate

    spec, params = lm
    prompt = np.ones((2, 5), np.int32)
    out, stats = speculative_generate(
        spec, params, spec, params, prompt, 12, spec_tokens=3,
        temperature=1.3, seed=2,
    )
    assert stats["acceptance"] >= 0.95
    assert out.shape == (2, 17) and out.max() < VOCAB
    # with the truncating warps, boundary flips may reject a token or two
    out2, stats2 = speculative_generate(
        spec, params, spec, params, prompt, 12, spec_tokens=3,
        temperature=1.3, top_k=8, top_p=0.9, seed=2,
    )
    assert stats2["acceptance"] >= 0.8
    assert out2.shape == (2, 17) and out2.max() < VOCAB


def test_speculative_sampled_preserves_target_distribution():
    """The Leviathan guarantee, measured: the token histogram of sampled
    speculative decoding matches plain sampled generate() on the same
    target (both draw from the identically-warped p). Aggregated over
    seeds × rows × positions; total-variation tolerance sized ~3× the
    expected sampling fluctuation at this n."""
    from distkeras_tpu.models import speculative_generate

    V = 16
    spec = transformer_lm(vocab=V, maxlen=16, dim=16, heads=2, depth=1,
                          dtype=jnp.float32)
    params, _ = spec.init_np(0)
    draft = transformer_lm(vocab=V, maxlen=16, dim=8, heads=2, depth=1,
                           dtype=jnp.float32)
    dparams, _ = draft.init_np(1)
    B, new, seeds = 64, 6, 12
    prompt = np.zeros((B, 2), np.int32)

    h_plain = np.zeros(V)
    h_spec = np.zeros(V)
    for s in range(seeds):
        g = generate(spec, params, prompt, new, temperature=1.5,
                     seed=1000 + s)
        h_plain += np.bincount(g[:, 2:].ravel(), minlength=V)
        o, _ = speculative_generate(spec, params, draft, dparams, prompt,
                                    new, spec_tokens=3, temperature=1.5,
                                    seed=2000 + s)
        h_spec += np.bincount(o[:, 2:].ravel(), minlength=V)
    n = h_plain.sum()
    assert n == h_spec.sum() == B * new * seeds
    tv = 0.5 * np.abs(h_plain / n - h_spec / n).sum()
    # expected TV between two empirical draws of p at n≈4600, V=16 is
    # ~0.02; 0.08 is a 3-4σ gate that still catches a wrong distribution
    # (e.g. greedy-biased acceptance shifts TV to ~0.3)
    assert tv < 0.08, f"token distributions diverge: TV={tv:.3f}"


@pytest.mark.slow  # sampled-spec x gqa x rope x warp composition; TV gate + reproducibility pins stay fast
def test_speculative_sampled_composes_with_gqa_rope_topk_topp():
    """Sampled verify rides the same block machinery: GQA caches, RoPE
    offsets, and the top-k/top-p warp all compose."""
    from distkeras_tpu.models import speculative_generate

    spec = transformer_lm(vocab=32, maxlen=48, dim=32, heads=4, depth=2,
                          kv_heads=2, pos_embedding="rope",
                          dtype=jnp.float32)
    params, _ = spec.init_np(3)
    draft = transformer_lm(vocab=32, maxlen=48, dim=16, heads=2, depth=1,
                           kv_heads=1, pos_embedding="rope",
                           dtype=jnp.float32)
    dparams, _ = draft.init_np(4)
    prompt = np.arange(10, dtype=np.int32).reshape(2, 5) % 32
    out, stats = speculative_generate(
        spec, params, draft, dparams, prompt, 8, spec_tokens=4,
        temperature=0.8, top_k=12, top_p=0.95, seed=1,
    )
    assert out.shape == (2, 13) and out.max() < 32
    assert 0.0 <= stats["acceptance"] <= 1.0


def test_speculative_sampled_validates_inputs(lm):
    from distkeras_tpu.models import speculative_generate

    spec, params = lm
    prompt = np.zeros((1, 4), np.int32)
    with pytest.raises(ValueError, match="temperature"):
        speculative_generate(spec, params, spec, params, prompt, 4,
                             temperature=-1.0)
    with pytest.raises(ValueError, match="top_k"):
        speculative_generate(spec, params, spec, params, prompt, 4,
                             temperature=1.0, top_k=0)
    with pytest.raises(ValueError, match="top_p"):
        speculative_generate(spec, params, spec, params, prompt, 4,
                             temperature=1.0, top_p=1.5)


def test_speculative_validates_inputs(lm):
    from distkeras_tpu.models import speculative_generate

    spec, params = lm
    prompt = np.zeros((1, 4), np.int32)
    other_vocab = transformer_lm(vocab=VOCAB * 2, maxlen=MAXLEN, dim=16,
                                 heads=2, depth=1, dtype=jnp.float32)
    ov_params, _ = other_vocab.init_np(0)
    with pytest.raises(ValueError, match="vocab"):
        speculative_generate(spec, params, other_vocab, ov_params,
                             prompt, 4)
    windowed = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=16, heads=2,
                              depth=1, attn_window=8, dtype=jnp.float32)
    w_params, _ = windowed.init_np(0)
    with pytest.raises(ValueError, match="sliding-window"):
        speculative_generate(windowed, w_params, windowed, w_params,
                             prompt, 4)
    with pytest.raises(ValueError, match="spec_tokens"):
        speculative_generate(spec, params, spec, params, prompt, 4,
                             spec_tokens=0)
    with pytest.raises(ValueError, match="maxlen"):
        # fits generate()'s bound but not the verify probe's headroom
        speculative_generate(spec, params, spec, params,
                             np.zeros((1, MAXLEN - 6), np.int32), 6,
                             spec_tokens=4)
    with pytest.raises(TypeError, match="draft"):
        from distkeras_tpu.models import mlp

        speculative_generate(spec, params, mlp(), params, prompt, 4)


@pytest.mark.slow  # long-wrap stress; prompt-longer-than-window ring pin stays fast
def test_ring_cache_shape_and_long_wraparound():
    """Sliding-window LM decode uses a RING cache of length window (not
    maxlen), and stays equal to the full windowed forward far past the
    first wrap-around (decode length >> window), composed with GQA+RoPE."""
    W = 5
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                          depth=DEPTH, dtype=jnp.float32, attn_window=W,
                          kv_heads=2, pos_embedding="rope")
    params, _ = spec.init_np(0)
    module = spec.module
    rng = np.random.default_rng(6)
    toks = rng.integers(0, VOCAB, size=(2, 28)).astype(np.int32)

    _, caches = module.apply(
        {"params": params}, toks[:, :3], method=TransformerLM.prefill
    )
    kc, vc = caches[0]
    assert kc.shape == (2, W, 2, DIM // HEADS)   # ring: window, not maxlen
    # 25 steps = 5 full wraps
    _assert_cached_decode_matches_full(module, params, toks, lp=3)


def test_ring_cache_prompt_longer_than_window():
    """Prefill with a prompt LONGER than the window seeds the ring with the
    last `window` positions only; decode continues exactly."""
    W = 4
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                          depth=1, dtype=jnp.float32, attn_window=W)
    params, _ = spec.init_np(0)
    rng = np.random.default_rng(7)
    toks = rng.integers(0, VOCAB, size=(2, 16)).astype(np.int32)
    # prompt (11) >> window (4); skip the prefill-logits check — it's the
    # ring seeding + continued decode under test here
    _assert_cached_decode_matches_full(spec.module, params, toks, lp=11,
                                       check_prefill_logits=False)


# -- beam search --------------------------------------------------------------


def _seq_logprob(spec, params, seq, lp):
    """Sum of log P(seq[t] | seq[:t]) for t >= lp, by full forward."""
    logits = spec.apply(params, {}, jnp.asarray(seq[None], jnp.int32),
                        training=False)[0][0]
    logprobs = jax.nn.log_softmax(np.asarray(logits, np.float32), axis=-1)
    return float(sum(
        logprobs[t - 1, seq[t]] for t in range(lp, len(seq))
    ))


def test_beam_one_equals_greedy(lm):
    from distkeras_tpu.models import beam_search

    spec, params = lm
    prompt = np.arange(8, dtype=np.int32).reshape(2, 4) % VOCAB
    greedy = generate(spec, params, prompt, max_new_tokens=6)
    toks, scores = beam_search(spec, params, prompt, max_new_tokens=6,
                               beams=1)
    assert toks.shape == (2, 1, 10)
    assert scores.shape == (2, 1)
    np.testing.assert_array_equal(toks[:, 0], greedy)


def test_beam_search_finds_higher_likelihood_than_greedy(lm):
    from distkeras_tpu.models import beam_search

    spec, params = lm
    prompt = np.array([[3, 1, 4, 1], [5, 9, 2, 6]], np.int32)
    new = 8
    greedy = generate(spec, params, prompt, max_new_tokens=new)
    toks, scores = beam_search(spec, params, prompt, max_new_tokens=new,
                               beams=4)
    for b in range(2):
        lp = prompt.shape[1]
        best = _seq_logprob(spec, params, toks[b, 0], lp)
        base = _seq_logprob(spec, params, greedy[b], lp)
        # the reported score must BE the sequence log-prob (this is the
        # oracle that catches a wrong parent-cache re-gather: a corrupted
        # cache changes the decode distribution, and the rescore diverges)
        np.testing.assert_allclose(scores[b, 0], best, rtol=1e-4, atol=1e-3)
        # beam-4 improving on greedy is NOT a theorem (the greedy path can
        # fall out of the beam), but it holds for this pinned fixture
        assert best >= base - 1e-4
        # beams come back best-first
        assert np.all(np.diff(scores[b]) <= 1e-6)


def test_beam_search_eos_freezes_finished_beams(lm):
    from distkeras_tpu.models import beam_search

    spec, params = lm
    prompt = np.array([[7, 7, 7, 7]], np.int32)
    eos = 5
    toks, scores = beam_search(spec, params, prompt, max_new_tokens=10,
                               beams=4, eos_id=eos)
    lp = prompt.shape[1]
    for k in range(4):
        seq = toks[0, k, lp:]
        hit = np.where(seq == eos)[0]
        if len(hit):
            # everything after the first eos is eos padding
            assert np.all(seq[hit[0]:] == eos)
    assert np.all(np.isfinite(scores))


def test_beam_search_length_penalty_and_validation(lm):
    from distkeras_tpu.models import beam_search

    spec, params = lm
    prompt = np.zeros((1, 4), np.int32)
    toks, scores = beam_search(spec, params, prompt, max_new_tokens=5,
                               beams=3, length_penalty=0.8, eos_id=2)
    assert toks.shape == (1, 3, 9)
    with pytest.raises(ValueError, match="beams"):
        beam_search(spec, params, prompt, max_new_tokens=2, beams=0)
    with pytest.raises(ValueError, match="eos_id"):
        beam_search(spec, params, prompt, max_new_tokens=2, eos_id=VOCAB)
    with pytest.raises(ValueError, match="maxlen"):
        beam_search(spec, params, prompt, max_new_tokens=MAXLEN)


@pytest.mark.slow  # beam x ring x gqa composition; beam-vs-greedy pin stays fast
def test_beam_search_with_ring_cache_and_gqa():
    """Beam search composes with the RoPE + GQA + sliding-window dialect:
    the per-beam caches are ring buffers and the parent re-gather must
    respect them."""
    from distkeras_tpu.models import beam_search

    spec = transformer_lm(vocab=32, maxlen=64, dim=32, heads=4, depth=2,
                          dtype=jnp.float32, kv_heads=2, attn_window=8,
                          pos_embedding="rope")
    params, _ = spec.init_np(1)
    prompt = np.arange(12, dtype=np.int32).reshape(1, 12) % 32
    toks, scores = beam_search(spec, params, prompt, max_new_tokens=16,
                               beams=3)
    assert toks.shape == (1, 3, 28)
    assert np.all(toks < 32) and np.all(toks >= 0)
    lp = prompt.shape[1]
    # every beam's reported score must match the full windowed forward's
    # log-prob of that sequence — a wrong ring-slot re-gather after a beam
    # switch would corrupt the decode distribution and break this (the
    # tolerance absorbs the pinned 2e-4/step cached-vs-full f32 noise
    # accumulated over 16 steps)
    for k in range(3):
        rescored = _seq_logprob(spec, params, toks[0, k], lp)
        np.testing.assert_allclose(scores[0, k], rescored, atol=5e-2)
    # distinct hypotheses, best-first
    assert len({tuple(t) for t in toks[0]}) == 3
    assert np.all(np.diff(scores[0]) <= 1e-6)


def test_generator_predictor_beam_mode(lm):
    """beams>1 routes through beam_search and keeps each row's best beam;
    sampling knobs are rejected in beam mode."""
    from distkeras_tpu.data import Dataset
    from distkeras_tpu.models import beam_search
    from distkeras_tpu.predictors import GeneratorPredictor

    spec, params = lm
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, VOCAB, size=(7, 5)).astype(np.int32)
    ds = Dataset({"features": prompts})
    p = GeneratorPredictor(spec, params, max_new_tokens=4, batch_size=4,
                           beams=3)
    out = p.predict(ds)
    assert out["generated"].shape == (7, 4)
    # chunked predictor output == direct best-beam on the same rows
    direct, _ = beam_search(spec, params, prompts[:4], max_new_tokens=4,
                            beams=3)
    np.testing.assert_array_equal(out["generated"][:4], direct[:, 0, 5:])

    with pytest.raises(ValueError, match="deterministic"):
        GeneratorPredictor(spec, params, beams=2, temperature=0.5)
    with pytest.raises(ValueError, match="beams"):
        GeneratorPredictor(spec, params, beams=0)


# -- weight tying -------------------------------------------------------------


def test_tied_embeddings_structure_and_logits():
    """tie_embeddings drops lm_head from the params tree and computes
    logits as hidden @ embedding.T (nn.Embed.attend)."""
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                          depth=1, dtype=jnp.float32, tie_embeddings=True)
    params, _ = spec.init_np(0)
    assert "lm_head" not in params
    assert params["embed"]["embedding"].shape == (VOCAB, DIM)
    toks = np.arange(8, dtype=np.int32).reshape(1, 8)
    logits = spec.apply(params, {}, jnp.asarray(toks), False)[0]
    h = spec.module.apply({"params": params}, jnp.asarray(toks),
                          method=TransformerLM.hidden)
    manual = np.asarray(h) @ np.asarray(params["embed"]["embedding"]).T
    np.testing.assert_allclose(np.asarray(logits), manual, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.slow  # tied x fused-ce composition; each pinned separately in the fast tier
def test_tied_fused_ce_matches_unfused():
    """fused_ce on a tied model contracts against the embedding transpose —
    loss and gradients equal the unfused tied path."""
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.trainers import _make_loss_step

    cfg = dict(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS, depth=1,
               dtype=jnp.float32, tie_embeddings=True)
    plain = transformer_lm(**cfg)
    fused = transformer_lm(**cfg, fused_ce=True, ce_chunk=8)
    params, _ = plain.init_np(0)
    rng = np.random.default_rng(4)
    toks = rng.integers(0, VOCAB, size=(3, 17)).astype(np.int32)
    batch = (toks[:, :-1], toks[:, 1:])
    name = "sparse_softmax_cross_entropy"
    sp = _make_loss_step(plain, get_loss(name), 1, loss_name=name)
    sf = _make_loss_step(fused, get_loss(name), 1, loss_name=name)
    (lp, _), gp = jax.value_and_grad(sp, has_aux=True)(params, {}, batch)
    (lf, _), gf = jax.value_and_grad(sf, has_aux=True)(params, {}, batch)
    np.testing.assert_allclose(float(lf), float(lp), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-6)


@pytest.mark.slow  # tied train+generate+quantize integration; tied structure/logits pin stays fast
def test_tied_lm_trains_generates_and_quantizes():
    """End to end on the cycle language: the tied model (V·dim fewer
    params) learns, decodes the cycle, beam-decodes it, and survives int8
    quantization (blocks quantized; the tied head stays in the trained
    dtype)."""
    from distkeras_tpu import ADAG
    from distkeras_tpu.models import beam_search, quantize_lm

    period = 8
    rng = np.random.default_rng(0)
    rows = np.stack([
        (np.arange(17) + s) % period for s in rng.integers(0, period, 512)
    ]).astype(np.int32)
    spec = transformer_lm(vocab=period, maxlen=32, dim=32, heads=4, depth=2,
                          dtype=jnp.float32, tie_embeddings=True)
    t = ADAG(spec, loss="sparse_softmax_cross_entropy",
             worker_optimizer="adam", learning_rate=5e-3, num_workers=4,
             batch_size=32, communication_window=2, num_epoch=6)
    t.train(next_token_dataset(rows), shuffle=True)
    params = t.trained_params_
    prompt = np.tile(np.arange(6) % period, (2, 1)).astype(np.int32)
    out = generate(spec, params, prompt, max_new_tokens=8)
    expect = (np.arange(6, 14) % period)[None].repeat(2, axis=0)
    assert np.array_equal(out[:, 6:], expect)
    btoks, _ = beam_search(spec, params, prompt, max_new_tokens=8, beams=3)
    assert np.array_equal(btoks[:, 0, 6:], expect)
    qspec, qparams = quantize_lm(spec, params)
    assert "lm_head" not in qparams
    qout = generate(qspec, qparams, prompt, max_new_tokens=8)
    assert np.array_equal(qout[:, 6:], expect)
