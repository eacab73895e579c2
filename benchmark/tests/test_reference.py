"""The plain reference against the program's TransformerLM at a tiny size."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, weights

HERE = os.path.dirname(os.path.abspath(__file__))


def tiny(name):
    with open(os.path.join(HERE, "data", "configs", name + ".json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("name", ["tiny-sincos", "tiny-rope-gqa"])
def test_forward_agrees_with_the_program_in_float32(name):
    from distkeras_tpu.models import transformer_lm

    m = tiny(name)
    key = weights.seed_key(2 ** 31 + 11)
    stacked = jax.jit(lambda k: weights.stacked(m, k))(key)
    tree = jax.jit(lambda k: weights.program_tree(m, k))(key)
    spec = transformer_lm(vocab=m["vocab"], maxlen=m["maxlen"], dim=m["dim"],
                          heads=m["heads"], kv_heads=m["kv_heads"], depth=m["depth"],
                          pos_embedding=m["pos_embedding"], tie_embeddings=True,
                          attn_window=m["attn_window"], dtype=jnp.float32,
                          attn_impl="reference")
    toks = np.random.default_rng(0).integers(0, m["vocab"], (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(spec.apply(tree, {}, toks, False)[0])
    for row, want in zip(toks, out):
        got = np.asarray(reference.next_logits(m, stacked, jnp.asarray(row)))
        assert np.abs(got - want[:-1]).max() < 2e-5


def test_the_two_layouts_hold_the_same_leaves_and_a_seed_decides_them():
    m = tiny("tiny-rope-gqa")
    a = jax.jit(lambda k: weights.stacked(m, k))(weights.seed_key(7))
    tree = jax.jit(lambda k: weights.program_tree(m, k))(weights.seed_key(7))
    back = weights.from_program_tree(tree, m["depth"])
    for name in weights.BLOCK_NAMES:
        assert np.array_equal(np.stack(back[name]), a[name]), name
    b = weights.stacked(m, weights.seed_key(2 ** 31 + 7))
    assert not np.array_equal(a["embed"], b["embed"])
    served = weights.stacked(m, weights.seed_key(7), "bfloat16")
    assert served["qkv_w"].dtype == jnp.bfloat16 and served["ln1_g"].dtype == jnp.float32


def test_gradient_and_adam_against_jax_and_optax():
    import optax

    m = tiny("tiny-sincos")
    rng = np.random.default_rng(3)
    batches = [tuple(rng.integers(0, m["vocab"], (2, 4, 32)).astype(np.int32))
               for _ in range(3)]
    out = reference.train_steps(m, 5, batches, 1e-3, rows_per_block=2)
    w = weights.stacked(m, weights.seed_key(5))
    tx = optax.adam(1e-3)
    state, w0 = tx.init(w), w

    def loss(w, x, y):
        return reference.nll_sum(m, w, jnp.asarray(x), jnp.asarray(y)) / x.size

    for i, (x, y) in enumerate(batches):
        l, g = jax.value_and_grad(loss)(w, x, y)
        assert float(l) == pytest.approx(out["losses"][i], rel=1e-5)
        if i == 0:
            want = weights.leaf_norms(m, g)
            for k in want:
                np.testing.assert_allclose(out["grad_norms"][k], want[k], rtol=1e-4, atol=1e-7)  # a key bias: round-off alone
        up, state = tx.update(g, state, w)
        w = optax.apply_updates(w, up)
    want = weights.leaf_norms(m, jax.tree.map(jnp.subtract, w, w0))
    for k in ("embed", "up_w", "qkv_w.q", "lnf_g"):
        np.testing.assert_allclose(out["delta_norms"][k], want[k], rtol=2e-3)
