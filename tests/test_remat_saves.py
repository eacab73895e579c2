"""What a rematted block saves (ISSUE 36): the flash forward's output and
log-sum-exp carry names (``ops.flash_attention._fa_fwd``) that every
``nn.remat`` site keeps (``ops.REMAT_SAVED``), so the gradient step runs
``flash_fwd`` once a layer and not twice, and computes what it computed.
All on the CPU, the kernels in interpret mode."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import ops
from tests.test_programs import BLOCKS, DATA, _rows

DEPTH = 2


def _grad_fn(block, remat):
    """The gradient of a two-layer model's fused loss on seeded rows of 128
    (the kernels' tile), and its weights."""
    config, build = BLOCKS[block]
    with open(os.path.join(DATA, config + ".json")) as f:
        m = dict(json.load(f)["model"], depth=DEPTH)
    spec = build(m, attn_impl="flash", fused_ce=True, ce_chunk=64, remat=remat)
    fused = spec.fused_losses["sparse_softmax_cross_entropy"]
    params, nt = jax.tree.map(jnp.asarray, spec.init_np(3))
    x, y = _rows(m, length=128)
    return jax.value_and_grad(lambda p: fused(p, nt, x, y, True), has_aux=True), params


def _calls(block, remat=True):
    fn, params = _grad_fn(block, remat)
    text = str(jax.make_jaxpr(fn)(params))
    return text.count("name=_fwd_call"), text.count("name=_bwd_call")


def _step(block, remat):
    """Loss and gradients computed operation by operation: a compiler that
    fuses the two steps differently (the CPU's drops a rounding to bf16
    here and reorders a float32 sum there) is not what is compared."""
    fn, params = _grad_fn(block, remat)
    with jax.disable_jit():
        (loss, _), grads = fn(params)
    return np.asarray(loss), jax.tree.map(np.asarray, grads)


@pytest.mark.parametrize("block", list(BLOCKS))
def test_a_rematted_block_runs_the_flash_forward_once(block):
    """``DEPTH`` forwards and ``DEPTH`` backwards; before the names were kept
    remat's forward ran the kernel again: 2 x ``DEPTH``."""
    assert _calls(block) == (DEPTH, DEPTH)
    assert _calls(block, remat=False) == (DEPTH, DEPTH)


@pytest.mark.parametrize("block", list(BLOCKS))
def test_the_saved_step_is_the_unrematted_step(block):
    """The same ``flash_fwd`` result reaches the same backward kernels: loss
    and every gradient leaf are what the step without remat gives."""
    (loss, grads), (want, want_grads) = _step(block, True), _step(block, False)
    assert np.array_equal(loss, want)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        assert np.array_equal(got, ref), jax.tree_util.keystr(path)


@pytest.mark.parametrize("kept", ["flash_out", "flash_lse"])
def test_the_kernel_comes_back_for_whichever_result_is_not_kept(monkeypatch, kept):
    """Why both names exist: the kernel gives both results, so remat runs it
    again for the one the policy left out."""
    assert {"flash_out", "flash_lse"} <= set(ops.REMAT_SAVED)
    monkeypatch.setattr(ops, "REMAT_SAVED", (kept, "router_bias"))
    assert _calls("dense") == (2 * DEPTH, DEPTH)


def test_the_encoder_keeps_them_too():
    from distkeras_tpu.models.transformer import TransformerClassifier

    def calls(remat):
        model = TransformerClassifier(vocab=64, maxlen=128, dim=32, heads=2, depth=DEPTH,
                                      attn_impl="flash", remat=remat)
        tokens = np.random.default_rng(5).integers(0, 64, (2, 128)).astype(np.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)
        text = str(jax.make_jaxpr(jax.grad(
            lambda p: model.apply(p, tokens, training=True).sum()))(params))
        return text.count("name=_fwd_call"), text.count("name=_bwd_call")

    assert calls(True) == calls(False) == (DEPTH, DEPTH)
