"""The latent-attention expert block (``transformer_lm(mla=MlaDims(...))``)
against the plain float32 equations of ``benchmark/reference_kanana.py`` at a
tiny size on the CPU: 4 heads of 16 + 8 / 16 over a latent of 32, 3 layers of
which the first is dense, 16 experts of width 32 of which 4 are held and 3
chosen a token beside 2 shared experts, vocabulary 256; and what is new under
it: two widths in the flash kernels, a sigmoid router with a selection bias
and its balancing rule, a shared expert beside the routed sum, layers of two
kinds in one model.

The program runs in float32 here, so what is left between the two is the order
of float32 sums (the flash kernel's tiles, the grouped product, the fused
loss's chunks): a few 1e-6 on numbers of order one. A dropped term (the rotary
part of the score, the scaling factor, the shared expert) is of order 1e-2 to
1.
"""

import dataclasses
import functools
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (checks, flops_kanana, limits_kanana, loader, reference_kanana,
                       weights_kanana)
from benchmark.drivers import train, train_kanana
from benchmark.drivers.train_kanana import program_lm
from distkeras_tpu.models.lm import (MlaDims, RoutedExperts, _added, _sigmoid_router, held_rows,
                                     moe_tokens, transformer_lm)
from distkeras_tpu.ops import flash_attention as fa
from distkeras_tpu.parallel.sequence import attention_reference

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "..", "benchmark", "tests", "data")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(DATA, "configs", "tiny-kanana.json")) as f:
    M = dict(json.load(f)["model"], dtype="float32")
with open(os.path.join(DATA, "traffic", "tiny-train-mla.json")) as f:
    JOB = json.load(f)
SEED = 2 ** 31 + 33
KEY = weights_kanana.seed_key(SEED)
ROWS = np.random.default_rng(33).integers(0, M["vocab"], (2, 129)).astype(np.int32)
X, Y = ROWS[:, :-1], ROWS[:, 1:]
EXPERT_LAYERS = list(weights_kanana.layers_of(M, "expert"))


@functools.lru_cache(maxsize=None)
def weights():
    """The seed's weights in the reference's layout and in the program's, and
    the routers' bias ``[expert layers, experts]``."""
    return (jax.jit(lambda k: weights_kanana.layered(M, k))(KEY),
            jax.jit(lambda k: weights_kanana.program_tree(M, k))(KEY),
            jnp.stack(weights_kanana.router_bias(M, KEY)))


def counters():
    return weights_kanana.counters_tree(M, KEY)


def cut():
    with open(os.path.join(loader.ROOT, "benchmark", "configs", "kanana-2-30b-a3b.json")) as f:
        return json.load(f)


# -- two widths in the flash kernels ---------------------------------------------


@functools.lru_cache(maxsize=None)
def _flash_and_oracles(shape, causal):
    """Forward and the three gradients at q / k of ``Dk`` and v of ``Dv`` from
    the kernels (interpret mode), the ``jnp`` path and the XLA backward
    oracle."""
    B, L, H, K, Dk, Dv = shape
    ks = jax.random.split(jax.random.PRNGKey(Dk), 4)
    q, g = jax.random.normal(ks[0], (B, L, H, Dk)), jax.random.normal(ks[1], (B, L, H, Dv))
    k, v = jax.random.normal(ks[2], (B, L, K, Dk)), jax.random.normal(ks[3], (B, L, K, Dv))
    out = {}
    for name, attend in (("flash", fa.flash_attention), ("jnp", attention_reference)):
        o, pull = jax.vjp(lambda q, k, v: attend(q, k, v, causal=causal), q, k, v)
        out[name] = dict(zip(("out", "dq", "dk", "dv"), map(np.asarray, (o,) + pull(g))))
    o, lse = fa._fa_forward(q, k, v, None, scale=Dk ** -0.5, causal=causal, interpret=True)
    math = fa._attention_bwd_math(q, k, v, None, lse, g, scale=Dk ** -0.5, causal=causal)
    out["math"] = dict(zip(("out", "dq", "dk", "dv"), map(np.asarray, (o,) + math)))
    return out


@pytest.mark.parametrize("shape", [
    (1, 256, 2, 2, 48, 32),        # one small pair of widths
    (1, 256, 4, 2, 48, 32),        # grouped heads
    (1, 1024, 2, 2, 192, 128),     # the model's, 512 x 1024 tiles cut down to 256
    (1, 256, 2, 2, 32, 32),        # one width: what it was
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_flash_kernels_take_q_and_k_of_one_width_and_v_of_another(shape, causal, what):
    got = _flash_and_oracles(shape, causal)
    B, L, H, K, Dk, Dv = shape
    width = {"out": Dv, "dq": Dk, "dk": Dk, "dv": Dv}[what]
    assert got["flash"][what].shape[-1] == width
    for oracle in ("jnp", "math"):
        want = got[oracle][what]
        assert np.abs(got["flash"][what] - want).max() < 2e-5 * max(1.0, np.abs(want).max())


def test_the_default_scale_is_the_query_width():
    q, k, v = (jnp.ones((1, 128, 1, d)) * 0.1 for d in (48, 48, 32))
    k = k.at[:, ::2].multiply(-1.0)
    a = fa.flash_attention(q, k, v * jnp.arange(128.0)[None, :, None, None])
    b = fa.flash_attention(q, k, v * jnp.arange(128.0)[None, :, None, None], scale=48 ** -0.5)
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kwargs, match", [
    (dict(widths=(48, 32, 32)), "q and k must be one width"),
    (dict(widths=(48, 48, 32), block_diffusion=4), "not written"),
    # all three head-major needs the count of heads, and the count needs them so
    (dict(widths=(48, 48, 32), qk_major=True, v_major=True), "heads says"),
    (dict(widths=(48, 48, 32), qk_major=True, heads=2), "heads says"),
    (dict(widths=(48, 48, 32), heads=2, v_major=True), "heads says"),
    (dict(widths=(48, 48, 32), qk_major=True, heads=3, v_major=True), "heads says"),
])
def test_flash_attention_names_the_widths_it_cannot_take(kwargs, match):
    dq, dk, dv = kwargs.pop("widths")
    q, k, v = (jnp.zeros((1, 256, 2, d)) for d in (dq, dk, dv))
    if kwargs.get("qk_major"):
        q, k = (a.reshape(2, 256, -1) for a in (q, k))
    if kwargs.pop("v_major", False):
        v = v.reshape(2, 256, -1)
    with pytest.raises(ValueError, match=match):
        fa.flash_attention(q, k, v, **kwargs)


@functools.lru_cache(maxsize=None)
def _head_major(mode):
    """Forward and gradients at 48 / 32 with q and k (``"qk"``) or all three
    operands (``"qkv"``) handed over head-major, grouped heads, and the
    launcher's own copies of the same numbers; gradients in the callers'
    layout."""
    B, L, H, K = 2, 256, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, g = jax.random.normal(ks[0], (B, L, H, 48)), jax.random.normal(ks[1], (B, L, H, 32))
    k, v = jax.random.normal(ks[2], (B, L, K, 48)), jax.random.normal(ks[3], (B, L, K, 32))
    major = lambda a: jnp.moveaxis(a, 2, 1).reshape(-1, L, a.shape[-1])
    back = lambda a, heads: jnp.moveaxis(a.reshape(B, heads, L, -1), 1, 2)
    o, pull = jax.vjp(lambda q, k, v: fa.flash_attention(q, k, v, causal=True), q, k, v)
    want = dict(zip(("out", "dq", "dk", "dv"), map(np.asarray, (o,) + pull(g))))
    options = dict(qk_major=True, **(dict(heads=H) if mode == "qkv" else {}))
    o, pull = jax.vjp(lambda q, k, v: fa.flash_attention(q, k, v, causal=True, **options),
                      major(q), major(k), major(v) if mode == "qkv" else v)
    dq, dk, dv = pull(g)
    assert dq.shape == (B * H, L, 48) and dk.shape == (B * K, L, 48)
    assert dv.shape == ((B * K, L, 32) if mode == "qkv" else (B, L, K, 32))
    got = dict(out=o, dq=back(dq, H), dk=back(dk, K), dv=back(dv, K) if mode == "qkv" else dv)
    return {n: np.asarray(a) for n, a in got.items()}, want


@pytest.mark.parametrize("mode", ["qk", "qkv"])
@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_two_widths_run_head_major(mode, what):
    """The call that raised at PR 33 (two widths under ``qk_major``) runs, and
    with ``heads`` v and dv travel head-major too: the same kernels on the same
    numbers, so the same results to the bit."""
    got, want = _head_major(mode)
    assert np.abs(want[what]).max() > 0.1
    np.testing.assert_array_equal(got[what], want[what])


def test_one_width_builds_the_launchers_it_built():
    """Where ``Dk == Dv`` the per-row statistics stay columns and the kernels'
    operands what they were: the accepted cells' programs."""
    assert not fa._stat_rows(None, 64, 64) and not fa._stat_rows(None, 128, 128)
    assert fa._stat_rows(None, 192, 128) and fa._stat_rows((4, 512), 128, 128)
    q = jnp.zeros((1, 256, 2, 32))
    text = jax.jit(lambda q: fa.flash_attention(q, q, q, causal=True)).lower(q).as_text()
    assert "256x1xf32" in text.replace(" ", "")          # [B·H, L, 1] columns
    wide = jax.jit(lambda q, v: fa.flash_attention(q, q, v, causal=True)).lower(
        jnp.zeros((1, 256, 2, 48)), q).as_text()
    assert "1x256xf32" in wide.replace(" ", "")          # [B·H, 1, L] rows
    # and what the launchers run around the kernels is PR 33's, operation for operation: a
    # band caller's twelve copies at one width and at two, six fewer under ``qk_major``
    # (tests/test_qk_prep.py has every accepted cell's call), and three left (the result,
    # dO and the saved result) where all three operands come head-major
    from tests.test_qk_prep import _PARENTS, _primitives

    def count(q, k, v, **options):
        def both(q, k, v):
            o, pull = jax.vjp(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, **options), q, k, v)
            return (o,) + pull(o)
        return _primitives(jax.make_jaxpr(both)(q, k, v).jaxpr, {})

    major = lambda a: jnp.moveaxis(a, 2, 1).reshape(-1, 256, a.shape[-1])
    for dk, dv, broadcasts in ((32, 32, 4), (48, 32, 2)):   # columns, rows: the statistics
        q, v = jnp.zeros((2, 256, 4, dk)), jnp.zeros((2, 256, 4, dv))
        want = dict(_PARENTS, broadcast_in_dim=broadcasts)
        assert count(q, q, v) == want
        assert count(major(q), major(q), v, qk_major=True) == dict(want, transpose=6, reshape=6)
        assert count(major(q), major(q), major(v), qk_major=True, heads=4) == dict(
            want, transpose=3, reshape=3)


# -- the sigmoid router, alone ---------------------------------------------------


class _RouterAlone(nn.Module):
    """The least module a router part needs: its dims and ``counting``."""

    z: MlaDims

    def counting(self):
        return self.is_mutable_collection("counters") and not self.is_initializing()

    @nn.compact
    def __call__(self, h):
        return _sigmoid_router(self, h, None)


def _router_alone(z, h, wr, bias, train=False):
    """``_sigmoid_router`` alone: ``(chosen, weight, the state it leaves)``."""
    variables = {"params": {"router": {"kernel": wr}}, "counters": {"router_bias": bias}}
    with jax.default_matmul_precision("highest"):
        (chosen, weight, _), state = _RouterAlone(z).apply(
            variables, h, mutable=["counters"] if train else [])
    return np.asarray(chosen), np.asarray(weight), state


def test_the_router_chooses_by_s_plus_b_and_weights_by_s():
    z = MlaDims(experts=8, experts_per_token=2, route_scale=2.448, bias_rate=0.001)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(1, 64, 16)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(16, 8)) / 4, jnp.float32)
    bias = jnp.asarray([0.5, -0.5, 0, 0, 0, 0, 0.3, -0.3], jnp.float32)
    chosen, weight, _ = _router_alone(z, h, wr, bias)
    s = 1.0 / (1.0 + np.exp(-np.asarray(h, np.float64) @ np.asarray(wr, np.float64)))
    want = np.argsort(-(s + np.asarray(bias)), -1)[..., :2]
    assert np.array_equal(np.sort(chosen, -1), np.sort(want, -1))
    assert not np.array_equal(np.sort(chosen, -1), np.sort(np.argsort(-s, -1)[..., :2], -1))
    top = np.take_along_axis(s, chosen, -1)
    assert np.allclose(weight, 2.448 * top / top.sum(-1, keepdims=True), atol=1e-6)
    assert np.allclose(weight.sum(-1), 2.448, atol=1e-5)           # not 1
    # not of s + b: expert 0's weight would be larger by its bias
    biased = np.take_along_axis(s + np.asarray(bias), chosen, -1)
    assert np.abs(weight - 2.448 * biased / biased.sum(-1, keepdims=True)).max() > 0.05


def test_the_bias_moves_by_the_sign_rule_on_the_steps_own_counts():
    """A hand-made load: logits that send every token to experts 0 and 1."""
    z = MlaDims(experts=4, experts_per_token=2, bias_rate=0.001)
    h = jnp.ones((1, 8, 4), jnp.float32)
    wr = jnp.asarray(np.array([[3.0, 2.0, -2.0, -3.0]] * 4) / 4, jnp.float32)
    bias = jnp.asarray([0.01, 0.0, 0.0, -0.01], jnp.float32)
    chosen, _, state = _router_alone(z, h, wr, bias, train=True)
    assert np.array_equal(np.sort(chosen, -1), np.broadcast_to([0, 1], (1, 8, 2)))
    # n = [8, 8, 0, 0], mean 4: the loaded experts' bias falls, the idle ones' rises
    after = np.asarray(state["counters"]["router_bias"])
    assert np.allclose(after, [0.009, -0.001, 0.001, -0.009], atol=1e-7)
    assert np.allclose(np.asarray(reference_kanana.moved_bias(
        dict(experts=4, bias_rate=0.001), bias, jnp.asarray(chosen))), after, atol=1e-7)
    # an evaluation step leaves it alone
    _, _, state = _router_alone(z, h, wr, bias)
    assert "counters" not in state


# -- the shares of a layer ---------------------------------------------------------


def _expert_sublayer(held, x, flat, bias, layer=1, shared=True):
    """The program's expert sublayer alone, holding ``held``, on the weights of
    ``layer`` made for ALL experts (any share is cut from them)."""
    first, count = held
    z = MlaDims(experts=M["experts"], experts_per_token=M["experts_per_token"],
                experts_held=tuple(held), expert_dim=M["expert_dim"],
                shared_experts=M["shared_experts"], route_scale=M["route_scale"])
    w = weights_kanana.layer_of(dict(M, experts_held=[0, M["experts"]]), flat, layer)
    params = {"ln": {"scale": w["ln2_g"]}, "router": {"kernel": w["wr"]},
              "experts_in": w["ex_in"][first:first + count],
              "experts_out": w["ex_out"][first:first + count],
              "shared_in": {"kernel": w["sh_in"]}, "shared_out": {"kernel": w["sh_out"]}}
    state = {"moe_tokens": jnp.zeros((M["experts"],), jnp.int32), "router_bias": bias}
    with jax.default_matmul_precision("highest"):
        return RoutedExperts(
            M["dim"], z, jnp.float32, router=_sigmoid_router, join=_added,
            shared_dim=z.shared_experts * z.expert_dim if shared else 0).apply(
            {"params": params, "counters": state}, x, None)[0]


def test_the_shares_add_up_to_the_whole_layer_with_the_shared_expert_once():
    """The 4 shares of 4 experts each: their ROUTED sums added, plus what
    every chip computes alike (the shared expert, the residual) counted once,
    are the uncut reference's layer."""
    whole = dict(M, experts_held=[0, M["experts"]])
    flat = jax.jit(lambda k: weights_kanana.layered(whole, k))(KEY)
    bias = weights()[2][0]
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 64, M["dim"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference_kanana.experts(whole, "float32", x,
                                           weights_kanana.layer_of(whole, flat, 1), bias)
    routed = [_expert_sublayer((first, 4), x, flat, bias, shared=False) - x
              for first in range(0, M["experts"], 4)]
    alike = _expert_sublayer((0, 4), x, flat, bias) - routed[0]        # x + the shared expert
    assert np.abs(np.asarray(sum(routed) + alike - want)).max() < 2e-5
    assert all(np.abs(np.asarray(s)).max() > 1e-3 for s in routed)
    assert np.abs(np.asarray(alike - x)).max() > 1e-2                  # the shared expert is there
    # every share's layer holds the shared expert whole: counted four times it is wrong
    four = sum(_expert_sublayer((first, 4), x, flat, bias) - x for first in range(0, 16, 4)) + x
    assert np.abs(np.asarray(four - want)).max() > 1e-2


# -- two kinds of layer in one model -----------------------------------------------


def test_layer_0_is_dense_and_the_others_have_experts():
    spec = program_lm(M, fused_ce=True)
    params, state = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    assert set(params["blocks_0"]) == {"attn", "mlp"}
    assert set(params["blocks_0"]["mlp"]) == {"ln", "mlp_in", "mlp_out"}
    assert params["blocks_0"]["mlp"]["mlp_in"]["kernel"].shape == (M["dim"], 2 * M["dense_dim"])
    for i in EXPERT_LAYERS:
        assert set(params[f"blocks_{i}"]) == {"attn", "moe"}
        assert set(params[f"blocks_{i}"]["moe"]) == {
            "ln", "router", "experts_in", "experts_out", "shared_in", "shared_out"}
    assert set(state["counters"]) == {f"blocks_{i}" for i in EXPERT_LAYERS}    # no blocks_0
    made = jax.eval_shape(lambda k: (weights_kanana.program_tree(M, k),
                                     weights_kanana.counters_tree(M, k)), KEY)
    assert jax.tree.structure(made) == jax.tree.structure((params, state))
    assert jax.tree.leaves(jax.tree.map(lambda a, b: a.shape == b.shape, made,
                                        (params, state))).count(False) == 0


def test_moe_tokens_rows_are_the_expert_layers():
    counts = {f"blocks_{i}/moe/moe_tokens": np.full(M["experts"], i) for i in EXPERT_LAYERS}
    counts["blocks_1/moe/router_bias"] = np.zeros(M["experts"])
    got = moe_tokens(counts)
    assert got.shape == (len(EXPERT_LAYERS), M["experts"])
    assert got[:, 0].tolist() == EXPERT_LAYERS              # row 0 is layer 1
    # chunks of pairs from experts_per_token, as for any top-k layer
    assert held_rows(512, MlaDims(experts=16, experts_per_token=3, experts_held=(4, 4))) == (
        1024, 1536)


# -- the model against the plain reference --------------------------------------------


@functools.lru_cache(maxsize=None)
def _step(remat=True):
    """One training step's loss, state and gradients from the program and
    from the reference, on the seed's weights and bias."""
    flat, tree, bias = weights()
    spec = program_lm(M, attn_impl="flash", fused_ce=True, ce_chunk=64, remat=remat)
    fused = spec.fused_losses["sparse_softmax_cross_entropy"]
    with jax.default_matmul_precision("highest"):
        (loss, state), grads = jax.jit(jax.value_and_grad(
            lambda p: fused(p, counters(), X, Y, True), has_aux=True))(tree)
        (want, routes), ref_grads = jax.jit(jax.value_and_grad(
            lambda w: reference_kanana.nll_sum(M, w, bias, jnp.asarray(X), jnp.asarray(Y),
                                               queries=64), has_aux=True))(flat)
    return dict(loss=float(loss), state=state, grads=weights_kanana.from_program_tree(M, grads),
                want=float(want) / X.size, ref_grads=ref_grads, routes=np.asarray(routes),
                spec=spec)


@pytest.mark.parametrize("remat", [False, True])
def test_the_loss_agrees_and_the_state_counts_and_moves_the_bias(remat):
    s = _step(remat)
    assert abs(s["loss"] - s["want"]) < 1e-5
    state, bias = s["state"]["counters"], weights()[2]
    assert set(state) == {f"blocks_{i}" for i in EXPERT_LAYERS}
    for at, i in enumerate(EXPERT_LAYERS):
        counted = np.asarray(state[f"blocks_{i}"]["moe"]["moe_tokens"])
        assert counted.sum() == X.size * M["experts_per_token"]
        assert np.array_equal(counted, np.bincount(s["routes"][at].ravel(),
                                                   minlength=M["experts"]))
        want = reference_kanana.moved_bias(M, bias[at], jnp.asarray(s["routes"][at]))
        got = np.asarray(state[f"blocks_{i}"]["moe"]["router_bias"])
        assert np.allclose(got, np.asarray(want), rtol=0, atol=1e-7)
        assert np.abs(got - np.asarray(bias[at])).max() == pytest.approx(M["bias_rate"], rel=1e-3)


@pytest.mark.parametrize("leaf", sorted(weights_kanana.block_leaves(M)) + sorted(
    weights_kanana.top_leaves(M)))
def test_every_gradient_agrees(leaf):
    s = _step()
    stack = lambda a: np.stack(a) if isinstance(a, list) else np.asarray(a)
    a, b = stack(s["grads"][leaf]), stack(s["ref_grads"][leaf]) / X.size
    # against the leaf's own largest entry: float32 summation order; a
    # gradient through a route flipped by rounding would show as 1e-2
    assert np.abs(a - b).max() <= 2e-5 * max(np.abs(b).max(), 1e-3)
    assert np.abs(b).max() > 0


def test_the_routes_are_the_references_sets():
    """The 3 experts of every position in every expert layer, as sets."""
    _, tree, _ = weights()
    spec = program_lm(M, fused_ce=True)
    _, seen = jax.jit(lambda p, x: spec.module.apply(
        {"params": p, **counters()}, x, training=True, method="hidden",
        mutable=["intermediates", "counters"]))(tree, X)
    assert "moe" not in seen["intermediates"].get("blocks_0", {})       # layer 0 has no router
    got = np.sort(np.stack([seen["intermediates"][f"blocks_{i}"]["moe"]["moe_chosen"][0]
                            for i in EXPERT_LAYERS]), -1)
    assert got.shape == (len(EXPERT_LAYERS), 2, 128, M["experts_per_token"])
    assert np.array_equal(got, np.sort(_step()["routes"], -1))


def test_a_forward_gives_the_references_logits():
    flat, tree, bias = weights()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(_step()["spec"].apply(tree, counters(), X, False)[0])
        h, _ = reference_kanana.hidden(M, flat, bias, jnp.asarray(X), queries=64)
        want = np.asarray(h @ flat["head"])
    assert got.shape == X.shape + (M["vocab"],)
    assert np.abs(got - want).max() < 5e-5


def test_three_adam_steps_agree_with_the_reference():
    """The driver's own comparison at float32: ``MeshTrainer`` on the normal
    path against ``reference_kanana.train_steps``, the bias after three steps
    among it."""
    from distkeras_tpu.data import Dataset
    from distkeras_tpu.trainers import MeshTrainer

    rows = np.random.default_rng(3).integers(0, M["vocab"], (12, 129)).astype(np.int32)
    x, y = rows[:, :-1], rows[:, 1:]
    spec = program_lm(M, attn_impl="flash", fused_ce=True, ce_chunk=64, remat=True)
    spec = dataclasses.replace(spec, init=lambda _: (weights()[1], counters()))
    trainer = MeshTrainer(spec, loss="sparse_softmax_cross_entropy", worker_optimizer="adam",
                          learning_rate=1e-3, mesh_shape={"dp": 1}, batch_size=4,
                          input_mode="stream", num_epoch=1, seed=1, log_metrics=True)
    with jax.default_matmul_precision("highest"):
        params = trainer.train(Dataset({"features": x, "label": y}))
        ref = reference_kanana.train_steps(
            M, SEED, [(x[i:i + 4], y[i:i + 4]) for i in (0, 4, 8)], 1e-3,
            rows_per_block=2, queries_per_block=64)
    assert np.allclose(trainer.get_history().losses(), ref["losses"], rtol=0, atol=2e-5)
    delta = jax.tree.map(jnp.subtract, params, weights()[1])
    got = jax.device_get(weights_kanana.leaf_norms(M, weights_kanana.from_program_tree(M, delta)))
    assert {"wq_rope", "wkva_rope", "wkvb_k", "wkvb_v", "sh_gate", "dn_down", "ex_up.5"} <= set(got)
    for name, want in ref["delta_norms"].items():
        assert np.allclose(got[name], want, rtol=2e-3), name
    state = trainer.trained_nt_["counters"]
    bias = np.stack([np.asarray(state[f"blocks_{i}"]["moe"]["router_bias"])
                     for i in EXPERT_LAYERS])
    # three steps of the rule: an entry is 3, 1, -1 or -3 steps from the seed's
    apart = np.abs(bias - ref["bias"])
    assert (apart > 1e-6).mean() < 0.05, apart.max()
    moved = np.abs(bias - np.asarray(weights()[2])) / M["bias_rate"]
    assert np.all(np.isclose(moved, 1, atol=1e-3) | np.isclose(moved, 3, atol=1e-3)
                  | np.isclose(moved, 0, atol=1e-3) | np.isclose(moved, 2, atol=1e-3))
    pairs = moe_tokens(trainer.counters_)
    assert pairs.shape == (len(EXPERT_LAYERS), M["experts"])
    assert pairs.sum(1).tolist() == [3 * 4 * 128 * M["experts_per_token"]] * len(EXPERT_LAYERS)


# -- what the block refuses ---------------------------------------------------------------


@pytest.mark.parametrize("entry", ["prefill", "decode_step", "extend", "prefill_raw",
                                   "paged_extend_rows"])
def test_serving_entry_points_raise_by_name(entry):
    _, tree, _ = weights()
    module = program_lm(M).module
    tok = jnp.asarray(X[:, :16])
    args = {"prefill": (tok,), "prefill_raw": (tok,),
            "decode_step": (tok[:, 0], ((None, None),) * M["depth"], 0),
            "extend": (tok, ((None, None),) * M["depth"], 0),
            "paged_extend_rows": (tok, (None,) * M["depth"], (None,) * M["depth"],
                                  None, None, jnp.zeros((2,), jnp.int32), 16)}[entry]
    with pytest.raises(NotImplementedError, match="latent cache"):
        module.apply({"params": tree, **counters()}, *args, method=entry)


@pytest.mark.parametrize("option, match", [
    (dict(attn_window=64), "attn_window"),
    (dict(pos_embedding="sincos"), "pos_embedding"),
    (dict(mla=MlaDims(qk_rope_dim=7)), "odd qk_rope_dim"),
    (dict(mla=MlaDims(experts=4, experts_per_token=8)), "more experts a token"),
    (dict(mla=MlaDims(dense_layers=3)), "dense_layers"),
])
def test_transformer_lm_refuses_what_the_block_cannot_honour(option, match):
    kwargs = dict(vocab=64, maxlen=32, dim=32, heads=4, depth=2, pos_embedding="rope",
                  mla=MlaDims(qk_nope_dim=8, qk_rope_dim=4, v_dim=8, kv_rank=16, experts=4,
                              experts_per_token=2, expert_dim=16, dense_dim=32))
    with pytest.raises(ValueError, match=match):
        transformer_lm(**{**kwargs, **option})


def test_one_family_of_block_a_model_and_no_quantized_one():
    from distkeras_tpu.models import SdarDims, quantize_lm

    with pytest.raises(ValueError, match="one family of block"):
        transformer_lm(vocab=64, maxlen=32, dim=32, heads=4, depth=2, pos_embedding="rope",
                       fused_ce=True, mla=MlaDims(), sdar=SdarDims())
    with pytest.raises(ValueError, match="quant"):
        spec, params = quantize_lm(program_lm(M), weights()[1])
        spec.apply(params, counters(), X, False)


# -- the counts, the configuration, the cell's files ------------------------------------


def test_counts_at_the_published_sizes_and_at_the_cut():
    """30.67 B parameters as published, 3.6 B of them active a token; ISSUE
    33's 687.5 M (10.24 GiB at 16 B, 7.68 GiB resident) at the cut."""
    config = cut()
    m, pub = config["model"], config["published"]
    whole = dict(m, depth=pub["num_hidden_layers"], vocab=pub["vocab_size"],
                 experts_held=[0, pub["n_routed_experts"]])
    assert flops_kanana.param_count(whole) == pytest.approx(30.67e9, rel=1e-3)
    assert flops_kanana.param_count(whole, experts=6) == pytest.approx(3.6e9, rel=0.02)
    assert flops_kanana.param_count(m) == 687_502_336
    assert flops_kanana.param_count(m) == pytest.approx(687.5e6, rel=1e-4)
    assert flops_kanana.param_count(m) * 16 / 2 ** 30 == pytest.approx(10.24, abs=0.01)
    assert flops_kanana.param_count(m) * 12 / 2 ** 30 == pytest.approx(7.68, abs=0.01)
    assert flops_kanana.attention_params(m) == 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert (flops_kanana.shared_params(m), flops_kanana.dense_params(m)) == (
        3 * 2048 * 1536, 3 * 2048 * 6144)
    # the program's tree is those parameters
    spec = train_kanana.program_lm(m)
    shapes = jax.eval_shape(spec.init, jax.random.PRNGKey(0))[0]
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 687_502_336
    step = flops_kanana.step_flops(m, 4, 8192, [24576.0] * 5)
    share = {k: v / sum(step.values()) for k, v in step.items()}
    assert sum(step.values()) / 1e12 == pytest.approx(107.45, abs=0.05)
    assert share["scores"] == pytest.approx(0.461, abs=0.002)
    assert share["projections"] == pytest.approx(0.289, abs=0.002)
    assert (round(share["shared"], 2), round(share["dense"], 2), round(share["head"], 2),
            round(share["experts"], 2)) == (0.09, 0.07, 0.06, 0.03)
    pairs = 4 * 32 * flops_kanana.causal_pairs(8192)
    assert [flops_kanana.flash_call_flops(m, k, 4, 8192) / pairs for k in ("fwd", "dq", "dkv")] \
        == [2 * 320, 2 * 512, 2 * 640]
    one = 4 * 32 * 8192 * 2
    assert flops_kanana.flash_call_bytes(m, "fwd", 4, 8192) == one * (2 * 192 + 2 * 128)
    assert flops_kanana.flash_call_bytes(m, "dkv", 4, 8192) == one * (3 * 192 + 3 * 128)


def test_the_configuration_keeps_every_published_number_it_does_not_list_as_reduced():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        entry = next(json.loads(line) for line in f
                     if '"name": "kanana-2-30b-a3b-instruct-2601"' in line)
    config = cut()
    assert config["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in config["reduced"]:
            assert config[key] != value and config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size",
                                 "max_position_embeddings"]
    m = config["model"]
    assert (m["depth"], m["experts_held"], m["vocab"], m["maxlen"]) == (6, [0, 16], 16032, 8192)
    assert (m["dim"], m["heads"], m["qk_nope_dim"], m["qk_rope_dim"], m["v_dim"], m["kv_rank"],
            m["experts"], m["experts_per_token"], m["expert_dim"], m["shared_experts"],
            m["dense_layers"], m["dense_dim"], m["route_scale"]) == (
        2048, 32, 128, 64, 128, 512, 128, 6, 768, 2, 1, 6144, 2.448)
    assert len(config["assumed"]) >= 4 and len(config["departures"]) >= 3 and config["deployment"]
    assert set(config["cut"]) == set(config["reduced"])


def test_the_cell_finds_every_file():
    loaded = loader.load_cell("kanana-2-30b-a3b.train")
    job = loaded["traffic"]
    assert job["driver"] == "train_kanana" and loaded["cell"]["chips"] == 1
    assert (job["batch_size"], job["seq_len"], job["learning_rate"]) == (4, 8192, 1e-4)
    assert set(job["limits"]) == {"loss_gap", "grad_norm_gap", "expert_grad_gap",
                                  "delta_norm_gap", "route_count_gap", "bias_gap"}
    assert set(job["limits_why"]) == set(job["limits"])
    names = [m["name"] for m in loaded["per_layer"]]
    # the cell's own two, then PR 35's five readers of the step's parts
    own = ["mfu.train.mla", "flash_roofline.mla"]
    assert names[11:13] == own and len(names) == 18
    assert names[13:] == ["step_scoped_pct.train", "loss_ms.train", "attn_outside_flash_ms.train",
                          "moe_route_ms.train", "remat_forward_ms.train"]
    assert {"moe_expert_roofline", "moe_load_max_over_mean"} <= set(names)
    for m in loaded["per_layer"]:
        assert callable(loader.load_reader(m["reader"]))
    bench = loader.load_benchmark()
    assert len(bench["workloads"]) == 5 and all(w["chips"] == 1 for w in bench["workloads"])
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    for other in ("xglm-564m.train", "zaya1-8b.train", "sdar-30b-a3b.train"):
        theirs = {m["name"] for m in loader.load_cell(other)["per_layer"]}
        assert not theirs & set(own)


def test_the_readers_return_nothing_without_what_they_read():
    metrics = os.path.join(loader.ROOT, "benchmark", "metrics")
    m = cut()["model"]
    mfu = loader.load_reader(os.path.join(metrics, "mfu.train.mla.py"))
    flash = loader.load_reader(os.path.join(metrics, "flash_roofline.mla.py"))
    assert mfu({"model": m, "moe": {}}) is None and mfu({"model": m}) is None
    assert flash({"model": m, "trace": None}) is None
    job = loader.load_cell("kanana-2-30b-a3b.train")["traffic"]
    run = {"model": m, "traffic": job, "chips": 1, "window": {"steps": 24, "seconds": 51.0},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "moe": {"window_tokens": np.full((5, 128), 24 * 1536)}}
    assert mfu(run) == pytest.approx(100 * 24 * 107.453e12 / 51.0 / 197e12, rel=1e-4)
    # a step's flash calls at their least: 24 layer-calls of 2 forwards, dq and dk/dv
    run["trace"] = {"ops": {"flash_fwd.1": (48e9, 96), "flash_dq.2": (30e9, 48),
                            "%flash_dkv.3": (30e9, 48), "fusion.9": (1e9, 1)}}
    pairs = 128 * flops_kanana.causal_pairs(8192)
    least = 8 * 6 * pairs * (2 * 640 + 1024 + 1280) / 197e12
    assert flash(run) == pytest.approx(100 * least / 108.0, rel=1e-6)


# -- the controls ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _sound():
    x, y = train.token_pool(M, JOB, SEED)
    first = [(x[i:i + 4], y[i:i + 4]) for i in (0, 4, 8)]
    steps = dict(learning_rate=JOB["learning_rate"], rows_per_block=2, queries_per_block=64)
    return first, steps, reference_kanana.train_steps(M, SEED, first, **steps)


@pytest.mark.parametrize("control", limits_kanana.CONTROLS)
def test_each_control_fails_a_limit(control):
    """The reference with each fault planted, put where the program stood: at
    this size, in float32, the sound reference against itself reads 0 on every
    number, and every control reads over a limit a hundredth of the tiny
    cell's (which are set for bf16)."""
    first, steps, ref = _sound()
    got = reference_kanana.train_steps(M, SEED, first, **steps,
                                       **limits_kanana.planted(M, control))
    limits = {name: limit / 100 for name, limit in JOB["limits"].items()}
    assert checks.holds(train_kanana.mla_checks(M, ref, ref, limits))
    read = train_kanana.mla_checks(M, got, ref, limits)
    assert not checks.holds(read), read
    told_by = {"no_rope": "_rope", "no_shared": "sh_", "scale_128": "w"}.get(control)
    if told_by:         # a leaf made for it reads worst
        assert read["grad_norm_gap"]["value"] > 0.1 and told_by in read["grad_norm_gap"]["leaf"], \
            read
    if control in ("biased_weights", "unscaled", "other_experts"):
        assert read["expert_grad_gap"]["value"] > 0.02, read


def test_the_controls_script_prints_a_line_a_control(capsys):
    tiny = os.path.join(DATA, "BENCHMARK.kanana.json")
    assert limits_kanana.main(["tiny-kanana.tiny-train-mla", "11", "unscaled,no_rope"], tiny) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [(r["seed"], r["control"]) for r in lines] == [(11, "unscaled"), (11, "no_rope")]
    assert lines[0]["expert_grad_gap"] > 0.1 and lines[0]["bias_gap"] < 0.25
    assert "grad_norm_gap" in lines[1]["fails"] and "_rope" in lines[1]["grad_leaf"]
