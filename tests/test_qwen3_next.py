"""The hybrid linear-attention expert block (``transformer_lm(next=NextDims(...))``)
against the plain float32 equations of ``benchmark/reference_next.py`` at a
tiny size on the CPU: 4 layers (3 Gated DeltaNet of 2 key / 4 value heads of
16, 1 gated attention of 4 query / 2 key-value heads of 32 with rotary on 8
columns), 16 experts of width 32 of which 4 are held and 3 chosen a token
beside a gated shared expert, vocabulary 256; and what is new under it: the
chunked gated delta rule (``ops.gated_delta``) against its per-token form,
the shared expert's gate, centred norms, partial rotary, layers of two kinds.

The program runs in float32 here, so what is left between the two is the order
of float32 sums (the chunked rule, the flash kernel's tiles, the grouped
product, the fused loss's chunks): a few 1e-6 on numbers of order one. A
dropped term (the output gate, the L2 norm, the shared gate) or a decay in the
wrong place is of order 1e-2 to 1.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (checks, flops_next, limits_next, loader, reference_next,
                       weights_next)
from benchmark.drivers import train, train_next
from benchmark.drivers.train_next import program_lm
from distkeras_tpu.models.lm import (GatedAttention, GatedDeltaNet, NextDims, RoutedExperts,
                                     _added, _topk_router, CentredRMSNorm, moe_tokens,
                                     transformer_lm)
from distkeras_tpu.ops import kernel_impl
from distkeras_tpu.ops.gated_delta import (gated_delta, gated_delta_kernel, gated_delta_tokens,
                                           unit_lower_inverse)
from tests.test_kanana import CATALOG

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "..", "benchmark", "tests", "data")
with open(os.path.join(DATA, "configs", "tiny-next.json")) as f:
    M = dict(json.load(f)["model"], dtype="float32")
with open(os.path.join(DATA, "traffic", "tiny-train-gdn.json")) as f:
    JOB = json.load(f)
SEED = 2 ** 31 + 37
KEY = weights_next.seed_key(SEED)
ROWS = np.random.default_rng(37).integers(0, M["vocab"], (2, 129)).astype(np.int32)
X, Y = ROWS[:, :-1], ROWS[:, 1:]


def dims(m=M, **changes) -> NextDims:
    return dataclasses.replace(train_next.program_lm(m).module.next, **changes)


@functools.lru_cache(maxsize=None)
def weights():
    """The seed's weights in the reference's layout and in the program's."""
    return (jax.jit(lambda k: weights_next.layered(M, k))(KEY),
            jax.jit(lambda k: weights_next.program_tree(M, k))(KEY))


def cut():
    with open(os.path.join(loader.ROOT, "benchmark", "configs", "qwen3-next-80b-a3b.json")) as f:
        return json.load(f)


def _err(a, b, floor=1e-12):
    """Largest gap over the larger of ``b``'s largest entry and ``floor``."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), floor)


# -- the chunked gated delta rule against its per-token form -------------------


@functools.lru_cache(maxsize=None)
def _rule_inputs(decay):
    """q, k ``[2, 96, 2, 8]`` L2-normed, v ``[2, 96, 4, 8]``, write strengths
    in (0, 1) and log-decays a token of the given kind: ``"none"`` (near 0),
    ``"steep"`` (near -16) or ``"mixed"`` (log U(1, 16) times a softplus)."""
    r = np.random.default_rng(1)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = unit(r.normal(size=(2, 2, 96, 8))), unit(r.normal(size=(2, 2, 96, 8)))
    q, k = np.moveaxis(q, 1, 2), np.moveaxis(k, 1, 2)
    v = r.normal(size=(2, 96, 4, 8))
    g = {"none": -r.uniform(0, 1e-3, (2, 96, 4)), "steep": -r.uniform(15, 16, (2, 96, 4)),
         "mixed": -np.exp(np.log(r.uniform(1, 16, 4))) * np.log1p(np.exp(r.normal(size=(2, 96, 4)) + 1))}[decay]
    beta = 1 / (1 + np.exp(-r.normal(size=(2, 96, 4))))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def _rule(impl, chunk=64):
    """The chunked rule with its forward in XLA (``"xla"``) or in the
    ``gated_delta_fwd`` kernel (``"kernel"``, in the interpreter here: at any
    shape); the backward is XLA's either way."""
    if impl == "xla":
        return functools.partial(gated_delta, chunk=chunk, impl="xla")
    return functools.partial(gated_delta_kernel, chunk=chunk, interpret=True)


@pytest.mark.parametrize("impl", ["xla", "kernel"])
@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("decay", ["none", "steep", "mixed"])
def test_the_chunked_rule_is_the_per_token_recurrence(chunk, decay, impl):
    """Values and all five gradients; 96 tokens are one chunk and a half of
    64 (the tail padded with tokens that neither decay nor write). At -16 a
    token a chunk's Γ reaches -1024: exp(-Γ) would be inf, every decay here
    is exp of a masked difference."""
    x = _rule_inputs(decay)
    probe = jnp.asarray(np.random.default_rng(2).normal(size=(2, 96, 4, 8)), jnp.float32)
    got, pull = jax.vjp(_rule(impl, chunk), *x)
    want, pull_ref = jax.vjp(gated_delta_tokens, *x)
    assert np.all(np.isfinite(np.asarray(got)))
    assert _err(got, want) < 2e-5
    # against the gradient's own scale, or a hundredth where it is all but 0
    # (the decay's, where every token decays by e^-16)
    for a, b in zip(pull(probe), pull_ref(probe)):
        assert np.all(np.isfinite(np.asarray(a))) and _err(a, b, 1e-2) < 5e-5


@functools.lru_cache(maxsize=None)
def _at_the_kernels_shape():
    """``(o, dq, dk, dv, dg, dbeta)`` of the kernel's forward (through
    ``gated_delta(impl="pallas")``), the XLA form and the per-token rule at a
    shape the kernel takes on the chip (key and value heads of 128, two value
    heads a key head): 600 tokens are nine chunks of 64 and a tail of 24, three
    grid steps of four chunks of which the third is mostly padding; a fifth of
    the tokens neither decay nor write (``g`` and ``beta`` 0, as a mask makes
    them)."""
    B, T, Hk, Hv, d = 1, 600, 2, 4, 128
    r = np.random.default_rng(5)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(r.normal(size=(B, T, Hk, d))) * d ** -0.5
    k = unit(r.normal(size=(B, T, Hk, d)))
    v = r.normal(size=(B, T, Hv, d))
    g = -r.uniform(1, 16, Hv) * np.log1p(np.exp(r.normal(size=(B, T, Hv)) + 1)) / 8
    beta = 1 / (1 + np.exp(-r.normal(size=(B, T, Hv))))
    kept = r.uniform(size=(B, T, 1)) > 0.2
    x = tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g * kept, beta * kept))
    probe = jnp.asarray(r.normal(size=(B, T, Hv, d)), jnp.float32)
    out = {}
    for name, fn in (("kernel", functools.partial(gated_delta, impl="pallas", interpret=True)),
                     ("xla", _rule("xla")), ("tokens", gated_delta_tokens)):
        o, pull = jax.vjp(fn, *x)
        out[name] = (o,) + tuple(pull(probe))
    return out


@pytest.mark.parametrize("against", ["tokens", "xla"])
def test_the_kernels_forward_is_the_recurrence_and_the_xla_form(against):
    got = _at_the_kernels_shape()
    assert np.all(np.isfinite(np.asarray(got["kernel"][0])))
    assert _err(got["kernel"][0], got[against][0]) < 2e-5


@pytest.mark.parametrize("against", ["tokens", "xla"])
@pytest.mark.parametrize("part", ["dq", "dk", "dv", "dg", "dbeta"])
def test_the_gradients_through_the_kernels_forward(part, against):
    """The XLA backward fed by the kernel's ``starts``: each gradient against
    its own scale, or a hundredth where it is all but 0."""
    i = ("dq", "dk", "dv", "dg", "dbeta").index(part) + 1
    got = _at_the_kernels_shape()
    assert _err(got["kernel"][i], got[against][i], 1e-2) < 5e-5


def test_the_kernels_tiling_does_not_change_its_result():
    """A grid step of one key head and one block of 128 tokens gives what the
    default step (both key heads, two blocks) gives, ``o`` and the state at
    each chunk's start alike: the (block, value head)s a step takes side by
    side are independent, and the state is handed over across steps."""
    from distkeras_tpu.ops.gated_delta import _kernel_fwd

    B, T, Hk, Hv, d = 1, 600, 2, 4, 128
    r = np.random.default_rng(7)
    x = tuple(jnp.asarray(a, jnp.float32) for a in (
        r.normal(size=(B, T, Hk, d)) / d, r.normal(size=(B, T, Hk, d)) / d ** 0.5,
        r.normal(size=(B, T, Hv, d)), -r.uniform(0, 1, (B, T, Hv)), r.uniform(0, 1, (B, T, Hv))))
    wide = _kernel_fwd(*x, chunk=64, interpret=True)
    narrow = _kernel_fwd(*x, chunk=64, interpret=True, tokens=128, key_heads=1)
    assert wide[1].shape == (10, B, Hv, d, d)
    for a, b in zip(wide, narrow):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


CELL = dict(T=4096, dk=128, dv=128, Hk=16, Hv=32)


@pytest.mark.parametrize("impl, dims, want", [
    ("pallas", CELL, "pallas"),                                  # the cell's call
    ("pallas", dict(CELL, dk=64), "xla"),                        # a key head of 64
    ("pallas", dict(CELL, dv=192), "xla"),                       # no multiple of 128
    ("pallas", dict(CELL, Hk=3), "xla"),                         # 32 value heads over 3
    ("pallas", dict(CELL, dtype="float16"), "xla"),
    ("pallas", dict(T=128, dk=16, dv=16, Hk=2, Hv=4, dtype="float32"), "xla"),  # the tiny one
    ("xla", CELL, "xla"),
    ("auto", CELL, "xla"),                                       # no chip here
])
def test_the_dispatch_takes_the_kernel_where_its_tiles_fit(impl, dims, want):
    assert kernel_impl("gated_delta", impl, **dims) == want


def test_the_kernel_runs_per_device_under_a_declared_mesh():
    """Inside a jit over several chips each device runs the forward kernel on
    its own batch rows (``o`` split on dim 0, the starts on dim 1), and the
    backward reads them so: value and gradients are the XLA form's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distkeras_tpu.ops import kernel_mesh

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    B, T, Hk, Hv, d = 2, 128, 1, 2, 128
    r = np.random.default_rng(6)
    x = tuple(jnp.asarray(a, jnp.float32) for a in (
        r.normal(size=(B, T, Hk, d)) / d, r.normal(size=(B, T, Hk, d)) / d ** 0.5,
        r.normal(size=(B, T, Hv, d)), -r.uniform(0, 1, (B, T, Hv)), r.uniform(0, 1, (B, T, Hv))))
    probe = jnp.asarray(r.normal(size=(B, T, Hv, d)), jnp.float32)

    def loss(fn, *x):
        return jnp.sum(fn(*x) * probe)

    def declared(*x):
        with kernel_mesh(mesh, "dp"):
            return jax.value_and_grad(functools.partial(loss, _rule("kernel")),
                                      (0, 1, 2, 3, 4))(*x)

    rows = NamedSharding(mesh, P("dp"))
    got = jax.jit(declared)(*(jax.device_put(a, rows) for a in x))
    want = jax.value_and_grad(functools.partial(loss, _rule("xla")), (0, 1, 2, 3, 4))(*x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _err(a, b, 1e-2) < 5e-5
    assert str(jax.make_jaxpr(declared)(*x)).count("shard_map") >= 1


def test_the_triangular_inverse_doubles_to_the_whole_chunk():
    a = jnp.asarray(np.tril(np.random.default_rng(3).normal(size=(3, 64, 64)), -1) * 0.3,
                    jnp.float32)
    t = np.asarray(unit_lower_inverse(a), np.float64)
    assert np.abs(t @ (np.eye(64) + np.asarray(a, np.float64)) - np.eye(64)).max() < 1e-4
    assert np.allclose(np.triu(t, 1), 0) and np.allclose(np.diagonal(t, axis1=-2, axis2=-1), 1)


def test_the_rule_counts_its_own_work():
    """``flops_next``'s 6 dk dv forward and 14 backward, counted again from the
    per-token step's jaxpr: a product's multiply-adds (2 x its result's size x
    the contracted size) and the decay's gradient (a product summed)."""
    dk, dv = 4, 6
    s, q, k = jnp.ones((dk, dv)), jnp.ones((dk,)), jnp.ones((dk,))
    v, g, b = jnp.ones((dv,)), jnp.float32(-0.5), jnp.float32(0.5)

    def step(s, q, k, v, g, b):
        s = jnp.exp(g) * s
        s = s + jnp.einsum("d,e->de", b * k, v - jnp.einsum("de,d->e", s, k))
        return jnp.einsum("de,d->e", s, q)

    def count(jaxpr):
        n = 0
        for e in jaxpr.eqns:
            if e.primitive.name == "dot_general":
                (lc, _), _ = e.params["dimension_numbers"]
                contracted = int(np.prod([e.invars[0].aval.shape[i] for i in lc]))
                n += 2 * int(np.prod(e.outvars[0].aval.shape)) * contracted
            elif e.primitive.name == "reduce_sum" and e.invars[0].aval.shape == (dk, dv):
                n += 2 * dk * dv
            for sub in jax.core.jaxprs_in_params(e.params):
                n += count(sub)
        return n

    fwd = count(jax.make_jaxpr(step)(s, q, k, v, g, b).jaxpr)
    _, pull = jax.vjp(step, s, q, k, v, g, b)
    bwd = count(jax.make_jaxpr(pull)(jnp.ones((dv,))).jaxpr)
    m = dict(linear_key_dim=dk, linear_value_dim=dv, linear_value_heads=1)
    assert flops_next.gated_delta_call_flops(m, "fwd", 1, 1) == fwd == 6 * dk * dv
    assert flops_next.gated_delta_call_flops(m, "bwd", 1, 1) == bwd == 14 * dk * dv


# -- the sublayers against the reference ----------------------------------------


def _sublayer(module, params, x, *args):
    with jax.default_matmul_precision("highest"):
        return np.asarray(module.apply({"params": params}, x, *args))


def test_the_gated_deltanet_sublayer_is_the_references():
    flat, tree = weights()
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 128, M["dim"])), jnp.float32)
    got = _sublayer(GatedDeltaNet(M["dim"], dims(), jnp.float32), tree["blocks_0"]["gdn"], x)
    with jax.default_matmul_precision("highest"):
        want = reference_next.gdn(M, "float32", x, weights_next.layer_of(M, flat, 0))
    assert _err(got - x, want - x) < 2e-5
    # a padded row: its masked tail neither decays nor writes the state
    mask = jnp.asarray(np.arange(128) < 100, jnp.float32)[None].repeat(2, 0)
    masked = _sublayer(GatedDeltaNet(M["dim"], dims(), jnp.float32), tree["blocks_0"]["gdn"],
                       x, mask)
    assert np.allclose(masked[:, :100], got[:, :100], atol=1e-6)


def test_the_gated_attention_sublayer_is_the_references():
    flat, tree = weights()
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 128, M["dim"])), jnp.float32)
    attn = GatedAttention(M["dim"], M["heads"], M["kv_heads"], dims(), jnp.float32, "flash")
    got = _sublayer(attn, tree["blocks_3"]["attn"], x)
    with jax.default_matmul_precision("highest"):
        want = reference_next.attention(M, "float32", x, weights_next.layer_of(M, flat, 3),
                                        queries=64)
    assert _err(got - x, want - x) < 2e-5


def _expert_sublayer(held, x, flat, shared=True, m=M):
    """The program's expert sublayer alone, holding ``held``, on layer 0's
    weights made for ALL experts (any share is cut from them)."""
    first, count = held
    w = weights_next.layer_of(dict(m, experts_held=[0, m["experts"]]), flat, 0)
    params = {"ln": {"scale": w["ln2_g"]}, "router": {"kernel": w["wr"]},
              "experts_in": w["ex_in"][first:first + count],
              "experts_out": w["ex_out"][first:first + count],
              "shared_in": {"kernel": w["sh_in"]}, "shared_out": {"kernel": w["sh_out"]},
              "shared_gate": {"kernel": w["sgate"]}}
    z = dims(m, experts_held=tuple(held), shared_dim=m["shared_dim"] if shared else 0)
    moe = RoutedExperts(m["dim"], z, jnp.float32, router=_topk_router, join=_added,
                        shared_dim=z.shared_dim, shared_gate=True, norm=CentredRMSNorm)
    if not shared:
        params = {k: v for k, v in params.items() if not k.startswith("shared")}
    state = {"moe_tokens": jnp.zeros((m["experts"],), jnp.int32)}
    with jax.default_matmul_precision("highest"):
        return moe.apply({"params": params, "counters": state}, x, None)[0]


def test_the_shares_add_up_to_the_whole_layer_with_the_shared_expert_once():
    """The deployment's 16 shares of 512 experts (32 each, 10 a token): their
    ROUTED sums added, plus what every chip computes alike (the gated shared
    expert, the residual) counted once, are the uncut reference's layer."""
    m = dict(M, experts=512, experts_per_token=10, experts_held=[0, 512])
    flat = jax.jit(lambda k: weights_next.layered(m, k))(KEY)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(1, 64, m["dim"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference_next.experts(m, "float32", x, weights_next.layer_of(m, flat, 0))
    routed = [_expert_sublayer((first, 32), x, flat, shared=False, m=m) - x
              for first in range(0, 512, 32)]
    alike = _expert_sublayer((0, 32), x, flat, m=m) - routed[0]        # x + the shared expert
    assert _err(sum(routed) + alike - x, want - x) < 2e-5
    assert sum(np.abs(np.asarray(s)).max() > 1e-4 for s in routed) >= 14
    assert np.abs(np.asarray(alike - x)).max() > 1e-2                  # the shared expert is there
    # every share's layer holds the shared expert whole: counted 16 times it is wrong
    sixteen = sum(_expert_sublayer((f, 32), x, flat, m=m) - x for f in range(0, 512, 32)) + x
    assert np.abs(np.asarray(sixteen - want)).max() > 1e-2


# -- the model against the reference ---------------------------------------------


def test_layers_of_two_kinds_and_the_trees_the_benchmark_makes():
    spec = program_lm(M, fused_ce=True)
    params, state = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    for i in range(4):
        assert set(params[f"blocks_{i}"]) == ({"attn", "moe"} if i == 3 else {"gdn", "moe"})
    assert set(params["blocks_0"]["gdn"]) == {"ln", "qkvz", "ba", "conv", "A_log", "dt_bias",
                                              "norm", "out"}
    assert set(params["blocks_3"]["moe"]) == {"ln", "router", "experts_in", "experts_out",
                                              "shared_in", "shared_out", "shared_gate"}
    assert set(state["counters"]) == {f"blocks_{i}" for i in range(4)}
    made = jax.eval_shape(lambda k: (weights_next.program_tree(M, k),
                                     weights_next.counters_tree(M)), KEY)
    assert jax.tree.structure(made) == jax.tree.structure((params, state))
    assert jax.tree.leaves(jax.tree.map(lambda a, b: a.shape == b.shape, made,
                                        (params, state))).count(False) == 0
    # centred norms start at 0, the gated norm's weight at 1
    real, _ = spec.init(jax.random.PRNGKey(0))
    assert not np.any(np.asarray(real["blocks_0"]["gdn"]["ln"]["scale"]))
    assert not np.any(np.asarray(real["ln_head"]["scale"]))
    assert np.all(np.asarray(real["blocks_0"]["gdn"]["norm"]) == 1)


@functools.lru_cache(maxsize=None)
def _step(remat):
    flat, tree = weights()
    spec = program_lm(M, attn_impl="flash", fused_ce=True, ce_chunk=64, remat=remat)
    fused = spec.fused_losses["sparse_softmax_cross_entropy"]
    with jax.default_matmul_precision("highest"):
        (loss, state), grads = jax.jit(jax.value_and_grad(
            lambda p: fused(p, weights_next.counters_tree(M), X, Y, True), has_aux=True))(tree)
        (want, routes), ref_grads = jax.jit(jax.value_and_grad(
            lambda w: reference_next.nll_sum(M, w, jnp.asarray(X), jnp.asarray(Y), queries=64),
            has_aux=True))(flat)
    return dict(loss=float(loss), state=state, grads=weights_next.from_program_tree(M, grads),
                want=float(want) / X.size, ref_grads=ref_grads, routes=np.asarray(routes))


@pytest.mark.parametrize("remat", [False, True])
def test_the_loss_agrees_and_the_state_counts_the_pairs(remat):
    s = _step(remat)
    assert abs(s["loss"] - s["want"]) < 1e-5
    for i in range(M["depth"]):
        counted = np.asarray(s["state"]["counters"][f"blocks_{i}"]["moe"]["moe_tokens"])
        assert counted.sum() == X.size * M["experts_per_token"]
        assert np.array_equal(counted, np.bincount(s["routes"][i].ravel(), minlength=M["experts"]))


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("leaf", sorted(weights_next.block_leaves(M)) + sorted(
    weights_next.top_leaves(M)))
def test_every_gradient_agrees(leaf, remat):
    s = _step(remat)
    stack = lambda a: np.stack(a) if isinstance(a, list) else np.asarray(a)
    a, b = stack(s["grads"][leaf]), stack(s["ref_grads"][leaf]) / X.size
    assert np.abs(a - b).max() <= 5e-4 * max(np.abs(b).max(), 1e-3)
    assert np.abs(b).max() > 0


def test_a_forward_gives_the_references_logits():
    flat, tree = weights()
    spec = program_lm(M, attn_impl="flash")
    with jax.default_matmul_precision("highest"):
        got = np.asarray(spec.apply(tree, weights_next.counters_tree(M), X, False)[0])
        h, _ = reference_next.hidden(M, flat, jnp.asarray(X), queries=64)
        want = np.asarray(h @ flat["head"])
    assert got.shape == X.shape + (M["vocab"],) and _err(got, want) < 2e-4


def test_three_adam_steps_agree_with_the_reference():
    """``drivers/train_next.py``'s comparison at float32: ``MeshTrainer`` on the normal
    path against ``reference_next.train_steps``."""
    from distkeras_tpu.data import Dataset
    from distkeras_tpu.trainers import MeshTrainer

    rows = np.random.default_rng(3).integers(0, M["vocab"], (6, 129)).astype(np.int32)
    x, y = rows[:, :-1], rows[:, 1:]
    spec = program_lm(M, attn_impl="flash", fused_ce=True, ce_chunk=64, remat=True)
    spec = dataclasses.replace(spec, init=lambda _: (weights()[1], weights_next.counters_tree(M)))
    trainer = MeshTrainer(spec, loss="sparse_softmax_cross_entropy", worker_optimizer="adam",
                          learning_rate=1e-4, mesh_shape={"dp": 1}, batch_size=2,
                          input_mode="stream", num_epoch=1, seed=1, log_metrics=True)
    with jax.default_matmul_precision("highest"):
        params = trainer.train(Dataset({"features": x, "label": y}))
        ref = reference_next.train_steps(M, SEED, [(x[i:i + 2], y[i:i + 2]) for i in (0, 2, 4)],
                                         1e-4, rows_per_block=2, queries_per_block=64)
    # the first loss to float32's order of sums; after an Adam step a leaf whose
    # gradient is all but 0 moves by the sign of its rounding, a few 1e-5 of the loss
    losses = trainer.get_history().losses()
    assert abs(losses[0] - ref["losses"][0]) < 2e-5
    assert np.allclose(losses[1:], ref["losses"][1:], rtol=0, atol=2e-4)
    delta = jax.tree.map(jnp.subtract, params, weights()[1])
    got = jax.device_get(weights_next.leaf_norms(M, weights_next.from_program_tree(M, delta)))
    assert {"g_q", "g_z", "g_a", "a_gate", "sgate", "sh_gate", "ex_up.5"} <= set(got)
    for name, want in ref["delta_norms"].items():
        assert np.allclose(got[name], want, rtol=2e-3, atol=1e-6), name
    pairs = moe_tokens(trainer.counters_)
    assert pairs.shape == (M["depth"], M["experts"])
    assert pairs.sum(1).tolist() == [3 * 2 * 128 * M["experts_per_token"]] * M["depth"]


# -- what the block refuses ---------------------------------------------------------


@pytest.mark.parametrize("entry", ["prefill", "decode_step", "extend", "prefill_raw",
                                   "paged_extend_rows"])
def test_serving_entry_points_raise_by_name(entry):
    module = program_lm(M).module
    tok = jnp.asarray(X[:, :16])
    args = {"prefill": (tok,), "prefill_raw": (tok,),
            "decode_step": (tok[:, 0], ((None, None),) * M["depth"], 0),
            "extend": (tok, ((None, None),) * M["depth"], 0),
            "paged_extend_rows": (tok, (None,) * M["depth"], (None,) * M["depth"],
                                  None, None, jnp.zeros((2,), jnp.int32), 16)}[entry]
    with pytest.raises(NotImplementedError, match="fixed-size state"):
        module.apply({"params": weights()[1], **weights_next.counters_tree(M)}, *args,
                     method=entry)


@pytest.mark.parametrize("option, match", [
    (dict(attn_window=64), "attn_window"),
    (dict(pos_embedding="sincos"), "pos_embedding"),
    (dict(next=NextDims(rotary_dim=7)), "odd rotary_dim"),
    (dict(next=NextDims(linear_value_heads=24)), "no multiple"),
    (dict(next=NextDims(experts=4, experts_per_token=8)), "more experts a token"),
])
def test_transformer_lm_refuses_what_the_block_cannot_honour(option, match):
    from distkeras_tpu.models import MlaDims

    kwargs = dict(vocab=64, maxlen=32, dim=32, heads=4, kv_heads=2, depth=4,
                  pos_embedding="rope", next=NextDims())
    with pytest.raises(ValueError, match=match):
        transformer_lm(**{**kwargs, **option})
    with pytest.raises(ValueError, match="one family of block"):
        transformer_lm(**kwargs, mla=MlaDims())


# -- the counts, the configuration, the cell's files ------------------------------------


def test_counts_at_the_published_sizes_and_at_the_cut():
    """About 80 B parameters as published, about 3 B active a token; the
    cut's 625,667,136 (6.99 GiB resident) at the cut; its forward FLOPs a
    token by part (GDN 50 %, gated attention 21, experts 12, head 18)."""
    config = cut()
    m, pub = config["model"], config["published"]
    whole = dict(m, depth=pub["num_hidden_layers"], vocab=pub["vocab_size"],
                 experts_held=[0, pub["num_experts"]])
    assert flops_next.param_count(whole) == pytest.approx(79.7e9, rel=0.01)
    # "A3B": 3.25 B active a token without the table and the head, 3.87 B with them
    active = flops_next.param_count(whole, experts=10)
    assert active - 2 * flops_next.head_params(whole) == pytest.approx(3.25e9, rel=0.01)
    assert flops_next.param_count(m) == 625_667_136
    assert flops_next.gdn_params(m) == 33_718_464
    assert flops_next.param_count(m) * 12 / 2 ** 30 == pytest.approx(6.99, abs=0.01)
    shapes = jax.eval_shape(train_next.program_lm(m).init, jax.random.PRNGKey(0))[0]
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 625_667_136
    step = flops_next.step_flops(m, 8, 4096, [8 * 4096 * 10 * 32 / 512] * 4)
    total = sum(step.values())
    assert total / 1e12 == pytest.approx(42.07, abs=0.01)
    gdn = step["gdn_projections"] + step["gdn_conv"] + step["delta_rule"]
    assert (round(gdn / total, 2), round((step["attn_projections"] + step["scores"]) / total, 2),
            round(step["head"] / total, 2)) == (0.50, 0.21, 0.18)
    one = 8 * 4096 * 2
    assert flops_next.gated_delta_call_bytes(m, "fwd", 8, 4096) == one * (
        2 * 16 * 128 + 2 * 32 * 128) + 2 * 8 * 4096 * 32 * 4


def test_the_configuration_keeps_every_published_number_it_does_not_list_as_reduced():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        entry = next(json.loads(line) for line in f
                     if '"name": "Qwen3-Next-80B-A3B-Instruct"' in line)
    config = cut()
    assert config["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in config["reduced"]:
            assert config[key] != value and config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                                 "max_position_embeddings"]
    m = config["model"]
    assert (m["depth"], m["experts_held"], m["vocab"], m["maxlen"]) == (4, [0, 32], 18992, 4096)
    assert (m["dim"], m["heads"], m["kv_heads"], m["head_dim"], m["rotary_dim"],
            m["linear_key_heads"], m["linear_value_heads"], m["linear_key_dim"],
            m["linear_value_dim"], m["conv_kernel"], m["experts"], m["experts_per_token"],
            m["expert_dim"], m["shared_dim"], m["full_attention_interval"]) == (
        2048, 16, 2, 256, 64, 16, 32, 128, 128, 4, 512, 10, 512, 512, 4)
    assert m["rotary_dim"] == m["head_dim"] * config["partial_rotary_factor"]
    assert len(config["assumed"]) >= 2 and len(config["departures"]) >= 3 and config["deployment"]
    assert set(config["cut"]) == set(config["reduced"])


def test_the_cell_finds_every_file():
    loaded = loader.load_cell("qwen3-next-80b-a3b.train")
    job = loaded["traffic"]
    assert job["driver"] == "train_next" and loaded["cell"]["chips"] == 1
    assert (job["batch_size"], job["seq_len"], job["learning_rate"]) == (8, 4096, 1e-5)
    assert set(job["limits"]) == {"loss_gap", "grad_norm_gap", "expert_grad_gap",
                                  "delta_norm_gap", "route_count_gap"}
    assert set(job["limits_why"]) == set(job["limits"])
    names = [m["name"] for m in loaded["per_layer"]]
    assert names[-3:] == ["mfu.train.gdn", "gated_delta_roofline", "gdn_ms.train"]
    assert {"moe_expert_roofline", "moe_load_max_over_mean", "remat_forward_ms.train",
            "attn_outside_flash_ms.train", "moe_route_ms.train"} <= set(names)
    for m in loaded["per_layer"]:
        assert callable(loader.load_reader(m["reader"]))
    bench = loader.load_benchmark()
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    for other in ("xglm-564m.train", "zaya1-8b.train", "sdar-30b-a3b.train",
                  "kanana-2-30b-a3b.train"):
        theirs = {m["name"] for m in loader.load_cell(other)["per_layer"]}
        assert not theirs & {"mfu.train.gdn", "gated_delta_roofline", "gdn_ms.train"}


def test_the_readers_return_nothing_without_what_they_read():
    from benchmark import parts

    m = cut()["model"]
    read = {n: loader.load_reader(os.path.join(loader.ROOT, "benchmark", "metrics", n + ".py"))
            for n in ("mfu.train.gdn", "gated_delta_roofline", "gdn_ms.train")}
    assert read["mfu.train.gdn"]({"model": m, "moe": {}}) is None
    assert read["gated_delta_roofline"]({"model": m, "trace": None}) is None
    assert read["gdn_ms.train"]({"model": m, "trace": None}) is None
    job = loader.load_cell("qwen3-next-80b-a3b.train")["traffic"]
    run = {"model": m, "traffic": job, "chips": 1, "window": {"steps": 24, "seconds": 51.0},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "moe": {"window_tokens": np.full((4, 512), 24 * 640)}, "trace": {"ops": {}},
           "trace_dir": "fake"}
    assert read["mfu.train.gdn"](run) == pytest.approx(
        100 * 24 * 42.0716e12 / 51.0 / 197e12, rel=1e-4)
    # the calls the table shows: two forwards and the written-out backward a
    # layer, whose operations carry remat's pass
    G = ("blocks_*", "blocks_*.attend", "gdn", "gated_delta")
    parts._made["fake"] = {"steps": 8, "parts": {
        (G, "forward"): [80e6, 1], (G, "remat"): [80e6, 1],
        (G + ("gated_delta_bwd",), "remat"): [240e6, 1],
        (("blocks_*", "blocks_*.attend", "gdn", "qkvz"), "forward"): [40e6, 1]}}
    try:
        moved = 8 * 4096 * 2 * (2 * 16 * 128 + 2 * 32 * 128) + 2 * 8 * 4096 * 32 * 4
        fwd = max(6 * 128 * 128 * 8 * 4096 * 32 / 197e12, moved / 819e9)      # memory-bound
        bwd = max(14 * 128 * 128 * 8 * 4096 * 32 / 197e12, moved / 819e9)     # compute-bound
        want = 100 * (3 * 2 * fwd + 3 * bwd) * 1e3 / 50.0
        assert read["gated_delta_roofline"](run) == pytest.approx(want, rel=1e-9)
        assert read["gdn_ms.train"](run) == pytest.approx(55.0)
    finally:
        parts._made.pop("fake")


# -- the controls ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _sound():
    x, y = train.token_pool(M, JOB, SEED)
    first = [(x[i:i + 2], y[i:i + 2]) for i in (0, 2, 4)]
    steps = dict(learning_rate=JOB["learning_rate"], rows_per_block=2, queries_per_block=64)
    return first, steps, reference_next.train_steps(M, SEED, first, **steps)


@pytest.mark.parametrize("control", limits_next.CONTROLS)
def test_each_control_fails_a_limit(control):
    """The reference with each fault planted, put where the program stood: at
    this size, in float32, the sound reference against itself reads 0 on every
    number, and every control reads over a limit a hundredth of the tiny
    cell's."""
    first, steps, ref = _sound()
    got = reference_next.train_steps(M, SEED, first, **steps, **limits_next.planted(control))
    limits = {name: limit / 100 for name, limit in JOB["limits"].items()}
    assert checks.holds(train_next.next_checks(M, ref, ref, limits))
    read = train_next.next_checks(M, got, ref, limits)
    assert not checks.holds(read), read
    told_by = {"no_output_gate": "a_gate", "shared_ungated": "sgate"}.get(control)
    if told_by:         # the leaf that no longer gets a gradient moves by nothing
        assert read["delta_norm_gap"]["value"] > 0.1 and read["delta_norm_gap"]["leaf"].startswith(
            told_by), read
