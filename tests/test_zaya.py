"""ZAYA1's block (``transformer_lm(zaya=ZayaDims(...))``) against the plain
float32 equations of ``benchmark/reference_zaya.py`` at a tiny size on the CPU:
4 query / 2 key-value heads of 16, 3 layers, 4 experts of width 64 behind a
router MLP of 16, vocabulary 256.

The program runs in float32 here, so what is left between the two is the order
of float32 sums (the flash kernel's tiles, the grouped product, the fused
loss's chunks): a few 1e-6 on numbers of order one. Every tolerance below is
that with a decade of room, and a dropped term (a convolution tap, the q-k
mean, the value's shift, a residual scale) is of order 1e-2 to 1.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_zaya, weights_zaya
from benchmark.drivers.train_moe import program_lm, program_routes
from distkeras_tpu.models.lm import RoutedExperts, ZayaDims, transformer_lm
from distkeras_tpu.parallel.expert import dropless_experts

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "benchmark", "tests", "data", "configs",
                       "tiny-zaya.json")) as f:
    M = dict(json.load(f)["model"], dtype="float32")
KEY = weights_zaya.seed_key(2 ** 31 + 27)
TOKENS = np.random.default_rng(27).integers(0, M["vocab"], (2, 129)).astype(np.int32)
X, Y = TOKENS[:, :-1], TOKENS[:, 1:]


@functools.lru_cache(maxsize=None)
def _weights(sizes: str):
    m = json.loads(sizes)
    flat = jax.jit(lambda k: weights_zaya.layered(m, k))(KEY)
    return flat, weights_zaya.to_program_tree(m, flat)


def weights(m=M):
    """The seed's weights in the reference's layout and in the program's, made
    once a size (the tests read them, none writes)."""
    return _weights(json.dumps(m, sort_keys=True))


def counters(m=M):
    """The model's state: counters at nought, the routers' bias the seed's."""
    return weights_zaya.counters_tree(m, KEY)


@pytest.mark.parametrize("attn_impl", ["reference", "flash"])
def test_logits_agree_with_the_plain_equations(attn_impl):
    flat, tree = weights()
    spec = program_lm(M, attn_impl=attn_impl)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(spec.apply(tree, counters(), X, False)[0])
    want = np.asarray(reference_zaya.logits(M, flat, jnp.asarray(X)))
    # logits are of order one; float32 summation order only
    assert np.abs(got - want).max() < 5e-5


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_agree_with_fused_loss(remat):
    flat, tree = weights()
    spec = program_lm(M, attn_impl="flash", fused_ce=True, ce_chunk=64, remat=remat)
    fused = spec.fused_losses["sparse_softmax_cross_entropy"]
    with jax.default_matmul_precision("highest"):
        (loss, state), grads = jax.jit(jax.value_and_grad(
            lambda p: fused(p, counters(), X, Y, True), has_aux=True))(tree)
        # a training step first balances the routers' bias on its own tokens
        flat = dict(flat, rbias=reference_zaya.step_balancer(M)(flat, X))
        (want, _), ref_grads = jax.jit(jax.value_and_grad(
            lambda w: reference_zaya.nll_sum(M, w, X, Y), has_aux=True))(flat)
    n = X.size
    assert abs(float(loss) - float(want) / n) < 1e-5
    got = weights_zaya.from_program_tree(M, grads)
    for name in got:
        a = np.stack(got[name]) if isinstance(got[name], list) else np.asarray(got[name])
        b = (np.stack(ref_grads[name]) if isinstance(ref_grads[name], list)
             else np.asarray(ref_grads[name])) / n
        # against the leaf's own largest entry: float32 summation order, and
        # a gradient that flows through a flipped-by-rounding route would show
        # as a whole token's worth (1e-2 of a leaf)
        assert np.abs(a - b).max() <= 2e-5 * max(np.abs(b).max(), 1e-3), name
    # the balancing bias is no parameter: argmax passes it no gradient
    assert "rbias" not in got and not np.any(np.stack(ref_grads["rbias"]))
    # (f) the counters add up to tokens x layers
    per_layer = [np.asarray(state["counters"][f"blocks_{i}"]["moe"]["moe_tokens"])
                 for i in range(M["depth"])]
    assert [int(c.sum()) for c in per_layer] == [n] * M["depth"]


def test_router_state_reaches_the_next_layer_under_remat_as_without():
    """(e): layer i's ``r`` feeds layer i + 1's router; were it dropped (zeros
    into every layer), the later layers' choices would change."""
    flat, tree = weights()
    routes = {remat: program_routes(program_lm(M, remat=remat), (tree, counters()), X)
              for remat in (False, True)}
    assert np.array_equal(routes[False], routes[True])
    with jax.default_matmul_precision("highest"):
        flat = dict(flat, rbias=reference_zaya.step_balancer(M)(flat, X))
        want = np.asarray(reference_zaya.hidden(M, flat, jnp.asarray(X))[1])
    assert np.array_equal(routes[True], want)
    # the reference with the state cut between layers routes otherwise
    cut = dict(flat, gamma=[jnp.zeros_like(g) for g in flat["gamma"]])
    with jax.default_matmul_precision("highest"):
        other = np.asarray(reference_zaya.hidden(M, cut, jnp.asarray(X))[1])
    assert np.array_equal(other[0], want[0]) and not np.array_equal(other[1:], want[1:])
    # and the balancing gave every expert its share of the step's tokens
    for layer in want:
        counts = np.bincount(layer.ravel(), minlength=M["experts"])
        assert np.abs(counts - X.size / M["experts"]).max() <= 0.1 * X.size / M["experts"]


def _expert_sublayer(held, x, r, flat, layer=0):
    """The program's expert sublayer alone, holding ``held``, on the weights
    of ``layer`` (every expert's, so that any share can be cut from them)."""
    m = dict(M, experts_held=list(held))
    z = ZayaDims(head_dim=m["head_dim"], router_dim=m["router_dim"], experts=m["experts"],
                 experts_held=tuple(held), expert_dim=m["expert_dim"])
    _, tree = weights(m)
    state = counters(m)["counters"][f"blocks_{layer}"]["moe"]
    with jax.default_matmul_precision("highest"):
        return RoutedExperts(m["dim"], z, jnp.float32).apply(
            {"params": tree[f"blocks_{layer}"]["moe"], "counters": state}, x, r)


def test_the_shares_add_up_to_the_whole_layer():
    """(c): what experts (0, 2) give plus what experts (2, 2) give, with the
    residual terms that every chip computes alike counted once, is the uncut
    reference's result for the whole layer."""
    whole = dict(M, experts_held=[0, M["experts"]])
    flat = jax.jit(lambda k: weights_zaya.layered(whole, k))(KEY)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 64, M["dim"])), jnp.float32)
    r = jnp.asarray(rng.normal(size=(2, 64, M["router_dim"])), jnp.float32)
    w = reference_zaya._layer(whole, flat, 0)
    with jax.default_matmul_precision("highest"):
        want, want_r, _ = reference_zaya.experts(whole, "float32", x, r, w)
    (a, ra), (b, rb) = (_expert_sublayer(h, x, r, flat) for h in ((0, 2), (2, 2)))
    alike = (w["a2"] * x + w["b2"]) + w["e2"]       # what both shares hold
    assert np.abs(np.asarray(a + b - alike - want)).max() < 2e-5
    assert np.allclose(ra, want_r, atol=1e-5) and np.allclose(rb, want_r, atol=1e-5)


@pytest.mark.parametrize("case", ["all_to_one", "one_expert_idle"])
def test_dropless(case):
    """(d): every token sent to one expert loses nothing (a capacity would
    drop most of them); an expert that gets no token gets no gradient."""
    rng = np.random.default_rng(9)
    T, d, f, count = 96, 32, 48, 3
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(count, d, 2 * f)) * d ** -0.5, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(count, f, d)) * f ** -0.5, jnp.float32)
    weight = jnp.asarray(rng.uniform(0.2, 1.0, size=(T,)), jnp.float32)
    expert = (np.full((T,), 1) if case == "all_to_one"
              else rng.choice([0, 2, 3], size=T)).astype(np.int32)   # 3 is not held

    def plain(w_in, w_out):
        y = jnp.zeros_like(x)
        for j in range(count):
            gu = x @ w_in[j]
            out = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_out[j]
            y = y + jnp.where((expert == j)[:, None], weight[:, None] * out, 0.0)
        return y

    def layer(w_in, w_out):
        return dropless_experts(x, jnp.asarray(expert), weight, w_in, w_out,
                                experts=(0, count), total=4)

    with jax.default_matmul_precision("highest"):
        y, tokens = layer(w_in, w_out)
        want = plain(w_in, w_out)
        g = jax.grad(lambda a, b: jnp.sum(layer(a, b)[0] ** 2), argnums=(0, 1))(w_in, w_out)
        g_want = jax.grad(lambda a, b: jnp.sum(plain(a, b) ** 2), argnums=(0, 1))(w_in, w_out)
    assert np.abs(np.asarray(y - want)).max() < 1e-5
    assert np.array_equal(tokens, np.bincount(expert, minlength=4))
    for a, b in zip(g, g_want):
        assert np.abs(np.asarray(a - b)).max() < 1e-4 * np.abs(np.asarray(b)).max()
    if case == "all_to_one":
        assert np.all(np.abs(np.asarray(y)).sum(-1) > 0)       # no token lost
    else:
        assert not np.any(np.asarray(g[0][1])) and not np.any(np.asarray(g[1][1]))
        assert not np.any(np.asarray(y)[expert == 3])          # absent expert: 0


def test_dropless_experts_refuses_a_share_that_is_no_range():
    x = jnp.zeros((4, 8))
    with pytest.raises(ValueError, match="not a range"):
        dropless_experts(x, jnp.zeros((4,), jnp.int32), jnp.ones((4,)),
                         jnp.zeros((2, 8, 16)), jnp.zeros((2, 8, 8)), experts=(3, 2), total=4)
    with pytest.raises(ValueError, match="weights hold"):
        dropless_experts(x, jnp.zeros((4,), jnp.int32), jnp.ones((4,)),
                         jnp.zeros((3, 8, 16)), jnp.zeros((3, 8, 8)), experts=(0, 2), total=4)


@pytest.mark.parametrize("entry", ["prefill", "decode_step", "extend", "prefill_raw",
                                   "paged_extend_rows"])
def test_serving_entry_points_raise_by_name(entry):
    """(g): no silent fallback to the dense block."""
    _, tree = weights()
    module = program_lm(M).module
    tok = jnp.asarray(X[:, :16])
    args = {"prefill": (tok,), "prefill_raw": (tok,),
            "decode_step": (tok[:, 0], ((None, None),) * M["depth"], 0),
            "extend": (tok, ((None, None),) * M["depth"], 0),
            "paged_extend_rows": (tok, (None,) * M["depth"], (None,) * M["depth"],
                                  None, None, jnp.zeros((2,), jnp.int32), 16)}[entry]
    with pytest.raises(NotImplementedError, match="no serving path"):
        module.apply({"params": tree, **counters()}, *args, method=entry)


@pytest.mark.parametrize("option, match", [
    (dict(attn_window=64), "attn_window"),
    (dict(pos_embedding="sincos"), "pos_embedding"),
    (dict(kv_heads=None), "kv_heads"),
])
def test_transformer_lm_refuses_what_the_block_cannot_honour(option, match):
    kwargs = dict(vocab=64, maxlen=32, dim=32, heads=4, kv_heads=2, depth=1,
                  pos_embedding="rope", zaya=ZayaDims(head_dim=8, router_dim=8, experts=4,
                                                      expert_dim=16))
    with pytest.raises(ValueError, match=match):
        transformer_lm(**{**kwargs, **option})


def test_quantize_lm_refuses_the_block():
    from distkeras_tpu.models import quantize_lm

    with pytest.raises(ValueError, match="quant"):
        spec, params = quantize_lm(program_lm(M), weights()[1])
        spec.apply(params, counters(), X, False)


def test_a_training_step_balances_the_bias_on_its_own_tokens():
    """A training step first balances each router's bias on the step's tokens
    (from the bias it was given, as the reference's ``step_balancer`` does),
    routes with the result and leaves it in the state; every expert then gets
    its share; a forward outside training routes with the bias as it stands
    and moves nothing."""
    flat, tree = weights()
    spec = program_lm(M, attn_impl="flash", fused_ce=True, ce_chunk=64, remat=True)
    fused = spec.fused_losses["sparse_softmax_cross_entropy"]
    with jax.default_matmul_precision("highest"):
        _, state = jax.jit(lambda p, s: fused(p, s, X, Y, True))(tree, counters())
        _, still = jax.jit(lambda p, s: spec.apply(p, s, X, False))(tree, counters())
        want = reference_zaya.step_balancer(M)(flat, X)
        chosen = np.asarray(reference_zaya.hidden(M, dict(flat, rbias=want), jnp.asarray(X))[1])
    E = M["experts"]
    for i in range(M["depth"]):
        moe = state["counters"][f"blocks_{i}"]["moe"]
        # float32 summation order upstream of a sorted cut: probabilities are
        # of order 1/E and agree to 1e-6
        assert np.allclose(moe["router_bias"], want[i], atol=2e-6)
        counts = np.bincount(chosen[i].ravel(), minlength=E)
        assert np.array_equal(moe["moe_tokens"], counts)        # routed with the new bias
        assert np.abs(counts - X.size / E).max() <= 0.1 * X.size / E
        assert abs(float(np.sum(moe["router_bias"]))) < 1e-5
        # the seed's, to the last bit of a normal made twice
        assert np.allclose(still["counters"][f"blocks_{i}"]["moe"]["router_bias"],
                           flat["rbias"][i], rtol=0, atol=1e-8)
    assert not np.any(still["counters"]["blocks_0"]["moe"]["moe_tokens"])


def _mesh_trainer(m, **options):
    from distkeras_tpu.trainers import MeshTrainer

    spec = program_lm(m, attn_impl="flash", fused_ce=True, ce_chunk=64, remat=True)
    return MeshTrainer(spec, loss="sparse_softmax_cross_entropy", worker_optimizer="adam",
                       learning_rate=3e-3, mesh_shape={"dp": 2}, batch_size=4,
                       input_mode="stream", log_metrics=True, seed=1, **options)


def _rows(m):
    from distkeras_tpu.data import Dataset

    rows = np.random.default_rng(1).integers(0, m["vocab"], (16, 129)).astype(np.int32)
    return Dataset({"features": rows[:, :-1], "label": rows[:, 1:]})


def _state_tokens(nt, m):
    return np.stack([np.asarray(nt["counters"][f"blocks_{i}"]["moe"]["moe_tokens"])
                     for i in range(m["depth"])])


def test_mesh_trainer_trains_it_and_fetches_the_counters():
    """The normal path: ``MeshTrainer`` -> ``SPMDEngine`` step -> fused loss;
    the per-expert counters come out of the step's state, are fetched with the
    loss at each epoch's end and reach the history, the run log and the
    metrics registry. The trainer knows them by path only; ``moe_tokens``
    lays them out by layer."""
    from distkeras_tpu.models.lm import moe_tokens
    from distkeras_tpu.observability import trace, training_metrics

    m = dict(M, dtype="bfloat16")
    trainer = _mesh_trainer(m, num_epoch=3)
    trainer.train(_rows(m))
    losses = trainer.get_history().losses()
    assert len(losses) == 12 and losses[-1] < losses[0]
    per_epoch = [r["counters"] for r in trainer.get_history() if "counters" in r]
    assert len(per_epoch) == 3
    assert sorted(per_epoch[0]) == [f"blocks_{i}/moe/moe_tokens" for i in range(m["depth"])]
    for counts in per_epoch:                       # 4 steps x 4 rows x 128 tokens a layer
        tokens = moe_tokens(counts)
        assert tokens.shape == (m["depth"], m["experts"])
        assert tokens.sum(1).tolist() == [4 * 4 * 128] * m["depth"]
    total = moe_tokens(trainer.counters_)
    assert np.array_equal(total, np.sum([moe_tokens(c) for c in per_epoch], axis=0))
    # the run's own entries are the log's last (it keeps 4096, and a worker
    # that ran other files first has filled it: no index into it holds)
    logged = [e for e in trace.run_log() if e["name"] == "train.counters"][-3:]
    assert [e["args"]["epoch"] for e in logged] == [0, 1, 2]
    assert logged[1]["args"]["counts"] == per_epoch[1]
    text = training_metrics(total).to_prometheus()
    assert 'dk_train_moe_tokens_total{expert="0",layer="0"}' in text.replace(
        'layer="0",expert="0"', 'expert="0",layer="0"')
    # the state that comes back holds the run's totals
    assert np.array_equal(_state_tokens(trainer.trained_nt_, m), total)
    assert moe_tokens({}) is None and moe_tokens(None) is None


def test_a_resumed_run_counts_only_its_own_epochs(tmp_path):
    """A run resumed from a checkpoint takes the checkpoint's counters as
    seen: its history and ``counters_`` hold the tokens of the epochs it
    trained itself, and the state goes on from the checkpoint's totals."""
    from distkeras_tpu.models.lm import moe_tokens

    m = dict(M, dtype="bfloat16")
    first = _mesh_trainer(m, num_epoch=2, checkpoint_dir=str(tmp_path))
    first.train(_rows(m))
    saved = _state_tokens(first.trained_nt_, m)
    assert saved.sum(1).tolist() == [2 * 4 * 4 * 128] * m["depth"]
    again = _mesh_trainer(m, num_epoch=3, checkpoint_dir=str(tmp_path), resume=True)
    again.train(_rows(m))
    records = [r for r in again.get_history() if "counters" in r]
    assert [r["epoch"] for r in records] == [2]
    epoch = moe_tokens(records[0]["counters"])
    assert epoch.sum(1).tolist() == [4 * 4 * 128] * m["depth"]
    assert np.array_equal(moe_tokens(again.counters_), epoch)
    assert np.array_equal(_state_tokens(again.trained_nt_, m), saved + epoch)
