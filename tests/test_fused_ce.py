"""Chunked fused linear+cross-entropy (``ops/fused_ce.py``).

Oracle: the unfused path — materialize ``hidden @ kernel + bias`` and take
``sparse_softmax_cross_entropy`` (masked form when a mask is given). The
fused op must match it in value AND in the gradients w.r.t. hidden, kernel,
and bias, across chunk sizes that do and don't divide the row count.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops import losses
from distkeras_tpu.ops.fused_ce import chunked_softmax_cross_entropy


def _oracle(hidden, labels, kernel, bias, mask=None):
    logits = (
        jnp.dot(hidden, kernel, preferred_element_type=jnp.float32)
        .astype(jnp.float32)
    )
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if mask is None:
        return losses.sparse_softmax_cross_entropy(labels, logits)
    return losses.masked_sparse_softmax_cross_entropy(labels, logits, mask)


def _problem(rng, n=37, d=16, v=101, dtype=np.float32):
    h = rng.normal(size=(n, d)).astype(dtype)
    w = (rng.normal(size=(d, v)) * 0.3).astype(dtype)
    b = (rng.normal(size=(v,)) * 0.1).astype(np.float32)
    y = rng.integers(0, v, n).astype(np.int32)
    return h, y, w, b


@pytest.mark.parametrize("chunk", [8, 16, 37, 64])
def test_matches_unfused_f32(rng, chunk):
    h, y, w, b = _problem(rng)
    fused = chunked_softmax_cross_entropy(h, y, w, b, chunk=chunk)
    ref = _oracle(h, y, w, b)
    np.testing.assert_allclose(float(fused), float(ref), rtol=1e-6)


def test_gradients_match_unfused_f32(rng):
    h, y, w, b = _problem(rng)

    gf = jax.grad(
        lambda h, w, b: chunked_softmax_cross_entropy(h, y, w, b, chunk=16),
        argnums=(0, 1, 2),
    )(h, w, b)
    gr = jax.grad(
        lambda h, w, b: _oracle(h, y, w, b), argnums=(0, 1, 2)
    )(h, w, b)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-5, atol=1e-7)


def test_masked_rows_are_excluded(rng):
    h, y, w, b = _problem(rng, n=24)
    mask = (rng.uniform(size=24) > 0.3).astype(np.float32)
    fused = chunked_softmax_cross_entropy(h, y, w, b, mask=mask, chunk=7)
    ref = _oracle(h, y, w, b, mask=mask)
    np.testing.assert_allclose(float(fused), float(ref), rtol=1e-6)
    # a masked row's hidden state must get zero gradient
    gh = jax.grad(
        lambda h: chunked_softmax_cross_entropy(h, y, w, b, mask=mask,
                                                chunk=7)
    )(jnp.asarray(h))
    dead = np.asarray(gh)[mask == 0.0]
    assert np.all(dead == 0.0)


def test_bias_free_head_matches_and_differentiates(rng):
    h, y, w, _ = _problem(rng, n=21)
    fused = chunked_softmax_cross_entropy(h, y, w, None, chunk=8)
    ref = _oracle(h, y, w, None)
    np.testing.assert_allclose(float(fused), float(ref), rtol=1e-6)
    gf = jax.grad(
        lambda h, w: chunked_softmax_cross_entropy(h, y, w, None, chunk=8),
        argnums=(0, 1),
    )(h, w)
    gr = jax.grad(lambda h, w: _oracle(h, y, w, None), argnums=(0, 1))(h, w)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-5, atol=1e-7)


def test_mask_gradient_matches_unfused(rng):
    """mask is a differentiable loss weight: d(loss)/d(mask) must equal the
    autodiff of the unfused masked mean (nll_i/D − T·[Σm>1]/D²)."""
    h, y, w, b = _problem(rng, n=19)
    mask = rng.uniform(0.2, 1.0, size=19).astype(np.float32)
    gm_f = jax.grad(
        lambda m: chunked_softmax_cross_entropy(h, y, w, b, mask=m, chunk=5)
    )(jnp.asarray(mask))
    gm_r = jax.grad(lambda m: _oracle(h, y, w, b, mask=m))(jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(gm_f), np.asarray(gm_r),
                               rtol=2e-5, atol=1e-7)


def test_bf16_params_close_to_f32_oracle(rng):
    h, y, w, b = _problem(rng, n=32, d=32, v=64)
    h16, w16 = h.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    fused = chunked_softmax_cross_entropy(h16, y, w16, b, chunk=16)
    ref = _oracle(jnp.asarray(h), y, jnp.asarray(w), b)
    np.testing.assert_allclose(float(fused), float(ref), rtol=3e-2)
    gh = jax.grad(
        lambda x: chunked_softmax_cross_entropy(x, y, w16, b, chunk=16)
    )(h16)
    assert gh.dtype == jnp.bfloat16
    gr = jax.grad(lambda x: _oracle(x, y, jnp.asarray(w), b))(jnp.asarray(h))
    rel = np.abs(np.asarray(gh, np.float32) - np.asarray(gr))
    assert float(rel.max()) <= 5e-2 * float(np.abs(np.asarray(gr)).max()) + 1e-4


# every edge of the backward's vocabulary tiling (``_vocab_tiles``; the width
# aimed at is 4096 or the forward's budget, whichever is larger): several
# tiles with padded columns, V a multiple of the tile, N not a multiple of
# ``chunk``, one tile (``N <= chunk``, or V no wider than the floor), with
# and without a bias, both dtypes
_TILING_CASES = [
    # n, d, v, chunk, bias, dtype, (tiles, width) that _vocab_tiles gives
    (37, 16, 101, 8, True, "float32", (1, 101)),      # V <= floor: one tile
    (37, 16, 101, 64, False, "float32", (1, 101)),    # N <= chunk too
    (96, 8, 9000, 16, True, "float32", (3, 3072)),    # 216 padded columns
    (96, 8, 9000, 16, False, "bfloat16", (3, 3072)),
    (90, 8, 9000, 16, True, "bfloat16", (3, 3072)),   # N % chunk != 0
    (32, 8, 12300, 16, False, "float32", (2, 6272)),  # budget over the floor
    (64, 8, 13000, 8, True, "float32", (4, 3328)),    # 312 padded columns
    (64, 8, 13000, 8, False, "bfloat16", (4, 3328)),
    (50, 8, 13000, 7, True, "bfloat16", (4, 3328)),   # 8 ragged row chunks
    (48, 8, 8192, 24, True, "float32", (2, 4096)),    # V = tiles x width
    (16, 8, 4099, 16, True, "float32", (1, 4099)),    # N == chunk, wide V
]


@pytest.mark.parametrize("n,d,v,chunk,use_bias,dtype,tiling", _TILING_CASES)
def test_value_and_all_gradients_across_tiling_edges(rng, n, d, v, chunk,
                                                     use_bias, dtype, tiling):
    """Loss and the gradients of hidden, kernel, bias and mask against the
    unfused oracle (float32 operands); a label in the last real column and
    masked rows in every case; the case reaches the edge it names."""
    from distkeras_tpu.ops.fused_ce import _vocab_tiles

    assert _vocab_tiles(n, v, chunk) == tiling
    h, y, w, b = _problem(rng, n=n, d=d, v=v)
    y[0] = y[n - 1] = v - 1
    mask = rng.uniform(0.2, 1.0, size=n).astype(np.float32)
    mask[rng.uniform(size=n) < 0.25] = 0.0
    mask[0], mask[1] = 1.0, 0.0
    low = jnp.dtype(dtype)
    # the oracle sees the operands as the op does (rounded once), in float32
    h32 = jnp.asarray(h).astype(low).astype(jnp.float32)
    w32 = jnp.asarray(w).astype(low).astype(jnp.float32)
    bias = jnp.asarray(b) if use_bias else None

    def fused(h, w, b, m):
        return chunked_softmax_cross_entropy(h, y, w, b, mask=m, chunk=chunk)

    def oracle(h, w, b, m):
        return _oracle(h, y, w, b, mask=m)

    argnums = (0, 1, 2, 3) if use_bias else (0, 1, 3)
    lf, gf = jax.value_and_grad(fused, argnums)(
        h32.astype(low), w32.astype(low), bias, jnp.asarray(mask))
    lr, gr = jax.value_and_grad(oracle, argnums)(
        h32, w32, bias, jnp.asarray(mask))
    exact = low == jnp.float32
    np.testing.assert_allclose(float(lf), float(lr),
                               rtol=1e-6 if exact else 1e-5)
    assert gf[0].dtype == low and gf[1].dtype == low
    assert gf[0].shape == (n, d) and gf[1].shape == (d, v)
    for a, e in zip(gf, gr):
        a, e = np.asarray(a, np.float32), np.asarray(e)
        # bf16: dlogits and the written gradients are rounded to 8 bits
        tol = 1e-7 if exact else 2e-2 * float(np.abs(e).max())
        np.testing.assert_allclose(a, e, rtol=2e-5 if exact else 2e-2,
                                   atol=tol)
    assert np.all(np.asarray(gf[0], np.float32)[mask == 0.0] == 0.0)


def _budget_rule(n, v, chunk):
    """``_vocab_tiles`` as it was before PR 30: the forward's budget alone."""
    from distkeras_tpu.ops.fused_ce import _cdiv

    vb = 128 * _cdiv(_cdiv(v, max(1, _cdiv(n, chunk))), 128)
    return (1, v) if vb >= v else (_cdiv(v, vb), vb)


@pytest.mark.parametrize("chunk", [64, 256])
@pytest.mark.parametrize("v", [1000, 4096, 4097, 9000, 32784, 151936, 256008])
@pytest.mark.parametrize("n", [96, 4096, 32768])
def test_vocab_tiles_properties(n, v, chunk):
    """The tiles cover V with under 128 padded columns a tile and none wholly
    padded; a tile is whole 128-lane groups unless it is the only one; where
    V is wider than the floor a tile is wider than half of it (so above the
    962 columns at which the carry's traffic equals the product, where the
    budget rule gave 384 at ZAYA's cut); and the loop never makes more passes
    over the carry than the budget rule alone did."""
    from distkeras_tpu.ops.fused_ce import _BWD_TILE_COLUMNS, _vocab_tiles

    tiles, vb = _vocab_tiles(n, v, chunk)
    old_tiles, old_vb = _budget_rule(n, v, chunk)
    assert _BWD_TILE_COLUMNS % 128 == 0
    assert tiles * vb >= v > (tiles - 1) * vb
    assert tiles * vb - v < 128 * tiles
    assert tiles <= old_tiles
    if n <= chunk or v <= _BWD_TILE_COLUMNS:
        assert (tiles, vb) == (1, v)
    if tiles == 1:
        assert vb == v
    else:
        assert vb % 128 == 0
        assert vb > max(_BWD_TILE_COLUMNS, old_vb) // 2 and vb > 962


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _count(jaxpr, *names):
    return sum(e.primitive.name in names for e in _eqns(jaxpr))


def test_backward_walks_vocabulary_tiles_and_carries_no_kernel(rng):
    """The finding of PERF.md §6 (PR 26), held structurally: the backward is
    one loop over ``_vocab_tiles`` vocabulary tiles whose only carry is the
    ``[N, D]`` float32 hidden gradient — no loop carries a kernel-shaped
    array — and the log-sum-exp comes from the forward, so a tile holds one
    logits product and the two gradient products, not a second logits pass."""
    from distkeras_tpu.ops.fused_ce import _vocab_tiles

    n, d, v, chunk = 128, 8, 9000, 16
    assert _vocab_tiles(n, v, chunk) == (3, 3072)
    # the benchmark's two cells (xglm-564m.train's head, where the budget
    # asks for the floor's width itself; zaya1-8b.train's cut, where it asks
    # for 384 columns), a head that fits one row chunk, one under the floor
    assert _vocab_tiles(16384, 256008, 256) == (63, 4096)
    assert _vocab_tiles(32768, 32784, 256) == (9, 3712)
    assert _vocab_tiles(200, 50000, 256) == (1, 50000)
    assert _vocab_tiles(512, 1000, 64) == (1, 1000)
    h, y, w, _ = _problem(rng, n=n, d=d, v=v)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda h, w: chunked_softmax_cross_entropy(h, y, w, None,
                                                   chunk=chunk),
        argnums=(0, 1)))(h, w).jaxpr
    loops = [e for e in _eqns(jaxpr) if e.primitive.name in ("scan", "while")]
    assert [e.primitive.name for e in loops] == ["scan", "scan"]
    fwd, bwd = loops
    assert fwd.params["length"] == n // chunk
    assert bwd.params["length"] == _vocab_tiles(n, v, chunk)[0]

    def carries(eqn):
        k, c = eqn.params["num_consts"], eqn.params["num_carry"]
        return [tuple(x.aval.shape) for x in eqn.invars[k:k + c]]

    assert carries(fwd) == [()]
    assert carries(bwd) == [(n, d)]
    for eqn in loops:
        for shape in carries(eqn):
            assert int(np.prod(shape)) < d * v, shape
    # d_kernel leaves the loop as its stacked output, one tile a step
    assert (3, d, 3072) in [tuple(x.aval.shape) for x in bwd.outvars]
    # one logits product in the forward; logits, d_hidden, d_kernel in a tile
    assert _count(fwd.params["jaxpr"].jaxpr, "dot_general") == 1
    assert _count(bwd.params["jaxpr"].jaxpr, "dot_general") == 3
    assert _count(jaxpr, "dot_general") == 4
    # the backward takes no log-sum-exp of its own
    assert _count(bwd.params["jaxpr"].jaxpr, "reduce_max") == 0
    # the compiled step's op_name metadata says whose operations these are
    for eqn, scope in ((fwd, "fused_ce_fwd"), (bwd, "fused_ce_bwd")):
        dots = [e for e in eqn.params["jaxpr"].jaxpr.eqns
                if e.primitive.name == "dot_general"]
        assert all(scope in str(e.source_info.name_stack) for e in dots)


def test_sharded_vocabulary_and_rows_give_the_same_gradients(rng):
    """The megatron layout shards the head over its vocabulary and data
    parallelism shards the rows: the backward slices both by tile inside one
    jit, and GSPMD must keep value and gradients what one device computes."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n, d, v, chunk = 96, 8, 9000, 16           # 3 tiles of 3072, 216 padded
    h, y, w, _ = _problem(rng, n=n, d=d, v=v)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    hs = jax.device_put(h, NamedSharding(mesh, P("dp", None)))
    ws = jax.device_put(w, NamedSharding(mesh, P(None, "tp")))

    def fused(h, w):
        return chunked_softmax_cross_entropy(h, y, w, None, chunk=chunk)

    ls, gs = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))(hs, ws)
    lr, gr = jax.value_and_grad(
        lambda h, w: _oracle(h, y, w, None), argnums=(0, 1))(h, w)
    np.testing.assert_allclose(float(ls), float(lr), rtol=1e-6)
    for a, e in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-5, atol=1e-7)


def test_shape_validation(rng):
    h, y, w, b = _problem(rng, n=8, d=4, v=11)
    with pytest.raises(ValueError, match="rows, dim"):
        chunked_softmax_cross_entropy(h[None], y, w, b)
    with pytest.raises(ValueError, match="chunk"):
        chunked_softmax_cross_entropy(h, y, w, b, chunk=0)


# -- model/trainer integration ------------------------------------------------


def _lm_pair(**kw):
    from distkeras_tpu.models.lm import transformer_lm

    cfg = dict(vocab=97, maxlen=16, dim=32, heads=4, depth=1,
               dtype=jnp.float32)
    cfg.update(kw)
    plain = transformer_lm(**cfg)
    fused = transformer_lm(fused_ce=True, ce_chunk=8, **cfg)
    return plain, fused


def test_lm_fused_loss_step_matches_plain(rng):
    from distkeras_tpu.trainers import _make_loss_step
    from distkeras_tpu.ops.losses import get_loss

    plain, fused = _lm_pair()
    assert fused.fused_losses and "sparse_softmax_cross_entropy" in \
        fused.fused_losses
    params, nt = plain.init_np(0)
    toks = rng.integers(0, 97, size=(4, 17)).astype(np.int32)
    batch = (toks[:, :-1], toks[:, 1:])
    loss_name = "sparse_softmax_cross_entropy"
    step_p = _make_loss_step(plain, get_loss(loss_name), 1,
                             loss_name=loss_name)
    step_f = _make_loss_step(fused, get_loss(loss_name), 1,
                             loss_name=loss_name)
    (lp, _), gp = jax.value_and_grad(step_p, has_aux=True)(params, nt, batch)
    (lf, _), gf = jax.value_and_grad(step_f, has_aux=True)(params, nt, batch)
    np.testing.assert_allclose(float(lf), float(lp), rtol=1e-5)
    flat_p = jax.tree.leaves(gp)
    flat_f = jax.tree.leaves(gf)
    for a, e in zip(flat_f, flat_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=5e-4, atol=1e-6)


def test_lm_trains_with_fused_ce(rng):
    from distkeras_tpu.models.lm import next_token_dataset, transformer_lm
    from distkeras_tpu.trainers import ADAG

    period = 8
    spec = transformer_lm(vocab=period, maxlen=16, dim=32, heads=4, depth=1,
                          dtype=jnp.float32, fused_ce=True, ce_chunk=64)
    # the deterministic "count up mod period" language is quickly learnable
    rows = np.stack([
        (np.arange(13) + s) % period for s in rng.integers(0, period, 256)
    ]).astype(np.int32)
    ds = next_token_dataset(rows)
    tr = ADAG(spec, loss="sparse_softmax_cross_entropy",
              worker_optimizer="adam", learning_rate=5e-3, batch_size=32,
              communication_window=2, num_epoch=6, num_workers=2, seed=0)
    tr.train(ds, shuffle=True)
    hist = [float(l) for l in tr.get_history().losses()]
    assert np.isfinite(hist).all()
    assert np.mean(hist[-2:]) < 0.5 * np.mean(hist[:2])


def test_validator_scores_through_fused_loss(rng):
    """validation_data on a fused_ce model must not materialize full logits:
    the _Validator routes through the fused fn and reports the same val_loss
    as the unfused path (accuracy is undefined for per-token labels on both
    paths)."""
    from distkeras_tpu.models.lm import next_token_dataset
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.trainers import _Validator

    plain, fused = _lm_pair()
    name = "sparse_softmax_cross_entropy"
    params, nt = plain.init_np(0)
    rows = rng.integers(0, 97, size=(11, 17)).astype(np.int32)
    ds = next_token_dataset(rows)
    v_plain = _Validator(plain, get_loss(name), ds, ["features"], "label", 4)
    v_fused = _Validator(fused, get_loss(name), ds, ["features"], "label", 4,
                         fused_loss=fused.fused_losses[name])
    r_plain = v_plain(params, nt)
    r_fused = v_fused(params, nt)
    np.testing.assert_allclose(r_fused["val_loss"], r_plain["val_loss"],
                               rtol=1e-5)
    assert "val_accuracy" not in r_fused and "val_accuracy" not in r_plain


def test_mesh_trainer_strategy_warns_fused_loss_unused():
    """Strategy engines rebuild the forward and cannot consume the fused
    loss; MeshTrainer must say so instead of silently training unfused."""
    import pytest as _pytest

    from distkeras_tpu.trainers import MeshTrainer

    _, fused = _lm_pair()
    t = MeshTrainer(fused, loss="sparse_softmax_cross_entropy",
                    mesh_shape={"pp": 8}, strategy="pipeline", batch_size=8)
    with _pytest.warns(UserWarning, match="unfused"):
        try:
            t._build_engine()
        except Exception:
            pass  # the LM isn't pipeline-compatible; the warning is the test


def test_fused_ce_through_mesh_trainer_fsdp(rng):
    """The fused loss under real parameter sharding: MeshTrainer's spmd
    strategy with fsdp consumes ModelSpec.fused_losses (loss falls; the
    fused fn reads the SHARDED lm_head params inside the global jit)."""
    from distkeras_tpu.models.lm import next_token_dataset, transformer_lm
    from distkeras_tpu.trainers import MeshTrainer

    period = 8
    spec = transformer_lm(vocab=period, maxlen=16, dim=32, heads=4, depth=1,
                          dtype=jnp.float32, fused_ce=True, ce_chunk=64)
    rows = np.stack([
        (np.arange(13) + s) % period for s in rng.integers(0, period, 256)
    ]).astype(np.int32)
    ds = next_token_dataset(rows)
    t = MeshTrainer(spec, loss="sparse_softmax_cross_entropy",
                    worker_optimizer="adam", learning_rate=5e-3,
                    mesh_shape={"dp": 8}, parameter_sharding="fsdp",
                    batch_size=32, num_epoch=6)
    t.train(ds, shuffle=True)
    losses = [r["loss"] for r in t.history.records if "loss" in r]
    assert np.isfinite(losses).all()
    assert np.mean(losses[-2:]) < 0.5 * np.mean(losses[:2])


@pytest.mark.slow  # remat+fused-ce composition; classifier remat equality pins stay fast
def test_lm_remat_gradient_and_decode_equality(rng):
    """transformer_lm(remat=True): same params tree, same gradients, same
    decode — only the backward's memory schedule changes; composes with
    fused_ce."""
    from distkeras_tpu.models import generate, transformer_lm
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.trainers import _make_loss_step

    cfg = dict(vocab=64, maxlen=32, dim=32, heads=4, depth=2,
               dtype=jnp.float32)
    plain = transformer_lm(**cfg)
    rem = transformer_lm(**cfg, remat=True)
    params, nt = plain.init_np(0)
    p2, _ = rem.init_np(0)
    assert jax.tree.structure(params) == jax.tree.structure(p2)
    toks = rng.integers(0, 64, size=(2, 17)).astype(np.int32)
    name = "sparse_softmax_cross_entropy"
    batch = (toks[:, :-1], toks[:, 1:])
    sp = _make_loss_step(plain, get_loss(name), 1, loss_name=name)
    sr = _make_loss_step(rem, get_loss(name), 1, loss_name=name)
    (lp, _), gp = jax.value_and_grad(sp, has_aux=True)(params, {}, batch)
    (lr, _), gr = jax.value_and_grad(sr, has_aux=True)(params, {}, batch)
    np.testing.assert_allclose(float(lr), float(lp), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    out_p = generate(plain, params, toks[:, :8], max_new_tokens=4)
    out_r = generate(rem, params, toks[:, :8], max_new_tokens=4)
    np.testing.assert_array_equal(out_p, out_r)

    fr = transformer_lm(**cfg, remat=True, fused_ce=True, ce_chunk=8)
    sf = _make_loss_step(fr, get_loss(name), 1, loss_name=name)
    (lf, _), gf = jax.value_and_grad(sf, has_aux=True)(params, {}, batch)
    np.testing.assert_allclose(float(lf), float(lp), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-6)
