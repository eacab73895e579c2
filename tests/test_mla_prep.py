"""``ops.mla_prep`` (latent attention's q, k and v from their projections'
results to the flash kernels' head-major operands: rotary on the 64 rope
columns, the one shared key laid beside every head's part, one kernel each
way) in interpret mode against the ``jnp`` lines it replaces
(``models.lm.latent_qkv`` and the launcher's ``bh``): alone, handing all three
operands to ``flash_attention(qk_major=True, heads=)``, inside
``LatentAttention``, and under a declared mesh. The native lowering at the
cell's shapes is ``tests/test_tpu_compile.py``'s, results on the chip
``chip_smoke.py``'s.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models.lm import (LatentAttention, MlaDims, latent_qkv,
                                     rope_angles)
from distkeras_tpu.ops import kernel_impl
from distkeras_tpu.ops.flash_attention import flash_attention
from distkeras_tpu.ops.mla_prep import mla_prep

DN, DR, DV = 128, 64, 128
PARTS = ("q", "k", "v", "dq", "dkv", "dk_rope")


def _angles(S):
    return jnp.asarray(rope_angles(S, DR, 1e6))


def _chain(q, kv, k_rope, heads):
    """The plain lines, handed over as the kernel hands them."""
    B, S, _ = q.shape
    return tuple(jnp.moveaxis(a, 2, 1).reshape(B * heads, S, -1)
                 for a in latent_qkv(q, kv, k_rope, _angles(S), heads, DN))


def _operands(key, B, S, heads, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return [(2.0 * jax.random.normal(k, (B, S, w))).astype(dtype)
            for k, w in zip(ks, (heads * (DN + DR), heads * (DN + DV), DR))]


@functools.lru_cache(maxsize=None)
def _alone(dtype, heads):
    """Results and gradients of the kernels and of the lines at 3 row tiles
    of 128, 2 batch rows, ``heads`` heads."""
    B, S = 2, 384
    x = _operands(jax.random.PRNGKey(heads), B, S, heads, dtype)
    g = tuple(jax.random.normal(k, (B * heads, S, w)).astype(dtype)
              for k, w in zip(jax.random.split(jax.random.PRNGKey(1), 3),
                              (DN + DR, DN + DR, DV)))
    out = {}
    for name, fn in (
            ("kernel", lambda *x: mla_prep(*x, _angles(S), heads=heads,
                                           nope=DN)),
            ("chain", lambda *x: _chain(*x, heads))):
        o, pull = jax.vjp(fn, *x)
        assert [a.shape for a in o] == [a.shape for a in g]
        assert all(a.dtype == dtype for a in o)
        out[name] = {k: np.asarray(v, np.float32)
                     for k, v in zip(PARTS, o + pull(g))}
    # the shared key's gradient from the same numbers in float32
    f32 = lambda t: [a.astype(jnp.float32) for a in t]
    exact = jax.vjp(lambda *x: _chain(*x, heads), *f32(x))[1](tuple(f32(g)))
    out["chain"]["dk_rope_exact"] = np.asarray(exact[2])
    return out["kernel"], out["chain"]


# 16 heads: two groups of 8 a row tile (the shared key's gradient is carried
# over them); 6: one group of 6; 2: one pair. 3 row tiles: an odd count
@pytest.mark.parametrize("what", PARTS)
@pytest.mark.parametrize("heads", [16, 6, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernel_agrees_with_the_lines(dtype, heads, what):
    got, want = _alone(dtype, heads)
    exact = want["dk_rope_exact"]
    got, want = got[what], want[what]
    assert np.abs(want).max() > 0.1
    if what in ("v", "dkv"):                  # copies
        np.testing.assert_array_equal(got, want)
    elif dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    elif what == "dk_rope":
        # the kernel sums the heads in float32 and rounds ONCE: one bf16
        # rounding from the float32 answer, and nearer to it than the lines,
        # which round the sum over heads before they rotate it
        np.testing.assert_allclose(got, exact, rtol=2.0 ** -8, atol=1e-5)
        assert np.abs(got - exact).max() <= np.abs(want - exact).max()
    else:
        # both round float32 once: where they differ it is by one bf16 ulp,
        # and nearly everywhere they are the same number
        assert np.mean(got != want) < 0.02
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=1e-6)


@pytest.mark.parametrize("heads", [4, 2])
def test_q_k_and_v_reach_the_flash_kernels_head_major(heads):
    """All three operands from the kernels into ``flash_attention(
    qk_major=True, heads=)`` and their gradients back, against the lines into
    the launcher's own copies: the same flash kernels on the same numbers, at
    q and k 192 wide and v 128, causal, under a key mask."""
    B, S = 2, 256
    x = _operands(jax.random.PRNGKey(7), B, S, heads)
    g = jax.random.normal(jax.random.PRNGKey(8), (B, S, heads, DV))
    mask = jnp.ones((B, S)).at[1, -40:].set(0.0)

    def fused(*x):
        q, k, v = mla_prep(*x, _angles(S), heads=heads, nope=DN)
        return flash_attention(q, k, v, causal=True, key_mask=mask,
                               qk_major=True, heads=heads)

    def plain(*x):
        q, k, v = latent_qkv(*x, _angles(S), heads, DN)
        return flash_attention(q, k, v, causal=True, key_mask=mask)

    got, pull = jax.vjp(fused, *x)
    want, pull_plain = jax.vjp(plain, *x)
    assert got.shape == (B, S, heads, DV)
    for name, a, b in zip(("out", "dq", "dkv", "dk_rope"),
                          (got,) + pull(g), (want,) + pull_plain(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                                   atol=5e-4, err_msg=name)


def _sublayer(nope, attn_impl, heads=2):
    z = MlaDims(qk_nope_dim=nope, qk_rope_dim=DR, v_dim=nope, kv_rank=32)
    return LatentAttention(64, heads, z, jnp.float32, attn_impl)


def _sublayer_and_gradients(module, params, x):
    def loss(params, x):
        y = module.apply({"params": params}, x)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y
    (_, y), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(params, x)
    return y, grads


def _program(module, params, x):
    return str(jax.make_jaxpr(
        lambda p, x: _sublayer_and_gradients(module, p, x))(params, x))


def test_the_sublayer_runs_the_kernel_where_it_fits_and_the_lines_elsewhere():
    """``LatentAttention`` under ``attn_impl="flash"``: at 128 + 64 / 128 its
    program holds ``mla_prep_fwd`` and ``mla_prep_bwd`` (a q call and a k / v
    call each) and agrees, gradients and all, with the reference path (the
    lines into XLA attention) on the same weights; at parts of 64, an odd
    count of heads, or rows no tile divides, ``kernel_impl`` says ``"xla"``
    and the program holds no such kernel."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 64))
    flash, ref = _sublayer(128, "flash"), _sublayer(128, "reference")
    params = ref.init(jax.random.PRNGKey(0), x)["params"]
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size),
                                              p.shape), params)
    text = _program(flash, params, x)
    assert text.count("name=mla_prep_fwd") == 2
    assert text.count("name=mla_prep_bwd") == 2
    assert "mla_prep" not in _program(ref, params, x)
    with jax.default_matmul_precision("highest"):
        got = _sublayer_and_gradients(flash, params, x)
        want = _sublayer_and_gradients(ref, params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                                   atol=5e-4)

    for module in (_sublayer(64, "flash"), _sublayer(128, "flash", heads=3)):
        small = module.init(jax.random.PRNGKey(0), x)["params"]
        text = _program(module, small, x)
        assert "mla_prep" not in text and "name=flash_fwd" in text
    short = x[:, :200]
    text = _program(flash, flash.init(jax.random.PRNGKey(0), short)["params"],
                    short)
    assert "mla_prep" not in text and "flash_fwd" not in text


CELL = dict(S=8192, nope=128, rope=64, v=128, heads=32)


@pytest.mark.parametrize("impl, dims, want", [
    ("pallas", CELL, "pallas"),
    ("pallas", dict(S=384, nope=256, rope=64, v=128), "pallas"),
    ("pallas", dict(CELL, nope=64), "xla"),     # half a lane tile with no position
    ("pallas", dict(CELL, rope=128), "xla"),    # a rotary part of a whole tile
    ("pallas", dict(CELL, v=192), "xla"),
    ("pallas", dict(CELL, heads=3), "xla"),     # no whole pairs of heads
    ("pallas", dict(CELL, S=200), "xla"),       # rows no tile divides
    ("xla", CELL, "xla"),
    ("auto", CELL, "xla"),                      # no chip here
])
def test_kernel_impl_answers_for_mla_prep(impl, dims, want):
    assert kernel_impl("mla_prep", impl, **dims) == want


@pytest.mark.parametrize("change, match", [
    (dict(heads=3), "not 3 heads"),
    (dict(heads=1), "even count of heads"),
    (dict(nope=64), "got heads=2, nope=64, rope=128"),
    (dict(rows=200), "S=200"),
    (dict(key=32), r"the shared key's 32"),
    (dict(angles=16), r"angles \(256, 16\)"),
    (dict(impl="mosaic"), "unknown mla_prep impl"),
])
def test_mla_prep_refuses_what_it_cannot_tile(change, match):
    S, heads = change.get("rows", 256), 2
    q, kv = jnp.zeros((1, S, heads * 192)), jnp.zeros((1, S, heads * 256))
    k_rope = jnp.zeros((1, S, change.get("key", DR)))
    angles = jnp.zeros((S, change.get("angles", DR // 2)))
    with pytest.raises(ValueError, match=match):
        if "impl" in change:
            kernel_impl("mla_prep", change["impl"], **CELL)
        mla_prep(q, kv, k_rope, angles, heads=change.get("heads", heads),
                 nope=change.get("nope", DN))


def test_the_kernels_run_per_device_under_a_declared_mesh():
    """Like the flash kernels: inside a jit over several chips each device
    runs the kernels on its own batch rows (a ``shard_map`` each way), the
    tables whole on every one."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distkeras_tpu.ops import kernel_mesh

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    B, S, heads = 4, 128, 2
    x = _operands(jax.random.PRNGKey(3), B, S, heads)
    g = [jax.random.normal(k, (B * heads, S, w))
         for k, w in zip(jax.random.split(jax.random.PRNGKey(4), 3),
                         (DN + DR, DN + DR, DV))]

    def scalar(out):
        return sum(jnp.sum(a * b) for a, b in zip(out, g))

    def declared(*x):
        with kernel_mesh(mesh, "dp"):
            return jax.value_and_grad(lambda *x: scalar(mla_prep(
                *x, _angles(S), heads=heads, nope=DN)), (0, 1, 2))(*x)

    rows = NamedSharding(mesh, P("dp"))
    got = jax.jit(declared)(*(jax.device_put(a, rows) for a in x))
    want = jax.value_and_grad(
        lambda *x: scalar(_chain(*x, heads)), (0, 1, 2))(*x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                                   atol=5e-4)
    assert str(jax.make_jaxpr(declared)(*x)).count("shard_map") >= 4
