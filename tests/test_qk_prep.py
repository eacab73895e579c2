"""``ops.qk_prep`` (a head's RMSNorm, rotary and the move into the flash
kernels' head-major layout as one kernel each way) in interpret mode against
the ``jnp`` chain it replaces (``models.lm.head_norm_rope``, a cast, the
launcher's ``bh``): alone, handing q and k to ``flash_attention(qk_major=True)``,
inside ``QKNormAttention``, and under a declared mesh. The native lowering at
the cell's shapes is ``tests/test_tpu_compile.py``'s, results on the chip
``chip_smoke.py``'s.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models.lm import (QKNormAttention, SdarDims, head_norm_rope,
                                     rope_angles_at)
from distkeras_tpu.ops import kernel_impl
from distkeras_tpu.ops.flash_attention import flash_attention
from distkeras_tpu.ops.qk_prep import qk_prep

EPS, D = 1e-6, 128


def _twice(S):
    """Angles of ``S`` rows that stand at ``0 .. S/2 - 1`` twice."""
    row = np.arange(S // 2)
    return jnp.asarray(rope_angles_at(np.concatenate([row, row]), D, 1e6))


def _chain(x, w, angles, heads):
    """The plain chain, handed over as the kernel hands it."""
    B, S, _ = x.shape
    y = head_norm_rope(x, w, angles, heads, EPS).astype(x.dtype)
    return jnp.moveaxis(y, 2, 1).reshape(B * heads, S, D)


@functools.lru_cache(maxsize=None)
def _alone(dtype, heads):
    """Result and gradients of the kernel and of the chain at 3 row tiles of
    128 (positions 0..191 twice), 2 batch rows, ``heads`` heads."""
    B, S = 2, 384
    ks = jax.random.split(jax.random.PRNGKey(heads), 3)
    x = (2.0 * jax.random.normal(ks[0], (B, S, heads * D))).astype(dtype)
    w = 1.0 + 0.2 * jax.random.normal(ks[1], (D,))
    g = jax.random.normal(ks[2], (B * heads, S, D)).astype(dtype)
    out = {}
    for name, fn in (("kernel", lambda x, w: qk_prep(
            x, w, _twice(S), heads=heads, eps=EPS)),
            ("chain", lambda x, w: _chain(x, w, _twice(S), heads))):
        o, pull = jax.vjp(fn, x, w)
        assert o.dtype == dtype and o.shape == (B * heads, S, D)
        out[name] = {k: np.asarray(v, np.float32)
                     for k, v in zip(("out", "dx", "dw"), (o,) + pull(g))}
    return out["kernel"], out["chain"]


# 16 heads: two tiles of 8; 6: one tile of 6; 1: a key-value head alone
@pytest.mark.parametrize("what", ["out", "dx", "dw"])
@pytest.mark.parametrize("heads", [16, 6, 1])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernel_agrees_with_the_chain(dtype, heads, what):
    got, want = _alone(dtype, heads)
    got, want = got[what], want[what]
    assert np.abs(want).max() > 0.1
    if dtype == "float32" or what == "dw":
        # the flash kernels' tolerances: the forward is the order of a
        # 128-lane sum, the gradients another way round the same derivative
        rtol, atol = (2e-4, 2e-5) if what == "out" else (5e-3, 5e-4)
    else:
        # both round float32 once: where they differ it is by one bf16 ulp,
        # and nearly everywhere they are the same number
        rtol, atol = 2.0 ** -7, 1e-6
        assert np.mean(got != want) < 0.02
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)


@pytest.mark.parametrize("heads, kv_heads", [(8, 1), (2, 2)])
def test_q_and_k_reach_the_flash_kernels_head_major(heads, kv_heads):
    """q and k from the kernel into ``flash_attention(qk_major=True)`` and
    their gradients back, against the chain into the launcher's own copies:
    the same kernels on the same numbers, under the block-diffusion mask over
    a noised and a clean copy of rows of 128."""
    B, S = 1, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    xq = jax.random.normal(ks[0], (B, S, heads * D))
    xk = jax.random.normal(ks[1], (B, S, kv_heads * D))
    v = jax.random.normal(ks[2], (B, S, kv_heads, D))
    wq, wk = (1.0 + 0.2 * jax.random.normal(k, (D,)) for k in ks[3:5])
    g = jax.random.normal(ks[5], (B, S, heads, D))
    angles = _twice(S)

    def fused(xq, xk, v, wq, wk):
        q = qk_prep(xq, wq, angles, heads=heads, eps=EPS)
        k = qk_prep(xk, wk, angles, heads=kv_heads, eps=EPS)
        return flash_attention(q, k, v, block_diffusion=4, qk_major=True)

    def plain(xq, xk, v, wq, wk):
        q = head_norm_rope(xq, wq, angles, heads, EPS)
        k = head_norm_rope(xk, wk, angles, kv_heads, EPS)
        return flash_attention(q, k, v, block_diffusion=4)

    got, pull = jax.vjp(fused, xq, xk, v, wq, wk)
    want, pull_plain = jax.vjp(plain, xq, xk, v, wq, wk)
    for name, a, b in zip(("out", "dxq", "dxk", "dv", "dwq", "dwk"),
                          (got,) + pull(g), (want,) + pull_plain(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                                   atol=5e-4, err_msg=name)


def _sublayer(head_dim, attn_impl, dtype=jnp.float32):
    z = SdarDims(head_dim=head_dim, experts=4, experts_per_token=2,
                 expert_dim=16, block_length=4)
    return QKNormAttention(256, 2, 1, z, dtype, attn_impl)


def _sublayer_and_gradients(module, params, x):
    def loss(params, x):
        y = module.apply({"params": params, "counters": {
            "first_block": jnp.zeros((4, x.shape[-1]))}}, x)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y
    (_, y), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(params, x)
    return y, grads


def test_the_sublayer_runs_the_kernel_where_it_fits_and_the_chain_elsewhere():
    """``QKNormAttention`` under ``attn_impl="flash"``: at heads of 128 its
    program holds ``qk_prep_fwd`` and ``qk_prep_bwd`` and agrees, gradients
    and all, with the reference path (the chain into XLA attention) on the
    same weights; at heads of 64, or rows no tile divides, ``kernel_impl``
    says ``"xla"`` and the program holds no such kernel."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 256))
    flash, ref = _sublayer(128, "flash"), _sublayer(128, "reference")
    params = ref.init(jax.random.PRNGKey(0), x)["params"]
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size),
                                              p.shape), params)
    assert kernel_impl("qk_prep", "pallas", S=256, D=128) == "pallas"
    text = str(jax.make_jaxpr(
        lambda p, x: _sublayer_and_gradients(flash, p, x))(params, x))
    assert text.count("qk_prep_fwd") == 2 and text.count("qk_prep_bwd") == 2
    assert "qk_prep" not in str(jax.make_jaxpr(
        lambda p, x: _sublayer_and_gradients(ref, p, x))(params, x))
    with jax.default_matmul_precision("highest"):
        got = _sublayer_and_gradients(flash, params, x)
        want = _sublayer_and_gradients(ref, params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                                   atol=5e-4)

    narrow = _sublayer(64, "flash")
    assert kernel_impl("qk_prep", "pallas", S=256, D=64) == "xla"
    small = narrow.init(jax.random.PRNGKey(0), x)["params"]
    text = str(jax.make_jaxpr(
        lambda p, x: _sublayer_and_gradients(narrow, p, x))(small, x))
    assert "qk_prep" not in text and "flash_fwd" in text


@pytest.mark.parametrize("impl, dims, want", [
    ("pallas", dict(S=8192, D=128), "pallas"),
    ("pallas", dict(S=384, D=256), "pallas"),
    ("pallas", dict(S=8192, D=64), "xla"),      # half a lane row a head
    ("pallas", dict(S=200, D=128), "xla"),      # rows no tile divides
    ("xla", dict(S=8192, D=128), "xla"),
    ("auto", dict(S=8192, D=128), "xla"),       # no chip here
])
def test_kernel_impl_answers_for_qk_prep(impl, dims, want):
    assert kernel_impl("qk_prep", impl, **dims) == want


def test_qk_prep_refuses_what_it_cannot_tile():
    x, w = jnp.zeros((1, 256, 128)), jnp.ones((64,))
    with pytest.raises(ValueError, match="multiple of 128"):
        qk_prep(x, w, jnp.zeros((256, 32)), heads=2, eps=EPS)
    with pytest.raises(ValueError, match="not 3 heads"):
        qk_prep(x, w, jnp.zeros((256, 32)), heads=3, eps=EPS)
    with pytest.raises(ValueError, match=r"angles \(256, 32\)"):
        qk_prep(x, jnp.ones((128,)), jnp.zeros((256, 32)), heads=1, eps=EPS)
    with pytest.raises(ValueError, match="unknown qk_prep impl"):
        kernel_impl("qk_prep", "mosaic", S=256, D=128)


def test_the_kernel_runs_per_device_under_a_declared_mesh():
    """Like the flash kernels: inside a jit over several chips each device
    runs the kernel on its own batch rows (a ``shard_map`` each way), the
    weight and the tables whole on every one."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distkeras_tpu.ops import kernel_mesh

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    B, S, heads = 4, 128, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (B, S, heads * D))
    w = 1.0 + 0.2 * jax.random.normal(ks[1], (D,))
    g = jax.random.normal(ks[2], (B * heads, S, D))
    angles = _twice(S)

    def declared(x, w):
        with kernel_mesh(mesh, "dp"):
            return jax.value_and_grad(lambda x, w: jnp.sum(qk_prep(
                x, w, angles, heads=heads, eps=EPS) * g), (0, 1))(x, w)

    got = jax.jit(declared)(
        jax.device_put(x, NamedSharding(mesh, P("dp"))), w)
    want = jax.value_and_grad(
        lambda x, w: jnp.sum(_chain(x, w, angles, heads) * g), (0, 1))(x, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                                   atol=5e-4)
    assert str(jax.make_jaxpr(declared)(x, w)).count("shard_map") >= 2


# -- the flash launchers every cell shares ---------------------------------------


def _primitives(jaxpr, out):
    """Primitives of ``jaxpr`` counted, through the launchers' own jits and
    not into a kernel's body."""
    for eqn in jaxpr.eqns:
        name = str(eqn.primitive)
        out[name] = out.get(name, 0) + 1
        if name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    _primitives(getattr(inner, "jaxpr", inner), out)
    return out


#: what ``flash_attention``'s forward and backward ran at PR 31, counted on
#: that tree: three kernels, the copies into and out of the head-major
#: layout (q, k, v forward; q, k, v, dO, o and dq, dk, dv backward; the
#: result), ``delta``; the key mask or the band's statistics as columns add
#: broadcasts. Since PR 36 also the two names a rematted block keeps the
#: forward's results by (``checkpoint_name``: no operation in the program)
_PARENTS = {"jit": 2, "mul": 1, "name": 2, "pallas_call": 3, "reduce_sum": 1,
            "reshape": 12, "slice": 1, "squeeze": 1, "transpose": 12}


@pytest.mark.parametrize("heads, kv_heads, head, mask, broadcasts", [
    (4, 4, 64, dict(causal=True), 4),            # xglm-564m.train's kind
    (4, 2, 128, dict(causal=True), 4),           # zaya1-8b.train's
    (2, 2, 64, dict(causal=True, key_mask=True), 8),
    (4, 2, 128, dict(block_diffusion=4), 2),     # any other block-diffusion caller
    (4, 4, (192, 128), dict(causal=True), 2),    # latent attention's two widths
])
def test_the_launchers_other_callers_run_what_they_ran(heads, kv_heads, head,
                                                       mask, broadcasts):
    """``qk_major`` and ``heads`` are decided while tracing: a caller that
    asks for neither gets the parent's program, operation for operation; one
    that asks for the first loses the six copies of q, k, dq and dk and nothing
    else (sdar-30b-a3b.train's call); with both, v's two and dv's go too and
    no copy of q, k, v, dq, dk or dv is left: the result's, dO's and the saved
    result's three stay."""
    head, v_head = head if isinstance(head, tuple) else (head, head)
    q = jnp.zeros((2, 256, heads, head))
    k = jnp.zeros((2, 256, kv_heads, head))
    v = jnp.zeros((2, 256, kv_heads, v_head))
    mask = dict(mask)
    if mask.pop("key_mask", False):
        mask["key_mask"] = jnp.ones((2, 256))

    def count(q, k, v, **major):
        def both(q, k, v):
            o, pull = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, **major, **mask), q, k, v)
            return (o,) + pull(o)
        return _primitives(jax.make_jaxpr(both)(q, k, v).jaxpr, {})

    want = dict(_PARENTS, broadcast_in_dim=broadcasts)
    assert count(q, k, v) == want
    major = lambda x: jnp.moveaxis(x, 2, 1).reshape(-1, 256, x.shape[-1])
    assert count(major(q), major(k), v, qk_major=True) == dict(
        want, transpose=6, reshape=6)
    assert count(major(q), major(k), major(v), qk_major=True,
                 heads=heads) == dict(want, transpose=3, reshape=3)
