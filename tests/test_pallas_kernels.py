"""Pallas fused-Adam kernel vs the optax oracle (interpret mode on CPU),
plus its integration through the trainer stack (vmap + scan over the kernel).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops.pallas_kernels import FusedAdamState, fused_adam
from tests.test_trainers import blobs_dataset, final_loss, model_spec


def random_tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "conv": rng.normal(size=(3, 3, 4, 8)).astype(np.float32),
        "bias": rng.normal(size=(8,)).astype(np.float32),   # tiny, pad-heavy
        "dense": rng.normal(size=(200, 33)).astype(np.float32),  # odd cols
    }


def test_fused_adam_matches_optax_over_steps():
    lr = 1e-2
    params = random_tree(0)
    fused = fused_adam(lr, interpret=True)
    oracle = optax_adam = __import__("optax").adam(lr)

    sf = fused.init(params)
    so = oracle.init(params)
    p_f = jax.tree.map(jnp.asarray, params)
    p_o = jax.tree.map(jnp.asarray, params)
    for step in range(4):
        grads = random_tree(step + 10)
        uf, sf = fused.update(grads, sf)
        uo, so = optax_adam.update(grads, so)
        for a, b in zip(jax.tree.leaves(uf), jax.tree.leaves(uo)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
        p_f = __import__("optax").apply_updates(p_f, uf)
        p_o = __import__("optax").apply_updates(p_o, uo)
    for a, b in zip(jax.tree.leaves(p_f), jax.tree.leaves(p_o)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # moments updated, not zero
    assert float(jnp.abs(jax.tree.leaves(sf.mu)[0]).sum()) > 0


def test_fused_adam_state_is_checkpointable_pytree():
    fused = fused_adam(1e-3, interpret=True)
    state = fused.init({"w": jnp.ones((4, 4))})
    from distkeras_tpu.utils import deserialize_weights, serialize_weights

    back = deserialize_weights(serialize_weights(state))
    assert isinstance(back, FusedAdamState)
    assert int(back.count) == 0


def test_fused_adam_under_vmap_matches_per_row():
    """The engine vmaps optimizer.update over the worker axis — the kernel
    must batch correctly."""
    lr = 1e-2
    fused = fused_adam(lr, interpret=True)
    W = 4
    params = {"w": jnp.arange(W * 24, dtype=jnp.float32).reshape(W, 24) / 10}
    grads = {"w": jnp.ones((W, 24), jnp.float32) * 0.3}
    state = jax.vmap(fused.init)(params)
    u_batched, _ = jax.vmap(fused.update)(grads, state)
    for i in range(W):
        pi = {"w": params["w"][i]}
        gi = {"w": grads["w"][i]}
        ui, _ = fused.update(gi, fused.init(pi))
        np.testing.assert_allclose(np.asarray(u_batched["w"][i]),
                                   np.asarray(ui["w"]), rtol=1e-5, atol=1e-7)


def test_trainer_with_fused_adam_learns_on_mesh():
    from distkeras_tpu import ADAG

    ds = blobs_dataset(n=2048)
    t = ADAG(model_spec(), loss="sparse_softmax_cross_entropy",
             worker_optimizer="fused_adam", learning_rate=5e-3,
             num_workers=8, batch_size=32, communication_window=2,
             num_epoch=3)
    t.train(ds, shuffle=True)
    assert final_loss(t) < 0.5, final_loss(t)


def test_fused_adam_vs_adam_trainer_equivalence():
    """Same data, same seed: fused_adam must track optax adam closely."""
    from distkeras_tpu import ADAG

    ds = blobs_dataset(n=512)
    common = dict(loss="sparse_softmax_cross_entropy", learning_rate=1e-2,
                  num_workers=4, batch_size=16, communication_window=2,
                  num_epoch=1, seed=2)
    p1 = ADAG(model_spec(), worker_optimizer="adam", **common).train(ds)
    p2 = ADAG(model_spec(), worker_optimizer="fused_adam", **common).train(ds)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# -- which implementation runs (ops.kernel_impl) ------------------------------


@pytest.mark.parametrize("op,impl,dims,native,want", [
    # a kernel asked for by name is the kernel, on any backend
    ("attention", "flash", dict(L=2048), False, "flash"),
    ("lstm_scan", "pallas", dict(B=64, H=512), False, "pallas"),
    ("q_matmul", "pallas", dict(k=8192, n=2048), False, "pallas"),
    # "auto": the kernel only natively and on tile-friendly shapes
    ("attention", "auto", dict(L=2048), True, "flash"),
    ("attention", "auto", dict(L=2048), False, "reference"),
    ("attention", "auto", dict(L=100), True, "reference"),
    ("lstm_scan", "auto", dict(B=64, H=512), True, "pallas"),
    ("lstm_scan", "auto", dict(B=64, H=512), False, "xla"),
    ("lstm_scan", "auto", dict(B=4, H=16), True, "xla"),
    # q_matmul's "auto" never looks at the backend (interpreter off-TPU)
    ("q_matmul", "auto", dict(k=2048, n=8192), False, "pallas"),
    ("q_matmul", "auto", dict(k=100, n=8192), True, "xla"),
    ("q_matmul", "auto", dict(k=16384, n=128), True, "xla"),
    # qk_prep by name is the kernel where its tiles fit and falls back where
    # they do not; "auto" also wants the chip
    ("qk_prep", "pallas", dict(S=8192, D=128), False, "pallas"),
    ("qk_prep", "pallas", dict(S=8192, D=64), True, "xla"),
    ("qk_prep", "auto", dict(S=8192, D=128), True, "pallas"),
    ("qk_prep", "auto", dict(S=8192, D=128), False, "xla"),
])
def test_kernel_impl_reports_what_runs(monkeypatch, op, impl, dims, native,
                                       want):
    """The one observable answer to "which implementation will this call
    take" — the dispatchers decide through the same resolvers, so a chip
    smoke run asserts on this instead of trusting that "auto" found the
    chip."""
    from distkeras_tpu import ops

    monkeypatch.setattr(ops, "native_kernels", lambda: native)
    assert ops.kernel_impl(op, impl, **dims) == want


def test_kernel_impl_rejects_unknown_op_and_impl():
    from distkeras_tpu import ops

    with pytest.raises(ValueError, match="unknown op"):
        ops.kernel_impl("conv", L=128)
    with pytest.raises(ValueError, match="attention impl"):
        ops.kernel_impl("attention", "warp", L=128)
    # lazy kernel modules resolve as attributes (Python 3.12's
    # `from pkg import sub` asks the package hook first)
    assert ops.quant.q_matmul is not None
    assert ops.pallas_kernels.fused_adam is fused_adam
