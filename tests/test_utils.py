import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import utils


def _tree():
    return {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones((4,))}}


def test_tree_math():
    t = _tree()
    s = utils.tree_add(t, t)
    assert np.allclose(s["a"], 2 * np.arange(6).reshape(2, 3))
    d = utils.tree_sub(s, t)
    assert np.allclose(d["b"]["c"], 1.0)
    z = utils.tree_zeros_like(t)
    assert np.allclose(z["a"], 0)
    sc = utils.tree_scale(t, 3.0)
    assert np.allclose(sc["b"]["c"], 3.0)
    n = utils.tree_to_numpy(t)
    assert isinstance(n["a"], np.ndarray)


def test_tree_stack_unstack():
    t = _tree()
    stacked = utils.tree_stack([t, utils.tree_scale(t, 2.0)])
    assert stacked["a"].shape == (2, 2, 3)
    back = utils.tree_unstack(stacked, 2)
    assert np.allclose(back[1]["b"]["c"], 2.0)
    b = utils.tree_broadcast_to_workers(t, 5)
    assert b["a"].shape == (5, 2, 3)
    assert np.allclose(b["a"][3], t["a"])


def test_weights_serde_roundtrip():
    t = {"w": np.random.default_rng(0).normal(size=(3, 5)).astype(np.float32),
         "nested": {"b": np.arange(7, dtype=np.int32)}}
    blob = utils.serialize_weights(t)
    assert isinstance(blob, bytes)
    back = utils.deserialize_weights(blob)
    assert np.array_equal(back["w"], t["w"])
    assert np.array_equal(back["nested"]["b"], t["nested"]["b"])
    assert back["nested"]["b"].dtype == np.int32


def test_uniform_weights():
    t = {"w": jnp.zeros((100, 10)), "b": jnp.zeros((10,), jnp.float32)}
    u = utils.uniform_weights(t, bounds=(-0.25, 0.25), seed=1)
    w = np.asarray(u["w"])
    assert w.min() >= -0.25 and w.max() <= 0.25
    assert w.std() > 0.05  # actually randomized


def test_count_params():
    t = _tree()
    assert utils.tree_count_params(t) == 10


@pytest.mark.parametrize("from_env", [True, False])
def test_enable_compilation_cache(tmp_path, monkeypatch, from_env):
    """The cache is placed from outside: JAX_COMPILATION_CACHE_DIR when set
    — that directory and no other — else ``.jax_cache`` at the checkout
    root, computed from the package's location. Nothing in the path moves
    between calls (no temp dir, pid or time), or a second run would never
    hit the first one's programs."""
    import pathlib

    import jax

    from distkeras_tpu.utils import enable_compilation_cache

    if from_env:
        want = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(pathlib.Path(utils.__file__).resolve().parent.parent
                   / ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert enable_compilation_cache() == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
