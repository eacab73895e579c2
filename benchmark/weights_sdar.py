"""Weights of an SDAR configuration (``"block": "sdar"``) from the seed, by
``benchmark/weights.py``'s rule: matrices N(0, 1/fan_in), norm weights
1 + N(0, 0.02^2).

One function makes every leaf; the program's tree (``TransformerLM`` with
``sdar=SdarDims(...)``) and the reference's layout (a block leaf a list over
layers) are two views of it. A leaf's bits depend on the seed, the leaf's
name, its layer and, for an expert's matrix, the expert's number among ALL the
router's experts: holding experts 16-31 in place of 0-15 gives other matrices.

The model's state (:func:`counters_tree`) holds what no gradient reaches: each
layer's (token, expert) pair counter and the last step's attention output at
the first noised block, the step count, the count of masked positions, and the
key the steps' noise is drawn from, ``noise_key``: made
from the seed here and given to the program as data, and to the reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import _leaf, _name_key, seed_key  # noqa: F401  (seed_key: the drivers')
from benchmark.weights_zaya import _get, _layer_keys, _put, held  # noqa: F401  (held: the drivers')


def block_leaves(m) -> dict:
    """name -> (shape, kind, fan_in, the program's path) of one block's leaves;
    the two expert leaves' shapes are one expert's."""
    d, dh, H, K = m["dim"], m["head_dim"], m["heads"], m["kv_heads"]
    E, F = m["experts"], m["expert_dim"]
    vec = lambda path, n=d: ((n,), "scale", 0, path)
    return {
        "ln1_g": vec(("attn", "ln", "scale")),
        "wq": ((d, H * dh), "matrix", d, ("attn", "q", "kernel")),
        "wk": ((d, K * dh), "matrix", d, ("attn", "k", "kernel")),
        "wv": ((d, K * dh), "matrix", d, ("attn", "v", "kernel")),
        "qn_g": vec(("attn", "q_norm"), dh),
        "kn_g": vec(("attn", "k_norm"), dh),
        "wo": ((H * dh, d), "matrix", H * dh, ("attn", "out", "kernel")),
        "ln2_g": vec(("moe", "ln", "scale")),
        "wr": ((d, E), "matrix", d, ("moe", "router", "kernel")),
        "ex_in": ((d, 2 * F), "matrix", d, ("moe", "experts_in")),
        "ex_out": ((F, d), "matrix", F, ("moe", "experts_out")),
    }


EXPERT_LEAVES = ("ex_in", "ex_out")


def top_leaves(m) -> dict:
    d, V = m["dim"], m["vocab"]
    return {"embed": ((V, d), "matrix", d, ("embed", "embedding")),
            "lnf_g": ((d,), "scale", 0, ("ln_head", "scale")),
            "head": ((d, V), "matrix", d, ("lm_head", "kernel"))}


def layered(m, key) -> dict:
    """The reference's layout, float32: a block leaf is a list over layers, and
    an expert leaf's entries are ``[held, ...]``. Each layer's leaf is made
    apart (its key folds the layer in), so nothing stacked is ever held."""
    out = {n: _leaf(_name_key(key, n), s, k, f, jnp.float32)
           for n, (s, k, f, _) in top_leaves(m).items()}
    first, count = held(m)
    for n, (s, k, f, _) in block_leaves(m).items():
        keys = _layer_keys(m, key, n)
        if n in EXPERT_LEAVES:
            out[n] = [jax.vmap(lambda e, lk=lk: _leaf(jax.random.fold_in(lk, e), s, k, f,
                                                      jnp.float32))(first + jnp.arange(count))
                      for lk in keys]
        else:
            out[n] = [_leaf(lk, s, k, f, jnp.float32) for lk in keys]
    return out


def program_tree(m, key) -> dict:
    """The same leaves as ``TransformerLM``'s parameter tree."""
    flat, tree = layered(m, key), {}
    for n, (_, _, _, path) in top_leaves(m).items():
        _put(tree, path, flat[n])
    for n, (_, _, _, path) in block_leaves(m).items():
        for i in range(m["depth"]):
            _put(tree, (f"blocks_{i}",) + path, flat[n][i])
    return tree


def noise_key(key):
    """The raw key a run's noise is drawn from, step by step."""
    return jax.random.key_data(_name_key(key, "bd_noise")).astype(jnp.uint32)


def counters_tree(m, key) -> dict:
    """The model's state as a run starts: every counter at nought and the
    noise's key the seed's."""
    zero = jnp.zeros((), jnp.int32)
    layers = {f"blocks_{i}": {
        "attn": {"first_block": jnp.zeros((m["block_length"], m["dim"]), jnp.float32)},
        "moe": {"moe_tokens": jnp.zeros((m["experts"],), jnp.int32)}}
        for i in range(m["depth"])}
    return {"counters": {"bd_key": noise_key(key), "bd_step": zero,
                         "bd_masked_tokens": zero, **layers}}


def from_program_tree(m, tree) -> dict:
    """A tree in the program's layout under the reference's names, block leaves
    as lists over layers."""
    out = {n: _get(tree, path) for n, (_, _, _, path) in top_leaves(m).items()}
    for n, (_, _, _, path) in block_leaves(m).items():
        out[n] = [_get(tree, (f"blocks_{i}",) + path) for i in range(m["depth"])]
    return out


def leaf_norms(m, tree: dict) -> dict:
    """L2 norm of every parameter of ``tree`` (the reference's layout), one
    norm a layer. Each held expert's gate, up and down matrix is a leaf of its
    own, named by the expert's number among all the router's."""
    def norm(leaves):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                          for a in leaves])

    first, count = held(m)
    out = {}
    for name, leaf in tree.items():
        if name not in EXPERT_LEAVES:
            out[name] = norm(leaf if name in block_leaves(m) else [leaf])
            continue
        for j in range(count):
            one = [a[j] for a in leaf]
            if name == "ex_in":
                out[f"ex_gate.{first + j}"] = norm([jnp.split(a, 2, -1)[0] for a in one])
                out[f"ex_up.{first + j}"] = norm([jnp.split(a, 2, -1)[1] for a in one])
            else:
                out[f"ex_down.{first + j}"] = norm(one)
    return out
