"""Weights of a ZAYA1 configuration from the seed, by ``benchmark/weights.py``'s
rule: matrices N(0, 1/fan_in), scales 1 + N(0, 0.02^2), offsets N(0, 0.02^2).

One function makes every leaf; the program's tree (``TransformerLM`` with
``zaya=ZayaDims(...)``) and the reference's layout (a block leaf a list over
layers) are two views of it. A leaf's bits depend on the seed, the leaf's
name, its layer and, for an expert's matrix, the expert's number among ALL the
router's experts: holding experts 8-15 in place of 0-7 gives other matrices.

The router's balancing bias (``rbias``, N(0, 0.02^2) from the seed) is no
parameter: no gradient reaches it, and every training step balances it on its
own tokens. The reference's layout carries it beside the parameters; in the
program it lies in the model's state (``counters/blocks_<i>/moe/router_bias``,
:func:`counters_tree`), and the per-leaf norms leave it out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import _leaf, _name_key, seed_key  # noqa: F401  (seed_key: the drivers')


def held(m) -> tuple:
    first, count = m["experts_held"]
    return int(first), int(count)


def block_leaves(m) -> dict:
    """name -> (shape, kind, fan_in, the program's path) of one block's leaves;
    the two expert leaves' shapes are one expert's."""
    d, dh, H, K = m["dim"], m["head_dim"], m["heads"], m["kv_heads"]
    R, E, F = m["router_dim"], m["experts"], m["expert_dim"]
    k0, k1 = m["conv_kernels"]
    vec = lambda kind, path, n=d: ((n,), kind, 0, path)
    return {
        "ln1_g": vec("scale", ("cca", "ln", "scale")),
        "wq": ((d, H * dh), "matrix", d, ("cca", "q", "kernel")),
        "wk": ((d, K * dh), "matrix", d, ("cca", "k", "kernel")),
        "wv_a": ((d, K * dh // 2), "matrix", d, ("cca", "v_now", "kernel")),
        "wv_b": ((d, K * dh // 2), "matrix", d, ("cca", "v_prev", "kernel")),
        "cq0": ((k0, H * dh), "matrix", k0, ("cca", "conv_q0")),
        "cq1": ((k1, H, dh, dh), "matrix", k1 * dh, ("cca", "conv_q1")),
        "ck0": ((k0, K * dh), "matrix", k0, ("cca", "conv_k0")),
        "ck1": ((k1, K, dh, dh), "matrix", k1 * dh, ("cca", "conv_k1")),
        "tau": vec("scale", ("cca", "tau"), K),
        "wo": ((H * dh, d), "matrix", H * dh, ("cca", "out", "kernel")),
        "a1": vec("scale", ("cca", "res_scale")), "b1": vec("bias", ("cca", "res_bias")),
        "g1": vec("scale", ("cca", "out_scale")), "e1": vec("bias", ("cca", "out_bias")),
        "ln2_g": vec("scale", ("moe", "ln", "scale")),
        "wd": ((d, R), "matrix", d, ("moe", "router_down", "kernel")),
        "gamma": vec("scale", ("moe", "router_gamma"), R),
        "lnr_g": vec("scale", ("moe", "ln_router", "scale"), R),
        "w1": ((R, R), "matrix", R, ("moe", "router_w1", "kernel")),
        "w2": ((R, R), "matrix", R, ("moe", "router_w2", "kernel")),
        "w3": ((R, E), "matrix", R, ("moe", "router_w3", "kernel")),
        "rbias": vec("bias", ("moe", "router_bias"), E),
        "ex_in": ((d, 2 * F), "matrix", d, ("moe", "experts_in")),
        "ex_out": ((F, d), "matrix", F, ("moe", "experts_out")),
        "a2": vec("scale", ("moe", "res_scale")), "b2": vec("bias", ("moe", "res_bias")),
        "g2": vec("scale", ("moe", "out_scale")), "e2": vec("bias", ("moe", "out_bias")),
    }


EXPERT_LEAVES = ("ex_in", "ex_out")
STATE_LEAVES = ("rbias",)       # in the reference's layout, not in the program's parameters


def top_leaves(m) -> dict:
    d = m["dim"]
    return {"embed": ((m["vocab"], d), "matrix", d, ("embed", "embedding")),
            "lnf_g": ((d,), "scale", 0, ("ln_head", "scale"))}


def _layer_keys(m, key, name):
    return [jax.random.fold_in(_name_key(key, name), i) for i in range(m["depth"])]


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


def _put(tree, path, leaf):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = leaf


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def program_tree(m, key) -> dict:
    """The same leaves as ``TransformerLM``'s parameter tree."""
    return to_program_tree(m, layered(m, key))


def to_program_tree(m, flat: dict) -> dict:
    tree: dict = {}
    for n, (_, _, _, path) in top_leaves(m).items():
        _put(tree, path, flat[n])
    for n, (_, _, _, path) in block_leaves(m).items():
        for i in range(m["depth"]):
            if n not in STATE_LEAVES:
                _put(tree, (f"blocks_{i}",) + path, flat[n][i])
    return tree


def counters_tree(m, key) -> dict:
    """The model's state as a run starts: every layer's counter at nought and
    its router's balancing bias the seed's (``layered``'s ``rbias``)."""
    shape, kind, fan_in, _ = block_leaves(m)["rbias"]
    return {"counters": {f"blocks_{i}": {"moe": {
        "moe_tokens": jnp.zeros((m["experts"],), jnp.int32),
        "router_bias": _leaf(lk, shape, kind, fan_in, jnp.float32)}}
        for i, lk in enumerate(_layer_keys(m, key, "rbias"))}}


def from_program_tree(m, tree) -> dict:
    """A tree in the program's layout under the reference's names, block leaves
    as lists over layers."""
    out = {n: _get(tree, path) for n, (_, _, _, path) in top_leaves(m).items()}
    for n, (_, _, _, path) in block_leaves(m).items():
        if n not in STATE_LEAVES:
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
        if name in STATE_LEAVES:
            continue
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
