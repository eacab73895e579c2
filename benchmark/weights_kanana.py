"""Weights of a latent-attention expert configuration (``"block": "mla"``)
from the seed, by ``benchmark/weights.py``'s rule: matrices N(0, 1/fan_in),
norm weights 1 + N(0, 0.02^2).

One function makes every leaf; the program's tree (``TransformerLM`` with
``mla=MlaDims(...)``) and the reference's layout (a block leaf a list over the
layers that HAVE it) are two views of it. Layers are of two kinds: every layer
has the attention leaves, the leading ``dense_layers`` the dense SwiGLU's, the
others the router's, the shared expert's and the held experts'. A leaf's bits
depend on the seed, the leaf's name, its layer among ALL layers and, for an
expert's matrix, the expert's number among ALL the router's experts: holding
experts 16-31 in place of 0-15 gives other matrices.

The routers' balancing bias (``rbias``, N(0, 0.05^2) from the seed: wide
enough that choosing by ``s + b`` and weighting by ``s`` are told apart) is no
parameter: no gradient reaches it, and every training step moves it by the
sign rule. The reference carries it beside the parameters
(:func:`router_bias`); in the program it lies in the model's state
(``counters/blocks_<i>/moe/router_bias``, :func:`counters_tree`), and the
per-leaf norms leave it out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import _leaf, _name_key, seed_key  # noqa: F401  (seed_key: the drivers')
from benchmark.weights_zaya import _get, _put, held  # noqa: F401  (held: the drivers')

BIAS_STD = 0.05


def layers_of(m, which: str) -> range:
    """The layers that have a leaf of kind ``which``: ``"all"``, ``"dense"``
    (the leading ones) or ``"expert"``."""
    lead = m["dense_layers"]
    return {"all": range(m["depth"]), "dense": range(lead),
            "expert": range(lead, m["depth"])}[which]


def block_leaves(m) -> dict:
    """name -> (shape, kind, fan_in, the program's path, which layers have it);
    the two expert leaves' shapes are one expert's."""
    d, H, R = m["dim"], m["heads"], m["kv_rank"]
    dn, dr, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_dim"]
    E, F = m["experts"], m["expert_dim"]
    Fs, Fd = m["shared_experts"] * F, m["dense_dim"]
    vec = lambda path, which, n=d: ((n,), "scale", 0, path, which)
    mat = lambda a, b, path, which: ((a, b), "matrix", a, path, which)
    return {
        "ln1_g": vec(("attn", "ln", "scale"), "all"),
        "wq": mat(d, H * (dn + dr), ("attn", "q", "kernel"), "all"),
        "wkva": mat(d, R + dr, ("attn", "kv_a", "kernel"), "all"),
        "kvn_g": vec(("attn", "kv_norm", "scale"), "all", R),
        "wkvb": mat(R, H * (dn + dv), ("attn", "kv_b", "kernel"), "all"),
        "wo": mat(H * dv, d, ("attn", "out", "kernel"), "all"),
        "lnd_g": vec(("mlp", "ln", "scale"), "dense"),
        "dn_in": mat(d, 2 * Fd, ("mlp", "mlp_in", "kernel"), "dense"),
        "dn_out": mat(Fd, d, ("mlp", "mlp_out", "kernel"), "dense"),
        "ln2_g": vec(("moe", "ln", "scale"), "expert"),
        "wr": mat(d, E, ("moe", "router", "kernel"), "expert"),
        "sh_in": mat(d, 2 * Fs, ("moe", "shared_in", "kernel"), "expert"),
        "sh_out": mat(Fs, d, ("moe", "shared_out", "kernel"), "expert"),
        "ex_in": mat(d, 2 * F, ("moe", "experts_in"), "expert"),
        "ex_out": mat(F, d, ("moe", "experts_out"), "expert"),
    }


EXPERT_LEAVES = ("ex_in", "ex_out")


def top_leaves(m) -> dict:
    d, V = m["dim"], m["vocab"]
    return {"embed": ((V, d), "matrix", d, ("embed", "embedding")),
            "lnf_g": ((d,), "scale", 0, ("ln_head", "scale")),
            "head": ((d, V), "matrix", d, ("lm_head", "kernel"))}


def _layer_key(key, name, layer):
    return jax.random.fold_in(_name_key(key, name), layer)


def layered(m, key) -> dict:
    """The reference's layout, float32: a block leaf is a list over the layers
    that have it (in order), and an expert leaf's entries are ``[held, ...]``.
    Each layer's leaf is made apart, so nothing stacked is ever held."""
    out = {n: _leaf(_name_key(key, n), s, k, f, jnp.float32)
           for n, (s, k, f, _) in top_leaves(m).items()}
    first, count = held(m)
    for n, (s, k, f, _, which) in block_leaves(m).items():
        keys = [_layer_key(key, n, i) for i in layers_of(m, which)]
        if n in EXPERT_LEAVES:
            out[n] = [jax.vmap(lambda e, lk=lk: _leaf(jax.random.fold_in(lk, e), s, k, f,
                                                      jnp.float32))(first + jnp.arange(count))
                      for lk in keys]
        else:
            out[n] = [_leaf(lk, s, k, f, jnp.float32) for lk in keys]
    return out


def layer_of(m, w, i) -> dict:
    """Layer ``i``'s leaves of ``w`` (the reference's layout) by name."""
    return {n: w[n][layers_of(m, which).index(i)]
            for n, (_, _, _, _, which) in block_leaves(m).items()
            if i in layers_of(m, which)}


def program_tree(m, key) -> dict:
    """The same leaves as ``TransformerLM``'s parameter tree."""
    flat, tree = layered(m, key), {}
    for n, (_, _, _, path) in top_leaves(m).items():
        _put(tree, path, flat[n])
    for n, (_, _, _, path, which) in block_leaves(m).items():
        for at, i in enumerate(layers_of(m, which)):
            _put(tree, (f"blocks_{i}",) + path, flat[n][at])
    return tree


def router_bias(m, key) -> list:
    """The seed's balancing bias of each expert layer, float32 ``[experts]``."""
    return [BIAS_STD * jax.random.normal(_layer_key(key, "rbias", i), (m["experts"],),
                                         jnp.float32)
            for i in layers_of(m, "expert")]


def counters_tree(m, key) -> dict:
    """The model's state as a run starts: every expert layer's pair counter at
    nought and its router's balancing bias the seed's. A dense layer has no
    state."""
    return {"counters": {f"blocks_{i}": {"moe": {
        "moe_tokens": jnp.zeros((m["experts"],), jnp.int32), "router_bias": b}}
        for i, b in zip(layers_of(m, "expert"), router_bias(m, key))}}


def from_program_tree(m, tree) -> dict:
    """A tree in the program's layout under the reference's names, block leaves
    as lists over the layers that have them."""
    out = {n: _get(tree, path) for n, (_, _, _, path) in top_leaves(m).items()}
    for n, (_, _, _, path, which) in block_leaves(m).items():
        out[n] = [_get(tree, (f"blocks_{i}",) + path) for i in layers_of(m, which)]
    return out


def leaf_norms(m, tree: dict) -> dict:
    """L2 norm of every parameter of ``tree`` (the reference's layout), one
    norm a layer that has it. Leaves of their own: each held expert's gate, up
    and down matrix (named by the expert's number among all the router's); the
    shared expert's and the dense layer's gate, up and down; the query
    projection's columns with no position and its rotary ones; the latent
    projection's latent and its shared rotary key; the second projection's
    keys and its values."""
    def norm(leaves):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                          for a in leaves])

    H, R = m["heads"], m["kv_rank"]
    dn, dr, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_dim"]
    by_head = lambda a, w: a.reshape(a.shape[0], H, w)
    halves = lambda leaf, i: [jnp.split(a, 2, -1)[i] for a in leaf]
    first, count = held(m)
    out = {}
    for name, leaf in tree.items():
        if name == "wq":
            out["wq_nope"] = norm([by_head(a, dn + dr)[..., :dn] for a in leaf])
            out["wq_rope"] = norm([by_head(a, dn + dr)[..., dn:] for a in leaf])
        elif name == "wkva":
            out["wkva_c"] = norm([a[:, :R] for a in leaf])
            out["wkva_rope"] = norm([a[:, R:] for a in leaf])
        elif name == "wkvb":
            out["wkvb_k"] = norm([by_head(a, dn + dv)[..., :dn] for a in leaf])
            out["wkvb_v"] = norm([by_head(a, dn + dv)[..., dn:] for a in leaf])
        elif name in ("sh_in", "dn_in"):
            out[name[:2] + "_gate"] = norm(halves(leaf, 0))
            out[name[:2] + "_up"] = norm(halves(leaf, 1))
        elif name in ("sh_out", "dn_out"):
            out[name[:2] + "_down"] = norm(leaf)
        elif name == "ex_in":
            for j in range(count):
                one = [a[j] for a in leaf]
                out[f"ex_gate.{first + j}"] = norm(halves(one, 0))
                out[f"ex_up.{first + j}"] = norm(halves(one, 1))
        elif name == "ex_out":
            for j in range(count):
                out[f"ex_down.{first + j}"] = norm([a[j] for a in leaf])
        else:
            out[name] = norm(leaf if name in block_leaves(m) else [leaf])
    return out
