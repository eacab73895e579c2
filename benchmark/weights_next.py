"""Weights of a hybrid linear-attention expert configuration (``"block":
"next"``) from the seed, by ``benchmark/weights.py``'s rule: matrices N(0,
1/fan_in) (the convolution's fan-in its taps), zero-centred norm weights N(0,
0.02^2), the gated norm's weight 1 + N(0, 0.02^2), ``A_log = log U(1, 16)``,
``dt_bias`` 1.

One function makes every leaf; the program's tree (``TransformerLM`` with
``next=NextDims(...)``) and the reference's layout (a block leaf a list over
the layers that HAVE it) are two views of it. Layers are of two kinds: every
``full_attention_interval``-th layer has the gated attention leaves
(``a_*``), the others the Gated DeltaNet leaves (``g_*``); every layer has the
expert sublayer's. A leaf's bits depend on the seed, the leaf's name, its
layer among ALL layers and, for an expert's matrix, the expert's number among
ALL the router's experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import _leaf, _name_key, seed_key  # noqa: F401  (seed_key: the drivers')
from benchmark.weights_zaya import _get, _put, held  # noqa: F401  (held: the drivers')


def layers_of(m, which: str) -> range | list:
    """The layers that have a leaf of kind ``which``: ``"all"``, ``"gdn"``
    (Gated DeltaNet) or ``"attn"`` (gated full attention)."""
    every = m["full_attention_interval"]
    full = [i for i in range(m["depth"]) if (i + 1) % every == 0]
    return {"all": range(m["depth"]), "attn": full,
            "gdn": [i for i in range(m["depth"]) if i not in full]}[which]


def block_leaves(m) -> dict:
    """name -> (shape, kind, fan_in, the program's path, which layers have it);
    the two expert leaves' shapes are one expert's."""
    d, H, K, dh = m["dim"], m["heads"], m["kv_heads"], m["head_dim"]
    Hk, Hv, dk, dv = (m["linear_key_heads"], m["linear_value_heads"], m["linear_key_dim"],
                      m["linear_value_dim"])
    E, F, Fs = m["experts"], m["expert_dim"], m["shared_dim"]
    conv = Hk * 2 * dk + Hv * dv
    vec = lambda n, path, which, kind="centred": ((n,), kind, 0, path, which)
    mat = lambda a, b, path, which: ((a, b), "matrix", a, path, which)
    return {
        "g_ln": vec(d, ("gdn", "ln", "scale"), "gdn"),
        "g_qkvz": mat(d, 2 * Hk * dk + 2 * Hv * dv, ("gdn", "qkvz", "kernel"), "gdn"),
        "g_ba": mat(d, 2 * Hv, ("gdn", "ba", "kernel"), "gdn"),
        "g_conv": ((m["conv_kernel"], conv), "matrix", m["conv_kernel"], ("gdn", "conv"), "gdn"),
        "g_alog": vec(Hv, ("gdn", "A_log"), "gdn", "a_log"),
        "g_dtb": vec(Hv, ("gdn", "dt_bias"), "gdn", "one"),
        "g_norm": vec(dv, ("gdn", "norm"), "gdn", "scale"),
        "g_out": mat(Hv * dv, d, ("gdn", "out", "kernel"), "gdn"),
        "a_ln": vec(d, ("attn", "ln", "scale"), "attn"),
        "a_q": mat(d, 2 * H * dh, ("attn", "q", "kernel"), "attn"),
        "a_k": mat(d, K * dh, ("attn", "k", "kernel"), "attn"),
        "a_v": mat(d, K * dh, ("attn", "v", "kernel"), "attn"),
        "a_qn": vec(dh, ("attn", "q_norm"), "attn"),
        "a_kn": vec(dh, ("attn", "k_norm"), "attn"),
        "a_out": mat(H * dh, d, ("attn", "out", "kernel"), "attn"),
        "ln2_g": vec(d, ("moe", "ln", "scale"), "all"),
        "wr": mat(d, E, ("moe", "router", "kernel"), "all"),
        "sh_in": mat(d, 2 * Fs, ("moe", "shared_in", "kernel"), "all"),
        "sh_out": mat(Fs, d, ("moe", "shared_out", "kernel"), "all"),
        "sgate": mat(d, 1, ("moe", "shared_gate", "kernel"), "all"),
        "ex_in": mat(d, 2 * F, ("moe", "experts_in"), "all"),
        "ex_out": mat(F, d, ("moe", "experts_out"), "all"),
    }


EXPERT_LEAVES = ("ex_in", "ex_out")


def top_leaves(m) -> dict:
    d, V = m["dim"], m["vocab"]
    return {"embed": ((V, d), "matrix", d, ("embed", "embedding")),
            "lnf_g": ((d,), "centred", 0, ("ln_head", "scale")),
            "head": ((d, V), "matrix", d, ("lm_head", "kernel"))}


def _make(key, shape, kind, fan_in):
    """One leaf, float32: ``_leaf``'s matrices and scales, a zero-centred
    norm weight N(0, 0.02^2), ``log U(1, 16)`` or ones."""
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    return _leaf(key, shape, "bias" if kind == "centred" else kind, fan_in, jnp.float32)


def _layer_key(key, name, layer):
    return jax.random.fold_in(_name_key(key, name), layer)


def layered(m, key) -> dict:
    """The reference's layout, float32: a block leaf is a list over the layers
    that have it (in order), and an expert leaf's entries are ``[held, ...]``."""
    out = {n: _make(_name_key(key, n), s, k, f) for n, (s, k, f, _) in top_leaves(m).items()}
    first, count = held(m)
    for n, (s, k, f, _, which) in block_leaves(m).items():
        keys = [_layer_key(key, n, i) for i in layers_of(m, which)]
        if n in EXPERT_LEAVES:
            out[n] = [jax.vmap(lambda e, lk=lk: _make(jax.random.fold_in(lk, e), s, k, f))(
                first + jnp.arange(count)) for lk in keys]
        else:
            out[n] = [_make(lk, s, k, f) for lk in keys]
    return out


def layer_of(m, w, i) -> dict:
    """Layer ``i``'s leaves of ``w`` (the reference's layout) by name."""
    return {n: w[n][list(layers_of(m, which)).index(i)]
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


def counters_tree(m, key=None) -> dict:
    """The model's state as a run starts: every layer's pair counter at
    nought."""
    return {"counters": {f"blocks_{i}": {"moe": {
        "moe_tokens": jnp.zeros((m["experts"],), jnp.int32)}} for i in range(m["depth"])}}


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
    shared expert's gate, up and down; the fused Gated DeltaNet projection's
    q, k, v and z columns and its b and a; the attention's query and output
    gate columns."""
    def norm(leaves):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                          for a in leaves])

    Hk, Hv, dk, dv = (m["linear_key_heads"], m["linear_value_heads"], m["linear_key_dim"],
                      m["linear_value_dim"])
    G, H, dh = Hv // Hk, m["heads"], m["head_dim"]
    halves = lambda leaf, i: [jnp.split(a, 2, -1)[i] for a in leaf]
    first, count = held(m)
    out = {}
    for name, leaf in tree.items():
        if name == "g_qkvz":
            cut = [dk, 2 * dk, 2 * dk + G * dv]
            parts = [jnp.split(a.reshape(a.shape[0], Hk, -1), cut, -1) for a in leaf]
            for j, part in enumerate(("g_q", "g_k", "g_v", "g_z")):
                out[part] = norm([p[j] for p in parts])
        elif name == "g_ba":
            by = [a.reshape(a.shape[0], Hk, 2, G) for a in leaf]
            out["g_b"], out["g_a"] = norm([a[:, :, 0] for a in by]), norm([a[:, :, 1] for a in by])
        elif name == "a_q":
            out["a_q"] = norm([a.reshape(a.shape[0], H, 2 * dh)[..., :dh] for a in leaf])
            out["a_gate"] = norm([a.reshape(a.shape[0], H, 2 * dh)[..., dh:] for a in leaf])
        elif name == "sh_in":
            out["sh_gate"], out["sh_up"] = norm(halves(leaf, 0)), norm(halves(leaf, 1))
        elif name == "sh_out":
            out["sh_down"] = norm(leaf)
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
