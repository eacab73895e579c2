"""Weights from the seed, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the same function feeds the
program (as the parameter tree ``TransformerLM`` expects, one leaf a layer) and
the plain reference (the same leaves stacked over layers). A leaf's bits depend
on the seed, the leaf's name and its layer, nothing else.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number; the driver's seeds pass 2**31. Made outside
    any jit and passed in as data, so that no program holds a seed as a constant
    and every seed finds the same programs in the compile cache."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def block_leaves(m) -> dict:
    """name -> (shape, kind, fan_in) of one block's leaves."""
    d, dh, f = m["dim"], m["dim"] // m["heads"], m["ffn"]
    qkv = (m["heads"] + 2 * (m["kv_heads"] or m["heads"])) * dh
    return {
        "ln1_g": ((d,), "scale", 0), "ln1_b": ((d,), "bias", 0),
        "qkv_w": ((d, qkv), "matrix", d), "qkv_b": ((qkv,), "bias", 0),
        "out_w": ((m["heads"] * dh, d), "matrix", m["heads"] * dh),
        "out_b": ((d,), "bias", 0),
        "ln2_g": ((d,), "scale", 0), "ln2_b": ((d,), "bias", 0),
        "up_w": ((d, f), "matrix", d), "up_b": ((f,), "bias", 0),
        "down_w": ((f, d), "matrix", f), "down_b": ((d,), "bias", 0),
    }


def top_leaves(m) -> dict:
    d = m["dim"]
    return {"embed": ((m["vocab"], d), "matrix", d),
            "lnf_g": ((d,), "scale", 0), "lnf_b": ((d,), "bias", 0)}


def _leaf(key, shape, kind, fan_in, dtype):
    """Matrices N(0, 1/fan_in), so that activations and logits stay of order
    one; biases N(0, 0.02^2) and scales 1 + N(0, 0.02^2), so that no leaf is a
    constant the program could drop unseen."""
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        x = x * fan_in ** -0.5
    elif kind == "bias":
        x = x * 0.02
    else:
        x = 1.0 + x * 0.02
    return x.astype(dtype)


def _name_key(key, name):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _dtype(name, served: str):
    """``served`` float32: every leaf float32 (training's master weights).
    bfloat16: matrices, the embedding and their biases bfloat16, LayerNorm's
    two vectors float32."""
    return jnp.float32 if name.startswith("ln") else jnp.dtype(served)


def stacked(m, key, served: str = "float32") -> dict:
    """The reference's layout: block leaves stacked ``[depth, ...]``. ``key``
    from :func:`seed_key`."""
    out = {n: _leaf(_name_key(key, n), s, k, f, _dtype(n, served))
           for n, (s, k, f) in top_leaves(m).items()}
    for n, (s, k, f) in block_leaves(m).items():
        out[n] = _leaf(_name_key(key, n), (m["depth"],) + s, k, f, _dtype(n, served))
    return out


# the program's names for the same leaves (distkeras_tpu.models.lm)
_PROGRAM_BLOCK = {
    "ln1_g": ("ln_attn", "scale"), "ln1_b": ("ln_attn", "bias"),
    "qkv_w": ("qkv", "kernel"), "qkv_b": ("qkv", "bias"),
    "out_w": ("attn_out", "kernel"), "out_b": ("attn_out", "bias"),
    "ln2_g": ("ln_mlp", "scale"), "ln2_b": ("ln_mlp", "bias"),
    "up_w": ("mlp_up", "kernel"), "up_b": ("mlp_up", "bias"),
    "down_w": ("mlp_down", "kernel"), "down_b": ("mlp_down", "bias"),
}
BLOCK_NAMES = tuple(_PROGRAM_BLOCK)
_PROGRAM_TOP = {"embed": ("embed", "embedding"),
                "lnf_g": ("ln_head", "scale"), "lnf_b": ("ln_head", "bias")}


def program_tree(m, key, served: str = "float32") -> dict:
    """The same leaves as ``TransformerLM``'s parameter tree: layer ``i`` of a
    block leaf is slice ``i`` of the stacked one. Under ``jit`` the compiler
    makes each slice where it is wanted and holds no stacked copy beside them."""
    flat = stacked(m, key, served)
    tree: dict = {}
    for n, (mod, leaf) in _PROGRAM_TOP.items():
        tree.setdefault(mod, {})[leaf] = flat[n]
    for n, (mod, leaf) in _PROGRAM_BLOCK.items():
        for i in range(m["depth"]):
            tree.setdefault(f"blocks_{i}", {}).setdefault(mod, {})[leaf] = flat[n][i]
    return tree


def from_program_tree(tree, depth: int, leaf=lambda x: x) -> dict:
    """Per-leaf values of a tree in the program's layout, under the reference's
    names: block leaves as lists over layers."""
    out = {n: leaf(tree[mod][lf]) for n, (mod, lf) in _PROGRAM_TOP.items()}
    for n, (mod, lf) in _PROGRAM_BLOCK.items():
        out[n] = [leaf(tree[f"blocks_{i}"][mod][lf]) for i in range(depth)]
    return out


def leaf_norms(m, tree: dict) -> dict:
    """L2 norm of every logical leaf of ``tree`` (the reference's names; a block
    leaf either stacked ``[depth, ...]`` or a list over layers), one norm a
    layer. The fused ``qkv`` leaves count as three, the query's, the key's and
    the value's part: a key's bias has no gradient under softmax, and inside
    the fused leaf no rule on a leaf's gradient could tell it apart."""
    dh = m["dim"] // m["heads"]
    cuts = (m["heads"] * dh, (m["heads"] + (m["kv_heads"] or m["heads"])) * dh)

    def norm(a):
        a = a.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim))))

    out = {}
    for name, leaf in tree.items():
        if name not in BLOCK_NAMES:
            out[name] = norm(leaf[None])
            continue
        a = jnp.stack(leaf) if isinstance(leaf, (list, tuple)) else leaf
        if name.startswith("qkv"):
            q, k, v = jnp.split(a, cuts, axis=-1)
            out.update({name + ".q": norm(q), name + ".k": norm(k), name + ".v": norm(v)})
        else:
            out[name] = norm(a)
    return out
