"""Expert parallelism: a mixture-of-experts MLP over an ``ep`` mesh axis.

The reference has no expert parallelism (SURVEY.md §2b.2 — "NO"), so this is
TPU-native surplus completing the parallelism portfolio (dp/tp/pp/sp/ep).

Design follows the classic einsum MoE formulation (Shazeer et al. 2017;
Lepikhin et al. 2020 GShard): a learned gate picks ``top_k`` experts per
token; tokens are packed into per-expert capacity slots via one-hot dispatch/
combine tensors (static shapes — XLA-friendly, no dynamic gathers); expert
weights live sharded one group per device along ``ep``; and the token↔expert
exchange is ``jax.lax.all_to_all`` over ICI — the TPU-native replacement for
the host-side shuffles a CPU framework would do. Tokens beyond an expert's
capacity are dropped (contribute zero — a residual connection around the
layer carries them), exactly the GShard semantics.

Everything is differentiable: gradients flow through the combine weights
(softmax probabilities), the standard straight-through-free MoE training
path. Equality with the single-device oracle is pinned by
tests/test_expert_parallel.py on an 8-device mesh.

:func:`dropless_experts` is the decoder's expert layer (``models/lm.py``
``ZayaBlock``): no capacity and no ``[tokens, experts, capacity]`` array. It
is told which experts it holds, sorts the tokens by chosen expert and runs
one grouped product a projection over the held experts' stacked weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.parallel.mesh import put_global


def init_moe_params(rng: np.random.Generator, d_model: int, d_hidden: int,
                    num_experts: int, scale: float = 0.02) -> dict:
    """Host-side init: gate + stacked expert MLP weights ``[E, …]``."""
    rnd = lambda *s: rng.normal(0, scale, size=s).astype(np.float32)
    return {
        "gate": rnd(d_model, num_experts),
        "w1": rnd(num_experts, d_model, d_hidden),
        "b1": np.zeros((num_experts, d_hidden), np.float32),
        "w2": rnd(num_experts, d_hidden, d_model),
        "b2": np.zeros((num_experts, d_model), np.float32),
    }


def _expert_mlp(w1, b1, w2, b2, x):
    """The per-expert feed-forward: x [..., d] → [..., d]."""
    h = jax.nn.gelu(jnp.einsum("...ecd,edh->...ech", x, w1) + b1[..., None, :])
    return jnp.einsum("...ech,ehd->...ecd", h, w2) + b2[..., None, :]


def _dispatch_combine(gate_logits, num_experts: int, capacity: int,
                      top_k: int):
    """Build GShard dispatch/combine tensors for local tokens.

    ``gate_logits`` [t, E] → (dispatch [t, E, C] float 0/1,
    combine [t, E, C] float, aux_loss scalar). Slots are assigned
    choice-major (all first choices before any second choice), tokens over
    capacity are dropped.
    """
    t = gate_logits.shape[0]
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)          # [t, k]
    # renormalize the kept probabilities so combine weights sum to 1
    top_vals = top_vals / jnp.maximum(
        jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9
    )

    oh = jax.nn.one_hot(top_idx, num_experts, dtype=jnp.float32)  # [t, k, E]
    # choice-major slot ranks: flatten to [k*t, E] with choice as the slow axis
    oh_cm = jnp.moveaxis(oh, 1, 0).reshape(top_k * t, num_experts)
    ranks = jnp.cumsum(oh_cm, axis=0) - oh_cm                 # [k*t, E]
    pos_cm = jnp.sum(ranks * oh_cm, axis=-1)                  # [k*t]
    pos = jnp.moveaxis(pos_cm.reshape(top_k, t), 0, 1)        # [t, k]
    keep = (pos < capacity).astype(jnp.float32)               # [t, k]

    # pos holds exact small integers in f32; one_hot wants an integer index
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=jnp.float32)                # [t, k, C]
    # [t, k, E, C] → sum over choices
    dispatch = jnp.einsum("tke,tkc,tk->tec", oh, pos_oh, keep)
    combine = jnp.einsum(
        "tke,tkc,tk->tec", oh, pos_oh, keep * top_vals
    )

    # GShard load-balancing auxiliary loss: E · Σ_e fraction_tokens_e · mean_prob_e
    frac = jnp.mean(oh[:, 0, :], axis=0)                      # first-choice share
    mean_prob = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_mlp_reference(params, x, top_k: int = 1,
                      capacity_factor: float | None = None):
    """Single-device oracle: same math, no mesh, no all_to_all.

    ``x`` [T, d] → ([T, d], aux_loss). ``capacity_factor=None`` means
    no token is ever dropped (capacity = T).
    """
    E = params["gate"].shape[1]
    T = x.shape[0]
    cap = T if capacity_factor is None else max(
        1, int(capacity_factor * T * top_k / E)
    )
    logits = x.astype(jnp.float32) @ params["gate"]
    dispatch, combine, aux = _dispatch_combine(logits, E, cap, top_k)
    xin = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    out = _expert_mlp(params["w1"], params["b1"], params["w2"], params["b2"],
                      xin)
    return jnp.einsum("tec,ecd->td", combine, out).astype(x.dtype), aux


def _moe_shard(params, x, *, axis_name, top_k, capacity):
    """Per-device body: local gating + all_to_all expert exchange."""
    E = params["gate"].shape[1]
    logits = x.astype(jnp.float32) @ params["gate"]
    dispatch, combine, aux = _dispatch_combine(logits, E, capacity, top_k)
    xin = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    # [E, C, d] → ship each device its expert group: [E/N, N·C, d]
    xin = jax.lax.all_to_all(xin, axis_name, split_axis=0, concat_axis=1,
                             tiled=True)
    out = _expert_mlp(params["w1"], params["b1"], params["w2"], params["b2"],
                      xin)
    out = jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                             tiled=True)
    y = jnp.einsum("tec,ecd->td", combine, out).astype(x.dtype)
    return y, jax.lax.pmean(aux, axis_name)


def moe_mlp(params, x, mesh: Mesh, axis: str = "ep", top_k: int = 1,
            capacity_factor: float = 2.0):
    """Expert-parallel MoE MLP: tokens AND experts sharded over ``axis``.

    - ``params`` from :func:`init_moe_params`; expert leaves ``[E, …]`` are
      sharded over ``axis`` (``E % mesh.shape[axis] == 0``), the gate is
      replicated.
    - ``x`` [T, d] tokens, ``T % mesh.shape[axis] == 0``; sharded over
      ``axis``.
    - capacity per expert = ``capacity_factor · T_local · top_k / E`` per
      shard, the GShard convention.

    Composition: on a multi-axis mesh (e.g. ``{"dp": 2, "ep": 4}``) only
    ``axis`` is mapped manually — the other axes stay *auto*, so an outer
    GSPMD program (a dp-sharded train step) partitions the per-shard work
    over them; expert weights replicate over dp by propagation. The math is
    identical to the ``ep``-only program (pinned by
    tests/test_expert_parallel.py).

    Returns ``(y [T, d], aux_loss)`` — ``y`` matches
    :func:`moe_mlp_reference` exactly when no token overflows capacity.
    """
    N = mesh.shape[axis]
    E = params["gate"].shape[1]
    T = x.shape[0]
    if E % N:
        raise ValueError(f"{E} experts not divisible by mesh axis "
                         f"'{axis}' of size {N}")
    if T % N:
        raise ValueError(f"{T} tokens not divisible by mesh axis "
                         f"'{axis}' of size {N}")
    t_local = T // N
    capacity = max(1, int(capacity_factor * t_local * top_k / E))

    pspec = {
        "gate": P(),
        "w1": P(axis), "b1": P(axis), "w2": P(axis), "b2": P(axis),
    }
    body = functools.partial(
        _moe_shard, axis_name=axis, top_k=top_k, capacity=capacity,
    )
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P(axis)),
        out_specs=(P(axis), P()),
        check_vma=False,
        # only `axis` is manual; other mesh axes (dp) stay auto for GSPMD
        axis_names=frozenset({axis}),
    )
    params = {
        k: put_global(v, NamedSharding(mesh, pspec[k]))
        for k, v in params.items()
    }
    if not isinstance(x, jax.core.Tracer):
        # host-call placement only: inside a jitted (dp-sharded) program a
        # sharding constraint to P(axis) would pin the tokens dp-REPLICATED
        # and force an all-gather per MoE block — leave the auto axes to
        # GSPMD there (shard_map reshards the manual axis as needed)
        x = put_global(x, NamedSharding(mesh, P(axis)))
    return fn(params, x)


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation ``perm`` whose inverse is ``inverse``: the
    backward pass is the gather ``g[inverse]``, never a scatter-add."""
    return x[perm]


def _permute_rows_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_rows_bwd(res, g):
    perm, inverse = res
    return g[inverse], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def dropless_experts(x, expert, weight, w_in, w_out, *, experts, total):
    """Top-1 SwiGLU experts without capacity: every token routed to a held
    expert is computed, none is dropped.

    ``x`` [T, d] tokens; ``expert`` int32 [T], each token's chosen expert out
    of ``total``; ``weight`` float32 [T], what the chosen expert's result is
    multiplied by (the router's probability). ``experts=(first, count)``:
    this layer holds experts ``first .. first + count - 1`` — ``w_in``
    ``[count, d, 2 f]`` (gate and up side by side) and ``w_out``
    ``[count, f, d]``. A token whose expert is not held gets 0: on an ``ep``
    axis that is another chip's part of the sum.

    Tokens are sorted by held expert (stable; absent experts' tokens last),
    each projection is ONE ``jax.lax.ragged_dot`` over the stacked weights
    (a grouped matmul kernel on TPU), and the result is put back in token
    order. Rows past the last group are masked on the way in and on the way
    out, so nothing depends on what the grouped product leaves there.

    Returns ``(y [T, d], tokens int32 [total])``: ``tokens[e]`` counts the
    tokens routed to expert ``e`` of all ``total``, held or not.
    """
    first, count = experts
    if not (0 <= first and count >= 1 and first + count <= total):
        raise ValueError(f"experts={experts!r} is not a range of {total}")
    if w_in.shape[0] != count or w_out.shape[0] != count:
        raise ValueError(
            f"experts={experts!r} but the weights hold {w_in.shape[0]} and "
            f"{w_out.shape[0]} experts")
    T = x.shape[0]
    with jax.named_scope("moe_route"):
        tokens = jnp.bincount(expert, length=total).astype(jnp.int32)
        local = expert - first
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, count)          # absent experts sort last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.zeros((T,), jnp.int32).at[order].set(
            jnp.arange(T, dtype=jnp.int32), unique_indices=True)
        sizes = tokens[first:first + count]
        live = (jnp.arange(T) < jnp.sum(sizes))[:, None]
        xs = jnp.where(live, _permute_rows(x, order, inverse), 0)
    with jax.named_scope("moe_experts"):
        gate, up = jnp.split(jax.lax.ragged_dot(xs, w_in.astype(x.dtype), sizes),
                             2, axis=-1)
        ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_out.astype(x.dtype),
                                sizes)
    with jax.named_scope("moe_route"):
        ys = jnp.where(live, ys, 0)
        y = _permute_rows(ys, inverse, order)
        y = y * jnp.where(held, weight, 0.0).astype(jnp.float32)[:, None]
    return y, tokens
