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
``RoutedExperts``): no capacity and no ``[tokens, experts, capacity]`` array.
It is told which experts it holds, sorts the tokens (top-1) or the (token,
expert) pairs (top-k) by chosen expert and runs one grouped product a
projection over the held experts' stacked weights; under top-k only the held
pairs' rows are gathered, a chunk at a time, in a loop as long as the load.
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


def _held_order(expert, experts, total):
    """The sort behind :func:`dropless_experts`, of ``expert`` flattened:
    ``(tokens [total], held, order, sizes [count])`` — every expert's count,
    which entries go to a held expert, the stable order by held expert with
    the absent experts' entries last, and the held experts' counts."""
    first, count = experts
    flat = expert.reshape(-1)
    tokens = jnp.bincount(flat, length=total).astype(jnp.int32)
    local = flat - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count)          # absent experts sort last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    return tokens, held, order, tokens[first:first + count]


def _swiglu_groups(xs, w_in, w_out, sizes):
    """The two grouped products over rows sorted by expert: gate and up side
    by side, SiLU, down."""
    with jax.named_scope("moe_experts"):
        gate, up = jnp.split(jax.lax.ragged_dot(xs, w_in, sizes), 2, axis=-1)
        return jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_out, sizes)


def _chunk_rows(y, x, w_in, w_out, scale, token, sizes, lo):
    """``y`` (float32 ``[T, d]``, the tokens' sums so far) with what the
    sorted pairs ``lo .. lo + R - 1`` add to them: ``token`` ``[R]`` is each
    pair's token, ``scale`` ``[R]`` its weight, ``sizes`` the held experts'
    counts over ALL sorted pairs (this chunk's share of each group is cut out
    of them). The chunk's rows past the last held pair are zeros given to the
    last group, so the products run all ``R`` rows whatever the load: a
    chunk's time is its size's, not the router's."""
    R = token.shape[0]
    with jax.named_scope("moe_route"):
        ends = jnp.cumsum(sizes)
        cut = lambda at: jnp.clip(at, lo, lo + R)
        mine = (cut(ends) - cut(ends - sizes)).astype(jnp.int32)
        mine = mine.at[-1].add(R - jnp.sum(mine))
        live = (lo + jnp.arange(R) < ends[-1])[:, None]
        xs = jnp.where(live, x[token], 0)
    ys = _swiglu_groups(xs, w_in, w_out, mine)
    with jax.named_scope("moe_route"):
        ys = jnp.where(live, ys, 0).astype(jnp.float32) * scale[:, None]
        return y.at[token].add(ys)


def _later_chunks(first, token, sizes):
    """``(rows a later chunk, later chunks that hold a held pair)``: the
    first chunk (``first`` rows) always runs, the padded ``token [C, R]``'s
    only as far as the held pairs reach past it."""
    R = token.shape[1]
    return R, jnp.maximum(0, (jnp.sum(sizes) - first + R - 1) // R)


def _paired_rows(x, w_in, w_out, scale0, token0, scale, token, sizes):
    """:func:`_chunk_rows` chunk after chunk: the sorted pairs' first
    ``R0`` (``token0``, ``scale0``) and then chunks of ``R`` (``token``,
    ``scale``: ``[C, R]``). A loop whose length follows the load: the first
    chunk always runs and, sized over the expected load, mostly alone; a
    later one only if a held pair lies in it, in the forward and, chunk by
    chunk from the inputs again, in the backward, so that no more than one
    chunk's rows are ever held and a load over the first chunk costs its
    excess in small steps and not a second chunk of the first's size. A
    chunk that runs costs its rows whatever share of them is held, so under
    the first chunk's size a step's time does not follow the router."""
    first = token0.shape[0]
    R, n = _later_chunks(first, token, sizes)
    return jax.lax.fori_loop(
        0, n, lambda c, y: _chunk_rows(y, x, w_in, w_out, scale[c], token[c],
                                       sizes, first + c * R),
        _chunk_rows(jnp.zeros(x.shape, jnp.float32), x, w_in, w_out, scale0,
                    token0, sizes, 0))


_paired_experts = jax.custom_vjp(_paired_rows)


def _paired_experts_fwd(*args):
    return _paired_rows(*args), args


def _paired_experts_bwd(res, g):
    x, w_in, w_out, scale0, token0, scale, token, sizes = res
    first = token0.shape[0]
    R, n = _later_chunks(first, token, sizes)

    def pull(s, t, lo):
        _, back = jax.vjp(
            lambda x, w_in, w_out, s: _chunk_rows(
                jnp.zeros(x.shape, jnp.float32), x, w_in, w_out, s, t, sizes,
                lo), x, w_in, w_out, s)
        return back(g)

    def more(c, acc):
        dx, dw_in, dw_out, dscale = acc
        a, b, d, e = pull(scale[c], token[c], first + c * R)
        return dx + a, dw_in + b, dw_out + d, dscale.at[c].set(e)

    dx, dw_in, dw_out, dscale0 = pull(scale0, token0, 0)
    dx, dw_in, dw_out, dscale = jax.lax.fori_loop(
        0, n, more, (dx, dw_in, dw_out, jnp.zeros_like(scale)))
    return dx, dw_in, dw_out, dscale0, None, dscale, None, None


_paired_experts.defvjp(_paired_experts_fwd, _paired_experts_bwd)


def dropless_experts(x, expert, weight, w_in, w_out, *, experts, total,
                     rows=None):
    """SwiGLU experts without capacity: every token routed to a held expert
    is computed, none is dropped.

    ``x`` [T, d] tokens; ``expert`` int32 [T] or [T, k], each token's chosen
    expert(s) out of ``total``; ``weight`` float32 of the same shape, what a
    chosen expert's result is multiplied by (the router's probability; under
    top-k the renormalised one). ``experts=(first, count)``: this layer holds
    experts ``first .. first + count - 1`` — ``w_in`` ``[count, d, 2 f]``
    (gate and up side by side) and ``w_out`` ``[count, f, d]``. A (token,
    expert) pair whose expert is not held adds 0: on an ``ep`` axis that is
    another chip's part of the sum.

    Pairs are sorted by held expert (stable; absent experts' pairs last),
    each projection is ONE ``jax.lax.ragged_dot`` over the stacked weights
    (a grouped matmul kernel on TPU), and the result is put back in token
    order. Rows past the last group are masked on the way in and on the way
    out, so nothing depends on what the grouped product leaves there.

    ``[T]`` (top-1) sorts the tokens themselves: ``[T, d]`` rows through the
    products, held or not, and a gather each way. ``[T, k]`` computes only the
    pairs that are held: the sorted pairs are cut into chunks, ``rows =
    (first, later)`` (required) rows in the first and in each later one, and
    a loop runs the chunks that hold a held pair, each gathering its tokens'
    rows and adding its results into the tokens' sums. With ``first`` over
    the expected load one chunk runs, at the cost of ITS rows (all of them go
    through the products, the rows past the held pairs as zeros: the time is
    the chunk's, whatever the router sent) and not of ``T k``; a heavier load
    runs ``later`` rows more at a time and still drops nothing.

    Returns ``(y [T, d], tokens int32 [total])``: ``tokens[e]`` counts the
    pairs routed to expert ``e`` of all ``total``, held or not.
    """
    first, count = experts
    if not (0 <= first and count >= 1 and first + count <= total):
        raise ValueError(f"experts={experts!r} is not a range of {total}")
    if w_in.shape[0] != count or w_out.shape[0] != count:
        raise ValueError(
            f"experts={experts!r} but the weights hold {w_in.shape[0]} and "
            f"{w_out.shape[0]} experts")
    if expert.shape != weight.shape or expert.ndim not in (1, 2):
        raise ValueError(
            f"expert {expert.shape} and weight {weight.shape} must both be "
            f"[tokens] or [tokens, k]")
    T = x.shape[0]
    if expert.ndim == 2:
        return _dropless_pairs(x, expert, weight, w_in, w_out, experts, total,
                               rows)
    if rows is not None:
        raise ValueError("rows= cuts the pairs of a [tokens, k] routing; a "
                         "[tokens] routing sorts the tokens themselves")
    with jax.named_scope("moe_route"):
        tokens, held, order, sizes = _held_order(expert, experts, total)
        inverse = jnp.zeros((T,), jnp.int32).at[order].set(
            jnp.arange(T, dtype=jnp.int32), unique_indices=True)
        live = (jnp.arange(T) < jnp.sum(sizes))[:, None]
        xs = jnp.where(live, _permute_rows(x, order, inverse), 0)
    ys = _swiglu_groups(xs, w_in.astype(x.dtype), w_out.astype(x.dtype), sizes)
    with jax.named_scope("moe_route"):
        ys = jnp.where(live, ys, 0)
        y = _permute_rows(ys, inverse, order)
        y = y * jnp.where(held, weight, 0.0).astype(jnp.float32)[:, None]
    return y, tokens


def _dropless_pairs(x, expert, weight, w_in, w_out, experts, total, rows):
    """:func:`dropless_experts` for ``expert`` and ``weight`` ``[T, k]``."""
    T, k = expert.shape
    pairs = T * k
    if not isinstance(rows, tuple) or len(rows) != 2 or min(rows) < 1:
        raise ValueError(
            f"a [tokens, k] routing needs rows=(first, later), the sorted "
            f"pairs a chunk holds, each >= 1; got {rows!r}")
    first, later = min(int(rows[0]), pairs), int(rows[1])
    chunks = max(1, -(-(pairs - first) // later))
    with jax.named_scope("moe_route"):
        tokens, _, order, sizes = _held_order(expert, experts, total)
        order = jnp.pad(order, (0, first + chunks * later - pairs))
        token = order // k
        scale = weight.astype(jnp.float32).reshape(pairs)[order]
    y = _paired_experts(
        x, w_in.astype(x.dtype), w_out.astype(x.dtype), scale[:first],
        token[:first], scale[first:].reshape(chunks, later),
        token[first:].reshape(chunks, later), sizes)
    return y, tokens
