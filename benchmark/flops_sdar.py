"""Operations and parameters of an SDAR configuration (``"block": "sdar"``)
trained by diffusion over blocks, as functions of its sizes.

``m`` is a configuration file's ``model`` group. Counts are what the
mathematics REQUIRES: a multiply-add is two operations; attention costs the
(query, key) pairs the block-diffusion mask admits, not the tiles a kernel
computes; an expert costs the (token, expert) pairs the program's counters say
went to a held expert; the head costs the MASKED positions (the loss reads no
other); recomputation under ``remat`` is not counted; and in the LAST layer
the clean copy's queries, attention output, router and experts feed nothing
(the head reads the noised copy), so only its keys and values are counted
there.
"""

from __future__ import annotations


def attention_params(m, parts=("q", "k", "v", "o")) -> int:
    """The attention sublayer's projections named in ``parts``."""
    d, dh, H, K = m["dim"], m["head_dim"], m["heads"], m["kv_heads"]
    size = {"q": d * H * dh, "k": d * K * dh, "v": d * K * dh, "o": H * dh * d}
    return sum(size[p] for p in parts)


def router_params(m) -> int:
    return m["dim"] * m["experts"]


def expert_params(m) -> int:
    """One SwiGLU expert: gate, up, down."""
    return 3 * m["dim"] * m["expert_dim"]


def head_params(m) -> int:
    return m["vocab"] * m["dim"]


def param_count(m, experts: int | None = None) -> int:
    """Every parameter with ``experts`` experts a layer (default: the held
    ones): the layers with their four norm vectors, the table, the untied
    head and its norm."""
    held = m["experts_held"][1] if experts is None else experts
    layer = (attention_params(m) + router_params(m) + held * expert_params(m)
             + 2 * m["dim"] + 2 * m["head_dim"])
    return m["depth"] * layer + 2 * head_params(m) + m["dim"]


def admitted_pairs(length: int, block: int) -> dict:
    """The (query, key) pairs the block-diffusion mask admits in one row of
    ``length`` clean tokens, by quarter of the ``2 length`` stream: the noised
    copy's own blocks (``nn``), noised queries over earlier clean blocks
    (``nc``), clean queries over their own and earlier clean blocks (``cc``)."""
    n = length // block
    earlier = block * block * n * (n - 1) // 2
    return {"nn": length * block, "nc": earlier, "cc": earlier + length * block}


def scores_flops(m, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, key) pairs, every head."""
    return 4.0 * pairs * m["heads"] * m["head_dim"]


def step_flops(m, rows: int, length: int, held_pairs, masked: float) -> dict:
    """Required forward-and-backward operations of one training step on
    ``rows`` rows of ``length`` clean tokens, by part. ``held_pairs``: a
    layer's (token, expert) pairs sent to held experts in the step, from the
    counters (both copies; the last layer's are halved: its clean copy's
    feed nothing, and the counters do not tell the copies apart); ``masked``:
    the step's masked positions, from ``bd_masked_tokens``."""
    depth, stream = m["depth"], 2 * rows * length
    pairs = admitted_pairs(length, m["block_length"])
    every = sum(pairs.values())
    last = pairs["nn"] + pairs["nc"]
    held_pairs = list(held_pairs)
    out = {
        "projections": 2.0 * ((depth - 1) * stream * attention_params(m)
                              + rows * length * attention_params(m)
                              + rows * length * attention_params(m, ("k", "v"))),
        "router": 2.0 * router_params(m) * ((depth - 1) * stream + rows * length),
        "scores": scores_flops(m, rows * ((depth - 1) * every + last)),
        "experts": 2.0 * expert_params(m) * (sum(held_pairs[:-1]) + held_pairs[-1] / 2.0),
        "head": 2.0 * head_params(m) * masked,
    }
    return {part: 3.0 * flops for part, flops in out.items()}


def held_pairs(m, tokens) -> "list[float]":
    """Of ``tokens`` ``[layers][experts]`` ((token, expert) pairs by expert),
    each layer's sum over held experts."""
    first, count = m["experts_held"]
    return [float(sum(row[first:first + count])) for row in tokens]


# flash attention: matrix products of [rows, dh] x [dh, keys] tiles a kernel makes
_FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call_flops(m, kind: str, rows: int, length: int) -> float:
    """One call of a flash kernel over ``rows`` block-diffusion streams of
    ``2 length`` positions, from the pairs the mask admits: ``fwd`` makes QK^T
    and PV; ``dq`` QK^T, dO V^T and dS K; ``dkv`` QK^T, dO V^T, P^T dO and
    dS^T Q."""
    pairs = sum(admitted_pairs(length, m["block_length"]).values())
    return _FLASH_MATMULS[kind] * 2.0 * rows * m["heads"] * pairs * m["head_dim"]


def flash_call_bytes(m, kind: str, rows: int, length: int, itemsize: int = 2) -> float:
    """The least one call moves: each operand read once and each result
    written once, over the ``2 length`` stream."""
    q = rows * m["heads"] * 2 * length * m["head_dim"] * itemsize
    kv = rows * m["kv_heads"] * 2 * length * m["head_dim"] * itemsize
    return {"fwd": 2 * q + 2 * kv, "dq": 4 * q + 2 * kv, "dkv": 3 * q + 4 * kv}[kind]


def grouped_call(m, rows: float, wide: bool, itemsize: int = 2):
    """``(operations, bytes)`` of one grouped product over the held experts'
    stacked weights with ``rows`` held pairs: ``benchmark/flops_zaya.py``'s
    count (the same arithmetic on pairs)."""
    from benchmark import flops_zaya

    return flops_zaya.grouped_call(m, rows, wide, itemsize)
