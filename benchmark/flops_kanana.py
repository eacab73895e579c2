"""Operations and parameters of a latent-attention expert configuration
(``"block": "mla"``: multi-head latent attention, a leading dense layer,
sigmoid-routed experts beside a shared expert), as functions of its sizes.

``m`` is a configuration file's ``model`` group. Counts are what the
mathematics REQUIRES: a multiply-add is two operations; attention costs the
causal (query, key) pairs, ``2 (Dk + Dv)`` a pair forward with ``Dk =
qk_nope_dim + qk_rope_dim`` and ``Dv = v_dim`` (the keys built from the latent,
not the absorbed form); an expert costs the (token, expert) pairs the
program's counters say went to a held expert; what every token passes (the
latent projections, the shared expert, the router, the dense layer, the head)
is counted for every token; recomputation under ``remat`` is not counted.
"""

from __future__ import annotations


def widths(m) -> tuple:
    """``(Dk, Dv)``: a head's width in q and k, and in v."""
    return m["qk_nope_dim"] + m["qk_rope_dim"], m["v_dim"]


def attention_params(m) -> int:
    """The attention sublayer's four projections: q, the latent and its rotary
    key, keys and values from the latent, the output."""
    d, H, R = m["dim"], m["heads"], m["kv_rank"]
    dk, dv = widths(m)
    return (d * H * dk + d * (R + m["qk_rope_dim"])
            + R * H * (m["qk_nope_dim"] + dv) + H * dv * d)


def router_params(m) -> int:
    return m["dim"] * m["experts"]


def expert_params(m) -> int:
    """One routed SwiGLU expert: gate, up, down."""
    return 3 * m["dim"] * m["expert_dim"]


def shared_params(m) -> int:
    """The shared experts: one SwiGLU of ``shared_experts * expert_dim``."""
    return m["shared_experts"] * expert_params(m)


def dense_params(m) -> int:
    """A leading layer's dense SwiGLU."""
    return 3 * m["dim"] * m["dense_dim"]


def head_params(m) -> int:
    return m["vocab"] * m["dim"]


def expert_layers(m) -> int:
    return m["depth"] - m["dense_layers"]


def param_count(m, experts: int | None = None) -> int:
    """Every parameter with ``experts`` routed experts a layer (default: the
    held ones): the layers with their norm vectors, the table, the untied head
    and its norm. The routers' balancing bias is state, not a parameter."""
    held = m["experts_held"][1] if experts is None else experts
    attn = attention_params(m) + 2 * m["dim"] + m["kv_rank"]
    sparse = attn + router_params(m) + shared_params(m) + held * expert_params(m)
    return (m["dense_layers"] * (attn + dense_params(m)) + expert_layers(m) * sparse
            + 2 * head_params(m) + m["dim"])


def causal_pairs(length: int) -> int:
    """(query, key) pairs of one head over one causal row."""
    return length * (length + 1) // 2


def step_flops(m, rows: int, length: int, held_pairs) -> dict:
    """Required forward-and-backward operations of one training step on
    ``rows`` rows of ``length`` tokens, by part. ``held_pairs``: each expert
    layer's (token, expert) pairs sent to held experts in the step, from the
    counters."""
    tokens = rows * length
    dk, dv = widths(m)
    out = {
        "projections": 2.0 * m["depth"] * tokens * attention_params(m),
        "scores": 2.0 * (dk + dv) * m["depth"] * rows * m["heads"] * causal_pairs(length),
        "shared": 2.0 * expert_layers(m) * tokens * shared_params(m),
        "dense": 2.0 * m["dense_layers"] * tokens * dense_params(m),
        "router": 2.0 * expert_layers(m) * tokens * router_params(m),
        "experts": 2.0 * expert_params(m) * float(sum(held_pairs)),
        "head": 2.0 * tokens * head_params(m),
    }
    return {part: 3.0 * flops for part, flops in out.items()}


def held_pairs(m, tokens) -> "list[float]":
    """Of ``tokens`` ``[expert layers][experts]`` ((token, expert) pairs by
    expert), each layer's sum over held experts."""
    first, count = m["experts_held"]
    return [float(sum(row[first:first + count])) for row in tokens]


# a pair's multiply-adds, in units of a column: fwd QK^T (Dk) and PV (Dv); dq
# QK^T, dO V^T (Dv) and dS K (Dk); dkv QK^T, dO V^T, P^T dO (Dv) and dS^T Q (Dk)
_FLASH_COLUMNS = {"fwd": (1, 1), "dq": (2, 1), "dkv": (2, 2)}


def flash_call_flops(m, kind: str, rows: int, length: int) -> float:
    """One call of a flash kernel over ``rows`` causal rows of ``length``, from
    the causal pairs: at 192 / 128 a pair costs ``fwd`` 2 x 320, ``dq`` 2 x
    512, ``dkv`` 2 x 640."""
    dk, dv = widths(m)
    a, b = _FLASH_COLUMNS[kind]
    return 2.0 * (a * dk + b * dv) * rows * m["heads"] * causal_pairs(length)


def flash_call_bytes(m, kind: str, rows: int, length: int, itemsize: int = 2) -> float:
    """The least one call moves: each operand read once and each result
    written once; q, k and their gradients ``Dk`` wide, v, the output, dO and
    dv ``Dv``; every head has its own keys and values."""
    dk, dv = widths(m)
    qk, vo = (rows * m["heads"] * length * w * itemsize for w in (dk, dv))
    return {"fwd": 2 * qk + 2 * vo, "dq": 3 * qk + 2 * vo, "dkv": 3 * qk + 3 * vo}[kind]
