"""Operations and parameters of a hybrid linear-attention expert configuration
(``"block": "next"``: Gated DeltaNet layers beside gated full attention,
softmax-routed experts beside a gated shared expert), as functions of its
sizes.

``m`` is a configuration file's ``model`` group. Counts are what the
mathematics REQUIRES: a multiply-add is two operations; full attention costs
the causal (query, key) pairs, ``2 (dh + dh)`` a pair forward; the gated
delta rule costs its own recurrence, ``6 dk dv`` a token and value head
forward and ``14 dk dv`` backward (:data:`DELTA_RULE_COLUMNS`), whatever
chunking adds; an expert costs the (token, expert) pairs the program's
counters say went to a held expert; what every token passes (the
projections, the convolution, the shared expert and its gate, the router, the
head) is counted for every token; recomputation under ``remat`` is not
counted.
"""

from __future__ import annotations

#: the delta rule's multiply-adds a token and value head, in units of ``dk
#: dv``. Forward: ``S̄ᵀk``, the write ``k eᵀ`` added to ``S̄``, ``Sᵀq``: 3.
#: Backward: ``q doᵀ`` and ``S do`` (through ``Sᵀq``); ``dS e`` and ``dSᵀ (beta
#: k)`` (through the write); ``k dpᵀ`` and ``S̄ dp`` (through ``S̄ᵀk``); ``sum(S ⊙
#: dS̄)`` (the decay's gradient): 7. Scaling a matrix by the decay is no
#: multiply-add and is not counted either way.
DELTA_RULE_COLUMNS = {"fwd": 3, "bwd": 7}


def layers(m, kind: str) -> int:
    """How many layers are gated full attention (``"attn"``) or Gated
    DeltaNet (``"gdn"``)."""
    full = m["depth"] // m["full_attention_interval"]
    return full if kind == "attn" else m["depth"] - full


def gdn_params(m) -> int:
    """The Gated DeltaNet sublayer's parameters but its input norm: the fused
    q/k/v/z and b/a projections, the convolution, ``A_log``, ``dt_bias``, the
    gated norm's weight and the output projection."""
    d, Hk, Hv = m["dim"], m["linear_key_heads"], m["linear_value_heads"]
    dk, dv = m["linear_key_dim"], m["linear_value_dim"]
    return (d * (2 * Hk * dk + 2 * Hv * dv) + d * 2 * Hv
            + m["conv_kernel"] * (2 * Hk * dk + Hv * dv) + 2 * Hv + dv + Hv * dv * d)


def gdn_products(m) -> int:
    """The Gated DeltaNet sublayer's matrix parameters a token passes."""
    d, Hk, Hv = m["dim"], m["linear_key_heads"], m["linear_value_heads"]
    dk, dv = m["linear_key_dim"], m["linear_value_dim"]
    return d * (2 * Hk * dk + 2 * Hv * dv) + d * 2 * Hv + Hv * dv * d


def attention_params(m) -> int:
    """The gated attention sublayer's four projections: q with its gate, k,
    v, the output."""
    d, H, K, dh = m["dim"], m["heads"], m["kv_heads"], m["head_dim"]
    return d * 2 * H * dh + 2 * d * K * dh + H * dh * d


def router_params(m) -> int:
    return m["dim"] * m["experts"]


def expert_params(m) -> int:
    """One routed SwiGLU expert: gate, up, down."""
    return 3 * m["dim"] * m["expert_dim"]


def shared_params(m) -> int:
    """The shared expert (a SwiGLU of ``shared_dim``) and its sigmoid gate."""
    return 3 * m["dim"] * m["shared_dim"] + m["dim"]


def head_params(m) -> int:
    return m["vocab"] * m["dim"]


def param_count(m, experts: int | None = None) -> int:
    """Every parameter with ``experts`` routed experts a layer (default: the
    held ones): the layers with their norm vectors, the table, the untied head
    and its norm."""
    d = m["dim"]
    held = m["experts_held"][1] if experts is None else experts
    sparse = d + router_params(m) + shared_params(m) + held * expert_params(m)
    return (layers(m, "gdn") * (d + gdn_params(m))
            + layers(m, "attn") * (d + attention_params(m) + 2 * m["head_dim"])
            + m["depth"] * sparse + 2 * head_params(m) + d)


def causal_pairs(length: int) -> int:
    """(query, key) pairs of one head over one causal row."""
    return length * (length + 1) // 2


def gated_delta_call_flops(m, kind: str, rows: int, length: int) -> float:
    """One call of the delta rule (``"fwd"`` or ``"bwd"``) over ``rows`` rows of
    ``length`` tokens: :data:`DELTA_RULE_COLUMNS` multiply-adds of ``dk dv`` a
    token and value head."""
    return (2.0 * DELTA_RULE_COLUMNS[kind] * m["linear_key_dim"] * m["linear_value_dim"]
            * rows * length * m["linear_value_heads"])


def gated_delta_call_bytes(m, kind: str, rows: int, length: int, itemsize: int = 2) -> float:
    """The least one call moves, each once at its dtype (``itemsize`` for q, k,
    v, o and their gradients, float32 for the decay and the write strength):
    forward q, k (key heads), v, g, beta, o (value heads); backward dO, dq,
    dk, dv, dg, dbeta."""
    tokens = rows * length
    qk = 2 * tokens * m["linear_key_heads"] * m["linear_key_dim"] * itemsize
    vo = 2 * tokens * m["linear_value_heads"] * m["linear_value_dim"] * itemsize
    return qk + vo + 2 * tokens * m["linear_value_heads"] * 4


def step_flops(m, rows: int, length: int, held_pairs) -> dict:
    """Required forward-and-backward operations of one training step on
    ``rows`` rows of ``length`` tokens, by part. ``held_pairs``: each layer's
    (token, expert) pairs sent to held experts in the step, from the
    counters."""
    tokens = rows * length
    dh = m["head_dim"]
    n_gdn, n_attn = layers(m, "gdn"), layers(m, "attn")
    conv = m["conv_kernel"] * (2 * m["linear_key_heads"] * m["linear_key_dim"]
                               + m["linear_value_heads"] * m["linear_value_dim"])
    out = {
        "gdn_projections": 2.0 * n_gdn * tokens * gdn_products(m),
        "gdn_conv": 2.0 * n_gdn * tokens * conv,
        "attn_projections": 2.0 * n_attn * tokens * attention_params(m),
        "scores": 2.0 * 2 * dh * n_attn * rows * m["heads"] * causal_pairs(length),
        "router": 2.0 * m["depth"] * tokens * router_params(m),
        "shared": 2.0 * m["depth"] * tokens * shared_params(m),
        "experts": 2.0 * expert_params(m) * float(sum(held_pairs)),
        "head": 2.0 * tokens * head_params(m),
    }
    out = {part: 3.0 * flops for part, flops in out.items()}
    out["delta_rule"] = n_gdn * sum(gated_delta_call_flops(m, kind, rows, length)
                                    for kind in DELTA_RULE_COLUMNS)
    return out


def held_pairs(m, tokens) -> "list[float]":
    """Of ``tokens`` ``[layers][experts]`` ((token, expert) pairs by expert),
    each layer's sum over held experts."""
    first, count = m["experts_held"]
    return [float(sum(row[first:first + count])) for row in tokens]
