"""Operations and parameters of a ZAYA1 configuration, as functions of its sizes.

``m`` is a configuration file's ``model`` group (``"block": "zaya"``). Counts
are what the mathematics requires: a multiply-add is two operations, causal
attention does half a square, recomputation under ``remat`` is not counted,
and an expert costs only the tokens routed to it.
"""

from __future__ import annotations


def cca_params(m) -> int:
    """CCA's four projections: q, k, the value's two halves, out."""
    d, dh, H, K = m["dim"], m["head_dim"], m["heads"], m["kv_heads"]
    return d * H * dh + 2 * d * K * dh + H * dh * d


def conv_params(m) -> int:
    """The two causal convolutions on q and on k: a token passes each weight
    once, as it does a matrix's."""
    dh, H, K = m["head_dim"], m["heads"], m["kv_heads"]
    k0, k1 = m["conv_kernels"]
    return (k0 + k1 * dh) * (H + K) * dh


def router_params(m) -> int:
    R = m["router_dim"]
    return m["dim"] * R + 2 * R * R + R * m["experts"]


def expert_params(m) -> int:
    """One SwiGLU expert: gate, up, down."""
    return 3 * m["dim"] * m["expert_dim"]


def vector_params(m) -> int:
    """A layer's norms, temperatures, residual scalings, router vectors."""
    return 2 * m["dim"] + m["kv_heads"] + 8 * m["dim"] + 2 * m["router_dim"] + m["experts"]


def table_params(m) -> int:
    return m["vocab"] * m["dim"]


def layer_params(m, experts: int) -> int:
    return (cca_params(m) + conv_params(m) + router_params(m) + vector_params(m)
            + experts * expert_params(m))


def param_count(m, experts: int | None = None) -> int:
    """Every parameter with ``experts`` experts a layer (default: the held
    ones), the tied table and the head's norm."""
    held = m["experts_held"][1] if experts is None else experts
    return m["depth"] * layer_params(m, held) + table_params(m) + m["dim"]


def active_params(m) -> int:
    """What one token passes outside the table: a layer's attention,
    convolutions, router, vectors and ONE expert."""
    return m["depth"] * layer_params(m, 1)


def scores_flops_forward(m, length: int) -> float:
    """QK^T and PV of one token in a causal sequence of ``length``, all layers."""
    return m["depth"] * 4.0 * (length + 1) / 2.0 * m["heads"] * m["head_dim"]


def dense_flops_per_token(m, length: int) -> float:
    """Forward and backward of what EVERY token passes: 6 a weight of CCA, its
    convolutions, the router and the tied head, and 3 x the causal scores."""
    every = m["depth"] * (cca_params(m) + conv_params(m) + router_params(m)) + table_params(m)
    return 6.0 * every + 3.0 * scores_flops_forward(m, length)


def expert_flops_per_routed_token(m) -> float:
    """Forward and backward of one token through one held expert."""
    return 6.0 * expert_params(m)


def train_flops_per_token(m, length: int, held_visits_per_token: float) -> float:
    """``held_visits_per_token``: (token, layer) pairs routed to a held expert,
    over tokens; from the program's counters, not assumed."""
    return (dense_flops_per_token(m, length)
            + held_visits_per_token * expert_flops_per_routed_token(m))


def held_tokens(m, tokens) -> "list[float]":
    """Of ``tokens`` ``[layers][experts]``, each layer's sum over held experts."""
    first, count = m["experts_held"]
    return [float(sum(row[first:first + count])) for row in tokens]


def grouped_call(m, rows: float, wide: bool, itemsize: int = 2):
    """``(operations, bytes)`` of one grouped product over the held experts'
    stacked weights with ``rows`` routed tokens: ``wide`` is the gate-and-up
    projection ``[held, d, 2 f]``, else the down projection ``[held, f, d]``.
    Its forward, its gradient to the rows and its gradient to the weights do
    the same operations and move the same arrays: the weights (or their
    gradient) once, the rows in and the rows out (or both in)."""
    d, f, held = m["dim"], m["expert_dim"], m["experts_held"][1]
    a, b = (d, 2 * f) if wide else (f, d)
    return 2.0 * rows * a * b, itemsize * (held * a * b + rows * (a + b))
