"""Operations and bytes a configuration needs, as functions of its sizes.

``m`` is a configuration file's ``model`` group. Counts are what the
mathematics requires: a multiply-add is two operations, causal attention does
half a square, recomputation under ``remat`` is not counted.
"""

from __future__ import annotations


def head_dim(m) -> int:
    return m["dim"] // m["heads"]


def kv_heads(m) -> int:
    return m["kv_heads"] or m["heads"]


def layer_matmul_params(m) -> int:
    """Weights of one block's four matrices (qkv, attention out, MLP up, down)."""
    d, dh = m["dim"], head_dim(m)
    qkv = d * (m["heads"] + 2 * kv_heads(m)) * dh
    return qkv + m["heads"] * dh * d + 2 * d * m["ffn"]


def head_params(m) -> int:
    """The output head's matrix: the embedding itself when tied."""
    return m["vocab"] * m["dim"]


def matmul_params(m) -> int:
    """Every weight a token is multiplied by: the blocks' matrices and the head."""
    return m["depth"] * layer_matmul_params(m) + head_params(m)


def param_count(m) -> int:
    """All parameters: matrices, their biases, LayerNorm vectors, embedding."""
    d, dh = m["dim"], head_dim(m)
    biases = (m["heads"] + 2 * kv_heads(m)) * dh + d + m["ffn"] + d
    per_layer = layer_matmul_params(m) + biases + 4 * d
    total = m["depth"] * per_layer + 2 * d + m["vocab"] * d
    if not m["tie_embeddings"]:
        total += head_params(m) + m["vocab"]
    return total


def _span(m, length: int) -> int:
    """Keys a query at the end of ``length`` positions attends to."""
    w = m["attn_window"]
    return min(length, w) if w else length


def attention_flops_forward(m, length: int) -> float:
    """QK^T and PV over one causal sequence of ``length``, all layers."""
    w = _span(m, length)
    # keys seen by all queries: a triangle up to w, then a band of width w
    pairs = w * (w + 1) / 2 + (length - w) * w
    return m["depth"] * 4.0 * pairs * m["heads"] * head_dim(m)


def train_flops_per_token(m, length: int) -> float:
    """Forward and backward of one token in a sequence of ``length``: 6 a
    matrix parameter, and three times the forward attention."""
    return 6.0 * matmul_params(m) + 3.0 * attention_flops_forward(m, length) / length


def serve_flops(m, prompt_len: int, new_tokens: int) -> float:
    """Forward of one request: every prompt and output position through the
    blocks, attention over what precedes it, and the head once for each token
    put out (the prompt's last position gives the first)."""
    n = prompt_len + new_tokens - 1          # positions run through the blocks
    blocks = 2.0 * m["depth"] * layer_matmul_params(m) * n
    head = 2.0 * head_params(m) * new_tokens
    return blocks + attention_flops_forward(m, n) + head


def weight_bytes(m, itemsize: int = 2) -> int:
    """Bytes of the matrices and the embedding as served in ``itemsize``."""
    return (m["depth"] * layer_matmul_params(m) + m["vocab"] * m["dim"]) * itemsize


def kv_bytes_per_position(m, itemsize: int = 2) -> int:
    """K and V of one position over all layers."""
    return 2 * m["depth"] * kv_heads(m) * head_dim(m) * itemsize


def decode_step_bytes(m, lengths, itemsize: int = 2) -> float:
    """The least one decode step reads: every weight once, and the K/V that the
    rows' real lengths hold."""
    return weight_bytes(m, itemsize) + kv_bytes_per_position(m, itemsize) * float(sum(lengths))


# flash attention: matrix products of [L, dh] x [dh, L] tiles a kernel call makes
_FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call_flops(kind: str, rows: int, heads: int, length: int, dh: int,
                     window=None) -> float:
    """One call of a flash kernel over ``rows`` causal sequences: ``fwd`` makes
    QK^T and PV; ``dq`` makes QK^T, dO V^T and dS K; ``dkv`` makes QK^T, dO V^T,
    P^T dO and dS^T Q."""
    w = min(length, window) if window else length
    pairs = w * (w + 1) / 2 + (length - w) * w
    return _FLASH_MATMULS[kind] * 2.0 * rows * heads * pairs * dh


def flash_call_bytes(kind: str, rows: int, heads: int, kvh: int, length: int,
                     dh: int, itemsize: int = 2) -> float:
    """The least one call moves: each operand read once and each result written
    once (q, o, do, dq at ``heads``; k, v, dk, dv at ``kvh``)."""
    q = rows * heads * length * dh * itemsize
    kv = rows * kvh * length * dh * itemsize
    return {"fwd": 2 * q + 2 * kv,          # q,k,v in; o out
            "dq": 4 * q + 2 * kv,           # q,o,do in; k,v in; dq out
            "dkv": 3 * q + 4 * kv}[kind]    # q,o,do in; k,v in; dk,dv out
