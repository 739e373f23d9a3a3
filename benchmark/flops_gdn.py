"""Operation and byte counts of one call of the gated delta rule's Pallas
kernels (``ops/pallas_gated_delta.py``), from the cell's configuration
(``linear_num_*``, ``linear_*_head_dim``, the trial's ``compute_dtype``)
and traffic (``batch_size``, ``seq_len``).

The operations are ``benchmark/flops_lm.py``'s term for the chunked rule,
a position and value head, over the call: the forward once, the backward
twice; what the backward recomputes of the forward is not counted, so a
share of the roofline can only read low.  The bytes are what the call
cannot avoid: its inputs read once and its outputs written once (q and k
float32 at the key heads, v in the compute dtype, g and beta float32, o
float32; the backward also reads the cotangent of o and writes the five
gradients, each in its input's dtype).
"""

from __future__ import annotations

DELTA_CHUNK = 64  # the chunk of flops_lm.py's count and of the kernels


def _call(config: dict, traffic: dict):
    b, s = int(traffic["batch_size"]), int(traffic["seq_len"])
    hk, hv = (int(config["linear_num_key_heads"]),
              int(config["linear_num_value_heads"]))
    dk, dv = (int(config["linear_key_head_dim"]),
              int(config["linear_value_head_dim"]))
    v_item = 2 if config["trial"].get("compute_dtype") == "bfloat16" else 4
    return b, s, hk, hv, dk, dv, v_item


def chunk_rule_flops(batch: int, seq_len: int, value_heads: int,
                     key_head_dim: int, value_head_dim: int) -> float:
    """The forward's matrix operations: flops_lm.py's chunk-rule term (a
    chunk's K K^T and Q K^T rows, the solve's half square over [V | K],
    three products with the state, the inner Q K^T V) for every position
    and value head."""
    c, dk, dv = DELTA_CHUNK, key_head_dim, value_head_dim
    return float(batch * seq_len * value_heads * (
        2 * 2 * c * dk + c * (dk + dv) + 3 * 2 * dk * dv + 2 * c * dv))


def _bytes(b, s, hk, hv, dk, dv, v_item):
    qk = 2 * b * s * hk * dk * 4
    v = b * s * hv * dv * v_item
    g_beta = 2 * b * s * hv * 4
    o = b * s * hv * dv * 4
    return qk, v, g_beta, o


def gdn_forward(config: dict, traffic: dict):
    """(operations, bytes) of one forward call."""
    b, s, hk, hv, dk, dv, v_item = _call(config, traffic)
    qk, v, g_beta, o = _bytes(b, s, hk, hv, dk, dv, v_item)
    return chunk_rule_flops(b, s, hv, dk, dv), float(qk + v + g_beta + o)


def gdn_backward(config: dict, traffic: dict):
    """(operations, bytes) of one backward call: twice the forward's
    operations; q, k, v, g, beta and the cotangent of o read, dq, dk, dv,
    dg and dbeta written."""
    b, s, hk, hv, dk, dv, v_item = _call(config, traffic)
    qk, v, g_beta, o = _bytes(b, s, hk, hv, dk, dv, v_item)
    return (2.0 * chunk_rule_flops(b, s, hv, dk, dv),
            float(2 * (qk + v + g_beta) + o))
