"""Operation and byte counts of the hybrid language model, from shapes
alone (matrix products only, two operations a multiply-add, backward twice
the forward, nothing recomputed counted).  ``cfg`` is the configuration
file: the published key names, ``held_experts`` and ``published``.
"""

from __future__ import annotations

DELTA_CHUNK = 64  # the chunk the counts of the chunked rule assume


def forward_flops_per_sequence(cfg: dict, seq_len: int,
                               pairs_per_layer: float) -> float:
    """One sequence's forward pass; ``pairs_per_layer`` is the token-expert
    pairs actually routed to the experts held here, a layer, for this
    sequence (the program's own count)."""
    s = int(seq_len)
    d = int(cfg["hidden_size"])
    hk, hv = int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    h, hkv, hd = (int(cfg["num_attention_heads"]),
                  int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    f, fs = (int(cfg["moe_intermediate_size"]),
             int(cfg["shared_expert_intermediate_size"]))
    every = int(cfg["full_attention_interval"])
    layers = int(cfg["num_hidden_layers"])
    n_full = layers // every
    n_linear = layers - n_full
    c = DELTA_CHUNK
    key_dim, value_dim = hk * dk, hv * dv
    linear = (
        2 * s * d * (2 * key_dim + 2 * value_dim + 2 * hv)     # in projections
        + 2 * s * value_dim * d                                # out projection
        # the chunked rule, a position and value head: K K^T and Q K^T rows
        # of a chunk, the solve's half square over [V | K], three products
        # with the state, the inner Q K^T V.
        + s * hv * (2 * 2 * c * dk + c * (dk + dv) + 3 * 2 * dk * dv
                    + 2 * c * dv)
    )
    full = (
        2 * s * d * (2 * h * hd + 2 * hkv * hd) + 2 * s * h * hd * d
        + 2 * 2 * (s * s / 2) * hd * h                         # causal half
    )
    moe = (
        2 * s * d * int(cfg["published"]["num_experts"])      # router
        + 3 * 2 * pairs_per_layer * d * f                      # routed here
        + 3 * 2 * s * d * fs + 2 * s * d                       # shared + gate
    )
    head = 2 * s * d * int(cfg["vocab_size"])
    return float(n_linear * linear + n_full * full + layers * moe + head)


def train_flops_per_sequence(cfg: dict, seq_len: int,
                             pairs_per_layer: float) -> float:
    return 3.0 * forward_flops_per_sequence(cfg, seq_len, pairs_per_layer)


def flash_causal_forward(batch: int, seq_len: int, heads: int, kv_heads: int,
                         head_dim: int, itemsize: int = 2):
    """(operations, bytes) of one causal flash forward call with grouped
    key/value heads: q.k^T and p.v over the lower half of the square; q
    read and the output written at ``heads``, k and v read at ``kv_heads``,
    the row statistics in float32."""
    ops = 2 * 2 * batch * heads * (seq_len * seq_len / 2) * head_dim
    q_bytes = batch * seq_len * heads * head_dim * itemsize
    kv_bytes = batch * seq_len * kv_heads * head_dim * itemsize
    return float(ops), float(2 * q_bytes + 2 * kv_bytes
                             + batch * heads * seq_len * 4)


def flash_causal_backward(batch: int, seq_len: int, heads: int, kv_heads: int,
                          head_dim: int, itemsize: int = 2):
    """(operations, bytes) of the backward pair (dK/dV and dQ) together:
    five S x S x D products over the lower half (each kernel's own
    recomputation of the scores is not counted); q, the output, its
    cotangent read and dQ written at ``heads``, k, v read and dK, dV
    written at ``kv_heads``."""
    ops = 5 * 2 * batch * heads * (seq_len * seq_len / 2) * head_dim
    q_bytes = batch * seq_len * heads * head_dim * itemsize
    kv_bytes = batch * seq_len * kv_heads * head_dim * itemsize
    return float(ops), float(4 * q_bytes + 4 * kv_bytes
                             + 2 * batch * heads * seq_len * 4)
