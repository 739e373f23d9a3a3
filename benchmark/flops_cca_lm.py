"""Operation counts of the compressed-convolutional-attention language
model, from shapes alone (matrix products only, two operations a
multiply-add, backward twice the forward, nothing recomputed counted).
``cfg`` is the configuration file: the published key names,
``held_experts`` and ``published``.  The causal flash kernels' operations
and bytes are ``benchmark/flops_lm.py``'s, which take the call's heads,
key-value heads and head size.
"""

from __future__ import annotations

ROUTER_DEPTH = 2  # hidden layers of the router MLP (``assumed.router``)


def forward_flops_per_sequence(cfg: dict, seq_len: int,
                               pairs_per_layer: float) -> float:
    """One sequence's forward pass; ``pairs_per_layer`` is the token-expert
    pairs actually routed to the experts held here, a layer, for this
    sequence (the program's own count)."""
    s = int(seq_len)
    d = int(cfg["hidden_size"])
    h, hkv, hd = (int(cfg["num_attention_heads"]),
                  int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    rh, f = int(cfg["router_hidden_size"]), int(cfg["moe_intermediate_size"])
    mixer = (
        2 * s * d * (h * hd + 2 * hkv * hd)     # q, k and the two value halves
        + 2 * s * h * hd * d                    # the output projection
        # the convolution inside a head: a [hd, hd] product a head and tap
        + 2 * s * (h + hkv) * int(cfg["cca_time1"]) * hd * hd
        + 2 * 2 * (s * s / 2) * hd * h          # causal half of q k^T and p v
    )
    router = 2 * s * (d * rh + ROUTER_DEPTH * rh * rh
                      + rh * int(cfg["published"]["num_experts"]))
    experts = 3 * 2 * pairs_per_layer * d * f
    head = 2 * s * d * int(cfg["vocab_size"])
    return float(
        int(cfg["num_hidden_layers"]) * (mixer + router + experts) + head
    )


def train_flops_per_sequence(cfg: dict, seq_len: int,
                             pairs_per_layer: float) -> float:
    return 3.0 * forward_flops_per_sequence(cfg, seq_len, pairs_per_layer)
