"""Operation and byte counts, from shapes alone.

The transformer arithmetic is the benchmark's copy of the program's
``ops/flops.py`` (matrix-product terms only, two operations a
multiply-add, backward twice the forward, nothing recomputed counted).
The flash kernels' counts are the benchmark's own: what the algorithm
needs for the call, not what the kernel happens to do.
"""

from __future__ import annotations


def forward_flops_per_window(cfg: dict, seq_len: int, features: int) -> float:
    """Matrix-product operations of one forward pass over one window."""
    d = int(cfg["d_model"])
    dff = int(cfg["dim_feedforward"])
    layers = int(cfg["num_layers"])
    s = int(seq_len)
    proj = 4 * 2 * s * d * d            # q, k, v, out
    attn = 2 * 2 * s * s * d            # q.k^T and p.v over all heads
    ff = 2 * 2 * s * d * dff
    embed = 2 * s * features * d
    head, width_in = 0, d
    for width in (128, 64, 32, 16, 1):
        head += 2 * width_in * width
        width_in = width
    return float(layers * (proj + attn + ff) + embed + head)


def train_flops_per_window(cfg: dict, seq_len: int, features: int) -> float:
    """Forward plus backward (twice the forward) for one training window."""
    return 3.0 * forward_flops_per_window(cfg, seq_len, features)


def flash_forward(batch: int, seq_len: int, heads: int, head_dim: int,
                  itemsize: int = 2):
    """(operations, bytes) of one flash forward call: q.k^T and p.v; q, k,
    v read and the output written once, the row statistics in float32."""
    ops = 2 * 2 * batch * heads * seq_len * seq_len * head_dim
    tensor = batch * seq_len * heads * head_dim * itemsize
    return float(ops), float(4 * tensor + batch * heads * seq_len * 4)


def flash_backward(batch: int, seq_len: int, heads: int, head_dim: int,
                   itemsize: int = 2):
    """(operations, bytes) of the backward pair (dK/dV and dQ) together.
    The algorithm needs five S x S x D products: the scores once, then dV,
    dP, dK and dQ.  Each kernel recomputes the scores for itself, which
    is recomputation and not counted.  q, k, v, the output and its
    cotangent are read, three gradients written."""
    ops = 5 * 2 * batch * heads * seq_len * seq_len * head_dim
    tensor = batch * seq_len * heads * head_dim * itemsize
    return float(ops), float(8 * tensor + 2 * batch * heads * seq_len * 4)


def roofline_floor_s(ops: float, nbytes: float, peak: dict):
    """The least time the chip could take and which bound sets it."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
