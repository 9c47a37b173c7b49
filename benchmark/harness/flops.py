"""Operations and bytes the algorithm needs, computed from shapes.

These are the numerators of every utilisation and roofline number the
benchmark prints; the program's own ``profiler.model_flops_per_token``
(8N under remat, no attention) is not used. ``shape`` is the dict a
family adapter's ``shape()`` returns: ``layers, hidden, heads, head_dim,
ffn, vocab`` — the published sizes, not the padded ones.
"""

from __future__ import annotations

from typing import Dict, Iterable


def matmul_params(shape: Dict[str, int]) -> int:
    """Parameters that sit in a matrix multiplication of the forward
    pass of a dense decoder block stack with a (tied) output head:
    QKV 3h^2, attention output h^2, two feed-forward matrices 2*h*ffn a
    layer, and vocab*h for the head. Embedding look-ups, biases and
    norms do no matrix work and are left out."""
    h, f = shape["hidden"], shape["ffn"]
    return shape["layers"] * (4 * h * h + 2 * h * f) + shape["vocab"] * h


def train_flops_per_token(shape: Dict[str, int], seq: int) -> float:
    """Model FLOPs a trained token needs, forward and backward:
    ``6 * N`` for the matrices (2 forward, 4 backward) plus
    ``12 * L * s * h`` for attention scores and values (4*s*h a layer
    forward, counted as full attention — the convention of the PaLM
    paper's MFU, appendix B). Recomputed operations are not counted."""
    return (6.0 * matmul_params(shape)
            + 12.0 * shape["layers"] * seq * shape["hidden"])


def causal_pairs(length: int) -> int:
    """(query, key) pairs causal attention over ``length`` tokens needs."""
    return length * (length + 1) // 2


def flash_flops(lengths: Iterable[int], shape: Dict[str, int], *,
                backward: bool) -> float:
    """FLOPs the causal attention kernel NEEDS for sequences of the
    given real lengths, all layers and heads: two matrix products a
    pair forward (QK^T and PV, 4*d FLOPs), five more in the backward
    pass (recomputed scores, dV, dP, dQ, dK; 10*d); ``backward=True``
    counts both passes. Padding up to a bucket or a block is not needed
    work and is not counted."""
    pairs = sum(causal_pairs(n) for n in lengths)
    per_pair = 4.0 * shape["head_dim"] * (3.5 if backward else 1.0)
    return pairs * per_pair * shape["heads"] * shape["layers"]


def decode_attn_bytes(contexts: Iterable[int], shape: Dict[str, int], *,
                      bytes_per_el: int = 2) -> float:
    """HBM bytes decode attention NEEDS for one token of each live row:
    it reads K and V of every filled cache position once, in every
    layer and head (``contexts`` = filled positions per live row). The
    one-row write and the query are negligible beside it."""
    per_pos = (2 * shape["layers"] * shape["heads"] * shape["head_dim"]
               * bytes_per_el)
    return float(sum(contexts)) * per_pos
