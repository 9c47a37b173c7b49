"""Operations and bytes DeepSeek-V3.2's layer needs, computed from
shapes (beside ``harness/flops.py``, which knows GPT-2's block).

``shape`` is what ``families/deepseek_v32.shape()`` returns. Counts are
of the work the algorithm NEEDS for real tokens at the chip's share of
the deployment: bucket padding, the per-head intermediates of a
materialised product and re-read pages are not needed work. A
multiply-add is two operations; bfloat16 is two bytes.
"""

from __future__ import annotations

from typing import Dict

BYTES = 2


def attn_matrix_params(s: Dict[str, int]) -> int:
    """Parameters in the matrix products of one layer's mixer, MLA and
    indexer: ``q_a, q_b, kv_a, W_uk, W_uv, o`` and ``wq_b, wk,
    weights_proj``."""
    h, heads = s["hidden"], s["heads"]
    mla = (h * s["q_rank"] + s["q_rank"] * heads * (s["nope"] + s["rope"])
           + h * (s["kv_rank"] + s["rope"])
           + heads * s["kv_rank"] * (s["nope"] + s["v"])
           + heads * s["v"] * h)
    index = (s["q_rank"] * s["index_heads"] * s["index_dim"]
             + h * s["index_dim"] + h * s["index_heads"])
    return mla + index


def expert_params(s: Dict[str, int]) -> int:
    """One routed (or the shared) expert: three matrices."""
    return 3 * s["hidden"] * s["expert_ffn"]


def dense_ffn_params(s: Dict[str, int]) -> int:
    return 3 * s["hidden"] * s["dense_ffn"]


def moe_layers(s: Dict[str, int]) -> int:
    return s["layers"] - s["dense_layers"]


def held_share(s: Dict[str, int]) -> float:
    """Of a token's routed pairs, the part an even load brings here."""
    return s["experts_held"] / s["experts_all"]


def matrix_flops_per_token(s: Dict[str, int]) -> float:
    """Matrix FLOPs one token needs through all layers here: the mixer's
    matrices, the dense feed-forward, the router, the shared expert and
    the token's share of routed experts held (``experts_per_token *
    held / all`` of them, 0.5 as published on one chip of 16) — the
    head is counted by the caller, once per token that needs logits."""
    per_moe = (s["hidden"] * s["experts_all"]
               + expert_params(s) * (s["shared_experts"]
                                     + s["experts_per_token"]
                                     * held_share(s)))
    n = (s["layers"] * attn_matrix_params(s)
         + s["dense_layers"] * dense_ffn_params(s)
         + moe_layers(s) * per_moe)
    return 2.0 * n


def head_flops(s: Dict[str, int]) -> float:
    return 2.0 * s["vocab"] * s["hidden"]


def index_flops_per_pair(s: Dict[str, int]) -> float:
    """One (query, key) pair of one layer's indexer: the heads' dot
    products and their weighted sum."""
    return 2.0 * s["index_heads"] * s["index_dim"] + 2.0 * s["index_heads"]


def attend_flops_per_key(s: Dict[str, int]) -> float:
    """One (query, attended key) pair of one layer, absorbed form: the
    score over ``rank + rope`` numbers and the weighted sum over
    ``rank``, for every head."""
    return 2.0 * s["heads"] * (2 * s["kv_rank"] + s["rope"])


def index_key_bytes(s: Dict[str, int]) -> int:
    return s["index_dim"] * BYTES


def latent_row_bytes(s: Dict[str, int]) -> int:
    return (s["kv_rank"] + s["rope"]) * BYTES


def non_expert_weight_bytes(s: Dict[str, int]) -> float:
    """What every decode step reads whatever it routes: the mixers, the
    dense feed-forward, routers, shared experts and the head."""
    per_moe = (s["hidden"] * s["experts_all"]
               + s["shared_experts"] * expert_params(s))
    return BYTES * float(
        s["layers"] * attn_matrix_params(s)
        + s["dense_layers"] * dense_ffn_params(s)
        + moe_layers(s) * per_moe + s["vocab"] * s["hidden"])


def expert_bytes(s: Dict[str, int]) -> int:
    return BYTES * expert_params(s)
