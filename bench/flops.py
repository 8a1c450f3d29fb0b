"""Model FLOPs per trained token, from a configuration's shapes.

6 × the matmul weights a token passes through (forward 2, backward 4): the
attention projections, the feed-forward (for MoE the router and the top-k
experts only) and the head; plus causal attention's two matmuls (QKᵀ and
PV) over the causal half, the backward twice the forward.  Rematerialized
recomputation and the embedding lookup do not count.
"""

from __future__ import annotations


def matmul_weights_per_token(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    attn = d * hd * (2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"])
    if c.get("num_experts"):
        ffn = (d * c["num_experts"]
               + c["num_experts_per_tok"] * 3 * d * c["intermediate_size"])
    else:
        ffn = 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * (attn + ffn) + d * c["vocab_size"]


def attention_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward of QKᵀ and PV; a token at position i attends i + 1
    keys, (seq + 1) / 2 on average."""
    width = c["num_attention_heads"] * c["head_dim"]
    forward = 2 * 2 * width * (seq + 1) / 2
    return 3 * forward * c["num_hidden_layers"]


def flops_per_token(c: dict, seq: int) -> float:
    return 6 * matmul_weights_per_token(c) + attention_flops_per_token(c, seq)
