"""Model operations of the target, counted from its sizes.

Per token, the target's model operations are 2 x its matmul parameters
(the head included) plus attention's 4 x layers x query heads x head_dim x
context, where the context is the number of keys the token attends to (its
position + 1).  The draft model and rejected drafts do not count: they are
the cost of speculation, not the model's work.
"""
from __future__ import annotations

__all__ = ["matmul_params", "ops_for_tokens"]


def matmul_params(m: dict) -> int:
    L, d, f = m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or d // h
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return L * per_layer + d * m["vocab_size"]


def ops_for_tokens(m: dict, positions) -> float:
    """Operations to run the target over tokens at the given 0-based
    positions of their sequences."""
    L, h = m["num_hidden_layers"], m["num_attention_heads"]
    hd = m.get("head_dim") or m["hidden_size"] // h
    positions = list(positions)
    dense = 2.0 * matmul_params(m) * len(positions)
    attn = 4.0 * L * h * hd * sum(p + 1 for p in positions)
    return dense + attn
