"""Model FLOPs of one training token, from a configuration file.

The arithmetic of the repository's analytic model (6 x active parameters per
token, plus the causal half of attention's score and value products, three
times over for the forward and backward passes), kept here so that the
yardstick does not move when the program does. Recomputation is not counted.
Active parameters are the matrices a token passes through: attention, the
dense MLP or the router plus ``top_k / experts`` of the routed experts, the
norms and biases, and the tied embedding once, as the output head (its use
as a lookup table costs no FLOPs).
"""

from __future__ import annotations


def active_params(c: dict) -> float:
    d, L = c["hidden_size"], c["num_hidden_layers"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    attn = 2 * d * h * hd + 2 * d * kv * hd
    if c["qkv_bias"]:
        attn += h * hd + 2 * kv * hd
    experts = c.get("num_local_experts", 0)
    if experts:
        ffn = d * experts + 3 * d * c["intermediate_size"] * c["num_experts_per_tok"]
    else:
        ffn = 3 * d * c["intermediate_size"]
    per_layer = attn + ffn + 2 * d
    head = c["vocab_size"] * d
    return float(L * per_layer + d + head)


def attention_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward score and value products of one token, over the
    causal half of a ``seq_len`` context."""
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    return 3.0 * c["num_hidden_layers"] * 2 * h * (0.5 * seq_len) * (hd + hd)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    return 6.0 * active_params(c) + attention_flops_per_token(c, seq_len)
