"""Operations a training step needs, from a configuration's shapes.

Forward and backward passes count three times the forward's matrix
products (the backward computes two products per forward one). The
forward counts 2 operations per multiply-add of every projection, the MLP
and the output head, and for causal attention the scores and the weighted
sum over the keys each query sees, (S + 1) / 2 on average. Operations a
plan recomputes (rematerialisation) and elementwise work do not count.
"""
from __future__ import annotations


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = d // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    n_mlp = 3 if cfg["mlp"] == "swiglu" else 2
    per_layer = d * (nq + 2 * nkv) + nq * d + n_mlp * d * ff
    matmul = cfg["num_hidden_layers"] * per_layer + d * v
    attn = cfg["num_hidden_layers"] * 2 * nq * (seq_len + 1)
    return 3.0 * (2.0 * matmul + attn)
