"""Model operations of a Qwen2-style decoder (GQA attention with QKV
bias, SwiGLU FFN, untied head), counted from its published shapes.

A multiply-add is two operations.  Only the work a token needs is
counted: no padding, no recomputation.  The prompt's forward runs the
head once (the next-token logits); each later token runs one decode
forward over the cache it attends.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def dense_per_token(cfg: dict) -> int:
    """Projection and FFN operations of one token through one layer."""
    d, h, kv, dh, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"],
                        cfg["intermediate_size"])
    qkvo = 2 * d * h * dh + 2 * 2 * d * kv * dh + 2 * h * dh * d
    bias = (h + 2 * kv) * dh
    return qkvo + bias + 3 * 2 * d * ff


def attention_per_token(cfg: dict, n_keys: int) -> int:
    """Scores and weighted values of one query over ``n_keys`` keys in
    one layer."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * n_keys


def head_per_token(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prompt_flops(cfg: dict, n: int) -> int:
    """Causal forward over an ``n``-token prompt, head at the last."""
    layers = cfg["num_hidden_layers"]
    attn = sum(attention_per_token(cfg, i + 1) for i in range(n))
    return layers * (n * dense_per_token(cfg) + attn) + head_per_token(cfg)


def decode_flops(cfg: dict, position: int) -> int:
    """One decode forward of the token at ``position`` (0-based)."""
    return cfg["num_hidden_layers"] * (
        dense_per_token(cfg) + attention_per_token(cfg, position + 1)) \
        + head_per_token(cfg)


def request_flops(cfg: dict, prompt_len: int, n_out: int) -> int:
    """A greedy request: the prompt's forward gives the first output
    token, and each of the other ``n_out - 1`` costs one decode."""
    return prompt_flops(cfg, prompt_len) + sum(
        decode_flops(cfg, prompt_len + j) for j in range(n_out - 1))


def total_flops(cfg: dict, requests: Iterable[Tuple[int, int]]) -> int:
    """``requests``: (prompt tokens, output tokens) of each request."""
    return sum(request_flops(cfg, p, n) for p, n in requests)
