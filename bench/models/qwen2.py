"""Qwen2 (``model_type`` "qwen2"): the widths the flops and the
reference read, the program's ``LMConfig`` for it, and its seeded
weights in the layout the program's transformer reads.

The published description (arXiv:2407.10671): grouped-query attention
with rotary positions and a bias on the q, k and v projections, a
SwiGLU FFN, RMSNorm, an untied output head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def shape(cfg: dict) -> dict:
    """The published widths the flops and the reference read."""
    out = {k: cfg[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "num_hidden_layers", "vocab_size",
        "rope_theta", "rms_norm_eps")}
    out["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
    return out


def lm_config(cfg: dict):
    from repro.common.config import LMConfig
    s = shape(cfg)
    return LMConfig(
        name=cfg["name"], family="lm-dense", source=cfg["source"],
        n_layers=s["num_hidden_layers"], d_model=s["hidden_size"],
        n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], d_head=s["head_dim"],
        d_ff=s["intermediate_size"], vocab_size=s["vocab_size"],
        rope_theta=float(s["rope_theta"]), qkv_bias=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(s["rms_norm_eps"]),
        max_seq_len=cfg["max_position_embeddings"])


def weights(cfg: dict, key) -> dict:
    """Every weight from ``key``, bfloat16: matrices N(0, 1/fan_in),
    biases N(0, 0.02^2), norm scales 1 + N(0, 0.02^2), the embedding
    N(0, 1).  Traced inside one jitted call (``bench.lm.make_params``)."""
    s = shape(cfg)
    L, d, h, kv, dh, ff, V = (s["num_hidden_layers"], s["hidden_size"],
                              s["num_attention_heads"],
                              s["num_key_value_heads"], s["head_dim"],
                              s["intermediate_size"], s["vocab_size"])
    dt = jnp.bfloat16
    ks = iter(jax.random.split(key, 16))

    def mat(shp, fan_in):
        return (jax.random.normal(next(ks), shp, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def near(shp, mean):
        return (mean + 0.02 * jax.random.normal(next(ks), shp,
                                                jnp.float32)).astype(dt)

    layer = {
        "attn": {"wq": mat((L, d, h * dh), d),
                 "wk": mat((L, d, kv * dh), d),
                 "wv": mat((L, d, kv * dh), d),
                 "wo": mat((L, h * dh, d), h * dh),
                 "bq": near((L, h * dh), 0.0),
                 "bk": near((L, kv * dh), 0.0),
                 "bv": near((L, kv * dh), 0.0)},
        "ffn": {"w_gate": mat((L, d, ff), d),
                "w_up": mat((L, d, ff), d),
                "w_down": mat((L, ff, d), ff)},
        "ln1": near((L, d), 1.0),
        "ln2": near((L, d), 1.0),
    }
    params = {"embed": mat((V, d), 1), "layers": (layer,),
              "final_norm": near((d,), 1.0)}
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = mat((d, V), d)
    return params
