"""The configuration's LM: weights made on the device from the seed,
in the type they are served in, and the program's engine over them.

The weights are the benchmark's, not the program's: one jitted call
draws every tensor in the layout the program's transformer reads, so
the reference (``reference/qwen2.py``) and the program start from the
same numbers and nothing of the program's own initialisation.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

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


def make_params(cfg: dict, seed: int):
    """Every weight from ``seed`` in one jitted program, bfloat16."""
    s = shape(cfg)
    L, d, h, kv, dh, ff, V = (s["num_hidden_layers"], s["hidden_size"],
                              s["num_attention_heads"],
                              s["num_key_value_heads"], s["head_dim"],
                              s["intermediate_size"], s["vocab_size"])
    dt = jnp.bfloat16

    def build(key):
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

    # a seed of more than 32 bits is folded in word by word
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.jit(build)(key)


class _Sink(dict):
    """The engine's result dict, which also keeps a copy of every
    finished request's tokens for the check."""

    def __init__(self, sink: Dict[int, List[int]]):
        super().__init__()
        self.sink = sink

    def __setitem__(self, k, v):
        self.sink[k] = [int(t) for t in v]
        super().__setitem__(k, v)


class Recorder:
    """What the engine served: each request's prompt, budget and
    tokens, kept from the engine's own entry and result points."""

    def __init__(self, engine):
        self.prompts: Dict[int, Tuple[str, int]] = {}
        self.outputs: Dict[int, List[int]] = {}
        submit = engine.submit

        def recording_submit(prompt, max_new_tokens=None, prefix=None):
            rid = submit(prompt, max_new_tokens, prefix=prefix)
            budget = engine.ecfg.max_new_tokens \
                if max_new_tokens is None else max_new_tokens
            self.prompts[rid] = (prompt, int(budget))
            return rid

        engine.submit = recording_submit
        engine._results = _Sink(self.outputs)

    def clear(self) -> None:
        self.prompts.clear()
        self.outputs.clear()

    def finished(self) -> List[Tuple[int, str, int, List[int]]]:
        """(request id, prompt, budget, served tokens), in order."""
        return [(rid, self.prompts[rid][0], self.prompts[rid][1],
                 self.outputs[rid]) for rid in sorted(self.outputs)
                if rid in self.prompts]
