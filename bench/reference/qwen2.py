"""Plain Qwen2 forward in float32, and its int8 control.

The published description (arXiv:2407.10671): pre-norm decoder layers
of RMSNorm, grouped-query attention with rotary positions and a bias
on the q, k and v projections, and a SwiGLU FFN; a final RMSNorm and
an untied output head.  Rotary angles use frequencies
``theta ** (-i / (head_dim / 2))`` on the two halves of each head.

``mode="f32"`` computes every product in float32 at the highest
matmul precision: the reference.  ``mode="int8"`` computes every
product in int8 (each operand quantised symmetrically per row of the
contraction, int32 accumulation): the control, one precision below the
configuration's bfloat16.  Both read the same bfloat16 weights.

The forward runs layer by layer, one jitted call each, over one
sequence at a time padded to a multiple of ``PAD``: causal attention
keeps the real positions independent of the padding.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PAD = 256


def _quant(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s).astype(jnp.int8), s


def mm(a, b, mode):
    """``a @ b`` over the last axis of ``a`` and the first of ``b``."""
    if mode == "f32":
        return jnp.matmul(a, b, precision=HI)
    qa, sa = _quant(a, -1)
    qb, sb = _quant(b, 0)
    acc = jax.lax.dot_general(qa, qb, (((a.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sa * sb


def bmm(a, b, mode):
    """Batched ``a @ b``: (n, i, k) x (n, k, j)."""
    if mode == "f32":
        return jnp.einsum("nik,nkj->nij", a, b, precision=HI)
    qa, sa = _quant(a, -1)
    qb, sb = _quant(b, 1)
    acc = jax.lax.dot_general(qa, qb, (((2,), (1,)), ((0,), (0,))),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sa * sb


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (heads, seq, head_dim)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None], jnp.sin(ang)[None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@functools.partial(jax.jit,
                   static_argnames=("h", "kv", "eps", "theta", "mode"))
def _layer(layers, i, x, h, kv, eps, theta, mode):
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False).astype(jnp.float32), layers)
    n = x.shape[0]
    dh = x.shape[1] // h
    a = lp["attn"]
    y = rms(x, lp["ln1"], eps)
    q = (mm(y, a["wq"], mode) + a["bq"]).reshape(n, h, dh).transpose(1, 0, 2)
    k = (mm(y, a["wk"], mode) + a["bk"]).reshape(n, kv, dh).transpose(1, 0, 2)
    v = (mm(y, a["wv"], mode) + a["bv"]).reshape(n, kv, dh).transpose(1, 0, 2)
    q, k = rope(q, theta), rope(k, theta)
    k = jnp.repeat(k, h // kv, axis=0)   # head j reads kv head j // g
    v = jnp.repeat(v, h // kv, axis=0)
    s = bmm(q, k.transpose(0, 2, 1), mode) * dh ** -0.5
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = bmm(p, v, mode).transpose(1, 0, 2).reshape(n, h * dh)
    x = x + mm(o, a["wo"], mode)
    f = lp["ffn"]
    y = rms(x, lp["ln2"], eps)
    g = mm(y, f["w_gate"], mode)
    x = x + mm(jax.nn.silu(g) * mm(y, f["w_up"], mode), f["w_down"], mode)
    return x


@functools.partial(jax.jit, static_argnames=("mode",))
def _embed(table, ids, mode):
    x = jnp.take(table, ids, axis=0).astype(jnp.float32)
    if mode == "f32":
        return x
    q, s = _quant(x, -1)
    return q.astype(jnp.float32) * s


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(final_norm, head, x, rows, eps, mode):
    y = rms(jnp.take(x, rows, axis=0), final_norm.astype(jnp.float32), eps)
    return mm(y, head.astype(jnp.float32), mode)


def logits(params, shp: Dict, ids: Sequence[int], rows: Sequence[int],
           mode: str = "f32") -> np.ndarray:
    """Next-token logits at positions ``rows`` of the sequence ``ids``:
    (len(rows), vocab) float32 on the host."""
    n = len(ids)
    padded = -(-n // PAD) * PAD
    tok = np.zeros(padded, np.int32)
    tok[:n] = ids
    x = _embed(params["embed"], jnp.asarray(tok), mode)
    layers = params["layers"][0]
    for i in range(shp["num_hidden_layers"]):
        x = _layer(layers, jnp.int32(i), x, shp["num_attention_heads"],
                   shp["num_key_value_heads"], float(shp["rms_norm_eps"]),
                   float(shp["rope_theta"]), mode)
    head = params["lm_head"] if "lm_head" in params \
        else params["embed"].T
    out = _head(params["final_norm"], head, x,
                jnp.asarray(np.asarray(rows, np.int32)),
                float(shp["rms_norm_eps"]), mode)
    return np.asarray(out)


def served_gaps(params, shp: Dict, prompt: List[int], served: List[int],
                control: bool = False) -> Dict[str, np.ndarray]:
    """At each served position, from the float32 reference's logits:
    ``gap``, how far the served token's logit lies below the best;
    ``margin``, the best's lead over the runner-up.  With ``control``,
    also ``control``: the gap of the token the int8 forward puts
    first."""
    seq = list(prompt) + list(served[:-1])
    rows = list(range(len(prompt) - 1, len(seq)))
    ref = logits(params, shp, seq, rows, "f32").astype(np.float64)
    top2 = np.sort(np.partition(ref, -2, axis=1)[:, -2:], axis=1)
    best = top2[:, 1]
    at = np.arange(len(served))
    out = {"gap": best - ref[at, served], "margin": best - top2[:, 0]}
    if control:
        low = logits(params, shp, seq, rows, "int8")
        out["control"] = best - ref[at, low.argmax(axis=1)]
    return out
