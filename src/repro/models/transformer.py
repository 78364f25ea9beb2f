"""Decoder-only LM (dense or MoE): init, train loss, prefill, decode.

Parameters are stacked over layers (leading L dim) and the forward pass
is a ``lax.scan`` with ``jax.checkpoint`` on the layer body — compile
time is O(1) in depth and activation memory follows the remat policy.
Shardings come from the logical-axes twin pytree (see
``common.sharding``); weights carry no batch dim so the same rule table
gives FSDP-style (data-axis) weight sharding plus tensor-parallel
(model-axis) sharding, while activations shard batch over (pod, data).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import LMConfig
from repro.models.layers import (
    attention_fwd,
    attention_init,
    dense_init,
    moe_fwd,
    moe_init,
    rmsnorm,
    swiglu_fwd,
    swiglu_init,
)
from repro.models.sharding_ctx import shard

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def block_size(cfg: LMConfig) -> int:
    """Layers per scan step: moe_every for interleaved-MoE archs."""
    return cfg.moe_every if cfg.is_moe else 1


def n_blocks(cfg: LMConfig) -> int:
    assert cfg.n_layers % block_size(cfg) == 0
    return cfg.n_layers // block_size(cfg)


def init_params(cfg: LMConfig, key, dtype=jnp.float32
                ) -> Tuple[Params, Params]:
    """Random parameters (and their logical-axes twin).

    One jitted program draws every weight, so XLA fuses each f32 draw
    with its cast: the peak is the ``dtype`` parameters themselves,
    never an f32 copy of a stacked weight (at a 7B width the stacked
    ``w_gate`` alone would be 4 GiB in f32 over 16 layers)."""
    axes = {}

    def build(k):
        params, ax = _init_params(cfg, k, dtype)
        axes.update(ax)
        return params

    return jax.jit(build)(key), axes


def _init_params(cfg: LMConfig, key, dtype) -> Tuple[Params, Params]:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    bs = block_size(cfg)

    def sub_init(k, is_moe_layer: bool):
        ka, kf = jax.random.split(k)
        attn, attn_axes = attention_init(ka, cfg, dtype)
        if is_moe_layer:
            ffn, ffn_axes = moe_init(kf, cfg.d_model, cfg.moe, dtype)
        else:
            ffn, ffn_axes = swiglu_init(kf, cfg.d_model, cfg.d_ff, dtype)
        p = {"attn": attn, "ffn": ffn,
             "ln1": jnp.ones((cfg.d_model,), dtype),
             "ln2": jnp.ones((cfg.d_model,), dtype)}
        ax = {"attn": attn_axes, "ffn": ffn_axes,
              "ln1": ("embed",), "ln2": ("embed",)}
        return p, ax

    def layer_init(k):
        # block = bs consecutive layers; the LAST one is MoE (llama4
        # interleaves dense/MoE 1:1 -> bs=2: [dense, moe])
        ks = jax.random.split(k, bs)
        pairs = [sub_init(ks[j], cfg.is_moe and j == bs - 1)
                 for j in range(bs)]
        return (tuple(p for p, _ in pairs),
                tuple(a for _, a in pairs))

    keys = jax.random.split(k_layers, n_blocks(cfg))
    layer_axes = layer_init(keys[0])[1]
    layers = jax.vmap(lambda k: layer_init(k)[0])(keys)

    params = {
        "embed": dense_init(k_emb, cfg.vocab_size, cfg.d_model,
                            scale=0.02, dtype=dtype),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    def _is_ax(x):
        return isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)

    axes = {
        "embed": ("vocab", "embed"),
        # stacked layer params get a leading "layers" axis
        "layers": jax.tree.map(
            lambda a: ("layers",) + a, layer_axes, is_leaf=_is_ax),
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model,
                                       cfg.vocab_size, scale=0.02,
                                       dtype=dtype)
        axes["lm_head"] = ("embed", "vocab")
    return params, axes


# ---------------------------------------------------------------------------
# layer body
# ---------------------------------------------------------------------------
def _layer_fwd(lp: Params, x: jnp.ndarray, cfg: LMConfig,
               positions, kv_cache=None, cache_len=None):
    # mixed precision: compute in the residual-stream dtype (bf16 on
    # TPU), master weights stay fp32 in the optimizer
    lp = jax.tree.map(
        lambda w: w.astype(x.dtype)
        if jnp.issubdtype(w.dtype, jnp.floating) else w, lp)
    h, cache = attention_fwd(
        lp["attn"], rmsnorm(x, lp["ln1"], cfg.norm_eps), cfg,
        positions, causal=True, kv_cache=kv_cache, cache_len=cache_len)
    x = x + h
    x = shard(x, ("batch", "seq", "embed"))
    aux = jnp.float32(0.0)
    y = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    # dispatch on the param structure: interleaved-MoE blocks mix dense
    # and MoE sub-layers under one cfg
    if "router" in lp["ffn"]:
        ff, aux = moe_fwd(lp["ffn"], y, cfg.moe)
    else:
        ff = swiglu_fwd(lp["ffn"], y)
    x = x + ff
    x = shard(x, ("batch", "seq", "embed"))
    return x, aux, cache


def _unroll() -> int | bool:
    """Full scan unroll for the dry-run cost-analysis probes (XLA's
    cost_analysis counts while-loop bodies once; see launch/dryrun)."""
    import os
    return True if os.environ.get("REPRO_UNROLL_SCANS") else 1


def _block_fwd(bp, x, cfg, positions, caches=None, cache_len=None):
    """Apply one block (= block_size stacked sub-layers)."""
    aux_total = jnp.float32(0.0)
    new_caches = []
    for j, sub in enumerate(bp):
        cache = caches[j] if caches is not None else None
        x, aux, nc = _layer_fwd(sub, x, cfg, positions, cache,
                                cache_len)
        aux_total += aux
        new_caches.append(nc)
    return x, aux_total, tuple(new_caches) if caches is not None \
        else None


def _backbone(params: Params, x: jnp.ndarray, cfg: LMConfig,
              positions, *, remat: bool = True,
              kv_caches=None, cache_len=None):
    """Scan the stacked blocks. Returns (hidden, aux_sum, new_caches)."""
    if kv_caches is None:
        def body(x, bp):
            out, aux, _ = _block_fwd(bp, x, cfg, positions)
            return out, aux

        body_fn = jax.checkpoint(body) if remat else body
        x, auxes = jax.lax.scan(body_fn, x, params["layers"],
                                unroll=_unroll())
        return x, jnp.sum(auxes), None

    def body_c(x, scanned):
        bp, caches = scanned
        out, aux, new_caches = _block_fwd(bp, x, cfg, positions,
                                          caches, cache_len)
        return out, (aux, new_caches)

    body_fn = jax.checkpoint(body_c) if remat else body_c
    x, (auxes, new_caches) = jax.lax.scan(
        body_fn, x, (params["layers"], kv_caches), unroll=_unroll())
    return x, jnp.sum(auxes), new_caches


def _logits(params: Params, x: jnp.ndarray, cfg: LMConfig) -> jnp.ndarray:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    return shard(logits, ("batch", "seq", "vocab"))


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], cfg: LMConfig,
            *, compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Dict]:
    tokens = batch["tokens"]                       # (b, l)
    labels = batch["labels"]                       # (b, l)
    b, l = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    x = shard(x, ("batch", "seq", "embed"))
    positions = jnp.arange(l)
    x, aux, _ = _backbone(params, x, cfg, positions, remat=True)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x, cfg).astype(jnp.float32)

    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None],
                               axis=-1)[..., 0]
    nll = (logz - gold).mean()
    loss = nll + aux.astype(jnp.float32)
    return loss, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def make_kv_cache(cfg: LMConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    shape = (n_blocks(cfg), batch, cfg.n_kv_heads, max_len, cfg.d_head)
    one = lambda: {"k": jnp.zeros(shape, dtype),
                   "v": jnp.zeros(shape, dtype)}
    return tuple(one() for _ in range(block_size(cfg)))


def kv_cache_axes(cfg: LMConfig):
    ax = {"k": ("layers", "batch", "kv_heads", "kv_seq", None),
          "v": ("layers", "batch", "kv_heads", "kv_seq", None)}
    return tuple(dict(ax) for _ in range(block_size(cfg)))


def prefill(params: Params, tokens: jnp.ndarray, cfg: LMConfig,
            max_len: Optional[int] = None, *,
            compute_dtype=jnp.bfloat16):
    """Full-sequence forward; returns (last-position logits, kv cache)."""
    b, l = tokens.shape
    max_len = max_len or l
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    x = shard(x, ("batch", "seq", "embed"))
    positions = jnp.arange(l)
    caches = make_kv_cache(cfg, b, max_len, compute_dtype)
    x, _, new_caches = _backbone(params, x, cfg, positions, remat=True,
                                 kv_caches=caches,
                                 cache_len=jnp.int32(0))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x[:, -1:, :], cfg)
    return logits[:, 0], new_caches


def prefill_padded(params: Params, tokens: jnp.ndarray,
                   lengths: jnp.ndarray, cfg: LMConfig,
                   max_len: Optional[int] = None, *,
                   compute_dtype=jnp.bfloat16):
    """Right-padded batched prefill (the serving engine's bucketed path).

    ``tokens``: (b, l) prompts right-padded to a shared bucket length;
    ``lengths``: (b,) true prompt lengths.  Causal masking makes every
    real position independent of the padding tail (a query at position
    ``i < lengths[b]`` only attends keys ``<= i``, all real), so row
    ``b``'s cache prefix ``[: lengths[b]]`` and its returned logits —
    taken at position ``lengths[b] - 1`` — match an unpadded per-row
    ``prefill``.  (Exact for dense FFN; MoE capacity routing couples
    batch rows by design.)  Cache rows at ``lengths[b]:`` hold padding
    K/V: decode overwrites position ``lengths[b]`` before reading it
    and masks the rest via ``kv_len``, so they are never observed.

    Returns (per-row next-token logits (b, vocab), kv caches).
    """
    b, l = tokens.shape
    max_len = max_len or l
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    x = shard(x, ("batch", "seq", "embed"))
    positions = jnp.arange(l)
    caches = make_kv_cache(cfg, b, max_len, compute_dtype)
    x, _, new_caches = _backbone(params, x, cfg, positions, remat=True,
                                 kv_caches=caches,
                                 cache_len=jnp.int32(0))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    # gather each row's last REAL position before the head so the
    # logits matmul stays O(b), not O(b * l)
    last = jnp.clip(lengths.astype(jnp.int32) - 1, 0, l - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)   # (b, 1, d)
    logits = _logits(params, x, cfg)
    return logits[:, 0], new_caches


def prefill_extend(params: Params, tokens: jnp.ndarray,
                   lengths: jnp.ndarray, offsets: jnp.ndarray,
                   caches, cfg: LMConfig, *,
                   compute_dtype=jnp.bfloat16):
    """Suffix prefill over per-row prefilled cache prefixes (the KV
    prefix-reuse admission path).

    ``tokens``: (b, l) suffix tokens right-padded to a shared bucket
    length; ``lengths``: (b,) true suffix lengths; ``offsets``: (b,)
    per-row cache prefix lengths (rows ``[: offsets[b]]`` of row b's
    cache already hold a reused prefix's K/V).  Row b's suffix token
    ``i`` runs at global position ``offsets[b] + i`` — RoPE angles,
    cache writes and the causal mask all use global positions, so the
    suffix K/V rows and the returned logits (taken at the last real
    suffix position) are bitwise those of a cold full-prompt
    ``prefill_padded`` whose first ``offsets[b]`` tokens produced the
    cached prefix.  Rows with ``lengths[b] == 0`` compute garbage the
    caller discards (engine merges caches row-wise).

    Returns (per-row next-token logits (b, vocab), kv caches).
    """
    b, l = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    x = shard(x, ("batch", "seq", "embed"))
    positions = offsets.astype(jnp.int32)[:, None] + \
        jnp.arange(l)[None, :]                               # (b, l)
    x, _, new_caches = _backbone(params, x, cfg, positions, remat=True,
                                 kv_caches=caches,
                                 cache_len=offsets.astype(jnp.int32))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.clip(lengths.astype(jnp.int32) - 1, 0, l - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)  # (b, 1, d)
    logits = _logits(params, x, cfg)
    return logits[:, 0], new_caches


def decode_step(params: Params, tokens: jnp.ndarray, caches,
                cache_len: jnp.ndarray, cfg: LMConfig, *,
                compute_dtype=jnp.bfloat16):
    """One-token decode. tokens: (b, 1); cache_len: scalar int32.

    Returns (logits (b, vocab), new caches)."""
    b, l = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    positions = cache_len + jnp.arange(l)
    x, _, new_caches = _backbone(params, x, cfg, positions, remat=False,
                                 kv_caches=caches, cache_len=cache_len)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x, cfg)
    return logits[:, -1], new_caches


def greedy_sample(logits: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
