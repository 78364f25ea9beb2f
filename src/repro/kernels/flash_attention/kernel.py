"""Pallas TPU kernel: online-softmax (flash) attention, GQA + causal.

Forward kernel for the LM serving hot paths: 32k prefill (the EraRAG
summarizer workload) and 1-token decode against long KV caches.  The
score matrix never touches HBM: each (bq, bk) tile is produced on the
MXU and folded into running (m, l, acc) statistics in VMEM scratch.

Grid: (b * hq, lq_tiles, lk_tiles); lk innermost ("arbitrary") so
scratch carries across KV tiles.  GQA is handled by the k/v index_map
(kv head = q head // group) — no materialized repeat.  Causal blocks
entirely above the diagonal are skipped via ``pl.when`` (the classic
2x saving for training shapes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv

_NEG = -1.0e30


def _fa_kernel(q_ref, k_ref, v_ref, out_ref, acc_ref, m_ref, l_ref, *,
               scale: float, causal: bool, bq: int, bk: int,
               lq: int, lk: int, n_k: int):
    i_q = pl.program_id(1)
    i_k = pl.program_id(2)

    @pl.when(i_k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: q global pos = i_q*bq + row + (lk - lq); skip blocks fully
    # above the diagonal.
    q_off = lk - lq  # decode convention: queries at end of window
    if causal:
        first_q = i_q * bq + q_off
        block_needed = (i_k * bk) <= (first_q + bq - 1)
    else:
        block_needed = i_k >= 0  # traced True

    @pl.when(block_needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)

        qpos = i_q * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0) + q_off
        kpos = i_k * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        mask = kpos < lk                                  # padding mask
        if causal:
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[...]                               # (bq, 128)
        m_cur = jnp.max(s, axis=1, keepdims=True)         # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)                # broadcast col
        p = jnp.exp(s - m_new[:, :1])                     # (bq, bk)
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])     # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(
            p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i_k == n_k - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows -> zeros
        out_ref[0, 0] = (acc_ref[...] / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = False,
                           scale: float | None = None,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (b, hq, lq, d); k, v: (b, hkv, lk, d) -> (b, hq, lq, d)."""
    b, hq, lq, d = q.shape
    _, hkv, lk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = float(scale if scale is not None else d ** -0.5)

    bq = min(block_q, lq)
    bk = min(block_k, lk)
    lq_pad = cdiv(lq, bq) * bq - lq
    lk_pad = cdiv(lk, bk) * bk - lk
    q_p = jnp.pad(q, ((0, 0), (0, 0), (0, lq_pad), (0, 0)))
    k_p = jnp.pad(k, ((0, 0), (0, 0), (0, lk_pad), (0, 0)))
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, lk_pad), (0, 0)))
    # flatten (b, h) into one grid axis
    q_f = q_p.reshape(b * hq, 1, q_p.shape[2], d)
    k_f = k_p.reshape(b * hkv, 1, k_p.shape[2], d)
    v_f = v_p.reshape(b * hkv, 1, v_p.shape[2], d)
    n_q = q_p.shape[2] // bq
    n_k = k_p.shape[2] // bk

    def kv_map(bh, iq, ik):
        # q head bh -> kv row (bh // hq) * hkv + (bh % hq) // group
        return ((bh // hq) * hkv + (bh % hq) // group, 0, ik, 0)

    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, lq=lq, lk=lk, n_k=n_k),
        grid=(b * hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bh, iq, ik: (bh, 0, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bh, iq, ik: (bh, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, q_p.shape[2], d),
                                       q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_f, k_f, v_f)
    return out.reshape(b, hq, q_p.shape[2], d)[:, :, :lq]
