"""Pallas TPU kernel: fused projection + sign + bit-pack LSH hashing.

Computes ``pack(sign(V @ H))`` without round-tripping the (n, k) float
projection through HBM: the projection tile is accumulated in a VMEM
scratch across d-tiles (MXU matmuls), and on the final d-tile the sign
bits are packed into uint32 words in-register and written out.  For
n = 10^6 chunks and k = 64 hyperplanes this saves an n*k fp32 HBM
round-trip (~256 MB) and writes only n*2 uint32 words (8 MB): a 33x
reduction in output bytes (see EXPERIMENTS.md kernel table).

Grid: (n_tiles, d_tiles); d is the innermost (arbitrary) dimension so
the scratch accumulator carries across d-tiles of one n-tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv


def _lsh_hash_kernel(v_ref, h_ref, out_ref, acc_ref, *, n_d: int, k: int):
    i_d = pl.program_id(1)

    @pl.when(i_d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # full f32 contraction: a bf16 pass would flip the sign bit of
    # projections near zero, so codes would depend on the backend
    acc_ref[...] += jnp.dot(v_ref[...], h_ref[...],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(i_d == n_d - 1)
    def _finalize():
        # Mosaic reduces no unsigned integers: each word is summed in
        # int32 (distinct powers of two, so the sum is the bitwise OR;
        # bit 31 wraps to the sign bit) and bitcast to uint32 unchanged
        proj = acc_ref[...]                       # (bn, k_pad)
        bits = (proj >= 0.0).astype(jnp.int32)
        pow2 = jnp.left_shift(
            jnp.int32(1), jax.lax.broadcasted_iota(jnp.int32, (1, 32), 1))
        words = [jnp.sum(bits[:, 32 * j:32 * (j + 1)] * pow2, axis=1,
                         keepdims=True)
                 for j in range(bits.shape[1] // 32)]
        words = words[0] if len(words) == 1 else \
            jnp.concatenate(words, axis=1)
        out_ref[...] = jax.lax.bitcast_convert_type(words, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("block_n", "block_d",
                                             "interpret"))
def lsh_hash_pallas(v: jnp.ndarray, h: jnp.ndarray, *,
                    block_n: int = 256, block_d: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """v: (n, d); h: (d, k) -> (n, ceil(k/32)) uint32 packed codes."""
    n, d = v.shape
    d2, k = h.shape
    assert d == d2
    n_words = cdiv(k, 32)
    k_pad = n_words * 32

    # pad: hyperplane pad columns produce sign(0)=1 bits beyond k; they
    # live in bit positions >= k of the last word.  Pad with -inf-free
    # columns: a zero column gives proj 0 -> bit 1, which would pollute
    # the last word, so instead pad h with a large negative constant
    # times nothing -- we pad with columns equal to -1 * mean direction?
    # Simplest correct scheme: pad h with zeros and mask the packed bits
    # afterwards in the wrapper.  Here we keep the raw packed words and
    # let ops.py mask the tail bits.
    bn = min(block_n, n)
    bd = min(block_d, d)
    n_pad = cdiv(n, bn) * bn - n
    d_pad = cdiv(d, bd) * bd - d
    v_p = jnp.pad(v, ((0, n_pad), (0, d_pad)))
    h_p = jnp.pad(h, ((0, d_pad), (0, k_pad - k)))
    n_t, d_t = v_p.shape[0] // bn, v_p.shape[1] // bd

    out = pl.pallas_call(
        functools.partial(_lsh_hash_kernel, n_d=d_t, k=k),
        grid=(n_t, d_t),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bd, k_pad), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, n_words), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((v_p.shape[0], n_words),
                                       jnp.uint32),
        scratch_shapes=[pltpu.VMEM((bn, k_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(v_p, h_p)
    return out[:n]
