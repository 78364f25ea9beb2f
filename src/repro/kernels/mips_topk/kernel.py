"""Pallas TPU kernel: blocked MIPS with online top-k (flash-style).

Retrieval hot path (paper Thm 3: ``V_search = O(Nd)`` for a flat index).
The kernel streams DB tiles through VMEM, computes the (bq, bn) score
tile on the MXU, and folds it into a running per-query top-k held in
VMEM scratch -- the full (b, n) score matrix is never materialized
(same online-reduction insight as flash attention, applied to top-k
instead of softmax).  HBM traffic is therefore O(nd) reads + O(bk)
writes instead of O(bn) score writes + re-reads for a separate sort.

The DB is read through its transposed view ``db.T``, shape ``(d, n)``.
The TPU keeps a tall ``(n, d)`` f32 buffer whose width is not a
multiple of 128 (the store's ``d + F`` columns) column-major, so that
view is the buffer as it already lies in HBM: XLA lowers the transpose
to a bitcast and the kernel reads the store's buffer in place.  Each
step takes a ``(bd, bn)`` tile of that view and the score tile is the
NN product ``(bq, bd) @ (bd, bn)``.

Grid: (b_tiles, n_tiles, d_tiles); d innermost accumulates partial dot
products; the top-k merge runs once per (b, n) tile on the last d tile.
Whenever the double-buffered ``(d, bn)`` tile fits the VMEM budget the
whole width is one tile (``bd = d``, one d-step, no feature pad; the row
tile halves from 1024 to 512 to fit a wider row); wider inputs take
``block_d`` tiles and pad the features to a multiple of it.
Rows are padded only when ``n`` is not a multiple of ``bn`` (the
store's default capacities, powers of two from 64, always are).
``db_padded`` says, from the shapes alone, whether a call pads the DB.
Merge is k passes of masked max+select (VPU-friendly; no argmax/sort
primitives needed on TPU).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv

_NEG = -3.0e38  # python float: avoids capturing a traced constant
BLOCK_N = 1024
BLOCK_D = 512


def _merge_topk(run_vals, run_idx, scores, base, k: int):
    """Fold (bq, bn) scores into running (bq, k) top-k. Returns new pair.

    Column ``j`` of ``scores`` is global row ``base + j``.

    First-occurrence tie-breaking reproduces jax.lax.top_k semantics:
    running entries hold earlier global indices than the score tile
    (tiles arrive in index order), so a tie between the two goes to the
    running entry, and within either block to the lower position.

    The k selection passes run as a ``fori_loop`` over the running and
    tile blocks separately: no ``(bq, k + bn)`` concatenation, and only
    one pass's ``(bq, bn)`` temporaries are live at a time, which keeps
    the kernel inside VMEM for query tiles of 128 rows.
    """
    bq, bn = scores.shape
    col_k = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    col_n = jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)

    def select(j, carry):
        run_v, tile_v, out_v, out_i = carry
        m_r = jnp.max(run_v, axis=1, keepdims=True)               # (bq, 1)
        m_t = jnp.max(tile_v, axis=1, keepdims=True)
        from_run = m_r >= m_t
        pos_r = jnp.min(jnp.where(run_v == m_r, col_k, k), axis=1,
                        keepdims=True)
        pos_t = jnp.min(jnp.where(tile_v == m_t, col_n, bn), axis=1,
                        keepdims=True)
        sel_r = (col_k == pos_r) & from_run
        sel_t = (col_n == pos_t) & ~from_run
        idx_r = jnp.sum(jnp.where(sel_r, run_idx, 0), axis=1,
                        keepdims=True)
        here = col_k == j
        out_v = jnp.where(here, jnp.maximum(m_r, m_t), out_v)
        out_i = jnp.where(here, jnp.where(from_run, idx_r, base + pos_t),
                          out_i)
        return (jnp.where(sel_r, _NEG, run_v),
                jnp.where(sel_t, _NEG, tile_v), out_v, out_i)

    init = (run_vals, scores, jnp.full((bq, k), _NEG, jnp.float32),
            jnp.zeros((bq, k), jnp.int32))
    _, _, out_v, out_i = jax.lax.fori_loop(0, k, select, init)
    return out_v, out_i


def _mips_kernel(q_ref, db_ref, out_v_ref, out_i_ref,
                 acc_ref, vals_ref, idx_ref, *,
                 k: int, bn: int, n: int, n_n: int, n_d: int):
    i_n = pl.program_id(1)
    i_d = pl.program_id(2)

    @pl.when((i_n == 0) & (i_d == 0))
    def _init_topk():
        vals_ref[...] = jnp.full_like(vals_ref, _NEG)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    @pl.when(i_d == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # full f32 contraction: the default MXU precision rounds the
    # operands to bf16, which reorders near-tied candidates
    acc_ref[...] += jnp.dot(q_ref[...], db_ref[...],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(i_d == n_d - 1)
    def _merge():
        base = i_n * bn
        scores = acc_ref[...]
        if n % bn:  # padded rows in the last tile never rank
            col = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
            scores = jnp.where(base + col < n, scores, _NEG)
        nv, ni = _merge_topk(vals_ref[...], idx_ref[...], scores, base, k)
        vals_ref[...] = nv
        idx_ref[...] = ni

    @pl.when((i_n == n_n - 1) & (i_d == n_d - 1))
    def _write():
        out_v_ref[...] = vals_ref[...]
        out_i_ref[...] = idx_ref[...]


# VMEM for the double-buffered (d, bn) DB tile when the whole width is
# one tile.  The rest of the scoped VMEM holds the query tile, the
# (bq, bn) accumulator and the merge's temporaries.
_DB_TILE_VMEM = 8 << 20


def _tiles(n: int, d: int, block_n: int,
           block_d: int) -> Tuple[int, int]:
    """(bn, bd): the whole width in one tile while it fits the budget,
    halving a wide row tile down to 512 for it (bn = 1024 up to d = 1024,
    512 up to d = 2048); wider inputs tile the features too."""
    tile_bytes = 2 * 4 * cdiv(d, 8) * 8
    bn = block_n
    while bn > 512 and tile_bytes * bn > _DB_TILE_VMEM:
        bn //= 2
    bn = min(bn, n)
    return bn, d if tile_bytes * bn <= _DB_TILE_VMEM else min(block_d, d)


def db_padded(n: int, d: int) -> bool:
    """True when ``mips_topk_pallas`` (default tiles) pads an ``(n, d)``
    DB."""
    bn, bd = _tiles(n, d, BLOCK_N, BLOCK_D)
    return n % bn != 0 or d % bd != 0


@functools.partial(jax.jit,
                   static_argnames=("k", "block_q", "block_n", "block_d",
                                    "interpret"))
def mips_topk_pallas(q: jnp.ndarray, db: jnp.ndarray, k: int, *,
                     block_q: int = 128, block_n: int = BLOCK_N,
                     block_d: int = BLOCK_D, interpret: bool = False):
    b, d = q.shape
    n, d2 = db.shape
    assert d == d2 and k <= n, (q.shape, db.shape, k)

    bq = min(block_q, b)
    bn, bd = _tiles(n, d, block_n, block_d)
    b_pad = cdiv(b, bq) * bq - b
    n_pad = cdiv(n, bn) * bn - n
    d_pad = cdiv(d, bd) * bd - d
    q_p = jnp.pad(q.astype(jnp.float32), ((0, b_pad), (0, d_pad)))
    # the buffer's own layout, a bitcast; no pad op when nothing pads
    db_t = jnp.pad(db.astype(jnp.float32).T, ((0, d_pad), (0, n_pad)))
    b_t = q_p.shape[0] // bq
    n_t = db_t.shape[1] // bn
    d_t = db_t.shape[0] // bd

    out_v, out_i = pl.pallas_call(
        functools.partial(_mips_kernel, k=k, bn=bn, n=n, n_n=n_t, n_d=d_t),
        grid=(b_t, n_t, d_t),
        in_specs=[
            pl.BlockSpec((bq, bd), lambda i, j, l: (i, l)),
            pl.BlockSpec((bd, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j, l: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j, l: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_p.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((q_p.shape[0], k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, bn), jnp.float32),
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q_p, db_t)
    return out_v[:b], out_i[:b]
