"""Public MIPS top-k ops: local scan, flag-masked scan, sharded-candidate
merge, and the single-launch collective sharded scan.

Every public op below also bumps a host-side *launch counter*, so
tests and benchmarks can assert exactly how many jitted dispatches a
query actually issued: one for the flat store's ``flagged_mips_topk``,
one per shard plus a merge for the sharded store's fallback loop, and
exactly ONE for ``sharded_mips_topk`` — the whole per-device scan /
``all_gather`` / merge pipeline is a single ``shard_map`` program.
The counter accounts DIRECT (host-level) calls only: a public op
traced inside someone else's jit bumps once at trace time, not per
execution, so callers that jit over these ops should count their own
outer dispatches (the store's query paths call the ops directly).

The counter itself is owned by the process-global obs registry
(``kernels.mips_topk.launches``); ``launch_count`` /
``reset_launch_count`` remain as thin shims over it.  It is
process-scoped BY DESIGN — per-store attribution lives on each
store's own ``StoreStats.kernel_launches``, so concurrently-live
stores cannot bleed into each other's accounting (see
``tests/test_obs.py``).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.common import interpret_default, on_tpu
from repro.kernels.mips_topk import ref
from repro.kernels.mips_topk.kernel import mips_topk_pallas
from repro.obs.metrics import global_registry

_LAUNCHES = global_registry().counter("kernels.mips_topk.launches")


def reset_launch_count() -> None:
    _LAUNCHES.reset()


def launch_count() -> int:
    """Jitted launches dispatched from the host since the last reset."""
    return _LAUNCHES.count


@functools.partial(jax.jit, static_argnames=("k", "use_pallas",
                                             "interpret"))
def _mips_topk(q: jnp.ndarray, db: jnp.ndarray, k: int, *,
               use_pallas: bool | None = None,
               interpret: bool | None = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        return mips_topk_pallas(
            q, db, k,
            interpret=interpret_default() if interpret is None else interpret)
    return ref.mips_topk_ref(q, db, k)


def mips_topk(q: jnp.ndarray, db: jnp.ndarray, k: int, *,
              use_pallas: bool | None = None,
              interpret: bool | None = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k inner products of each query row against the DB rows."""
    _LAUNCHES.inc()
    return _mips_topk(q, db, k, use_pallas=use_pallas,
                      interpret=interpret)


# Additive score bias that pushes a row below every real candidate
# (unit-norm embeddings score in [-1, 1]; any realistic inner product
# is dwarfed) while staying far above the kernel's internal -3e38
# padding sentinel, so masked rows rank after real rows but before
# out-of-range padding.
MASK_BIAS = -3.0e30


def augment_queries(q: jnp.ndarray,
                    flag_bias: Tuple[float, ...]) -> jnp.ndarray:
    """Concatenate the per-flag bias columns onto a ``(B, d)`` block.

    Hoisted out of ``flagged_mips_topk`` so a multi-shard scan (the
    sharded store's per-shard fallback loop) builds the augmented
    query block ONCE per batch instead of once per shard; the
    collective path folds the same concat into its single launch.
    Not counted as a launch — it is bookkeeping for its caller's scan.
    """
    n_flags = len(flag_bias)
    bias = jnp.broadcast_to(
        jnp.asarray(flag_bias, dtype=jnp.float32)[None, :],
        (q.shape[0], n_flags))
    return jnp.concatenate([q.astype(jnp.float32), bias], axis=1)


def flagged_mips_topk(q: jnp.ndarray, db_flagged: jnp.ndarray, k: int,
                      flag_bias: Tuple[float, ...], *,
                      use_pallas: bool | None = None,
                      interpret: bool | None = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k over a flag-augmented DB without touching the kernel.

    ``db_flagged`` is ``[embeddings | F indicator columns]`` (each 0/1);
    ``flag_bias`` gives one additive score bias per indicator column
    (``MASK_BIAS`` to exclude rows with that flag, 0 to ignore it).
    The bias is folded into the inner product by appending the bias
    values to every query row (``augment_queries``), so any plain MIPS
    top-k kernel — ref or Pallas, local or sharded — applies the mask
    for free.  This is how the vector store keeps tombstoned rows and
    layer filters on-device instead of re-stacking host-side subsets
    per query.
    """
    n_flags = len(flag_bias)
    d = db_flagged.shape[1] - n_flags
    assert d == q.shape[1], (q.shape, db_flagged.shape, n_flags)
    return mips_topk(augment_queries(q, flag_bias), db_flagged, k,
                     use_pallas=use_pallas, interpret=interpret)


def _merge_sharded_topk(vals: jnp.ndarray, idx: jnp.ndarray,
                        k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    s, b, kk = vals.shape
    flat_v = jnp.swapaxes(vals, 0, 1).reshape(b, s * kk)
    flat_i = jnp.swapaxes(idx, 0, 1).reshape(b, s * kk)
    order = jnp.lexsort((flat_i, -flat_v), axis=-1)[:, :k]
    return (jnp.take_along_axis(flat_v, order, axis=1),
            jnp.take_along_axis(flat_i, order, axis=1))


def merge_sharded_topk(vals: jnp.ndarray, idx: jnp.ndarray,
                       k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merge per-shard top-k results: (s, b, k) -> global (b, k).

    Used after an all_gather of per-shard candidates: k << N makes the
    gathered tensor tiny (s*k entries per query) so the collective cost
    is negligible next to the sharded scan.

    Score ties are broken by the *smaller index* — not by flattened
    (shard-major) candidate position — so when ``idx`` carries a global
    ordering (row offsets, or the sharded store's insertion-sequence
    numbers) the merged result is bitwise identical to a single
    ``jax.lax.top_k`` over the unsharded DB, whose tie-break is also
    lowest-index-first.
    """
    _LAUNCHES.inc()
    return _merge_sharded_topk(vals, idx, k)


@functools.partial(jax.jit, static_argnames=(
    "k_shard", "k_out", "flag_bias", "mesh", "axis_names",
    "use_pallas", "interpret"))
def _sharded_mips_topk(q, db, seq, *, k_shard, k_out, flag_bias,
                       mesh, axis_names, use_pallas, interpret):
    q_aug = augment_queries(q, flag_bias)  # folded into the one launch
    lead = axis_names if len(axis_names) != 1 else axis_names[0]

    def scan_gather_merge(qa, db_loc, seq_loc):
        # per-device: scan each LOCAL shard slot with the same
        # (b, d+F) x (cap, d+F) program the fallback loop dispatches,
        # so scores (and their tie-breaks) stay bitwise identical
        vs, ss = [], []
        for j in range(db_loc.shape[0]):  # static unroll over slots
            v, i = _mips_topk(qa, db_loc[j], k_shard,
                              use_pallas=use_pallas,
                              interpret=interpret)
            vs.append(v)
            ss.append(jnp.take(seq_loc[j], i))  # local row -> global seq
        v = jax.lax.all_gather(jnp.stack(vs), axis_names, axis=0,
                               tiled=True)
        s = jax.lax.all_gather(jnp.stack(ss), axis_names, axis=0,
                               tiled=True)
        # (S, b, k_shard) candidates are replicated after the gather;
        # every device computes the identical merged (b, k_out) block
        return _merge_sharded_topk(v, s, k_out)

    # the gathered candidates are replicated by construction; the
    # varying-manual-axes check cannot see that through all_gather
    return jax.shard_map(
        scan_gather_merge, mesh=mesh,
        in_specs=(P(None, None), P(lead, None, None), P(lead, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False)(q_aug, db, seq)


def sharded_mips_topk(q: jnp.ndarray, db_stacked: jnp.ndarray,
                      seq_stacked: jnp.ndarray, k_shard: int,
                      k_out: int, flag_bias: Tuple[float, ...], *,
                      mesh, axis_names: Sequence[str] = ("data",),
                      use_pallas: bool | None = None,
                      interpret: bool | None = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Collective sharded top-k: the WHOLE sharded query in ONE launch.

    ``db_stacked`` is the store's ``(S, cap, d + F)`` stacked shard
    buffer laid out over the ``axis_names`` mesh axes (slot dim
    sharded, rows/features replicated) and ``seq_stacked`` its
    ``(S, cap)`` int32 global-sequence plane.  The jitted program runs
    ``shard_map``: every device scans its local shard slots with the
    flag-masked MIPS kernel, maps local row indices to global sequence
    numbers, ``all_gather``s the tiny ``(S, b, k_shard)`` candidate
    block, and merges to ``(b, k_out)`` with the lowest-sequence
    tie-break — bitwise identical to the per-shard dispatch loop and to
    a flat scan, with zero host round-trips between stages.

    ``k_shard`` is the per-shard scan width (``min(k_out, cap)``);
    exactness needs ``S * k_shard >= k_out``, which holds whenever
    ``k_out`` is capped by the store's live row count.  Returns merged
    ``(vals, seqs)``; the caller maps sequence numbers back to ids.
    """
    s, cap, _ = db_stacked.shape
    assert k_shard <= cap and s * k_shard >= k_out, \
        (db_stacked.shape, k_shard, k_out)
    _LAUNCHES.inc()
    return _sharded_mips_topk(
        q, db_stacked, seq_stacked, k_shard=int(k_shard),
        k_out=int(k_out), flag_bias=tuple(flag_bias), mesh=mesh,
        axis_names=tuple(axis_names), use_pallas=use_pallas,
        interpret=interpret)
