"""Fused two-stage quantized retrieval: LSH sign-bit coarse scan ->
exact fp32 rescore.

The exact flat scan streams every ``(cap, d + F)`` float32 row per
query — memory-bandwidth-bound.  The two-stage pipeline scans a
compressed plane instead: each row is hashed ONCE at append time to a
packed sign-bit code (``kernels/lsh_hash`` over persisted
hyperplanes), the coarse stage ranks codes by Hamming distance
(``kernels/hamming_topk``, ~32x fewer bytes per row), and only the
top-C candidate rows are gathered for an exact fp32 rescore — so the
final scores are REAL inner products of real rows, never quantized
approximations, and candidates merge with the same
(score desc, row asc) tie-break as the exact path.  With
``n_coarse >= rows`` the candidate set is total and the result is
bitwise-equal to the exact single-stage scan (the differential suite's
strongest check).

Flag masking rides inside the codes.  The store's buffer carries
``F = n_flags`` trailing indicator columns (dead / summary / leaf);
the code layout mirrors them with one PENALTY WORD GROUP per flag —
``flag_words = ceil(n_bits + 1, 32)`` words each — after the
``code_words`` real code words:

- a DB row's group is all-ones when the flag is set, all-zeros
  otherwise (``encode_rows``; tombstoning flips the dead group in
  place, no rehash);
- a query penalizing a flag (bias != 0, i.e. ``MASK_BIAS``) carries an
  all-zeros group there: XOR distance is 0 against unflagged rows and
  ``32 * flag_words > n_bits`` against flagged ones — strictly larger
  than any real code distance, so flagged rows sort after every
  unflagged row in the coarse ranking (they can still surface when
  fewer than C unflagged rows exist; the rescore's ``MASK_BIAS`` then
  sinks them exactly like the exact path);
- a query ignoring a flag carries the half-bits pattern ``0x5555...``:
  popcount 16 per word against both all-zeros and all-ones groups — a
  constant offset that never reorders candidates.

Coarse selection has two set-identical implementations (dispatched on
``use_pallas``): the fused ``hamming_topk`` kernel on TPU, and a
sort-free counting-threshold mask on the XLA fallback (binary-search
the C-th smallest distance — a handful of O(N) streaming passes,
because XLA CPU lowers coarse-C ``top_k`` to an O(N·C) partial sort
that costs more than the dense scan it is meant to beat).  The rescore
gathers the candidate rows in ascending row order into one sub-matrix
and computes one 2-D ``q_aug @ sub.T`` matmul — column reductions are
independent of which other columns are present, so the rescored scores
are bitwise-equal to the exact scan's scores for the same rows, and
``lax.top_k`` over the ascending-row columns reproduces the exact
path's (score desc, row asc) tie-break with no explicit lexsort.

``sharded_quantized_topk`` is the collective form: ONE ``shard_map``
program runs coarse + gather + rescore per local shard slot, maps rows
to global sequence numbers, all_gathers the tiny candidate block, and
merges with the lowest-sequence tie-break — the quantized twin of
``mips_topk.sharded_mips_topk``, sharing its launch counter.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.common import cdiv, on_tpu
from repro.kernels.hamming_topk.ops import hamming_topk
from repro.kernels.hamming_topk.ref import hamming_dist_ref
from repro.kernels.lsh_hash.ops import lsh_hash
from repro.kernels.mips_topk import ops as mips_ops
from repro.kernels.mips_topk.ops import augment_queries

# db-side flag word values: group all-ones = flagged, all-zeros = not
_FLAG_SET = np.uint32(0xFFFFFFFF)
# query-side "ignore this flag" pattern: popcount 16 against both the
# all-ones and the all-zeros group — a constant, order-preserving offset
_FLAG_IGNORE = np.uint32(0x55555555)
# rescore padding for duplicate gathers: below every real or
# MASK_BIAS-masked (~-3e30) score, so a duplicate can only surface when
# the candidate pool is exhausted (it never is: distinct >= C >= k)
_DUP_PAD = float(np.finfo(np.float32).min)


@dataclass(frozen=True)
class QuantSpec:
    """Static layout of a compressed code plane (hashable: it keys the
    jitted helpers and the persisted snapshot fields)."""

    dim: int       # fp32 embedding width d (codes hash rows[:, :dim])
    n_bits: int    # hyperplane count = real code bits
    n_flags: int   # trailing indicator columns mirrored as penalty groups
    seed: int      # hyperplane PRNG seed (persisted with the store)

    @property
    def code_words(self) -> int:
        return cdiv(self.n_bits, 32)

    @property
    def flag_words(self) -> int:
        # penalty group width: 32 * flag_words must EXCEED n_bits so a
        # penalized flag outranks any real code distance
        return cdiv(self.n_bits + 1, 32)

    @property
    def n_words(self) -> int:
        return self.code_words + self.n_flags * self.flag_words

    def flag_group(self, flag: int) -> Tuple[int, int]:
        """Column span ``[lo, hi)`` of one flag's penalty group."""
        lo = self.code_words + flag * self.flag_words
        return lo, lo + self.flag_words


def hyperplanes(spec: QuantSpec) -> np.ndarray:
    """The persisted scan hyperplanes: ``(dim, n_bits)`` float32 drawn
    from PCG64(seed) — same derivation discipline as
    ``core/lsh.HyperplaneLSH``, so a restored store re-derives codes
    identical to the ones it snapshotted under."""
    gen = np.random.Generator(np.random.PCG64(spec.seed))
    return gen.standard_normal((spec.dim, spec.n_bits)) \
        .astype(np.float32)


def encode_rows(rows: jnp.ndarray, flags: jnp.ndarray,
                planes: jnp.ndarray, spec: QuantSpec, *,
                use_pallas: bool | None = None,
                interpret: bool | None = None) -> jnp.ndarray:
    """DB-side codes: ``(m, dim)`` rows + ``(m, n_flags)`` indicator
    columns -> ``(m, n_words)`` uint32 (code words | flag groups)."""
    codes = lsh_hash(rows, planes, use_pallas=use_pallas,
                     interpret=interpret)
    m = rows.shape[0]
    groups = [codes]
    for j in range(spec.n_flags):
        word = jnp.where(flags[:, j] > 0, _FLAG_SET, jnp.uint32(0))
        groups.append(jnp.broadcast_to(word[:, None],
                                       (m, spec.flag_words)))
    return jnp.concatenate(groups, axis=1)


def encode_queries(q: jnp.ndarray, planes: jnp.ndarray,
                   flag_bias: Tuple[float, ...], spec: QuantSpec, *,
                   use_pallas: bool | None = None,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Query-side codes: the flag groups encode the (static) bias —
    all-zeros to penalize a masked flag, half-bits to ignore it."""
    codes = lsh_hash(q, planes, use_pallas=use_pallas,
                     interpret=interpret)
    b = q.shape[0]
    groups = [codes]
    for bias in flag_bias:
        word = jnp.uint32(0) if bias != 0.0 else _FLAG_IGNORE
        groups.append(jnp.full((b, spec.flag_words), word, jnp.uint32))
    return jnp.concatenate(groups, axis=1)


def _coarse_mask(dist: jnp.ndarray, n_coarse: int, *,
                 maxd: int) -> jnp.ndarray:
    """Exact top-C candidate mask by ``(distance, row index)`` — the
    same SET ``hamming_topk``'s top-C returns, without a sort.

    Hamming distances are small bounded ints (``maxd = 32 * n_words``),
    so the C-th smallest distance per query falls out of a
    ``ceil(log2(maxd + 1))``-step binary search over counting passes —
    O(N) streaming compares instead of the O(N·C) partial sort XLA
    lowers coarse-C ``top_k`` to.  The boundary distance class is then
    filled lowest-index-first (rank by running count), which
    reproduces ``lax.top_k``'s tie-break exactly."""
    b = dist.shape[0]
    lo = jnp.zeros((b,), jnp.int32)
    hi = jnp.full((b,), maxd, jnp.int32)
    # invariant: count(dist <= hi) >= C; converges to the C-th
    # smallest distance t = final hi (count(dist <= maxd) = N >= C)
    for _ in range(max(1, (maxd + 1).bit_length())):
        mid = (lo + hi) // 2
        cnt = jnp.sum((dist <= mid[:, None]).astype(jnp.int32),
                      axis=-1)
        ge = cnt >= n_coarse
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, mid + 1)
    t = hi[:, None]
    below = dist < t
    n_below = jnp.sum(below.astype(jnp.int32), axis=-1, keepdims=True)
    eq = dist == t
    eq_rank = jnp.cumsum(eq.astype(jnp.int32), axis=-1)  # 1-based
    return below | (eq & (eq_rank <= n_coarse - n_below))


def _two_stage(q_aug: jnp.ndarray, q_codes: jnp.ndarray,
               db: jnp.ndarray, codes: jnp.ndarray, k: int,
               n_coarse: int, *, use_pallas: bool | None,
               interpret: bool | None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Coarse top-C -> gather -> exact rescore over one 2-D buffer.

    Both coarse implementations select the identical candidate set
    (top-C by ``(Hamming distance, row index)``), and the rescore
    gathers candidates in ascending row order — so ``lax.top_k`` over
    the rescored columns reproduces the exact path's
    ``(score desc, row asc)`` contract without an explicit lexsort,
    and the two dispatch paths return bitwise-identical results:

    - Pallas (TPU): the fused ``hamming_topk`` kernel emits per-query
      top-C indices; the flattened index lists are sorted, duplicate
      gathers masked to ``_DUP_PAD``.
    - XLA fallback: xor+popcount distances, then a counting-threshold
      mask (``_coarse_mask``) and ONE union gather of every selected
      row — no per-query index materialization, no sort (XLA CPU sorts
      and coarse-C ``top_k`` cost more than the dense scan they are
      meant to beat).

    One 2-D ``q_aug @ sub.T`` matmul rescores the gathered rows —
    column reductions are independent of which other columns are
    present, so rescored scores are bitwise-equal to the dense scan's
    for the same rows.  At least ``n_coarse >= k`` distinct candidates
    always survive masking, so padding never reaches the top-k."""
    if use_pallas is None:
        use_pallas = on_tpu()
    n = db.shape[0]
    if use_pallas:
        _, cand = hamming_topk(q_codes, codes, n_coarse,
                               use_pallas=True, interpret=interpret)
        cand = cand.astype(jnp.int32)
        b = cand.shape[0]
        # per-query ownership mask: a query rescores ONLY its own
        # top-C (results must not depend on batch co-occupants)
        sel = jnp.zeros((b, n), bool).at[
            jnp.arange(b)[:, None], cand].set(True)
        flat = jnp.sort(cand.reshape(-1))
        dup = jnp.concatenate([jnp.zeros((1,), bool),
                               flat[1:] == flat[:-1]])
        sub = jnp.take(db, flat, axis=0)
        scores = jnp.matmul(q_aug, sub.T,        # (B, B*C) exact fp32
                            precision=jax.lax.Precision.HIGHEST)
        cols = jnp.broadcast_to(flat[None, :], scores.shape)
        keep = jnp.take_along_axis(sel, cols, axis=1) & ~dup[None, :]
        scores = jnp.where(keep, scores, _DUP_PAD)
        vals, ci = jax.lax.top_k(scores, k)
        return vals, jnp.take_along_axis(cols, ci, axis=1)
    dist = hamming_dist_ref(q_codes, codes)
    sel = _coarse_mask(dist, n_coarse,
                       maxd=32 * int(codes.shape[-1]))
    b = q_aug.shape[0]
    u = min(b * n_coarse, n)
    union = jnp.nonzero(jnp.any(sel, axis=0), size=u,
                        fill_value=n)[0].astype(jnp.int32)
    valid = union < n
    uc = jnp.minimum(union, n - 1)               # clamp the padding
    cols = jnp.broadcast_to(uc[None, :], (b, u))
    sub = jnp.take(db, uc, axis=0)
    scores = jnp.matmul(q_aug, sub.T,            # (B, U) exact fp32
                        precision=jax.lax.Precision.HIGHEST)
    keep = jnp.take_along_axis(sel, cols, axis=1) & valid[None, :]
    scores = jnp.where(keep, scores, _DUP_PAD)
    vals, ci = jax.lax.top_k(scores, k)
    return vals, jnp.take_along_axis(cols, ci, axis=1)


@functools.partial(jax.jit, static_argnames=(
    "k", "n_coarse", "flag_bias", "spec", "use_pallas", "interpret"))
def _quantized_flagged_topk(q, db_flagged, codes, planes, *, k,
                            n_coarse, flag_bias, spec, use_pallas,
                            interpret):
    q_aug = augment_queries(q, flag_bias)
    qc = encode_queries(q, planes, flag_bias, spec,
                        use_pallas=use_pallas, interpret=interpret)
    return _two_stage(q_aug, qc, db_flagged, codes, k, n_coarse,
                      use_pallas=use_pallas, interpret=interpret)


def quantized_flagged_topk(q: jnp.ndarray, db_flagged: jnp.ndarray,
                           codes: jnp.ndarray, k: int, n_coarse: int,
                           flag_bias: Tuple[float, ...],
                           planes: jnp.ndarray, spec: QuantSpec, *,
                           use_pallas: bool | None = None,
                           interpret: bool | None = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Two-stage flag-masked top-k over one shard: the quantized twin
    of ``flagged_mips_topk``, fused into ONE launch (encode + coarse +
    gather + rescore).  Requires ``k <= n_coarse <= rows``; returns
    ``(vals, row_idx)`` with scores bitwise-equal to the exact scan's
    for the rows it returns."""
    assert k <= n_coarse <= db_flagged.shape[0], \
        (k, n_coarse, db_flagged.shape)
    assert codes.shape == (db_flagged.shape[0], spec.n_words), \
        (codes.shape, db_flagged.shape, spec)
    mips_ops._LAUNCHES.inc()
    return _quantized_flagged_topk(
        q, db_flagged, codes, planes, k=int(k), n_coarse=int(n_coarse),
        flag_bias=tuple(flag_bias), spec=spec, use_pallas=use_pallas,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "k_shard", "k_out", "n_coarse", "flag_bias", "spec", "mesh",
    "axis_names", "use_pallas", "interpret"))
def _sharded_quantized_topk(q, db, codes, seq, planes, *, k_shard,
                            k_out, n_coarse, flag_bias, spec, mesh,
                            axis_names, use_pallas, interpret):
    # query encoding is replicated work, folded into the one launch
    q_aug = augment_queries(q, flag_bias)
    qc = encode_queries(q, planes, flag_bias, spec,
                        use_pallas=use_pallas, interpret=interpret)
    lead = axis_names if len(axis_names) != 1 else axis_names[0]

    def scan_gather_merge(qa, qcs, db_loc, codes_loc, seq_loc):
        vs, ss = [], []
        for j in range(db_loc.shape[0]):  # static unroll over slots
            v, r = _two_stage(qa, qcs, db_loc[j], codes_loc[j],
                              k_shard, n_coarse,
                              use_pallas=use_pallas,
                              interpret=interpret)
            vs.append(v)
            ss.append(jnp.take(seq_loc[j], r))  # local row -> global seq
        v = jax.lax.all_gather(jnp.stack(vs), axis_names, axis=0,
                               tiled=True)
        s = jax.lax.all_gather(jnp.stack(ss), axis_names, axis=0,
                               tiled=True)
        return mips_ops._merge_sharded_topk(v, s, k_out)

    return jax.shard_map(
        scan_gather_merge, mesh=mesh,
        in_specs=(P(None, None), P(None, None), P(lead, None, None),
                  P(lead, None, None), P(lead, None)),
        out_specs=(P(None, None), P(None, None)), check_vma=False)(
            q_aug, qc, db, codes, seq)


def sharded_quantized_topk(q: jnp.ndarray, db_stacked: jnp.ndarray,
                           codes_stacked: jnp.ndarray,
                           seq_stacked: jnp.ndarray,
                           planes: jnp.ndarray, k_shard: int,
                           k_out: int, n_coarse: int,
                           flag_bias: Tuple[float, ...],
                           spec: QuantSpec, *, mesh,
                           axis_names: Sequence[str] = ("data",),
                           use_pallas: bool | None = None,
                           interpret: bool | None = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Collective two-stage sharded top-k in ONE ``shard_map`` launch:
    per-device coarse + gather + rescore over each local shard slot's
    ``(cap, n_words)`` code plane and ``(cap, d + F)`` rows, sequence
    mapping, all_gather of the ``(S, b, k_shard)`` candidates, and the
    lowest-sequence lexsort merge — the quantized twin of
    ``sharded_mips_topk`` (same specs, same merge, same counter)."""
    s, cap, _ = db_stacked.shape
    assert codes_stacked.shape == (s, cap, spec.n_words), \
        (codes_stacked.shape, db_stacked.shape, spec)
    assert k_shard <= n_coarse <= cap and s * k_shard >= k_out, \
        (db_stacked.shape, k_shard, n_coarse, k_out)
    mips_ops._LAUNCHES.inc()
    return _sharded_quantized_topk(
        q, db_stacked, codes_stacked, seq_stacked, planes,
        k_shard=int(k_shard), k_out=int(k_out),
        n_coarse=int(n_coarse), flag_bias=tuple(flag_bias), spec=spec,
        mesh=mesh, axis_names=tuple(axis_names),
        use_pallas=use_pallas, interpret=interpret)
