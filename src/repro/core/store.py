"""Incremental, device-resident flat index — single-buffer and sharded.

Mirrors the FAISS IndexFlat role in the paper, implemented on the
``mips_topk`` kernel, but maintained *incrementally*: instead of
re-stacking every embedding after each graph version bump (O(N) host
work per insert), the store consumes the graph's per-version
``(added_ids, removed_ids)`` deltas — new rows are appended into a
preallocated, geometrically-grown device buffer and removed rows are
tombstoned in place.  Tombstones are masked at query time through the
buffer's trailing indicator columns (``[emb | dead | summary | leaf]``)
plus a per-query bias vector (``flagged_mips_topk``), which also serves
layer filtering without any host-side row gathering.  When tombstones
exceed ``compact_threshold`` of a shard the store compacts it with one
on-device gather, preserving row order so top-k tie-breaking stays
bitwise-identical to a from-scratch rebuild.

All buffer maintenance lives in one place: ``_Shard`` owns the host
metadata and ``_StackedBuffers`` the device arrays — the single-buffer
``VectorStore`` is exactly one shard over a one-slot group; the
``ShardedVectorStore`` is N of them behind hash routing — so growth,
tombstoning, compaction, and persistence can never diverge between the
two stores.

Sharded design (``ShardedVectorStore``)
---------------------------------------
The row set is split over the ``data`` mesh axis: every node id is
hash-routed (stable blake2 of the id, mod ``n_shards``) to one owning
shard, so per-version deltas cost O(delta) *per shard* and per-chip
memory stays O(N / n_shards).  The shard buffers live in ONE stacked
``(n_shards, cap, d + N_FLAGS)`` device array whose slot dim is laid
out over the ``db_shards`` mesh axes by the ``common/sharding.py``
rules engine (``retrieval_rules`` + ``stacked_db_shardings``); slots
grow in LOCKSTEP to a shared capacity, with padding rows carrying the
dead flag (and a sentinel sequence number) so ``MASK_BIAS`` excludes
them for free.  A shard count that does not divide the device count is
padded up with permanently-empty slots rather than ever collapsing
rows onto one device.

Queries run as ONE collective launch (``sharded_mips_topk``): a single
``shard_map`` program scans every device's local slots with the
flag-masked MIPS kernel, maps local rows to global sequence numbers
through the on-device ``(n_shards, cap)`` seq plane, ``all_gather``s
the tiny ``(s, b, k)`` candidate block, and merges with the
lowest-sequence tie-break — no per-shard host dispatch, no host-side
merge.  The per-shard dispatch loop (one ``mips_topk`` per shard plus
a host-padded ``merge_sharded_topk``) remains as the differential
parity oracle and the fallback, selected by ``collective=False`` or
automatically when no multi-device mesh is available.

Compaction is OFF the query path: ``refresh()`` commits at most one
previously-scheduled shard compaction and schedules at most one new
one (shards rotate round-robin; the rest are deferred and counted in
``StoreStats.compactions_skipped``).  The scheduled gather lands in a
double buffer that is swapped in at the NEXT refresh, so a query
issued between refreshes never depends on a compaction gather —
tombstoned rows are masked anyway, making the deferral bitwise
invisible.  ``compact()`` stays as the forced, flush-everything escape
hatch.

Lifecycle (``repro.lifecycle``): the shard count is no longer frozen
at construction.  ``refresh()`` runs one lifecycle turn per call —
consult the attached ``LifecyclePolicy`` (skew / tombstone thresholds)
for a ``ReshardPlan``, build ONE staged target shard of an in-flight
``ShardMigration``, and, when the staging epoch is complete, commit it
with an atomic ``install_epoch`` swap (the migration analogue of the
compaction double buffer: queries issued mid-migration always serve
the OLD epoch, and the replayed store is bitwise-identical to a fresh
build at the target shard count).  ``export_rows`` is the replay
source; each store owns a private routing LRU (``_Router``) whose
hit/miss/bulk counters are exactly its own traffic.

Invariants (asserted by ``tests/test_store_sharded.py`` and
``tests/test_store_collective.py``):

- **routing determinism**: a node id's owning shard is a pure function
  of the id — the same corpus always shards the same way, across
  processes and restarts (bulk paths route through one vectorized
  blake2 pass that bypasses the small LRU instead of thrashing it).
- **global order parity**: every appended row carries a monotone global
  sequence number (graph node-creation order); within a shard, row
  order is always a subsequence of it (compaction preserves relative
  order), and the merge — host-side or in-collective — breaks score
  ties by lowest sequence.  Sharded ``search``/``search_batch``
  results are therefore *bitwise identical* to the single-buffer store
  and to a from-scratch rebuild, on either dispatch path.
- **lockstep growth**: all shard slots share one capacity after any
  delta replay — the precondition for the stacked collective scan.
- **delta locality**: a delta only touches the slots of the shards
  that own its ids; all other shards stage zero rows.

Two-stage quantized retrieval (``quantized=True``)
--------------------------------------------------
The store can maintain a COMPRESSED PLANE next to the fp32 rows: a
``(S, cap, n_words)`` uint32 stack of packed LSH sign-bit codes
(``kernels/lsh_hash`` over hyperplanes derived from the persisted
``scan_seed``), laid out with the same ``NamedSharding`` as the row
stack.  Queries then run the fused two-stage pipeline of
``kernels/quantized_scan`` — coarse Hamming top-C over the codes,
exact fp32 rescore of only the C gathered candidate rows — on every
dispatch path (flat, per-shard loop, and inside the one collective
``shard_map`` program), with ``C = coarse_mult * k`` clamped to the
capacity.  Scores are always REAL inner products (bitwise-equal to
the dense scan's for the rows returned); only WHICH rows make the
candidate set is approximate, so the exact path stays available as
the differential oracle (flip ``store.quantized``) with an asserted
recall floor (``tests/test_store_quantized.py``).

Compressed-plane invariants (everything the delta machinery must
preserve, asserted by the differential suite):

- **hash-at-append, once**: rows are encoded inside the same
  ``write_rows`` that uploads the fp32 block — on the incremental
  append, AND on ``load_state`` (snapshot restore / reshard replay),
  which funnels through the identical write.  The codes can never
  drift from the rows they mirror, and an epoch swap re-quantizes
  for free.
- **flag mirroring**: each buffer flag column is mirrored as a
  penalty word group in the code (all-ones when set): tombstoning
  flips the dead group IN PLACE (no rehash), and layer filters
  penalize their group through the query-side code so filtered rows
  lose the coarse ranking before they are ever gathered.
- **row alignment under compaction**: the code plane gathers by the
  SAME ``keep`` index as the fp32 double-buffer gather and commits in
  the same swap, so row <-> code alignment survives compaction
  bitwise.
- **derived, never persisted**: ``state_dict`` stores only the scan
  hyperparameters (``scan_bits`` / ``scan_seed`` / ``coarse_mult``);
  restore re-derives the hyperplanes from the seed and re-hashes, so
  restored codes match the saved store's exactly.

Queries are batched end-to-end: ``search_batch`` serves a ``(B, d)``
query block in one launch (collective) or one launch per shard
(fallback); ``search`` is the B=1 special case.  ``stats`` counts
refreshes, staged rows, tombstones, compactions (committed, and
skipped by the rotation), and routing-cache hits/misses; both stores
serialize with ``state_dict``/``from_state`` — paired with the graph's
persisted delta-log tail, a restored store resumes incrementally
instead of paying a full O(N) re-stack.
"""
from __future__ import annotations

import functools
import hashlib
import logging
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.mips_topk.ops import MASK_BIAS, augment_queries, \
    flagged_mips_topk, merge_sharded_topk, mips_topk, sharded_mips_topk
from repro.kernels.quantized_scan.ops import QuantSpec, encode_rows, \
    hyperplanes, quantized_flagged_topk, sharded_quantized_topk
from repro.obs.trace import NULL_TRACER

logger = logging.getLogger(__name__)

# trailing indicator columns of the device buffer
N_FLAGS = 3
_DEAD, _SUMMARY, _LEAF = 0, 1, 2

# sentinels for per-shard candidate padding: a value below every real
# (or even MASK_BIAS-masked, ~-3e30) score, and a sequence number above
# every real row's, so padded candidates always merge last.  The merge
# runs in int32 (jax default; x64 is disabled), so the monotone global
# counter is renumbered — host-side metadata only, order-preserving —
# before it can ever reach the sentinel / wrap (see _BaseStore._append).
_VAL_PAD = float(np.finfo(np.float32).min)
_SEQ_PAD = np.int64(2**31 - 1)
_SEQ_LIMIT = 2**31 - 2**16


@dataclass
class Hit:
    node_id: str
    score: float
    layer: int
    # global insertion-order sequence of the row that scored this hit:
    # the deterministic tie-break (matching the kernel-side
    # lowest-index merge) when callers combine hits from separate
    # scans whose scores collide
    seq: int = -1


@dataclass
class StoreStats:
    """Instrumented refresh counters (O(delta) maintenance evidence)."""

    refreshes: int = 0
    full_rebuilds: int = 0
    rows_staged: int = 0       # host rows uploaded to the device buffer
    rows_tombstoned: int = 0
    compactions: int = 0       # committed double-buffer swaps
    compactions_skipped: int = 0  # over-threshold shards deferred by
    # the one-shard-per-refresh rotation (they compact on a later turn)
    rows_compacted: int = 0
    growths: int = 0
    # id-routing cache movement (per store instance — each store owns
    # its routing LRU, so counters never bleed across stores/tests)
    route_hits: int = 0
    route_misses: int = 0
    bulk_routed: int = 0
    # lifecycle: epoch-swapped live resharding (see repro.lifecycle)
    reshards: int = 0        # committed epoch swaps
    reshard_steps: int = 0   # staged target shards built by refresh()
    # two-stage quantized retrieval: search launches served through the
    # coarse sign-bit scan + exact rescore instead of the dense scan
    quantized_scans: int = 0
    # host-side jitted dispatches issued by THIS store's query paths
    # (per-instance twin of the process-global kernel launch counter in
    # kernels/mips_topk/ops — per-store so concurrently-live stores
    # never bleed into each other's accounting)
    kernel_launches: int = 0


# ---------------------------------------------------------------------------
# id routing
# ---------------------------------------------------------------------------

_ROUTE_LRU_SIZE = 1 << 16
# at/above this many ids, routing bypasses the LRU: a full replay of a
# >65k-id corpus would otherwise evict every useful entry (pure-miss
# thrash) while paying the cache bookkeeping on top of the hashing
_BULK_ROUTE_MIN = 4096


def _route(node_id: str, n_shards: int) -> int:
    """Stable owning shard of a node id (pure content hash — identical
    across processes, restarts, and PYTHONHASHSEED)."""
    h = hashlib.blake2b(node_id.encode(), digest_size=8).digest()
    return int.from_bytes(h, "big") % n_shards


def _bulk_route(ids: List[str], n_shards: int) -> np.ndarray:
    """One blake2 sweep over the ids, then a single vectorized
    big-endian reduce + mod — the LRU-bypass bulk pass."""
    raw = b"".join(hashlib.blake2b(i.encode(), digest_size=8).digest()
                   for i in ids)
    h = np.frombuffer(raw, dtype=">u8")
    return (h % np.uint64(n_shards)).astype(np.int64)


class _Router:
    """One routing cache + its counters.

    A small LRU absorbs the delta path asking for the same id up to
    three times (stale check, tombstone routing, append routing)
    without pinning the whole corpus's ids; batches at/above
    ``_BULK_ROUTE_MIN`` (full rebuilds / replays) bypass it so bulk
    routing never thrashes the cache the hot path depends on.

    Every store owns a PRIVATE instance, so its ``route_hits`` /
    ``route_misses`` / ``bulk_routed`` stats are exactly its own
    traffic — they can never bleed across stores or test cases the way
    a process-global counter does.  The cache key includes
    ``n_shards``, so a live reshard (new shard count) never needs an
    invalidation sweep.  The module-level ``shard_of`` /
    ``shard_of_many`` / ``routing_cache_info`` utilities are one
    shared process-global instance of the same class.
    """

    def __init__(self):
        self.cached = functools.lru_cache(
            maxsize=_ROUTE_LRU_SIZE)(_route)
        self.bulk_routed = 0

    def one(self, node_id: str, n_shards: int) -> int:
        return self.cached(node_id, n_shards)

    def many(self, ids: Sequence[str], n_shards: int) -> np.ndarray:
        ids = list(ids)
        if len(ids) < _BULK_ROUTE_MIN:
            return np.fromiter(
                (self.cached(i, n_shards) for i in ids),
                np.int64, count=len(ids))
        self.bulk_routed += len(ids)
        return _bulk_route(ids, n_shards)

    def info(self) -> Dict[str, int]:
        info = self.cached.cache_info()
        return {"hits": info.hits, "misses": info.misses,
                "size": info.currsize, "maxsize": info.maxsize,
                "bulk_routed": self.bulk_routed}

    def reset(self) -> None:
        self.cached.cache_clear()
        self.bulk_routed = 0


_global_router = _Router()
shard_of = _global_router.cached


def shard_of_many(ids: Sequence[str], n_shards: int) -> np.ndarray:
    """Route an id batch in one pass (process-global cache)."""
    return _global_router.many(ids, n_shards)


def routing_cache_info() -> Dict[str, int]:
    """Counters of the process-global routing utilities (each store
    reports its own traffic through
    ``AnyStore.routing_cache_info()``)."""
    return _global_router.info()


# ---------------------------------------------------------------------------
# stacked device buffers (jitted helpers pinned to the stack's sharding)
# ---------------------------------------------------------------------------

def _pin(sharding) -> dict:
    return {} if sharding is None else {"out_shardings": sharding}


@functools.lru_cache(maxsize=None)
def _grow_buf_fn(sharding, pad_rows: int, dim: int):
    def grow(buf):
        pad_shape = buf.shape[:-2] + (pad_rows, buf.shape[-1])
        pad = jnp.zeros(pad_shape, jnp.float32) \
            .at[..., dim + _DEAD].set(1.0)
        return jnp.concatenate([buf, pad], axis=-2)
    return jax.jit(grow, **_pin(sharding))


@functools.lru_cache(maxsize=None)
def _grow_seq_fn(sharding, pad_rows: int):
    def grow(seq):
        pad = jnp.full(seq.shape[:-1] + (pad_rows,), int(_SEQ_PAD),
                       jnp.int32)
        return jnp.concatenate([seq, pad], axis=-1)
    return jax.jit(grow, **_pin(sharding))


@functools.lru_cache(maxsize=None)
def _write_rows_fn(sharding, flat2d: bool):
    def write(buf, block, slot, row0):
        if flat2d:
            return jax.lax.dynamic_update_slice(buf, block, (row0, 0))
        return jax.lax.dynamic_update_slice(buf, block[None],
                                            (slot, row0, 0))
    return jax.jit(write, **_pin(sharding))


@functools.lru_cache(maxsize=None)
def _mark_dead_fn(sharding, flat2d: bool, dim: int):
    def mark(buf, rows, slot):
        if flat2d:
            return buf.at[rows, dim + _DEAD].set(1.0)
        return buf.at[slot, rows, dim + _DEAD].set(1.0)
    return jax.jit(mark, **_pin(sharding))


@functools.lru_cache(maxsize=None)
def _compact_buf_fn(flat2d: bool, dim: int):
    # produces a STANDALONE compacted slice (the double buffer) — it is
    # swapped into the stack only at commit time, so queries dispatched
    # between refreshes never depend on this gather
    def compacted(buf, keep, slot):
        sl = buf if flat2d else buf[slot]
        out = jnp.zeros_like(sl).at[..., dim + _DEAD].set(1.0)
        return jax.lax.dynamic_update_slice(
            out, jnp.take(sl, keep, axis=0), (0, 0))
    return jax.jit(compacted)


@functools.lru_cache(maxsize=None)
def _compact_seq_fn():
    def compacted(seq, keep, slot):
        sl = seq[slot]
        out = jnp.full_like(sl, int(_SEQ_PAD))
        return jax.lax.dynamic_update_slice(
            out, jnp.take(sl, keep, axis=0), (0,))
    return jax.jit(compacted)


@functools.lru_cache(maxsize=None)
def _commit_buf_fn(sharding, flat2d: bool):
    def commit(buf, new_slice, slot):
        if flat2d:
            return new_slice
        return jax.lax.dynamic_update_slice(buf, new_slice[None],
                                            (slot, 0, 0))
    return jax.jit(commit, **_pin(sharding))


@functools.lru_cache(maxsize=None)
def _commit_seq_fn(sharding):
    def commit(seq, new_slice, slot):
        return jax.lax.dynamic_update_slice(seq, new_slice[None],
                                            (slot, 0))
    return jax.jit(commit, **_pin(sharding))


@functools.lru_cache(maxsize=None)
def _write_seq_fn(sharding):
    def write(seq, block, slot, row0):
        return jax.lax.dynamic_update_slice(seq, block[None],
                                            (slot, row0))
    return jax.jit(write, **_pin(sharding))


# -- compressed code plane (two-stage quantized retrieval) ------------------

_CODE_DEAD = np.uint32(0xFFFFFFFF)   # a set flag's penalty-group word


def _dead_coded(codes_slice: jnp.ndarray,
                spec: QuantSpec) -> jnp.ndarray:
    """Stamp every row's DEAD penalty group set (padding rows must sort
    after all live rows in the coarse scan, mirroring the fp32 padding
    rows' dead flag)."""
    lo, hi = spec.flag_group(_DEAD)
    return codes_slice.at[..., lo:hi].set(_CODE_DEAD)


@functools.lru_cache(maxsize=None)
def _grow_codes_fn(sharding, pad_rows: int, spec: QuantSpec):
    def grow(codes):
        pad_shape = codes.shape[:-2] + (pad_rows, codes.shape[-1])
        pad = _dead_coded(jnp.zeros(pad_shape, jnp.uint32), spec)
        return jnp.concatenate([codes, pad], axis=-2)
    return jax.jit(grow, **_pin(sharding))


@functools.lru_cache(maxsize=None)
def _encode_write_fn(sharding, flat2d: bool, spec: QuantSpec):
    # rows are hashed ONCE, here, at append (or snapshot replay —
    # load_state funnels through the same write): the compressed plane
    # can never drift from the fp32 rows it mirrors
    def write(codes, block, planes, slot, row0):
        enc = encode_rows(block[:, :spec.dim], block[:, spec.dim:],
                          planes, spec)
        if flat2d:
            return jax.lax.dynamic_update_slice(codes, enc, (row0, 0))
        return jax.lax.dynamic_update_slice(codes, enc[None],
                                            (slot, row0, 0))
    return jax.jit(write, **_pin(sharding))


@functools.lru_cache(maxsize=None)
def _mark_dead_codes_fn(sharding, flat2d: bool, spec: QuantSpec):
    lo, hi = spec.flag_group(_DEAD)

    def mark(codes, rows, slot):
        if flat2d:
            return codes.at[rows, lo:hi].set(_CODE_DEAD)
        return codes.at[slot, rows, lo:hi].set(_CODE_DEAD)
    return jax.jit(mark, **_pin(sharding))


@functools.lru_cache(maxsize=None)
def _compact_codes_fn(flat2d: bool, spec: QuantSpec):
    # codes ride the SAME keep index as the fp32 gather — the two
    # planes stay row-aligned by construction
    def compacted(codes, keep, slot):
        sl = codes if flat2d else codes[slot]
        out = _dead_coded(jnp.zeros_like(sl), spec)
        return jax.lax.dynamic_update_slice(
            out, jnp.take(sl, keep, axis=0), (0, 0))
    return jax.jit(compacted)


class _StackedBuffers:
    """Device side of the store: ONE stacked ``(S, cap, d + N_FLAGS)``
    buffer (plus an optional ``(S, cap)`` int32 global-sequence plane
    for the collective query) whose slots grow in LOCKSTEP — every slot
    always has the same capacity, and padding rows carry the dead flag
    (and ``_SEQ_PAD``) so ``MASK_BIAS`` excludes them for free.

    With a mesh the slot dim is laid out over the ``db_shards`` axes
    via a ``NamedSharding`` (every mutation helper pins its output to
    the same sharding, so the layout survives update chains) and the
    whole stack is one collectively-scannable array.  The single-buffer
    store is the ``S == 1`` case, held 2-D so its hot path needs no
    per-query slicing.
    """

    def __init__(self, n_slots: int, dim: int, *, sharding=None,
                 seq_sharding=None, min_capacity: int = 64,
                 track_seqs: bool = False,
                 quant: Optional[QuantSpec] = None,
                 stats: Optional[StoreStats] = None):
        self.n_slots = int(n_slots)
        self.dim = int(dim)
        self.sharding = sharding
        self.seq_sharding = seq_sharding
        self.min_capacity = int(min_capacity)
        self.track_seqs = bool(track_seqs)
        self.quant = quant
        # hyperplanes derive from the persisted (spec.dim, n_bits,
        # seed) alone — a restored store re-quantizes to the same codes
        self.planes = None if quant is None \
            else jnp.asarray(hyperplanes(quant))
        self.stats = stats if stats is not None else StoreStats()
        self._flat2d = self.n_slots == 1 and sharding is None
        self.reset()

    def reset(self) -> None:
        self.capacity = 0
        self.buf = None   # (S, cap, d+F) | (cap, d+F) when _flat2d
        self.seq = None   # (S, cap) int32 when track_seqs
        self.codes = None  # (S, cap, W) | (cap, W) u32 when quant
        self._views: Dict[int, Tuple[int, jnp.ndarray]] = {}
        self._code_views: Dict[int, Tuple[int, jnp.ndarray]] = {}
        self._version = 0

    def _mutated(self) -> None:
        self._version += 1

    def _put(self, arr: np.ndarray, sharding):
        if sharding is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, sharding)

    def ensure(self, need: int) -> None:
        """Lockstep geometric growth: every slot reaches the same new
        capacity in one allocation (padding rows pre-flagged dead)."""
        if need <= self.capacity:
            return
        cap = max(self.min_capacity, self.capacity)
        while cap < need:
            cap *= 2
        d = self.dim
        lead = () if self._flat2d else (self.n_slots,)
        if self.buf is None:
            base = np.zeros(lead + (cap, d + N_FLAGS), np.float32)
            base[..., d + _DEAD] = 1.0
            self.buf = self._put(base, self.sharding)
            if self.track_seqs:
                self.seq = self._put(
                    np.full(lead + (cap,), _SEQ_PAD, np.int32),
                    self.seq_sharding)
            if self.quant is not None:
                codes = np.zeros(lead + (cap, self.quant.n_words),
                                 np.uint32)
                lo, hi = self.quant.flag_group(_DEAD)
                codes[..., lo:hi] = _CODE_DEAD
                # the codes plane reuses the buf NamedSharding (both
                # are (S, rows, cols) with the slot dim laid out)
                self.codes = self._put(codes, self.sharding)
        else:
            pad = cap - self.capacity
            self.buf = _grow_buf_fn(self.sharding, pad, d)(self.buf)
            if self.track_seqs:
                self.seq = _grow_seq_fn(self.seq_sharding,
                                        pad)(self.seq)
            if self.quant is not None:
                self.codes = _grow_codes_fn(self.sharding, pad,
                                            self.quant)(self.codes)
        self.capacity = cap
        self.stats.growths += 1
        self._mutated()

    def write_rows(self, slot: int, row0: int, block: np.ndarray,
                   seqs: Optional[np.ndarray] = None) -> None:
        self.buf = _write_rows_fn(self.sharding, self._flat2d)(
            self.buf, block, np.int32(slot), np.int32(row0))
        if self.track_seqs and seqs is not None:
            self.seq = _write_seq_fn(self.seq_sharding)(
                self.seq, np.asarray(seqs, np.int32), np.int32(slot),
                np.int32(row0))
        if self.quant is not None:
            # hash-at-append: the block's flag columns (incl. a
            # snapshot's tombstones) become penalty word groups
            self.codes = _encode_write_fn(
                self.sharding, self._flat2d, self.quant)(
                self.codes, block, self.planes, np.int32(slot),
                np.int32(row0))
        self._mutated()

    def upload_seqs(self, slot: int, seqs: np.ndarray) -> None:
        """Re-stamp a slot's sequence prefix (renumbering support)."""
        if not self.track_seqs or len(seqs) == 0:
            return
        self.seq = _write_seq_fn(self.seq_sharding)(
            self.seq, np.asarray(seqs, np.int32), np.int32(slot),
            np.int32(0))
        self._mutated()

    def mark_dead(self, slot: int, rows: np.ndarray) -> None:
        rows = np.asarray(rows, np.int32)
        self.buf = _mark_dead_fn(self.sharding, self._flat2d,
                                 self.dim)(
            self.buf, rows, np.int32(slot))
        if self.quant is not None:
            # tombstones flip the dead penalty group in place: no
            # rehash — the code words stay whatever the row hashed to
            self.codes = _mark_dead_codes_fn(
                self.sharding, self._flat2d, self.quant)(
                self.codes, rows, np.int32(slot))
        self._mutated()

    def compact_gather(self, slot: int, keep: np.ndarray):
        """Dispatch the order-preserving gather into a DOUBLE BUFFER
        (standalone slice arrays); the stack is untouched until
        ``commit_compacted`` swaps them in.  The codes plane gathers
        by the SAME keep index, so the two planes stay row-aligned."""
        keep = np.asarray(keep, np.int32)
        buf_slice = _compact_buf_fn(self._flat2d, self.dim)(
            self.buf, keep, np.int32(slot))
        seq_slice = None
        if self.track_seqs:
            seq_slice = _compact_seq_fn()(self.seq, keep,
                                          np.int32(slot))
        codes_slice = None
        if self.quant is not None:
            codes_slice = _compact_codes_fn(self._flat2d, self.quant)(
                self.codes, keep, np.int32(slot))
        return buf_slice, seq_slice, codes_slice

    def commit_compacted(self, slot: int, compacted) -> None:
        buf_slice, seq_slice, codes_slice = compacted
        self.buf = _commit_buf_fn(self.sharding, self._flat2d)(
            self.buf, buf_slice, np.int32(slot))
        if self.track_seqs and seq_slice is not None:
            self.seq = _commit_seq_fn(self.seq_sharding)(
                self.seq, seq_slice, np.int32(slot))
        if self.quant is not None and codes_slice is not None:
            # _commit_buf_fn is dtype-agnostic (jit retraces per
            # dtype), so the uint32 plane commits through the same path
            self.codes = _commit_buf_fn(self.sharding, self._flat2d)(
                self.codes, codes_slice, np.int32(slot))
        self._mutated()

    def slice_view(self, slot: int) -> jnp.ndarray:
        """Per-slot 2-D view for the per-shard fallback scan, memoized
        per mutation version (the collective path never materializes
        these; the flat store's view is the buffer itself)."""
        if self._flat2d:
            return self.buf
        cached = self._views.get(slot)
        if cached is not None and cached[0] == self._version:
            return cached[1]
        view = self.buf[slot]
        self._views[slot] = (self._version, view)
        return view

    def codes_view(self, slot: int) -> jnp.ndarray:
        """Per-slot 2-D code-plane view (quantized fallback scan),
        memoized per mutation version like ``slice_view``."""
        if self._flat2d:
            return self.codes
        cached = self._code_views.get(slot)
        if cached is not None and cached[0] == self._version:
            return cached[1]
        view = self.codes[slot]
        self._code_views[slot] = (self._version, view)
        return view

    def read_rows(self, slot: int, n: int) -> np.ndarray:
        if n == 0:
            return np.zeros((0, self.dim + N_FLAGS), np.float32)
        sl = self.buf if self._flat2d else self.buf[slot]
        return np.asarray(sl[:n])


class _Shard:
    """Host metadata + maintenance for one slot of a
    ``_StackedBuffers`` group: id <-> row maps, layers, global
    sequence numbers, alive bits.  Device work (lockstep growth, slice
    updates, tombstone flags, double-buffered compaction gathers) is
    delegated to the group, so the flat and sharded stores can never
    diverge.  Each row carries a global sequence number (node-creation
    order) so cross-shard top-k ties merge exactly like a single
    buffer's row-index tie-break."""

    def __init__(self, dim: int, group: _StackedBuffers, slot: int, *,
                 stats: Optional[StoreStats] = None):
        self.dim = dim
        self.group = group
        self.slot = slot
        self.stats = stats if stats is not None else StoreStats()
        self.reset()

    def reset(self) -> None:
        self.count = 0              # rows in use, tombstones included
        self.n_dead = 0
        self.row_ids: List[str] = []
        self.row_layers = np.zeros((0,), np.int32)
        self.row_seq = np.zeros((0,), np.int64)  # global order
        self.alive = np.zeros((0,), bool)
        self.row_of: Dict[str, int] = {}
        self.n_alive = {"leaf": 0, "summary": 0}

    @property
    def capacity(self) -> int:
        return self.group.capacity

    @property
    def buf(self) -> jnp.ndarray:
        """This shard's (cap, d+F) buffer view (fallback-scan path)."""
        return self.group.slice_view(self.slot)

    def _grow_host(self, need: int) -> None:
        have = len(self.row_layers)
        if need <= have:
            return
        n = max(self.group.min_capacity, have)
        while n < need:
            n *= 2
        pad = n - have
        self.row_layers = np.concatenate(
            [self.row_layers, np.zeros((pad,), np.int32)])
        self.row_seq = np.concatenate(
            [self.row_seq, np.full((pad,), _SEQ_PAD, np.int64)])
        self.alive = np.concatenate(
            [self.alive, np.zeros((pad,), bool)])

    def append(self, nodes: dict, ids: Sequence[str],
               seqs: Sequence[int]) -> None:
        """Stage ``len(ids)`` new rows — the only host->device copy on
        the incremental path, O(delta) not O(N)."""
        if not ids:
            return
        m = len(ids)
        d = self.dim
        self.group.ensure(self.count + m)   # lockstep growth
        self._grow_host(self.count + m)
        block = np.zeros((m, d + N_FLAGS), np.float32)
        seq_arr = np.zeros((m,), np.int64)
        for j, (nid, seq) in enumerate(zip(ids, seqs)):
            node = nodes[nid]
            block[j, :d] = node.embedding
            cls = "summary" if node.layer > 0 else "leaf"
            block[j, d + (_SUMMARY if node.layer > 0 else _LEAF)] = 1.0
            row = self.count + j
            self.row_ids.append(nid)
            self.row_layers[row] = node.layer
            self.row_seq[row] = seq
            seq_arr[j] = seq
            self.alive[row] = True
            self.row_of[nid] = row
            self.n_alive[cls] += 1
        self.group.write_rows(self.slot, self.count, block, seq_arr)
        self.count += m
        self.stats.rows_staged += m

    def seqs_at(self, rows: np.ndarray) -> np.ndarray:
        """Global sequence numbers for kernel-returned row indices.

        The scan covers the full LOCKSTEP capacity, so it can return
        padding rows past this shard's own staged prefix (another
        shard's append may have grown the group); size the host arrays
        up first so those rows resolve to the ``_SEQ_PAD`` sentinel
        instead of walking off the end."""
        self._grow_host(self.capacity)
        return self.row_seq[rows]

    def tombstone(self, ids: Sequence[str]) -> List[int]:
        """Flag rows dead in place; returns the retired global
        sequence numbers (the store drops them from its seq map)."""
        rows = []
        seqs: List[int] = []
        for nid in ids:
            row = self.row_of.pop(nid, None)
            if row is None or not self.alive[row]:
                continue
            self.alive[row] = False
            cls = "summary" if self.row_layers[row] > 0 else "leaf"
            self.n_alive[cls] -= 1
            rows.append(row)
            seqs.append(int(self.row_seq[row]))
        if rows:
            self.group.mark_dead(self.slot, np.asarray(rows, np.int32))
            self.n_dead += len(rows)
            self.stats.rows_tombstoned += len(rows)
        return seqs

    # -- compaction: schedule (gather into double buffer) / commit ----
    def schedule_compact(self):
        """Dispatch the order-preserving gather of live rows into a
        double buffer; the swap happens at ``commit_compact`` (the next
        refresh), so no query issued in between depends on it."""
        keep = np.nonzero(self.alive[:self.count])[0]
        return keep, self.group.compact_gather(self.slot, keep)

    def commit_compact(self, keep: np.ndarray, compacted) -> None:
        self.group.commit_compacted(self.slot, compacted)
        n = len(keep)
        self.row_ids = [self.row_ids[i] for i in keep]
        size = len(self.row_layers)
        layers = np.zeros((size,), np.int32)
        layers[:n] = self.row_layers[keep]
        self.row_layers = layers
        seqs = np.full((size,), _SEQ_PAD, np.int64)
        seqs[:n] = self.row_seq[keep]
        self.row_seq = seqs
        alive = np.zeros((size,), bool)
        alive[:n] = True
        self.alive = alive
        self.row_of = {nid: i for i, nid in enumerate(self.row_ids)}
        self.count = n
        self.n_dead = 0
        self.stats.compactions += 1
        self.stats.rows_compacted += n

    def compact_now(self) -> None:
        """Forced, inline compaction (``compact()`` escape hatch)."""
        keep, compacted = self.schedule_compact()
        self.commit_compact(keep, compacted)

    def valid_count(self, layer_filter: Optional[str]) -> int:
        if layer_filter == "leaf":
            return self.n_alive["leaf"]
        if layer_filter == "summary":
            return self.n_alive["summary"]
        return self.n_alive["leaf"] + self.n_alive["summary"]

    def state_dict(self) -> dict:
        return {
            "buf": self.group.read_rows(self.slot, self.count),
            "row_ids": list(self.row_ids),
            "row_layers": self.row_layers[:self.count].copy(),
            "row_seq": self.row_seq[:self.count].copy(),
            "alive": self.alive[:self.count].copy(),
        }

    def load_state(self, state: dict) -> None:
        self.reset()
        ids = list(state["row_ids"])
        n = len(ids)
        if not n:
            return
        buf = np.asarray(state["buf"], np.float32)
        if buf.shape != (n, self.dim + N_FLAGS):
            raise ValueError(
                f"snapshot buffer is {buf.shape}, store expects "
                f"({n}, {self.dim + N_FLAGS}) — embed_dim mismatch or "
                f"truncated state")
        self.group.ensure(n)
        self._grow_host(n)
        self.row_ids = ids
        layers = np.asarray(state["row_layers"], np.int32)
        self.row_layers[:n] = layers
        self.row_seq[:n] = np.asarray(state["row_seq"], np.int64)
        self.group.write_rows(self.slot, 0, buf, self.row_seq[:n])
        alive = np.asarray(state["alive"], bool)
        self.alive[:n] = alive
        self.count = n
        self.n_dead = int(n - alive.sum())
        # vectorized alive bookkeeping: this is the reshard-replay hot
        # path (every staged target shard loads through here)
        live = np.nonzero(alive)[0]
        self.row_of = {ids[int(r)]: int(r) for r in live}
        n_sum = int(np.count_nonzero(layers[live] > 0))
        self.n_alive = {"summary": n_sum, "leaf": len(live) - n_sum}


def pack_export_rows(ids: List[str], layers: List[np.ndarray],
                     seqs: List[np.ndarray], rows: List[np.ndarray],
                     dim: int) -> Dict[str, np.ndarray]:
    """Assemble the canonical replay payload from per-shard alive-row
    pieces: ``{"ids", "layers", "seqs", "rows"}``, globally sorted by
    sequence number.  The single definition of the row-export contract
    — used by the live ``export_rows`` and the snapshot replay
    (``lifecycle.reshard.rows_from_state``), so the two sources can
    never drift."""
    if not ids:
        return {"ids": np.zeros((0,), dtype="<U1"),
                "layers": np.zeros((0,), np.int32),
                "seqs": np.zeros((0,), np.int64),
                "rows": np.zeros((0, dim + N_FLAGS), np.float32)}
    seq_all = np.concatenate(seqs)
    order = np.argsort(seq_all, kind="stable")
    return {"ids": np.asarray(ids)[order],
            "layers": np.concatenate(layers)[order],
            "seqs": seq_all[order],
            "rows": np.concatenate(rows)[order]}


def _quant_spec(dim: int, quantized: bool, scan_bits: int,
                scan_seed: int) -> Optional[QuantSpec]:
    """Code-plane layout for a store constructed quantized (None keeps
    the default store code-plane-free: zero memory / append overhead)."""
    if not quantized:
        return None
    return QuantSpec(dim=int(dim), n_bits=int(scan_bits),
                     n_flags=N_FLAGS, seed=int(scan_seed))


def _apply_quant_state(state: dict, kw: dict) -> None:
    """Fold a snapshot's quant entry into constructor kwargs (explicit
    kwargs win; snapshots predating the entry restore unquantized)."""
    for key, val in (state.get("quant") or {}).items():
        kw.setdefault(key, val)


def _filter_bias(layer_filter: Optional[str]) -> Tuple[float, ...]:
    return (MASK_BIAS,
            MASK_BIAS if layer_filter == "leaf" else 0.0,
            MASK_BIAS if layer_filter == "summary" else 0.0)


def _check_queries(queries: np.ndarray) -> np.ndarray:
    q = np.asarray(queries, dtype=np.float32)
    if q.ndim != 2:
        raise ValueError(f"queries must be (B, d), got {q.shape}")
    return q


class _BaseStore:
    """Delta-replay orchestration shared by both stores.

    Subclasses define the shard set (``self._shards``), the device
    group (``self._group``), and the routing function (``owner`` /
    ``owner_many``); everything else — stale-resurrection handling,
    per-version replay, the rotating off-query-path compaction,
    rebuild — is identical by construction, which is what keeps the
    flat and sharded stores bitwise-interchangeable."""

    _shards: List[_Shard]
    _group: _StackedBuffers
    _store_stats: StoreStats       # refresh / rebuild counters

    # span recorder for the query/lifecycle paths; the owning EraRAG
    # (or harness) swaps in its Observability tracer — the class-level
    # default keeps standalone stores on the inert no-op path
    tracer = NULL_TRACER

    def __init__(self, graph, compact_threshold: float):
        self._graph = graph
        self._version = -1          # graph version the index reflects
        self._next_seq = 0          # global row insertion order
        self._compact_threshold = float(compact_threshold)
        # merged-candidate id resolution for the sharded paths:
        # seq -> (node_id, layer, owning shard)
        self._seq_map: Dict[int, Tuple[str, int, int]] = {}
        self._track_seq_map = False
        # rotating, double-buffered compaction state
        self._pending: Optional[Tuple[int, np.ndarray, tuple]] = None
        self._compact_rr = 0
        # lifecycle state (see repro.lifecycle): the index epoch is
        # bumped by every committed reshard migration; `_migration` is
        # the staged (not yet installed) target epoch being built one
        # shard per refresh(); `_policy` is the pluggable trigger that
        # refresh() consults to start one
        self.epoch = 0
        self._migration = None      # Optional[lifecycle ShardMigration]
        self._policy = None         # Optional[LifecyclePolicy]
        self._router = _Router()    # per-instance routing LRU+counters
        self.query_hits = np.zeros(1, np.int64)  # per-shard hit skew

    def owner(self, node_id: str) -> int:
        raise NotImplementedError

    def owner_many(self, ids: Sequence[str]) -> np.ndarray:
        ids = list(ids)
        return np.fromiter((self.owner(i) for i in ids), np.int64,
                           count=len(ids))

    # ------------------------------------------------------------------
    # maintenance
    # ------------------------------------------------------------------
    def _append(self, ids: Sequence[str]) -> None:
        if not ids:
            return
        if self._next_seq + len(ids) >= _SEQ_LIMIT:
            self._renumber_seqs()
        nodes = self._graph.nodes
        owners = self.owner_many(ids)
        buckets: Dict[int, Tuple[List[str], List[int]]] = {}
        for nid, s in zip(ids, owners):
            b_ids, b_seqs = buckets.setdefault(int(s), ([], []))
            b_ids.append(nid)
            b_seqs.append(self._next_seq)
            if self._track_seq_map:
                self._seq_map[self._next_seq] = (
                    nid, int(nodes[nid].layer), int(s))
            self._next_seq += 1
        for s, (b_ids, b_seqs) in buckets.items():
            self._shards[s].append(nodes, b_ids, b_seqs)

    def _renumber_seqs(self) -> None:
        """Compact the global sequence numbers to 0..n_rows-1,
        preserving order, then re-stamp the device seq planes and the
        seq map.  Runs once per ~2^31 lifetime appends to keep the
        int32 merge exact; the host rewrite is O(N) metadata but the
        device upload is one slice write per shard."""
        rows = [(int(sh.row_seq[r]), sh, r)
                for sh in self._shards for r in range(sh.count)]
        rows.sort(key=lambda t: t[0])
        for new_seq, (_, sh, r) in enumerate(rows):
            sh.row_seq[r] = new_seq
        self._next_seq = len(rows)
        if self._group.track_seqs:
            for sh in self._shards:
                self._group.upload_seqs(sh.slot,
                                        sh.row_seq[:sh.count])
        if self._track_seq_map:
            self._rebuild_seq_map()

    def _rebuild_seq_map(self) -> None:
        self._seq_map.clear()
        for s, sh in enumerate(self._shards):
            for r in range(sh.count):
                if sh.alive[r]:
                    self._seq_map[int(sh.row_seq[r])] = (
                        sh.row_ids[r], int(sh.row_layers[r]), s)

    def _tombstone(self, ids: Sequence[str]) -> None:
        if not ids:
            return
        owners = self.owner_many(ids)
        buckets: Dict[int, List[str]] = {}
        for nid, s in zip(ids, owners):
            buckets.setdefault(int(s), []).append(nid)
        for s, b_ids in buckets.items():
            for seq in self._shards[s].tombstone(b_ids):
                self._seq_map.pop(seq, None)

    def _apply_delta(self, added: Sequence[str],
                     removed: Sequence[str]) -> None:
        self._tombstone(removed)
        # a re-added id (content-addressed resurrection) must move to
        # the buffer tail so row order keeps tracking the graph's node
        # insertion order (exact tie-break parity with a rebuild)
        stale = [nid for nid in added
                 if nid in self._shards[self.owner(nid)].row_of]
        if stale:
            self._tombstone(stale)
        self._append([nid for nid in added if nid in self._graph.nodes])

    def _full_rebuild(self) -> None:
        self._pending = None   # stale double buffer: drop, never swap
        self._migration = None  # staged epoch rows are stale too:
        # abort the migration (the policy will re-trigger if still
        # warranted) rather than install rows a re-stack superseded
        self._group.reset()
        for sh in self._shards:
            sh.reset()
        self._seq_map.clear()
        self._next_seq = 0
        self._store_stats.full_rebuilds += 1
        self._append(list(self._graph.nodes))

    def _commit_pending_compaction(self) -> None:
        if self._pending is None:
            return
        s, keep, compacted = self._pending
        self._pending = None
        self._shards[s].commit_compact(keep, compacted)

    def _schedule_threshold_compaction(self) -> None:
        """Schedule at most ONE over-threshold shard per refresh
        (round-robin rotation); the rest are deferred to later turns
        and surfaced in ``StoreStats.compactions_skipped``."""
        thresh = self._compact_threshold
        over = [i for i, sh in enumerate(self._shards)
                if sh.count and sh.n_dead > thresh * sh.count]
        if not over:
            return
        n = len(self._shards)
        pick = min(over, key=lambda i: (i - self._compact_rr) % n)
        self._compact_rr = (pick + 1) % n
        self._store_stats.compactions_skipped += len(over) - 1
        keep, compacted = self._shards[pick].schedule_compact()
        self._pending = (pick, keep, compacted)

    def _advance_migration(self) -> None:
        """Lifecycle turn (explicit ``refresh()`` only): build at most
        ONE staged target shard of an in-flight reshard migration —
        same one-unit-of-background-work-per-refresh discipline as the
        compaction rotation — and, once every target shard is built,
        install the new epoch with one atomic swap.  The install
        rewinds ``_version`` to the migration's plan version, so the
        replay loop below it brings the NEW epoch up to date through
        the graph's delta-log tail."""
        mig = self._migration
        if mig is None:
            return
        if not mig.done:
            desc = mig.describe()
            with self.tracer.span("reshard_step", epoch=self.epoch,
                                  built=desc["built"],
                                  total=desc["total"]):
                mig.step()
            self._store_stats.reshard_steps += 1
        if mig.done:
            self._migration = None
            with self.tracer.span("reshard_install",
                                  old_epoch=self.epoch,
                                  new_epoch=self.epoch + 1):
                mig.install()

    def _maybe_start_reshard(self) -> None:
        """Consult the attached lifecycle policy (skew / tombstone
        thresholds) for a reshard plan; at most one migration is in
        flight at a time."""
        if self._policy is None or self._migration is not None:
            return
        plan = self._policy.decide(self)
        if plan is None:
            return
        from repro.lifecycle.reshard import ShardMigration
        logger.info("lifecycle: starting reshard %d -> %d (%s)",
                    plan.n_from, plan.n_to, plan.reason)
        self._migration = ShardMigration(self, plan)

    def _refresh(self, force_commit: bool = False) -> None:
        g = self._graph
        if self._version == g.version and not force_commit:
            # version-synced queries take this hot path: they never
            # commit (or depend on) a staged compaction or advance a
            # migration — only an explicit refresh()/compact() does,
            # so a query issued mid-migration always serves the OLD
            # epoch unchanged
            return
        # a replay turn swaps in the previously staged compaction
        # FIRST: the gather had a full inter-refresh window to
        # complete, and the delta replay below must see the committed
        # row layout
        self._commit_pending_compaction()
        if force_commit:
            # one lifecycle turn per explicit refresh: build one
            # staged target shard, or commit the finished epoch swap
            # (which rewinds _version to the plan version — the replay
            # below then applies the delta tail to the new epoch)
            self._advance_migration()
        if self._version != g.version:
            self._store_stats.refreshes += 1
            deltas = g.deltas_since(self._version) \
                if hasattr(g, "deltas_since") else None
            if deltas is None:
                self._full_rebuild()
            else:
                for added, removed in deltas:
                    self._apply_delta(added, removed)
            self._schedule_threshold_compaction()
            self._version = g.version
        if force_commit:
            self._maybe_start_reshard()

    def _valid_count(self, layer_filter: Optional[str]) -> int:
        return sum(sh.valid_count(layer_filter)
                   for sh in self._shards)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def refresh(self) -> None:
        """Bring the index up to the graph's version (delta replay,
        routed to owning shards only); commits at most one pending
        compaction and schedules at most one new one."""
        self._refresh(force_commit=True)

    def rebuild(self) -> None:
        """Force a from-scratch re-stack (tests/benchmarks baseline)."""
        self._full_rebuild()
        self._version = self._graph.version

    def compact(self) -> None:
        """Forced escape hatch: flush the pending double buffer and
        compact EVERY shard that has tombstones, inline."""
        self._refresh(force_commit=True)
        self._commit_pending_compaction()
        for sh in self._shards:
            if sh.n_dead:
                sh.compact_now()

    @property
    def pending_compaction(self) -> Optional[int]:
        """Shard index whose compaction is staged in the double buffer
        (swapped in at the next refresh), or None."""
        return self._pending[0] if self._pending is not None else None

    @property
    def cache_token(self) -> Tuple[int, int]:
        """Exact invalidation token for result caches layered above the
        store: ``(epoch, graph version)``.

        Search results are a pure function of this token (for a fixed
        store configuration): the graph version covers every committed
        insert/delete a query-path ``_refresh`` will replay — including
        the flat store, which never bumps ``epoch`` — and the epoch
        covers committed reshard migrations (``install_epoch``).
        Queries issued mid-migration serve the OLD epoch and leave the
        token unchanged, so cached entries stay valid (and correct)
        until the atomic swap.  Staged compactions are bitwise
        result-transparent and need no token movement.  A TTL-free
        cache that compares this token can therefore never serve a
        stale retrieval."""
        return (self.epoch, self._graph.version)

    # ------------------------------------------------------------------
    # lifecycle (see repro.lifecycle: load reports, live resharding)
    # ------------------------------------------------------------------
    def attach_lifecycle(self, policy) -> None:
        """Attach a ``LifecyclePolicy``: every explicit ``refresh()``
        consults it and may start (then advance, one target shard per
        call) an epoch-swapped reshard migration."""
        self._policy = policy

    @property
    def migration(self):
        """The in-flight ``ShardMigration`` (staging epoch being built
        off the query path), or None."""
        return self._migration

    def routing_cache_info(self) -> Dict[str, int]:
        """This store's private routing-LRU counters (never another
        store's traffic — the cache is per instance)."""
        return self._router.info()

    def _quant_state(self) -> dict:
        """Persisted two-stage-scan hyperparameters.  The code plane
        itself is NEVER serialized: restore re-hashes every row through
        hyperplanes re-derived from the persisted ``scan_seed``, so the
        snapshot stays O(rows * d) and restored codes match the saved
        store's bitwise by construction."""
        return {"quantized": self.quantized,
                "coarse_mult": self.coarse_mult,
                "scan_bits": self.scan_bits,
                "scan_seed": self.scan_seed}

    def device_buffers(self) -> Dict[str, Optional[jnp.ndarray]]:
        """The device arrays the scans read, synced to the graph:
        ``rows`` (the stacked ``[emb | flags]`` buffer), ``codes``
        (the quantized plane, or None) and ``seq`` (the collective
        path's global-sequence plane, or None)."""
        self._refresh()
        g = self._group
        return {"rows": g.buf, "codes": g.codes, "seq": g.seq}

    def export_rows(self) -> Dict[str, np.ndarray]:
        """Alive rows in global-sequence order, captured to host: the
        replay source for the lifecycle ``Resharder``.  Returns
        ``{"ids", "layers", "seqs", "rows"}`` where ``rows`` is the
        ``(n, d + N_FLAGS)`` device-buffer content (embeddings + flag
        columns) — replaying these into a freshly-routed buffer at any
        shard count reproduces search results bitwise, because scores
        come from the identical float rows and the merge tie-break
        only depends on the (preserved) relative sequence order."""
        self._refresh()
        ids: List[str] = []
        layers: List[np.ndarray] = []
        seqs: List[np.ndarray] = []
        rows: List[np.ndarray] = []
        # ONE device->host transfer for the whole stack (read_rows per
        # shard would sync once per slot)
        stack = np.asarray(self._group.buf) \
            if self._group.buf is not None else None
        for sh in self._shards:
            n = sh.count
            if n == 0:
                continue
            keep = np.nonzero(sh.alive[:n])[0]
            if len(keep) == 0:
                continue
            buf = stack[:n] if stack.ndim == 2 else stack[sh.slot, :n]
            ids.extend(sh.row_ids[int(r)] for r in keep)
            layers.append(sh.row_layers[:n][keep])
            seqs.append(sh.row_seq[:n][keep])
            rows.append(np.asarray(buf[keep], np.float32))
        return pack_export_rows(ids, layers, seqs, rows,
                                self._group.dim)

    @property
    def size(self) -> int:
        self._refresh()
        return sum(sh.count - sh.n_dead for sh in self._shards)

    def search(self, query: np.ndarray, k: int,
               layer_filter: Optional[str] = None) -> List[Hit]:
        """layer_filter: None (all) | 'leaf' | 'summary'."""
        return self.search_batch(np.asarray(query)[None, :], k,
                                 layer_filter)[0]

    def search_batch(self, queries: np.ndarray, k: int,
                     layer_filter: Optional[str] = None
                     ) -> List[List[Hit]]:
        raise NotImplementedError


class VectorStore(_BaseStore):
    """Single-buffer store: exactly one ``_Shard`` over a one-slot
    group (everything routes to shard 0), searched with a single
    kernel launch — no merge."""

    def __init__(self, graph, *, compact_threshold: float = 0.25,
                 min_capacity: int = 64, quantized: bool = False,
                 coarse_mult: int = 4, scan_bits: int = 64,
                 scan_seed: int = 0):
        super().__init__(graph, compact_threshold)
        self.stats = StoreStats()
        self._store_stats = self.stats   # one object, all counters
        dim = graph.cfg.embed_dim
        self.quantized = bool(quantized)
        self.coarse_mult = int(coarse_mult)
        self.scan_bits = int(scan_bits)
        self.scan_seed = int(scan_seed)
        self._group = _StackedBuffers(
            1, dim, min_capacity=int(min_capacity),
            quant=_quant_spec(dim, quantized, scan_bits, scan_seed),
            stats=self.stats)
        self._s = _Shard(dim, self._group, 0, stats=self.stats)
        self._shards = [self._s]

    def owner(self, node_id: str) -> int:
        return 0

    def search_batch(self, queries: np.ndarray, k: int,
                     layer_filter: Optional[str] = None
                     ) -> List[List[Hit]]:
        """Per-query top-k hits for a (B, d) query batch in ONE kernel
        launch; row b of the result corresponds to ``queries[b]``.

        With ``quantized`` the launch is the fused two-stage pipeline
        (coarse Hamming over the code plane -> exact fp32 rescore of
        the top ``coarse_mult * k`` rows); the dense single-stage scan
        is the oracle and the fallback (flip ``self.quantized``)."""
        with self.tracer.span("route", epoch=self.epoch):
            self._refresh()
        q = _check_queries(queries)
        if q.shape[0] == 0:
            return []
        n_valid = self._s.valid_count(layer_filter)
        if n_valid == 0 or k <= 0:
            return [[] for _ in range(q.shape[0])]
        k_eff = min(k, n_valid)
        if self.quantized and self._group.quant is not None:
            # C = coarse_mult*k clamped to capacity: k <= C <= cap
            # always holds (k_eff <= n_valid <= rows <= cap), and at
            # C == cap the candidate set is total — bitwise equality
            # with the exact scan, no special-cased fallback
            n_coarse = min(self.coarse_mult * k_eff,
                           self._group.capacity)
            # ONE fused launch covers coarse scan + exact rescore, so
            # a single span (fused_rescore) covers both stages
            with self.tracer.span("coarse_scan", epoch=self.epoch,
                                  n=q.shape[0], k=k_eff,
                                  fused_rescore=True):
                vals, idx = quantized_flagged_topk(
                    jnp.asarray(q), self._s.buf,
                    self._group.codes_view(0),
                    k_eff, n_coarse, _filter_bias(layer_filter),
                    self._group.planes, self._group.quant)
            self._store_stats.quantized_scans += 1
        else:
            with self.tracer.span("scan", epoch=self.epoch,
                                  n=q.shape[0], k=k_eff):
                vals, idx = flagged_mips_topk(
                    jnp.asarray(q), self._s.buf, k_eff,
                    _filter_bias(layer_filter))
        self._store_stats.kernel_launches += 1
        vals = np.asarray(vals)
        idx = np.asarray(idx)
        out: List[List[Hit]] = []
        for b in range(q.shape[0]):
            out.append([
                Hit(node_id=self._s.row_ids[int(r)], score=float(v),
                    layer=int(self._s.row_layers[int(r)]),
                    seq=int(self._s.row_seq[int(r)]))
                for v, r in zip(vals[b], idx[b])])
        self.query_hits[0] += sum(len(hits) for hits in out)
        return out

    # ------------------------------------------------------------------
    # persistence
    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        """Serializable snapshot of the synced buffer (host arrays).

        Together with the graph's persisted delta-log tail this lets a
        restart resume with O(delta) refreshes instead of a full O(N)
        re-stack.
        """
        self._refresh()
        return {
            "kind": "flat",
            "version": self._version,
            "next_seq": self._next_seq,
            "quant": self._quant_state(),
            "shard": self._s.state_dict(),
        }

    @classmethod
    def from_state(cls, state: dict, graph, **kw) -> "VectorStore":
        _apply_quant_state(state, kw)
        store = cls(graph, **kw)
        store._s.load_state(state["shard"])
        store._next_seq = int(state["next_seq"])
        store._version = int(state["version"])
        return store


# ---------------------------------------------------------------------------
# sharded store
# ---------------------------------------------------------------------------

class ShardedVectorStore(_BaseStore):
    """Hash-sharded incremental index over the ``data`` mesh axis.

    Same public API and bitwise-identical results as ``VectorStore``
    (see the module docstring for the stacked-buffer + collective
    launch design and its invariants).  ``n_shards`` defaults to the
    mesh's data-axis size (or the local device count); the stacked
    shard buffer is laid out over the ``db_shards`` axes through the
    ``common/sharding.py`` rules engine when a mesh is given, else it
    lives on the default device.  ``collective`` selects the
    single-launch ``shard_map`` query (auto-disabled when the mesh
    degrades to one device or none is given); ``collective=False``
    keeps the per-shard dispatch loop as the parity oracle.
    """

    def __init__(self, graph, *, n_shards: Optional[int] = None,
                 mesh=None, compact_threshold: float = 0.25,
                 min_capacity: int = 64, rules=None,
                 collective: bool = True, quantized: bool = False,
                 coarse_mult: int = 4, scan_bits: int = 64,
                 scan_seed: int = 0):
        super().__init__(graph, compact_threshold)
        self.quantized = bool(quantized)
        self.coarse_mult = int(coarse_mult)
        self.scan_bits = int(scan_bits)
        self.scan_seed = int(scan_seed)
        axes: Tuple[str, ...] = ()
        axis_size = 1
        if mesh is not None:
            from repro.common.sharding import db_axis_size, \
                db_shard_axes, padded_slot_count, shard_placements, \
                stacked_db_shardings
            axes = db_shard_axes(mesh, rules)
            if not axes:
                raise ValueError(
                    f"mesh axes {tuple(mesh.shape)} match none of the "
                    f"rules' db_shards axes; refusing to silently "
                    f"collapse the index onto one device")
            axis_size = db_axis_size(mesh, rules)
            if n_shards is None:
                n_shards = axis_size
        elif n_shards is None:
            n_shards = max(1, len(jax.devices()))
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.n_shards = int(n_shards)
        self.mesh = mesh
        self._axis_names = axes
        self._collective_capable = mesh is not None and axis_size > 1
        self.collective = bool(collective)
        self._store_stats = StoreStats()
        dim = graph.cfg.embed_dim
        if mesh is not None:
            # the stacked slot dim must divide the shard axes: pad with
            # permanently-empty slots (all rows dead-flagged) rather
            # than ever collapsing rows onto one device
            n_slots = padded_slot_count(self.n_shards, axis_size)
            if n_slots != self.n_shards:
                logger.warning(
                    "ShardedVectorStore: %d shards padded to %d slots "
                    "to divide the %d-device %s axes", self.n_shards,
                    n_slots, axis_size, axes)
            sharding, seq_sharding = stacked_db_shardings(mesh, rules)
            self._placements = shard_placements(
                mesh, n_slots, rules=rules)[:self.n_shards]
        else:
            n_slots = self.n_shards
            sharding = seq_sharding = None
            self._placements = [None] * self.n_shards
        self._group = _StackedBuffers(
            n_slots, dim, sharding=sharding, seq_sharding=seq_sharding,
            min_capacity=int(min_capacity),
            track_seqs=self._collective_capable,
            quant=_quant_spec(dim, quantized, scan_bits, scan_seed),
            stats=self._store_stats)
        self._shards = [_Shard(dim, self._group, s)
                        for s in range(self.n_shards)]
        self._track_seq_map = True
        self.query_hits = np.zeros(self.n_shards, np.int64)

    def owner(self, node_id: str) -> int:
        return self._router.one(node_id, self.n_shards)

    def owner_many(self, ids: Sequence[str]) -> np.ndarray:
        return self._router.many(ids, self.n_shards)

    @property
    def collective_active(self) -> bool:
        """Whether ``search_batch`` runs as one collective launch."""
        return self.collective and self._collective_capable

    @property
    def stats(self) -> StoreStats:
        """Aggregate counters: store-level refresh/rebuild/compaction-
        rotation/reshard counts, per-shard staging/tombstone/compaction
        sums, and this instance's own routing-cache movement (each
        store owns its routing LRU, so the counters are exactly its
        traffic — never another store's or a test neighbor's)."""
        agg = StoreStats(**vars(self._store_stats))
        for sh in self._shards:
            agg.rows_staged += sh.stats.rows_staged
            agg.rows_tombstoned += sh.stats.rows_tombstoned
            agg.compactions += sh.stats.compactions
            agg.rows_compacted += sh.stats.rows_compacted
            agg.growths += sh.stats.growths
        route = self._router.info()
        agg.route_hits = route["hits"]
        agg.route_misses = route["misses"]
        agg.bulk_routed = route["bulk_routed"]
        return agg

    def shard_stats(self) -> List[StoreStats]:
        return [sh.stats for sh in self._shards]

    def shard_report(self) -> List[dict]:
        """Per-shard health: live rows, dead-row ratio, staged rows."""
        pending = self.pending_compaction
        return [{
            "rows": sh.count - sh.n_dead,
            "dead": sh.n_dead,
            "dead_ratio": sh.n_dead / max(1, sh.count),
            "capacity": sh.capacity,
            "staged": sh.stats.rows_staged,
            "compactions": sh.stats.compactions,
            "query_hits": int(self.query_hits[s]),
            "compact_pending": pending == s,
            "device": str(self._placements[s])
            if self._placements[s] is not None else None,
        } for s, sh in enumerate(self._shards)]

    def search_batch(self, queries: np.ndarray, k: int,
                     layer_filter: Optional[str] = None
                     ) -> List[List[Hit]]:
        """One collective ``sharded_mips_topk`` launch (default), or
        the per-shard dispatch loop + host merge when the collective is
        off; both bitwise identical to the single-buffer store."""
        with self.tracer.span("route", epoch=self.epoch):
            self._refresh()
        q = _check_queries(queries)
        n_q = q.shape[0]
        if n_q == 0:
            return []
        n_valid = self._valid_count(layer_filter)
        if n_valid == 0 or k <= 0:
            return [[] for _ in range(n_q)]
        k_eff = min(k, n_valid)
        bias = _filter_bias(layer_filter)
        grp = self._group
        quant = self.quantized and grp.quant is not None
        if self.collective_active:
            k_shard = min(k_eff, grp.capacity)
            if quant:
                # coarse + gather + rescore fused INSIDE the one
                # shard_map program; C clamps to the lockstep capacity
                # (C == cap => per-shard bitwise equality with exact)
                n_coarse = max(min(self.coarse_mult * k_eff,
                                   grp.capacity), k_shard)
                with self.tracer.span("coarse_scan", epoch=self.epoch,
                                      n=n_q, k=k_eff, collective=True,
                                      fused_rescore=True):
                    mv, ms = sharded_quantized_topk(
                        jnp.asarray(q), grp.buf, grp.codes, grp.seq,
                        grp.planes, k_shard, k_eff, n_coarse, bias,
                        grp.quant, mesh=self.mesh,
                        axis_names=self._axis_names)
                self._store_stats.quantized_scans += 1
            else:
                # scan + all_gather + merge fused in the ONE shard_map
                # launch — a single span covers the pipeline
                with self.tracer.span("scan", epoch=self.epoch,
                                      n=n_q, k=k_eff, collective=True):
                    mv, ms = sharded_mips_topk(
                        jnp.asarray(q), grp.buf, grp.seq, k_shard,
                        k_eff, bias, mesh=self.mesh,
                        axis_names=self._axis_names)
            self._store_stats.kernel_launches += 1
        else:
            mv, ms = self._loop_dispatch(q, k_eff, bias,
                                         quantized=quant)
            if quant:
                self._store_stats.quantized_scans += 1
        mv = np.asarray(mv)
        ms = np.asarray(ms)
        out: List[List[Hit]] = []
        for b in range(n_q):
            hits: List[Hit] = []
            for v, s in zip(mv[b], ms[b]):
                nid, layer, shard = self._seq_map[int(s)]
                self.query_hits[shard] += 1
                hits.append(Hit(node_id=nid, score=float(v),
                                layer=layer, seq=int(s)))
            out.append(hits)
        return out

    def _loop_dispatch(self, q: np.ndarray, k_eff: int,
                       bias: Tuple[float, ...],
                       quantized: bool = False):
        """Per-shard fallback/oracle: one ``mips_topk`` (or fused
        ``quantized_flagged_topk``) launch per non-empty shard (async
        dispatch — the scans overlap; the augmented query block is
        built ONCE for the whole loop), then host-side sentinel
        padding + ``merge_sharded_topk``."""
        grp = self._group
        q_dev = jnp.asarray(q)
        q_aug = None if quantized else augment_queries(q_dev, bias)
        pending: List[Tuple[_Shard, int, jnp.ndarray, jnp.ndarray]] = []
        span = "coarse_scan" if quantized else "scan"
        with self.tracer.span(span, epoch=self.epoch, n=q.shape[0],
                              k=k_eff, collective=False):
            for sh in self._shards:
                if sh.count == 0:
                    continue
                k_s = min(k_eff, sh.capacity)
                if quantized:
                    n_c = max(min(self.coarse_mult * k_eff,
                                  sh.capacity), k_s)
                    v, i = quantized_flagged_topk(
                        q_dev, sh.buf, grp.codes_view(sh.slot), k_s,
                        n_c, bias, grp.planes, grp.quant)
                else:
                    v, i = mips_topk(q_aug, sh.buf, k_s)
                pending.append((sh, k_s, v, i))
        val_blocks: List[np.ndarray] = []
        seq_blocks: List[np.ndarray] = []
        for sh, k_s, v, i in pending:
            v = np.asarray(v)
            seqs = sh.seqs_at(np.asarray(i))
            if k_s < k_eff:
                padw = ((0, 0), (0, k_eff - k_s))
                v = np.pad(v, padw, constant_values=_VAL_PAD)
                seqs = np.pad(seqs, padw, constant_values=_SEQ_PAD)
            val_blocks.append(v)
            seq_blocks.append(seqs)
        vals = jnp.asarray(np.stack(val_blocks))
        # int32 is exact: _renumber_seqs keeps every seq < _SEQ_LIMIT
        seqs = jnp.asarray(np.stack(seq_blocks).astype(np.int32))
        # one dispatch per non-empty shard above, plus the merge below
        self._store_stats.kernel_launches += len(pending) + 1
        with self.tracer.span("merge", epoch=self.epoch,
                              shards=len(pending)):
            return merge_sharded_topk(vals, seqs, k_eff)

    # ------------------------------------------------------------------
    # lifecycle: atomic epoch swap (reshard commit)
    # ------------------------------------------------------------------
    def install_epoch(self, staging: "ShardedVectorStore") -> None:
        """Atomically adopt ``staging``'s fully-built buffers, shards,
        and routing as this store's next epoch (the reshard commit).

        Every query dispatched before this call served the OLD epoch's
        stacked buffer untouched; after it, the store routes and scans
        at the new shard count.  ``_version`` rewinds to the staging
        snapshot's version, so the caller (``_refresh``'s replay loop,
        or the synchronous ``Resharder``) replays the graph's delta
        tail into the new epoch; a pending old-epoch compaction gather
        is dropped — its layout no longer exists."""
        assert staging._graph is self._graph, "epoch from another graph"
        self._pending = None
        self._compact_rr = 0
        self._group = staging._group
        self._group.stats = self._store_stats
        self._shards = staging._shards
        self.n_shards = staging.n_shards
        self.mesh = staging.mesh
        self._axis_names = staging._axis_names
        self._collective_capable = staging._collective_capable
        self._placements = staging._placements
        self._seq_map = staging._seq_map
        self._version = staging._version
        # appends after the swap must stay above every replayed seq
        self._next_seq = max(self._next_seq, staging._next_seq)
        self.query_hits = np.zeros(self.n_shards, np.int64)
        self.epoch += 1
        self._store_stats.reshards += 1

    # ------------------------------------------------------------------
    # persistence
    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        self._refresh()
        return {
            "kind": "sharded",
            "n_shards": self.n_shards,
            "version": self._version,
            "next_seq": self._next_seq,
            "quant": self._quant_state(),
            "shards": [sh.state_dict() for sh in self._shards],
        }

    @classmethod
    def from_state(cls, state: dict, graph, *, mesh=None,
                   n_shards: Optional[int] = None,
                   **kw) -> "ShardedVectorStore":
        """Restore a snapshot.  ``n_shards`` (None/0 = keep the
        snapshot's layout) may disagree with the snapshot: the rows
        are then replayed through the lifecycle ``Resharder`` into a
        freshly-routed store at the requested count — never loaded
        into a mismatched (ghost) layout, and never a full O(N)
        re-embed."""
        _apply_quant_state(state, kw)
        snap = int(state["n_shards"])
        want = snap if not n_shards else int(n_shards)
        if want != snap:
            from repro.lifecycle.reshard import Resharder
            return Resharder(mesh=mesh, **kw).replay_state(
                state, graph, want)
        store = cls(graph, n_shards=snap, mesh=mesh, **kw)
        for sh, sh_state in zip(store._shards, state["shards"]):
            sh.load_state(sh_state)
        store._rebuild_seq_map()
        store._next_seq = int(state["next_seq"])
        store._version = int(state["version"])
        return store


AnyStore = Union[VectorStore, ShardedVectorStore]


def store_from_state(state: dict, graph, *, mesh=None,
                     n_shards: Optional[int] = None, **kw) -> AnyStore:
    """Restore whichever store kind ``state`` was saved from.

    ``n_shards`` (None/0 = respect the snapshot's layout) reshards the
    snapshot through the lifecycle ``Resharder`` when it disagrees —
    including across kinds (flat snapshot -> sharded store and back).
    """
    _apply_quant_state(state, kw)   # replayed stores keep their plane
    want = int(n_shards) if n_shards else None
    if state.get("kind") == "sharded":
        if want is not None and want != int(state["n_shards"]):
            from repro.lifecycle.reshard import Resharder
            return Resharder(mesh=mesh, **kw).replay_state(
                state, graph, want, flat=want == 1)
        return ShardedVectorStore.from_state(state, graph, mesh=mesh,
                                             **kw)
    if want is not None and want != 1:
        from repro.lifecycle.reshard import Resharder
        return Resharder(mesh=mesh, **kw).replay_state(state, graph,
                                                       want)
    kw.pop("collective", None)   # flat store has no dispatch modes
    return VectorStore.from_state(state, graph, **kw)
