"""Batched LM serving engine (continuous-batching lite + KV prefix
reuse).

Requests queue up; the engine admits them into fixed decode slots with
*bucketed prefill*: each admission wave drains the queue into the free
slots, groups the pending prompts by padded length (pow-2 buckets up
to ``max_seq_len``), and runs ONE jitted ``prefill_padded`` launch per
bucket — a length mask picks each row's true last position and the
per-slot KV rows are scattered into the shared cache afterwards, so
concurrent admissions cost one kernel launch per *bucket* instead of
one per prompt.  ``stats['prefill_launches']`` vs
``stats['prefill_prompts']`` measures that sharing.  Decode is
*micro-batched* the same way: active slots are grouped by cache length
and each group shares ONE jitted ``decode_step`` launch (padded fixed
shapes — no recompilation); ``stats['decode_launches']`` vs
``stats['slot_steps']`` is the decode-side sharing ratio.  Slots free
as soon as a sequence emits EOS or hits its token budget and are
refilled from the queue: the slot-level admission/eviction is the
continuous-batching scheduling pattern (vLLM-style) restricted to
whole-slot granularity.  Over-long prompts are truncated
deterministically to ``max_seq_len - budget - 1`` tokens at admission,
so a mis-sized request can never spill into a neighbor slot's cache.

**KV prefix reuse** (``EngineConfig.prefix_cache_entries > 0``):
callers may declare a reusable leading block of the prompt — the RAG
pipeline passes the composed retrieval context, so N questions over
one retrieved context pay its prefill once.  Admission hashes the
prefix's token ids; on a hit the cached prefix K/V rows are copied
into the slot's cache, only the *suffix* (question + answer cue) runs
through a ``prefill_extend`` launch (global RoPE positions, per-row
cache offsets), and the slot decodes from the full combined length.
The hit path's suffix K/V and logits are bitwise those of a cold
full-prompt prefill (see ``models.transformer.prefill_extend``), so
answers are unchanged — only the prefill cost shrinks, measured by
``stats['prefix_hits']`` / ``stats['prefix_tokens_saved']``.  On a
miss the prefix slice of the freshly prefilled cache is captured into
an LRU keyed by the prefix token hash.  A prefix is only reused when
its token ids survive truncation intact and the suffix bucket still
fits (``plen + bucket(suffix) <= max_seq_len``); otherwise the request
silently takes the cold path.  Disabled (the default) the engine is
bitwise the pre-cache engine.

This is the LLM backend for EraRAG's summarizer (LMSummarizer), for
the QA reader in examples/rag_serve.py, and for
``RAGPipeline.answer_batch``'s shared-launch reader and multihop
bridge-extraction paths.
"""
from __future__ import annotations

import hashlib
import queue
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import LMConfig
from repro.data.tokenizer import BOS_ID, EOS_ID, HashTokenizer
from repro.models import transformer as T
from repro.obs.trace import NULL_TRACER


@dataclass
class EngineConfig:
    max_batch: int = 4
    max_seq_len: int = 512
    max_new_tokens: int = 64
    compute_dtype: Any = jnp.float32
    # KV prefix cache capacity (reusable prompt-prefix K/V blocks held
    # across requests); 0 disables reuse — the default path is bitwise
    # the pre-cache engine
    prefix_cache_entries: int = 0


@dataclass
class _Slot:
    active: bool = False
    length: int = 0
    budget: int = 0
    out_tokens: List[int] = field(default_factory=list)
    request_id: int = -1


class Engine:
    # span recorder for the serving path; RAGPipeline swaps in the
    # pipeline's Observability tracer (inert no-op by default)
    tracer = NULL_TRACER

    def __init__(self, cfg: LMConfig, params, ecfg: EngineConfig,
                 tokenizer: Optional[HashTokenizer] = None):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.tok = tokenizer or HashTokenizer(cfg.vocab_size)
        self.slots = [_Slot() for _ in range(ecfg.max_batch)]
        self.caches = T.make_kv_cache(cfg, ecfg.max_batch,
                                      ecfg.max_seq_len,
                                      ecfg.compute_dtype)
        self._queue: "queue.Queue" = queue.Queue()
        self._results: Dict[int, List[int]] = {}
        self._next_id = 0
        # launch-sharing instrumentation: slot_steps counts (slot,
        # token) decode units, decode_launches the kernel launches that
        # served them (equal-length grouping makes launches < steps);
        # prefill_prompts counts admitted prompts, prefill_launches the
        # bucketed prefill launches that served them (length-colliding
        # admissions make launches < prompts); generate_batches counts
        # ``generate_batch`` calls — the serving pipeline asserts its
        # multihop path costs exactly two per question block
        # prefix_hits / prefix_tokens_saved: admissions served from the
        # KV prefix cache and the prompt tokens they did NOT re-prefill
        self.stats = {"decode_launches": 0, "slot_steps": 0,
                      "prefill_launches": 0, "prefill_prompts": 0,
                      "generate_batches": 0, "prefix_hits": 0,
                      "prefix_tokens_saved": 0}
        # prefix token-hash -> (per-layer K/V slice pytree, plen), LRU
        self._prefix_cache: "OrderedDict[bytes, Tuple[Any, int]]" = \
            OrderedDict()

        # bucketed prefill: batch dim fixed at max_batch, length padded
        # to the pow-2 bucket -> at most log2(max_seq_len) compiles
        def engine_prefill(params, tokens, lengths):
            return T.prefill_padded(params, tokens, lengths, cfg,
                                    max_len=ecfg.max_seq_len,
                                    compute_dtype=ecfg.compute_dtype)

        def engine_decode(params, tokens, caches, length):
            return T.decode_step(params, tokens, caches, length, cfg,
                                 compute_dtype=ecfg.compute_dtype)

        # suffix prefill over per-row cache prefixes (prefix-cache hit
        # admission); compiles once per suffix bucket length
        def engine_prefill_extend(params, tokens, lengths, offsets,
                                  caches):
            return T.prefill_extend(params, tokens, lengths, offsets,
                                    caches, cfg,
                                    compute_dtype=ecfg.compute_dtype)

        # named functions give the programs stable names in the device
        # trace: jit_engine_prefill, jit_engine_decode and
        # jit_engine_prefill_extend
        self._prefill_bucket = jax.jit(engine_prefill)
        self._decode_step = jax.jit(engine_decode)
        self._prefill_extend = jax.jit(engine_prefill_extend)

    # ------------------------------------------------------------------
    def submit(self, prompt: str, max_new_tokens: Optional[int] = None,
               prefix: Optional[str] = None) -> int:
        """Queue a request.  ``max_new_tokens=None`` falls back to the
        engine default; an explicit non-positive budget is a caller bug
        and raises instead of silently decoding the default budget.
        ``prefix`` declares a reusable leading block of the prompt (the
        composed retrieval context) for the KV prefix cache — it must
        be a string prefix of ``prompt``."""
        if max_new_tokens is None:
            max_new_tokens = self.ecfg.max_new_tokens
        elif max_new_tokens <= 0:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prefix is not None and not prompt.startswith(prefix):
            raise ValueError("prefix is not a prefix of prompt")
        rid = self._next_id
        self._next_id += 1
        self._queue.put((rid, prompt, max_new_tokens, prefix))
        return rid

    @property
    def launches(self) -> int:
        """Total kernel launches issued so far (bucketed prefill +
        micro-batched decode).  The single number ingest benchmarks and
        the batched-summarization assertion compare: an N-segment
        update through ``generate_batch`` must cost O(length buckets),
        not N, launch growth."""
        return (self.stats["prefill_launches"]
                + self.stats["decode_launches"])

    def generate(self, prompt: str, max_new_tokens: Optional[int] = None,
                 prefix: Optional[str] = None) -> str:
        return self.generate_batch([prompt], max_new_tokens,
                                   prefixes=[prefix])[0]

    def generate_batch(self, prompts: List[str],
                       max_new_tokens: Optional[int] = None,
                       prefixes: Optional[List[Optional[str]]] = None
                       ) -> List[str]:
        """Submit a prompt batch before draining so concurrent requests
        land in slots together and share prefill + decode launches.
        ``prefixes`` optionally declares each prompt's reusable context
        block for the KV prefix cache (None entries opt out)."""
        if not prompts:
            return []
        self.stats["generate_batches"] += 1
        prefixes = prefixes or [None] * len(prompts)
        rids = [self.submit(p, max_new_tokens, prefix=px)
                for p, px in zip(prompts, prefixes)]
        self.run_until_done()
        out = []
        for r in rids:
            toks = self._results.pop(r)
            if toks and toks[-1] == EOS_ID:
                # the EOS sentinel is a stop signal, not text: keep it
                # out of the detokenized answer
                toks = toks[:-1]
            out.append(" ".join(f"tok{t}" for t in toks))
        return out

    # ------------------------------------------------------------------
    def _bucket_len(self, n: int) -> int:
        """Pow-2 padded length bucket, capped at ``max_seq_len``."""
        length = 8
        while length < n:
            length *= 2
        return min(length, self.ecfg.max_seq_len)

    def _prefix_tokens(self, prefix: str, ids: List[int]
                       ) -> Optional[List[int]]:
        """Prefix token ids ([BOS] + prefix words) when they survive in
        ``ids`` intact with a nonempty suffix after them, else None
        (truncation ate into the prefix, or the prefix/prompt split
        lands mid-token)."""
        pt = [BOS_ID] + [int(t) for t in
                         self.tok.encode(prefix, add_special=False)]
        if len(pt) < len(ids) and ids[: len(pt)] == pt:
            return pt
        return None

    @staticmethod
    def _prefix_key(ptoks: List[int]) -> bytes:
        return hashlib.blake2b(
            np.asarray(ptoks, np.int32).tobytes(),
            digest_size=16).digest()

    def _admit(self) -> None:
        """Drain the queue into free slots with bucketed prefill.

        Pending prompts are grouped by padded (pow-2) length and each
        bucket runs as ONE ``prefill_padded`` launch over a
        ``max_batch``-wide padded block — the length mask keeps every
        row independent of its padding tail — then each row's KV cache
        is scattered into its slot.  Prompts are truncated
        deterministically to ``max_seq_len - budget - 1`` tokens so an
        over-long request degrades alone instead of overflowing the
        shared cache.

        With the prefix cache enabled, prompts whose declared prefix
        hashes to a cached K/V block skip the cold path: the prefix
        rows are copied into the slot cache and only the suffix runs,
        bucketed the same way through ``prefill_extend`` (one launch
        per suffix bucket).  Cold prompts that declared a prefix
        capture its K/V slice after their bucket launch."""
        free = [i for i, s in enumerate(self.slots) if not s.active]
        cold, hits = [], []
        while free and not self._queue.empty():
            rid, prompt, budget, prefix = self._queue.get()
            budget = max(1, min(budget, self.ecfg.max_seq_len - 2))
            ids = self.tok.encode(prompt, add_special=True)
            ids = [int(t) for t in
                   ids[: max(1, self.ecfg.max_seq_len - budget - 1)]]
            pkey, plen = None, 0
            if prefix is not None and self.ecfg.prefix_cache_entries:
                ptoks = self._prefix_tokens(prefix, ids)
                if ptoks is not None:
                    pkey, plen = self._prefix_key(ptoks), len(ptoks)
            item = (free.pop(0), rid, ids, budget, pkey, plen)
            # a hit admits through suffix-only prefill when the suffix
            # bucket still fits behind the prefix; else degrade to cold
            if pkey is not None and pkey in self._prefix_cache and \
                    plen + self._bucket_len(len(ids) - plen) \
                    <= self.ecfg.max_seq_len:
                hits.append(item)
            else:
                cold.append(item)
        self._admit_cold(cold)
        self._admit_hits(hits)

    def _admit_cold(self, pending: List[tuple]) -> None:
        if not pending:
            return
        buckets: Dict[int, list] = {}
        for item in pending:
            buckets.setdefault(self._bucket_len(len(item[2])),
                               []).append(item)
        for blen, group in sorted(buckets.items()):
            tokens = np.zeros((self.ecfg.max_batch, blen), np.int32)
            lengths = np.zeros((self.ecfg.max_batch,), np.int32)
            for j, (_, _, ids, *_rest) in enumerate(group):
                tokens[j, :len(ids)] = ids
                lengths[j] = len(ids)
            with self.tracer.span("prefill", bucket=blen,
                                  prompts=len(group), prefix_hit=False):
                logits, cache = self._prefill_bucket(
                    self.params, jnp.asarray(tokens),
                    jnp.asarray(lengths))
            self.stats["prefill_launches"] += 1
            self.stats["prefill_prompts"] += len(group)
            dst = jnp.asarray([i for i, *_ in group], jnp.int32)
            src = jnp.arange(len(group), dtype=jnp.int32)

            def scatter(old, new):
                return old.at[:, dst].set(new[:, src])

            self.caches = jax.tree.map(scatter, self.caches, cache)
            with self.tracer.span("fetch"):
                logits = np.asarray(logits)
            for j, (i, rid, ids, budget, pkey, plen) in \
                    enumerate(group):
                if pkey is not None and \
                        pkey not in self._prefix_cache:
                    self._capture_prefix(pkey, cache, j, plen)
                self.slots[i] = _Slot(
                    active=True, length=len(ids), budget=budget,
                    out_tokens=[int(np.argmax(logits[j]))],
                    request_id=rid)

    def _capture_prefix(self, pkey: bytes, cache, row: int,
                        plen: int) -> None:
        """LRU-insert the prefix K/V slice of a freshly prefilled row."""
        kv = jax.tree.map(lambda c: c[:, row, :, :plen], cache)
        self._prefix_cache[pkey] = (kv, plen)
        while len(self._prefix_cache) > self.ecfg.prefix_cache_entries:
            self._prefix_cache.popitem(last=False)

    def _admit_hits(self, pending: List[tuple]) -> None:
        """Prefix-cache-hit admission: seed each slot's cache with the
        reused prefix rows, then ONE ``prefill_extend`` launch per
        suffix bucket computes only the suffix K/V (global positions,
        per-row offsets).  Row-wise cache merge keeps every other
        slot's cache untouched."""
        if not pending:
            return
        buckets: Dict[int, list] = {}
        for item in pending:
            slen = len(item[2]) - item[5]
            buckets.setdefault(self._bucket_len(slen), []).append(item)
        for blen, group in sorted(buckets.items()):
            tokens = np.zeros((self.ecfg.max_batch, blen), np.int32)
            lengths = np.zeros((self.ecfg.max_batch,), np.int32)
            offsets = np.zeros((self.ecfg.max_batch,), np.int32)
            for i, rid, ids, budget, pkey, plen in group:
                kv, _ = self._prefix_cache[pkey]
                self._prefix_cache.move_to_end(pkey)
                # slot-indexed batch layout: the launch reads/writes
                # row i of the live cache directly
                self.caches = jax.tree.map(
                    lambda old, pre: old.at[:, i, :, :plen].set(pre),
                    self.caches, kv)
                suf = ids[plen:]
                tokens[i, :len(suf)] = suf
                lengths[i] = len(suf)
                offsets[i] = plen
            with self.tracer.span("prefill", bucket=blen,
                                  prompts=len(group), prefix_hit=True):
                logits, new_caches = self._prefill_extend(
                    self.params, jnp.asarray(tokens),
                    jnp.asarray(lengths), jnp.asarray(offsets),
                    self.caches)
            rows = jnp.asarray([i for i, *_ in group], jnp.int32)

            def keep_rows(old, new):
                return old.at[:, rows].set(new[:, rows])

            self.caches = jax.tree.map(keep_rows, self.caches,
                                       new_caches)
            self.stats["prefill_launches"] += 1
            self.stats["prefill_prompts"] += len(group)
            self.stats["prefix_hits"] += len(group)
            self.stats["prefix_tokens_saved"] += sum(
                item[5] for item in group)
            with self.tracer.span("fetch"):
                logits = np.asarray(logits)
            for i, rid, ids, budget, pkey, plen in group:
                self.slots[i] = _Slot(
                    active=True, length=len(ids), budget=budget,
                    out_tokens=[int(np.argmax(logits[i]))],
                    request_id=rid)

    def step(self) -> int:
        """One engine iteration: admit + micro-batched decode.

        ``decode_step`` strides the whole slot batch at ONE cache
        length, so slots are grouped by length and each group shares a
        single launch (slots admitted together stay in lock-step and
        keep sharing until one finishes).  Rows outside the group
        compute garbage that is discarded — their caches and outputs
        are untouched.  Returns number of active slots stepped."""
        self._admit()
        active = [i for i, s in enumerate(self.slots) if s.active]
        if not active:
            return 0
        groups: Dict[int, List[int]] = {}
        for i in active:
            groups.setdefault(self.slots[i].length, []).append(i)
        for length, idxs in sorted(groups.items()):
            tok = np.zeros((self.ecfg.max_batch, 1), dtype=np.int32)
            for i in idxs:
                tok[i, 0] = self.slots[i].out_tokens[-1]
            with self.tracer.span("decode", length=length,
                                  slots=len(idxs)):
                logits, new_caches = self._decode_step(
                    self.params, jnp.asarray(tok), self.caches,
                    jnp.int32(length))
            rows = jnp.asarray(np.asarray(idxs, np.int32))

            def keep_rows(old, new):
                return old.at[:, rows].set(new[:, rows])
            self.caches = jax.tree.map(keep_rows, self.caches,
                                       new_caches)
            self.stats["decode_launches"] += 1
            self.stats["slot_steps"] += len(idxs)
            # the per-token sync: the host waits for the decode launch
            with self.tracer.span("fetch"):
                logits = np.asarray(logits)
            for i in idxs:
                slot = self.slots[i]
                nxt = int(np.argmax(logits[i]))
                slot.out_tokens.append(nxt)
                slot.length += 1
                done = (nxt == EOS_ID or
                        len(slot.out_tokens) >= slot.budget or
                        slot.length >= self.ecfg.max_seq_len - 1)
                if done:
                    self._results[slot.request_id] = slot.out_tokens
                    self.slots[i] = _Slot()
        return len(active)

    def run_until_done(self, max_iters: int = 10_000) -> None:
        for _ in range(max_iters):
            if self._queue.empty() and not any(s.active
                                               for s in self.slots):
                return
            self.step()
        raise RuntimeError("engine did not drain")
