"""Smoke run of EraRAG's main path on a TPU, through its user entry points.

    python chip_smoke.py               # one chip: build -> LM insert -> query -> answer
    python chip_smoke.py --chips 4     # the sharded index on a 4-chip data mesh only
    python chip_smoke.py --rehearse    # the same phases at a tiny size on the CPU

One chip (no options), in one process:

1. build: ``EraRAG`` with the paper's config at the DPR passage width
   (``embed_dim=768``) over 20k seeded synthetic documents (~112k
   index rows), summarized extractively;
2. lm_insert: one incremental round whose summaries come from
   ``LMSummarizer`` on an ``Engine`` running Qwen2-7B at its published
   width, cut in depth to 16 of 28 layers (bf16 weights and KV);
3. query: ``query_batch`` at B = 1, 8, 32, 64 (collapsed), checked
   against a NumPy brute-force top-k over ``store.export_rows()``; the
   quantized profile, checked against a NumPy two-stage reference and
   the exact scores; one multihop block;
4. answer: ``RAGPipeline.answer_batch`` twice, same tokens both times.

``--chips 4`` builds the same corpus into a ``ShardedVectorStore`` over
a 4-device ``data`` mesh (collective ``sharded_mips_topk``) and checks
it against the flat store on the same rows.

Any failed check raises, so the process exits nonzero; on success the
last stdout line is ``{"ok": true, "device": {...}}``.  Without
``--rehearse`` a backend other than TPU is an immediate failure, and
on the TPU every main-path kernel must compile to a Mosaic custom call.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common.utils import enable_compile_cache  # noqa: E402
from repro.configs.erarag import ERARAG_DEFAULT, ERARAG_QUANTIZED  # noqa: E402
from repro.configs.qwen2_7b import qwen2_7b  # noqa: E402
from repro.core.erarag import EraRAG, make_store  # noqa: E402
from repro.core.store import ShardedVectorStore, VectorStore  # noqa: E402
from repro.core.summarize import LMSummarizer  # noqa: E402
from repro.data.corpus import SyntheticCorpus  # noqa: E402
from repro.embed.hashing import HashingEmbedder  # noqa: E402
from repro.kernels.hamming_topk.ops import hamming_topk  # noqa: E402
from repro.kernels.lsh_hash.ops import lsh_hash  # noqa: E402
from repro.kernels.mips_topk import ops as mips_ops  # noqa: E402
from repro.kernels.quantized_scan.ops import QuantSpec, encode_queries, \
    hyperplanes  # noqa: E402
from repro.launch.mesh import local_data_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving.engine import Engine, EngineConfig  # noqa: E402
from repro.serving.rag_pipeline import RAGPipeline  # noqa: E402

BATCHES = (1, 8, 32, 64)
# |score| <= 1 (unit-norm rows and queries); an f32 dot over 768 terms
# is off the float64 reference by at most ~768 * 2**-24 ~ 4.6e-5
SCORE_TOL = 5e-5

# the sizes a run uses: "full" on the chip, "tiny" for --rehearse
SIZES = {
    "full": dict(
        docs=20_000, embed_dim=768,
        # 0.2% more documents: on one v5e this 40-document round
        # re-summarized 724 segments in ~255 s; 2% of 20k (~7,000 LM
        # summaries) would take ~40 minutes at the engine's max_batch 4
        lm_docs=40, lm_layers=16, max_batch=4, max_seq_len=4096,
        summary_tokens=16, answer_tokens=16, n_answers=4),
    "tiny": dict(
        docs=60, embed_dim=64, lm_docs=4, lm_layers=2, max_batch=4,
        max_seq_len=512, summary_tokens=4, answer_tokens=4,
        n_answers=4),
}
LM_CUT = ("28 layers of bf16 weights take 14.2 GiB of the chip's 16 GiB "
          "and leave no room for KV, prefill temporaries or the index; "
          "16 layers keep ~9 GiB")


def log(msg: str) -> None:
    print(msg, flush=True)


class Phases:
    """Wall time per phase (each ends in a host sync or an explicit
    ``block_until_ready``) and the backend compile seconds inside it."""

    def __init__(self, where: str):
        self.where = where
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def run(self, name: str, fn):
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        log(f"phase {name}: {time.perf_counter() - t0:.3f} s wall "
            f"({self.where}), of which compile "
            f"{self.compile_s - c0:.3f} s")
        return out


# ---------------------------------------------------------------------------
# NumPy references
# ---------------------------------------------------------------------------
def ref_topk(emb: np.ndarray, seqs: np.ndarray, q: np.ndarray, k: int):
    """Exact top-k in float64; ties go to the lower sequence number."""
    scores = q.astype(np.float64) @ emb.astype(np.float64).T
    out = []
    for s in scores:
        order = np.lexsort((seqs, -s))[:k]
        out.append((order, s))
    return out


def check_ranked(hit_rows, hit_scores, ref_order, ref_scores, what):
    """Hit rows equal the reference ranking and scores agree within
    ``SCORE_TOL``.  Two rows may only trade places when their exact
    scores differ, by less than the tolerance (a tie the f32 scan
    cannot resolve); exactly equal scores must follow sequence order.
    Returns the number of such near-tie swaps."""
    hit_rows = np.asarray(hit_rows)
    want = ref_order[:len(hit_rows)]
    err = np.abs(np.asarray(hit_scores, np.float64) - ref_scores[want])
    if err.size and err.max() > SCORE_TOL:
        raise AssertionError(f"{what}: score error {err.max():.3e} "
                             f"> {SCORE_TOL}")
    swaps = 0
    for got, exp in zip(hit_rows, want):
        if got == exp:
            continue
        gap = abs(ref_scores[got] - ref_scores[exp])
        if gap == 0.0 or gap > SCORE_TOL:
            raise AssertionError(
                f"{what}: row {got} where the reference has {exp} "
                f"(exact score gap {gap:.3e})")
        swaps += 1
    return swaps


def popcount_dist(qc: np.ndarray, codes: np.ndarray) -> np.ndarray:
    return np.bitwise_count(qc[None, :] ^ codes).sum(axis=1,
                                                     dtype=np.int64)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def build_corpus(sz: dict, seed: int):
    corpus = SyntheticCorpus.generate(n_docs=sz["docs"] + sz["lm_docs"],
                                      seed=seed)
    cfg = dataclasses.replace(ERARAG_DEFAULT, embed_dim=sz["embed_dim"],
                              seed=seed)
    return corpus, cfg


def index_bytes(store) -> int:
    return sum(a.nbytes for a in store.device_buffers().values()
               if a is not None)


def make_engine(sz: dict, seed: int, tiny: bool):
    if tiny:
        lm = dataclasses.replace(qwen2_7b(), n_layers=sz["lm_layers"],
                                 d_model=64, n_heads=4, n_kv_heads=2,
                                 d_head=0, d_ff=128, vocab_size=512)
    else:
        lm = dataclasses.replace(qwen2_7b(), n_layers=sz["lm_layers"])
    params, _ = T.init_params(lm, jax.random.PRNGKey(seed),
                              dtype=jnp.bfloat16)
    jax.block_until_ready(params)
    wbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    log(f"lm: {lm.name} d_model={lm.d_model} heads={lm.n_heads} "
        f"kv_heads={lm.n_kv_heads} d_ff={lm.d_ff} vocab={lm.vocab_size} "
        f"qkv_bias={lm.qkv_bias} n_layers={lm.n_layers} (of "
        f"{qwen2_7b().n_layers}) weight_bytes={wbytes} "
        f"({wbytes / 2**30:.3f} GiB)")
    if not tiny:
        log(f"lm depth cut: {LM_CUT}")
    ecfg = EngineConfig(max_batch=sz["max_batch"],
                        max_seq_len=sz["max_seq_len"],
                        max_new_tokens=sz["answer_tokens"],
                        compute_dtype=jnp.bfloat16)
    return Engine(lm, params, ecfg)


def lm_insert(rag: EraRAG, engine: Engine, docs, sz: dict):
    rag.graph.summarizer = LMSummarizer(engine,
                                        max_tokens=sz["summary_tokens"])
    before = dict(engine.stats)
    rep = rag.insert_docs(docs)
    prompts = engine.stats["prefill_prompts"] - before["prefill_prompts"]
    log(f"lm_insert: docs={len(docs)} new_chunks={rep.n_new_chunks} "
        f"resummarized={rep.n_resummarized} lm_prompts={prompts} "
        f"prefill_launches="
        f"{engine.stats['prefill_launches'] - before['prefill_launches']} "
        f"decode_launches="
        f"{engine.stats['decode_launches'] - before['decode_launches']} "
        f"tokens_in={rep.tokens_in} tokens_out={rep.tokens_out}")
    fresh = rep.n_resummarized - rep.summary_cache_hits
    if fresh <= 0 or prompts != fresh:
        raise AssertionError(f"{fresh} summaries were due, the engine "
                             f"served {prompts}")
    return rep


def check_exact(rag: EraRAG, questions, k: int) -> None:
    ex = rag.store.export_rows()
    d = rag.cfg.embed_dim
    pos = {nid: i for i, nid in enumerate(ex["ids"].tolist())}
    for b in BATCHES:
        qs = questions[:b]
        rets = rag.query_batch(qs, k=k)
        refs = ref_topk(ex["rows"][:, :d], ex["seqs"],
                        rag.embedder.encode(qs), k)
        swaps = 0
        for i, (r, (order, s)) in enumerate(zip(rets, refs)):
            if not r.hits:
                raise AssertionError(f"B={b} query {i}: no hits")
            swaps += check_ranked([pos[h.node_id] for h in r.hits],
                                  [h.score for h in r.hits], order, s,
                                  f"exact B={b} query {i}")
        log(f"query exact B={b}: ids equal to the NumPy top-{k} over "
            f"{len(ex['ids'])} rows, scores within {SCORE_TOL} "
            f"(near-tie swaps: {swaps})")


def check_quantized(rag: EraRAG, questions, k: int) -> None:
    """The quantized profile over the same graph: hits equal a NumPy
    two-stage reference (Hamming top-C over the device codes, lowest
    row first, then an exact float64 rescore), and every score is the
    row's exact score."""
    qcfg = dataclasses.replace(ERARAG_QUANTIZED,
                               embed_dim=rag.cfg.embed_dim,
                               seed=rag.cfg.seed)
    exact_store, rag.store = rag.store, make_store(rag.graph, qcfg)
    try:
        ex = rag.store.export_rows()
        bufs = rag.store.device_buffers()
        n = len(ex["ids"])
        codes = np.asarray(bufs["codes"])[:n]  # fresh build: no dead rows
        d = qcfg.embed_dim
        spec = QuantSpec(d, qcfg.scan_bits, ex["rows"].shape[1] - d,
                         qcfg.seed)
        planes = jnp.asarray(hyperplanes(spec))
        pos = {nid: i for i, nid in enumerate(ex["ids"].tolist())}
        emb = ex["rows"][:, :d].astype(np.float64)
        c = qcfg.coarse_mult * k
        exact_refs = None
        for b in BATCHES:
            qs = questions[:b]
            rets = rag.query_batch(qs, k=k)
            q = rag.embedder.encode(qs)
            qc = np.asarray(encode_queries(jnp.asarray(q), planes,
                                           (0.0,) * spec.n_flags, spec))
            exact_refs = ref_topk(ex["rows"][:, :d], ex["seqs"], q, k)
            recall = 0.0
            for i, r in enumerate(rets):
                dist = popcount_dist(qc[i], codes)
                cand = np.lexsort((np.arange(n), dist))[:c]
                s = np.full(n, -np.inf)
                s[cand] = emb[cand] @ q[i].astype(np.float64)
                order = np.lexsort((np.arange(n), -s))[:k]
                rows = [pos[h.node_id] for h in r.hits]
                check_ranked(rows, [h.score for h in r.hits], order, s,
                             f"quantized B={b} query {i}")
                recall += len(set(rows) & set(exact_refs[i][0][:k]
                                              .tolist())) / k
            log(f"query quantized B={b}: ids equal to the NumPy "
                f"two-stage reference (C={c}), scores exact within "
                f"{SCORE_TOL}; recall@{k} vs exact {recall / b:.4f}")
    finally:
        rag.store = exact_store


def check_multihop(rag: EraRAG, questions, k: int) -> None:
    ex = rag.store.export_rows()
    d = rag.cfg.embed_dim
    score = {nid: row for nid, row in zip(ex["ids"].tolist(),
                                          ex["rows"][:, :d])}
    rounds = rag.stats["retrieval_rounds"]
    rets = rag.query_batch(questions, k=k, mode="multihop")
    used = rag.stats["retrieval_rounds"] - rounds
    hops = [r.hops for r in rets]
    for i, r in enumerate(rets):
        for rr, text in zip(r.rounds, [questions[i], r.bridge_query]):
            qv = rag.embedder.encode([text])[0].astype(np.float64)
            got = np.asarray([h.score for h in rr.hits])
            want = np.asarray([score[h.node_id] @ qv for h in rr.hits])
            if not len(got) or np.abs(got - want).max() > SCORE_TOL:
                raise AssertionError(f"multihop query {i}: scores off")
            if np.any(np.diff(got) > SCORE_TOL):
                raise AssertionError(f"multihop query {i}: unsorted")
    if used > 2:
        raise AssertionError(f"multihop block took {used} rounds")
    log(f"query multihop B={len(questions)}: rounds={used} "
        f"hops={hops}, every hit scored exactly within {SCORE_TOL}")


def check_answers(rag: EraRAG, engine: Engine, questions) -> None:
    pipe = RAGPipeline(rag, engine=engine)
    first = pipe.answer_batch(questions)
    second = pipe.answer_batch(questions)
    for q, a, b in zip(questions, first, second):
        log(f"answer: {q!r} -> {a.answer!r} "
            f"(context {a.n_context_tokens} tokens, {a.hits} hits)")
        if a.answer != b.answer or not a.answer:
            raise AssertionError(f"answers differ or are empty: "
                                 f"{a.answer!r} vs {b.answer!r}")
    log(f"answer: {len(questions)} answers token-identical over two runs")


def kernel_programs(rag: EraRAG, n_hash: int, b: int, k: int) -> dict:
    """Compiled text of each main-path kernel's program at the shapes
    the run served: the exact scan, the LSH hash, the Hamming scan."""
    d = rag.cfg.embed_dim
    buf = rag.store.device_buffers()["rows"]
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    n_words = QuantSpec(d, ERARAG_QUANTIZED.scan_bits, buf.shape[1] - d,
                        0).n_words
    return {
        "mips_topk": mips_ops._mips_topk.lower(
            sds((b, buf.shape[1]), f32), sds(buf.shape, f32), k=k),
        "lsh_hash": lsh_hash.lower(
            sds((n_hash, d), f32), sds((d, rag.cfg.n_hyperplanes), f32)),
        "hamming_topk": hamming_topk.lower(
            sds((b, n_words), jnp.uint32),
            sds((buf.shape[0], n_words), jnp.uint32),
            k=ERARAG_QUANTIZED.coarse_mult * k),
    }


def check_kernels(programs: dict, on_chip: bool) -> None:
    for name, lowered in programs.items():
        found = "tpu_custom_call" in lowered.compile().as_text()
        log(f"kernel {name}: tpu_custom_call "
            f"{'present' if found else 'absent'}")
        if on_chip and not found:
            raise AssertionError(f"{name} did not compile to a TPU "
                                 f"kernel")


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else \
        f"{peak} ({peak / 2**30:.3f} GiB)"


def one_chip(sz: dict, seed: int, ph: Phases, on_chip: bool) -> None:
    corpus, cfg = build_corpus(sz, seed)
    base, extra = corpus.docs[:sz["docs"]], corpus.docs[sz["docs"]:]
    rag = EraRAG(cfg, HashingEmbedder(cfg.embed_dim, seed=seed))
    ph.run("build", lambda: (rag.insert_docs(base),
                             rag.store.device_buffers()["rows"]))
    log(f"index: rows={rag.store.size} dim={cfg.embed_dim} "
        f"nodes={len(rag.graph.nodes)} "
        f"device_bytes={index_bytes(rag.store)}")

    engine = ph.run("lm_init", lambda: make_engine(sz, seed, not on_chip))
    rep = ph.run("lm_insert", lambda: (
        lm_insert(rag, engine, extra, sz),
        rag.store.device_buffers()["rows"])[0])
    log(f"index: rows={rag.store.size} after the LM round")

    k = cfg.top_k
    detailed = [x.question for x in corpus.qa if x.kind == "detailed"]
    multihop = [x.question for x in corpus.qa if x.kind == "multihop"]
    ph.run("query_exact", lambda: check_exact(rag, detailed, k))
    ph.run("query_quantized", lambda: check_quantized(rag, detailed, k))
    ph.run("query_multihop", lambda: check_multihop(rag, multihop[:8], k))
    ph.run("answer", lambda: check_answers(
        rag, engine, detailed[:sz["n_answers"]]))
    check_kernels(kernel_programs(rag, rep.n_new_chunks, max(BATCHES), k),
                  on_chip)


def four_chips(sz: dict, seed: int, ph: Phases, on_chip: bool) -> None:
    """The same corpus in a ShardedVectorStore over a 4-device data
    mesh (one collective launch per query) against the flat store on
    the same rows."""
    mesh = local_data_mesh(min_devices=4, n_devices=4)
    if mesh is None:
        raise AssertionError(f"--chips 4 needs 4 devices, found "
                             f"{len(jax.devices())}")
    corpus, cfg = build_corpus(sz, seed)
    rag = EraRAG(cfg, HashingEmbedder(cfg.embed_dim, seed=seed))
    ph.run("build", lambda: rag.insert_docs(corpus.docs[:sz["docs"]]))
    flat = VectorStore(rag.graph)
    sharded = ShardedVectorStore(rag.graph, n_shards=4, mesh=mesh)
    stack = ph.run("stack", lambda: (flat.device_buffers()["rows"],
                                     sharded.device_buffers()["rows"]))[1]
    if not sharded.collective_active:
        raise AssertionError("the collective scan is not active")
    placed = sorted((s.index[0].start, s.device.id, s.data.shape[0])
                    for s in stack.addressable_shards)
    log(f"sharded stack {stack.shape}: (slot, device, slots) {placed}")
    if len({dev for _, dev, _ in placed}) != 4 or \
            [(slot, n) for slot, _, n in placed] != \
            [(i, 1) for i in range(4)]:
        raise AssertionError("the stacked buffer is not one shard slot "
                             "per device")

    ex = flat.export_rows()
    d = cfg.embed_dim
    pos = {nid: i for i, nid in enumerate(ex["ids"].tolist())}
    detailed = [x.question for x in corpus.qa if x.kind == "detailed"]
    k = cfg.top_k

    def compare():
        for b in BATCHES:
            q = rag.embedder.encode(detailed[:b])
            launches = sharded.stats.kernel_launches
            got = sharded.search_batch(q, k)
            if sharded.stats.kernel_launches - launches != 1:
                raise AssertionError("sharded query took more than one "
                                     "launch")
            want = flat.search_batch(q, k)
            refs = ref_topk(ex["rows"][:, :d], ex["seqs"], q, k)
            for i, (g, w, (order, s)) in enumerate(zip(got, want, refs)):
                if [h.node_id for h in g] != [h.node_id for h in w]:
                    raise AssertionError(f"B={b} query {i}: sharded ids "
                                         f"differ from the flat store")
                diff = max(abs(x.score - y.score) for x, y in zip(g, w))
                if diff > SCORE_TOL:
                    raise AssertionError(f"B={b} query {i}: sharded "
                                         f"scores off by {diff:.3e}")
                check_ranked([pos[h.node_id] for h in g],
                             [h.score for h in g], order, s,
                             f"sharded B={b} query {i}")
            log(f"sharded B={b}: one collective launch, ids equal to "
                f"the flat store and the NumPy top-{k}, scores within "
                f"{SCORE_TOL}")

    ph.run("sharded_query", compare)
    bufs = sharded.device_buffers()
    qs = jax.ShapeDtypeStruct((max(BATCHES), d), jnp.float32)
    text = mips_ops._sharded_mips_topk.lower(
        qs, bufs["rows"], bufs["seq"], k_shard=k, k_out=k,
        flag_bias=(mips_ops.MASK_BIAS, 0.0, 0.0), mesh=mesh,
        axis_names=("data",), use_pallas=None,
        interpret=None).compile().as_text()
    found = {"tpu_custom_call": "tpu_custom_call" in text,
             "all-gather": "all-gather" in text}
    log(f"sharded program: {found}")
    if on_chip and not all(found.values()):
        raise AssertionError(f"sharded program lacks {found}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-index path on 4 chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no ok line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU (backend {dev.platform!r}); "
              f"use --rehearse for a CPU rehearsal", file=sys.stderr)
        return 2
    sz = SIZES["tiny" if args.rehearse else "full"]
    where = f"{'chip run' if dev.platform == 'tpu' else 'rehearsal'} " \
            f"on {dev.platform} {dev.device_kind}"
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"sizes: {'tiny' if args.rehearse else 'full'}; seed {args.seed}")
    ph = Phases(where)
    on_chip = dev.platform == "tpu"
    try:
        if args.chips == 4:
            four_chips(sz, args.seed, ph, on_chip)
        else:
            one_chip(sz, args.seed, ph, on_chip)
    finally:
        ph.close()
    log(f"compile: {ph.compile_s:.3f} s backend compile in total")
    log(f"peak_bytes_in_use: {peak_bytes(dev)}")
    if args.rehearse:
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices) if args.chips == 4 else 1}}), flush=True)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
