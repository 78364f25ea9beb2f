"""Closed loop of single-document inserts, summarized by the LM.

Each round inserts the next held-out document through
``EraRAG.insert_docs`` (chunk, embed, LSH-route, re-partition the
touched segments, re-summarize them through ``LMSummarizer`` on the
``Engine``) and ends with the store refreshed and its buffer ready on
the device, so the document is searchable.  Rounds run back to back;
the window closes at the end of the round in progress when
``--seconds`` runs out, so no round is cut.

Traffic parameters (``traffic/<name>.json``): ``docs_per_round`` and
``warm_rows``.  The held-out documents are taken in the order the
configuration's corpus lists them, whatever the seed, so that every
seed starts from the same insert; the seed draws the LM's weights, and
through its summaries how far the upper layers re-partition.

``correct`` compares what the window served (see ``check``).
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List

import numpy as np

from bench.common import log
from bench.index import open_index
from bench.lm import Recorder, lm_config, make_params, shape
from bench.reference import index as rix
from bench.reference import qwen2 as rq
from bench.reference import text as rt

PREFIX = ("Summarize the following passages into one coherent "
          "paragraph:\n")
# prefill buckets the window's prompts can fall in: a prompt is the
# instruction and 1..s_max passages of at most ~96 words
BUCKETS = (64, 128, 256, 512, 1024, 2048)
# requests, beyond the longest, that the LM check samples
SAMPLE = 15
# a near tie: a served position where the reference's best logit leads
# its runner-up by less than this
TIE = 0.05
# the LM numbers a run reads (each compared when the configuration
# gives it a limit)
LM_NUMBERS = ("lm_logit_gap", "lm_gap_mean", "lm_tie_gap")


def _gap_numbers(gap: np.ndarray, margin: np.ndarray) -> dict:
    """Over served positions: the widest gap, the mean gap, and the
    gaps summed per near tie.  A token other than the reference's best
    is served only near a tie, and how many near ties a sequence has
    is the weights' doing, not the arithmetic's: per near tie, the sum
    reads the arithmetic's error alone."""
    if not len(gap):
        return {k: float("inf") for k in LM_NUMBERS}
    ties = max(1, int(np.sum(margin < TIE)))
    return {"lm_logit_gap": float(gap.max()),
            "lm_gap_mean": float(gap.mean()),
            "lm_tie_gap": float(gap.sum() / ties)}


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, cache_dir,
                 limits: Dict[str, float]):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.cache_dir = cache_dir
        self.limits = limits
        self.reports: List = []
        self.docs: List = []

    # ------------------------------------------------------------------
    def setup(self, annotate: Callable = contextlib.nullcontext) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core.summarize import LMSummarizer
        from repro.serving.engine import Engine, EngineConfig

        cfg = self.cfg
        self.rag, held_out, _ = open_index(cfg, self.cache_dir)
        self.base_ids = set(self.rag.graph.nodes)
        self.pool = list(held_out)
        sv = cfg["serving"]
        self.params = make_params(cfg, self.seed)
        jax.block_until_ready(self.params)
        self.engine = Engine(lm_config(cfg), self.params, EngineConfig(
            max_batch=sv["max_batch"], max_seq_len=sv["max_seq_len"],
            compute_dtype=jnp.bfloat16))
        self.rec = Recorder(self.engine)
        self.rag.graph.summarizer = LMSummarizer(self.engine)
        with annotate("bench.warm"):
            self._warm()
        self.rec.clear()
        self.stats0 = dict(self.engine.stats)

    def _warm(self) -> None:
        """Compile every shape the window can use: each prefill
        bucket, decode groups of 1..max_batch slots, and the store's
        row programs at 1..``warm_rows`` rows (the most one round
        appends, tombstones or hashes at once)."""
        import jax
        import jax.numpy as jnp
        from repro.core import store as S
        from repro.kernels.lsh_hash.ops import lsh_hash

        eng, mb = self.engine, self.cfg["serving"]["max_batch"]
        for b in [b for b in BUCKETS
                  if b <= self.cfg["serving"]["max_seq_len"] // 2]:
            eng.generate_batch([" ".join(["w"] * (b - 2))],
                               max_new_tokens=2)
        for g in range(2, mb + 1):
            eng.generate_batch([" ".join(["w"] * 60)] * g,
                               max_new_tokens=2)
        g = self.rag.store._group
        planes = jnp.asarray(self.rag.graph.lsh.hyperplanes)
        cols = g.buf.shape[-1]
        d = self.cfg["index"]["embed_dim"]
        write = S._write_rows_fn(g.sharding, g._flat2d)
        dead = S._mark_dead_fn(g.sharding, g._flat2d, g.dim)
        outs = []
        for m in range(1, self.traffic["warm_rows"] + 1):
            outs.append(lsh_hash(jnp.zeros((m, d), jnp.float32), planes))
            outs.append(write(g.buf, np.zeros((m, cols), np.float32),
                              np.int32(0), np.int32(0)))
            outs.append(dead(g.buf, np.zeros((m,), np.int32),
                             np.int32(0)))
            jax.block_until_ready(outs)
            outs.clear()

    # ------------------------------------------------------------------
    def window(self, seconds: float,
               annotate: Callable = contextlib.nullcontext) -> dict:
        import jax
        rag = self.rag
        t0 = time.perf_counter()
        i = 0
        while True:
            n = self.traffic["docs_per_round"]
            docs = [self.pool[j % len(self.pool)] for j in range(i, i + n)]
            i += n
            with annotate("bench.ingest_round"):
                rep = rag.insert_docs(docs)
                rag.store.refresh()
                jax.block_until_ready(rag.store.device_buffers()["rows"])
            self.reports.append(rep)
            self.docs.extend(docs)
            if time.perf_counter() - t0 >= seconds:
                break
        self.elapsed = time.perf_counter() - t0
        chunks = sum(r.n_new_chunks for r in self.reports)
        log(f"ingest: {len(self.reports)} rounds, {chunks} chunks, "
            f"{sum(r.n_resummarized for r in self.reports)} segments "
            f"re-summarized in {self.elapsed:.3f} s")
        return {"ingest_chunks_per_s": chunks / self.elapsed,
                "attempted": len(self.reports), "failed": 0}

    def counters(self) -> dict:
        st = self.engine.stats
        d = {k: st[k] - self.stats0[k] for k in st}
        served = []
        sv = self.cfg["serving"]
        vocab = self.cfg["vocab_size"]
        for _, prompt, budget, toks in self.rec.finished():
            ids = rt.served_prompt(prompt, vocab, budget,
                                   sv["max_seq_len"])
            served.append((len(ids), len(toks)))
        return {"engine": d, "served": served,
                "shape": shape(self.cfg),
                "chunks": sum(r.n_new_chunks for r in self.reports),
                "tokens_in": sum(r.tokens_in for r in self.reports),
                "tokens_out": sum(r.tokens_out for r in self.reports)}

    def release(self) -> None:
        """Free the engine's caches before the reference runs."""
        self.engine.caches = None
        self.engine._prefix_cache.clear()

    # ------------------------------------------------------------------
    def check(self) -> List[tuple]:
        """(name, value, limit) for each number compared."""
        lm = self.lm_numbers()["program"]
        return [(k, lm[k], self.limits[k]) for k in LM_NUMBERS
                if k in self.limits] + [
            ("summary_mismatch", self.summary_mismatch(), 0),
            ("visible_missing", self.visible_missing(), 0),
            ("lsh_mismatch", self.lsh_mismatch(), 0),
            ("graph_violations", self.graph_violations(), 0)]

    def readings(self) -> dict:
        """The LM numbers of the program and of the int8 control, on
        the same sample of served requests."""
        return self.lm_numbers(control=True)

    def sample(self) -> List[tuple]:
        """The longest finished request and ``SAMPLE`` more, drawn from
        the seed: (prompt ids, served tokens)."""
        sv = self.cfg["serving"]
        done = [(rt.served_prompt(p, self.cfg["vocab_size"], b,
                                  sv["max_seq_len"]), toks)
                for _, p, b, toks in self.rec.finished()]
        if not done:
            return []
        longest = max(range(len(done)),
                      key=lambda j: len(done[j][0]) + len(done[j][1]))
        rest = [j for j in range(len(done)) if j != longest]
        rng = np.random.default_rng(self.seed)
        pick = rng.choice(len(rest), size=min(SAMPLE, len(rest)),
                          replace=False) if rest else []
        return [done[longest]] + [done[rest[int(j)]] for j in pick]

    def lm_numbers(self, control: bool = False) -> dict:
        """Over the sampled requests' served tokens, how far each lies
        below the float32 reference's best logit (``_gap_numbers``).
        With ``control``, the same of the tokens the int8 forward puts
        first, at the same positions."""
        t = time.perf_counter()
        shp = shape(self.cfg)
        per = [rq.served_gaps(self.params, shp, prompt, toks, control)
               for prompt, toks in self.sample()]
        a = {k: np.concatenate([r[k] for r in per] or [np.zeros(0)])
             for k in ("gap", "margin", "control")
             if not per or k in per[0]}
        self.gap_arrays = a
        out = {"program": _gap_numbers(a["gap"], a["margin"])}
        if control:
            out["control"] = _gap_numbers(a["control"], a["margin"])
        log(f"lm reference: {sum(len(r['gap']) for r in per)} served "
            f"tokens of {len(per)} requests in "
            f"{time.perf_counter() - t:.3f} s; {out}")
        return out

    def new_nodes(self) -> Dict[str, object]:
        return {nid: n for nid, n in self.rag.graph.nodes.items()
                if nid not in self.base_ids}

    def summary_mismatch(self) -> int:
        """New summary nodes whose text is not what the LM served for
        the prompt of their members, plus those the LM never served
        beyond the summary cache's hits."""
        served = {p: rt.detok(t) for _, p, _, t in self.rec.finished()}
        nodes = self.rag.graph.nodes
        bad = unserved = 0
        for n in self.new_nodes().values():
            if n.layer == 0:
                continue
            prompt = PREFIX + "\n".join(nodes[c].text for c in n.children)
            if prompt in served:
                bad += served[prompt] != n.text
            else:
                unserved += 1
        hits = sum(r.summary_cache_hits for r in self.reports)
        return bad + max(0, unserved - hits)

    def visible_missing(self) -> int:
        """Inserted chunks and new summaries that are not searchable,
        or are stored off the reference embedding of their text, and
        removed nodes that are still searchable."""
        ix = self.cfg["index"]
        ex = self.rag.store.export_rows()
        d = ix["embed_dim"]
        row = {nid: i for i, nid in enumerate(ex["ids"].tolist())}
        emb = rt.Embedder(d, ix["embedder_features"], ix["embedder_seed"])
        want = [c for doc_id, text in dict.fromkeys(self.docs)
                for c in rt.chunks(doc_id, text, ix["chunk_tokens"])]
        want += [(nid, n.text) for nid, n in self.new_nodes().items()
                 if n.layer > 0]
        ref = emb.encode([t for _, t in want])
        bad = 0
        for (nid, _), e in zip(want, ref):
            i = row.get(nid)
            if i is None or np.max(np.abs(ex["rows"][i, :d] - e)) > 1e-5:
                bad += 1
        graph = self.rag.graph.nodes
        bad += sum(1 for nid in row if nid not in graph)
        return bad

    def lsh_mismatch(self) -> int:
        """New nodes whose bucket key is not the sign pattern of their
        embedding on the seeded hyperplanes (projections within 1e-4
        of zero may fall either way and are not counted)."""
        ix = self.cfg["index"]
        new = list(self.new_nodes().values())
        if not new:
            return 0
        proj = rt.lsh_projections(np.stack([n.embedding for n in new]),
                                  ix["embed_dim"], ix["n_hyperplanes"],
                                  ix["seed"])
        return sum(1 for n, p in zip(new, proj)
                   if np.min(np.abs(p)) > 1e-4 and rt.lsh_key(p) != n.key)

    def graph_violations(self) -> int:
        g = self.rag.graph
        layers = [[(tuple(s.members), s.parent) for s in segs]
                  for segs in g.segments]
        errs = rix.hierarchy_violations(
            layers, {nid: n.layer for nid, n in g.nodes.items()},
            {nid: n.children for nid, n in g.nodes.items()},
            self.cfg["index"]["s_max"])
        for e in errs[:5]:
            log(f"hierarchy: {e}")
        return len(errs)
