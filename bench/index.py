"""The base index of a configuration: built once per checkout, kept as
a snapshot, restored by every later run.

The base corpus and the index belong to the configuration, not to the
run's seed: every run starts from the same deployment state.  The seed
draws the questions and their arrival times, and the LM's weights; the
held-out documents arrive in the corpus's own order, the same for
every seed.
The snapshot is keyed by the configuration's index fields and a digest
of the program's source, so a changed program builds its own.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
from pathlib import Path
from typing import List, Tuple

from bench.common import CACHE, log, src_digest

ERARAG_KEYS = ("n_hyperplanes", "s_min", "s_max", "max_layers",
               "embed_dim", "chunk_tokens", "top_k", "token_budget",
               "seed")


def erarag_config(cfg: dict):
    from repro.common.config import EraRAGConfig
    return EraRAGConfig(**{k: cfg["index"][k] for k in ERARAG_KEYS})


def embedder(cfg: dict):
    from repro.embed.hashing import HashingEmbedder
    ix = cfg["index"]
    return HashingEmbedder(ix["embed_dim"],
                           n_features=ix["embedder_features"],
                           seed=ix["embedder_seed"])


def _key(cfg: dict) -> str:
    basis = json.dumps({"index": cfg["index"],
                        "corpus_docs": cfg["corpus_docs"]},
                       sort_keys=True)
    h = hashlib.blake2b(digest_size=10)
    h.update(basis.encode())
    h.update(src_digest().encode())
    return h.hexdigest()


def open_index(cfg: dict, cache_dir: Path = CACHE
               ) -> Tuple[object, List[Tuple[str, str]], list]:
    """(EraRAG over the base corpus, held-out documents, QA items as
    ``(question, answer, kind)``), from the snapshot when there is
    one."""
    from repro.core.erarag import EraRAG
    path = Path(cache_dir) / "index" / f"{_key(cfg)}.pkl"
    if path.exists():
        with open(path, "rb") as f:
            snap = pickle.load(f)
        rag = EraRAG.from_state(snap["state"], embedder(cfg))
        return rag, snap["held_out"], snap["qa"]

    from repro.data.corpus import SyntheticCorpus
    ix = cfg["index"]
    n_base = cfg["corpus_docs"]
    corpus = SyntheticCorpus.generate(n_docs=n_base + ix["held_out_docs"],
                                      seed=ix["corpus_seed"])
    rag = EraRAG(erarag_config(cfg), embedder(cfg))
    log(f"index: building {n_base} documents (once per checkout)")
    rag.insert_docs(corpus.docs[:n_base])
    snap = {"state": rag.state_dict(include_store=True),
            "held_out": corpus.docs[n_base:],
            "qa": [(q.question, q.answer, q.kind) for q in corpus.qa]}
    path.parent.mkdir(parents=True, exist_ok=True)
    part = path.with_suffix(".part")
    with open(part, "wb") as f:
        pickle.dump(snap, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(part, path)
    # restore from the snapshot just written, so the first run serves
    # the same objects every later run does
    return open_index(cfg, cache_dir)
