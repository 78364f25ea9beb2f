"""Plain re-statements of the program's documented text handling, for
the references: word tokens and their hashed ids, the engine's prompt
truncation, sentence chunking and the hashing embedder.  Written from
the documented rules; nothing here imports the program.
"""
from __future__ import annotations

import hashlib
import re
from typing import List, Sequence, Tuple

import numpy as np

WORD = re.compile(r"[A-Za-z0-9_]+|[^\sA-Za-z0-9_]")
SENT = re.compile(r"(?<=[.!?])\s+")
BOS, EOS, RESERVED = 1, 2, 4


def words(text: str) -> List[str]:
    return WORD.findall(text)


def _h8(s: str) -> int:
    return int.from_bytes(hashlib.blake2b(s.encode("utf-8"),
                                          digest_size=8).digest(), "little")


def token_ids(text: str, vocab: int) -> List[int]:
    """BOS, one id per lower-cased word, EOS."""
    span = vocab - RESERVED
    return [BOS] + [RESERVED + _h8(w.lower()) % span
                    for w in words(text)] + [EOS]


def served_prompt(text: str, vocab: int, budget: int,
                  max_seq_len: int) -> List[int]:
    """The prompt ids a request is served with: the engine keeps the
    first ``max_seq_len - budget - 1`` ids (budget clamped to
    ``[1, max_seq_len - 2]``)."""
    budget = max(1, min(budget, max_seq_len - 2))
    return token_ids(text, vocab)[:max(1, max_seq_len - budget - 1)]


def detok(ids: Sequence[int]) -> str:
    """A generated text: ``tok<id>`` words, the final EOS dropped."""
    ids = list(ids)
    if ids and ids[-1] == EOS:
        ids = ids[:-1]
    return " ".join(f"tok{t}" for t in ids)


def chunks(doc_id: str, text: str, chunk_tokens: int
           ) -> List[Tuple[str, str]]:
    """(chunk id, text): sentences packed greedily up to
    ``chunk_tokens`` words; the id hashes the document id and text."""
    out, cur, n_cur = [], [], 0
    for sent in [s for s in SENT.split(text.strip()) if s]:
        n = len(words(sent))
        if cur and n_cur + n > chunk_tokens:
            out.append(" ".join(cur))
            cur, n_cur = [], 0
        cur.append(sent)
        n_cur += n
    if cur:
        out.append(" ".join(cur))
    return [(hashlib.blake2b(f"{doc_id}\x00{t}".encode("utf-8"),
                             digest_size=12).hexdigest(), t) for t in out]


class Embedder:
    """Hashed unigram and bigram counts, log1p-damped, through a
    seeded Gaussian projection, L2-normalised; in float64."""

    def __init__(self, dim: int, n_features: int, seed: int):
        rng = np.random.Generator(np.random.PCG64(seed))
        proj = rng.standard_normal((n_features, dim)).astype(np.float32)
        self.proj = proj.astype(np.float64) / np.sqrt(dim)
        self.nf = n_features

    def encode(self, texts: Sequence[str]) -> np.ndarray:
        feats = np.zeros((len(texts), self.nf))
        for i, t in enumerate(texts):
            ws = [w.lower() for w in words(t)]
            for w in ws:
                feats[i, _h8("u:" + w) % self.nf] += 1.0
            for a, b in zip(ws, ws[1:]):
                feats[i, _h8(f"b:{a}:{b}") % self.nf] += 1.0
        v = np.log1p(feats) @ self.proj
        n = np.linalg.norm(v, axis=1, keepdims=True)
        n[n == 0] = 1.0
        return v / n


def lsh_projections(emb: np.ndarray, dim: int, n_planes: int,
                    seed: int) -> np.ndarray:
    """Projections on the seeded hyperplanes, in float64."""
    rng = np.random.Generator(np.random.PCG64(seed))
    planes = rng.standard_normal((dim, n_planes)).astype(np.float32)
    return np.asarray(emb, np.float64) @ planes.astype(np.float64)


def lsh_key(proj_row: np.ndarray) -> int:
    """Bit j is set when projection j is >= 0 (little-endian)."""
    return sum(1 << j for j, p in enumerate(proj_row) if p >= 0)
