"""Open-loop retrieval: questions arrive as a Poisson stream at a fixed
rate, and the server takes every question due (at most ``max_batch``)
into one ``EraRAG.query_batch`` per iteration.

Traffic parameters (``traffic/<name>.json``): ``rate_per_s``,
``max_batch``, ``qa_kinds`` (the QA pool the questions are drawn from,
uniformly), ``mode`` and ``k``.  The seed draws the arrival gaps and
the questions; a question's latency runs from its scheduled arrival to
the return of its composed context, so a stalled server shows as late
answers.  Questions due before the window closes are all answered,
after the close if need be, and all count.  Only the answers of the
questions the check samples are kept: a server keeps none, and the
host's garbage collector should not pay for the harness's record.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Callable, Dict, List

import numpy as np

from bench.common import log
from bench.index import open_index
from bench.reference import index as rix
from bench.reference import text as rt

# questions the check compares with the reference, drawn from the seed
SAMPLE = 512
# base rows, besides those either side ranks for the sampled questions,
# that the check re-embeds from their node's text
ROWS = 2048


def schedule(rate: float, seconds: float, n_pool: int, seed: int):
    """Arrival times (s) and question indices of a Poisson stream."""
    rng = np.random.default_rng(seed)
    n = int(rate * seconds * 1.5) + 64
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, size=n))])
    t = t[t < seconds]
    return t, rng.integers(0, n_pool, size=len(t))


class _GcPauses:
    """Host pauses of Python's garbage collector, by generation."""

    def __init__(self):
        self.n = [0, 0, 0]
        self.s = [0.0, 0.0, 0.0]
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            g = info["generation"]
            self.n[g] += 1
            self.s[g] += time.perf_counter() - self._t

    def close(self) -> None:
        gc.callbacks.remove(self._cb)

    def __str__(self) -> str:
        return "gc pauses " + ", ".join(
            f"gen{g} {self.n[g]} ({self.s[g]:.3f} s)" for g in range(3))


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, cache_dir,
                 limits: Dict[str, float]):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.cache_dir = cache_dir
        self.limits = limits

    def setup(self, annotate: Callable = contextlib.nullcontext) -> None:
        self.rag, _, qa = open_index(self.cfg, self.cache_dir)
        kinds = set(self.traffic["qa_kinds"])
        self.pool = [q for q, _, kind in qa if kind in kinds]
        with annotate("bench.warm"):
            for b in range(1, self.traffic["max_batch"] + 1):
                self.rag.query_batch(self.pool[:b], k=self.traffic["k"],
                                     mode=self.traffic["mode"])
        # the embedder is host NumPy, so the host clock times it soundly
        self.embed_s = 0.0
        emb = self.rag.embedder
        encode = emb.encode

        def timed_encode(texts):
            t = time.perf_counter()
            try:
                return encode(texts)
            finally:
                self.embed_s += time.perf_counter() - t

        emb.encode = timed_encode

    def window(self, seconds: float,
               annotate: Callable = contextlib.nullcontext,
               rate: float = None) -> dict:
        tr = self.traffic
        rate = rate or tr["rate_per_s"]
        arrive, which = schedule(rate, seconds, len(self.pool), self.seed)
        n, mb = len(arrive), tr["max_batch"]
        done_at = np.full(n, np.nan)
        self.which = which
        self.idx = self._sample(n)
        keep = np.zeros(n, bool)
        keep[self.idx] = True
        self.kept: Dict[int, object] = {}
        waits = []
        late = []          # pick-up delay of arrivals at an idle server
        idle = False
        self.embed_s = 0.0
        self.sizes: List[int] = []
        nxt = 0
        batches = 0
        pauses = _GcPauses()
        t0 = time.perf_counter()
        while nxt < n:
            now = time.perf_counter() - t0
            if arrive[nxt] > now:
                idle = True
                time.sleep(min(arrive[nxt] - now, 0.0005))
                continue
            end = nxt + 1
            while end < n and end - nxt < mb and arrive[end] <= now:
                end += 1
            waits.append(now - arrive[nxt])
            if idle:
                late.append(now - arrive[nxt])
                idle = False
            with annotate("bench.query_batch"):
                res = self.rag.query_batch(
                    [self.pool[int(j)] for j in which[nxt:end]],
                    k=tr["k"], mode=tr["mode"])
            t = time.perf_counter() - t0
            res = (list(res) + [None] * (end - nxt))[:end - nxt]
            done_at[nxt:end] = [t if r is not None else np.nan
                                for r in res]
            for j in np.flatnonzero(keep[nxt:end]):
                self.kept[nxt + int(j)] = res[j]
            self.sizes.append(end - nxt)
            nxt = end
            batches += 1
        self.elapsed = time.perf_counter() - t0
        pauses.close()
        # an unanswered question misses every latency limit: it counts
        # as waiting until the end of the run, a bound it exceeds
        lat_ms = (np.where(np.isnan(done_at), self.elapsed, done_at)
                  - arrive) * 1e3
        self.n = n
        self.batches = batches
        self.lat_ms = lat_ms
        backlog_s = self.elapsed - seconds
        log(f"retrieve: {n} questions at {rate:.1f}/s in {batches} "
            f"batches (mean {n / max(batches, 1):.2f}); p50 "
            f"{np.percentile(lat_ms, 50):.3f} ms, p95 "
            f"{np.percentile(lat_ms, 95):.3f} ms, p99 "
            f"{np.percentile(lat_ms, 99):.3f} ms; oldest question waited "
            f"up to {max(waits) * 1e3:.3f} ms before service; answered "
            f"{backlog_s:.3f} s past the close; {pauses}")
        if late:
            log(f"generator: an idle server took up {len(late)} arrivals "
                f"late by p50 {np.percentile(late, 50) * 1e3:.3f} ms, max "
                f"{max(late) * 1e3:.3f} ms")
        return {"retrieve_p95_ms": float(np.percentile(lat_ms, 95)),
                "p50_ms": float(np.percentile(lat_ms, 50)),
                "backlog_s": backlog_s, "attempted": n,
                "failed": int(np.isnan(done_at).sum())}

    def counters(self) -> dict:
        return {"queries": self.n, "batches": self.batches,
                "sizes": list(self.sizes), "embed_s": self.embed_s,
                "rows": int(self.rag.store._group.buf.shape[0]),
                "cols": int(self.rag.store._group.buf.shape[1]),
                "k": self.traffic["k"]}

    def release(self) -> None:
        pass

    # ------------------------------------------------------------------
    NUMBERS = ("hit_mismatch", "score_err", "context_mismatch",
               "unanswered", "row_mismatch")

    def check(self) -> List[tuple]:
        got = dict(zip(self.NUMBERS, self.compare()))
        return [(n, got[n], self.limits.get(n, 0)) for n in self.NUMBERS]

    def readings(self) -> dict:
        """The compared numbers of the program and of the control
        (the ``high``-precision scan in the program's place)."""
        return {"program": dict(zip(self.NUMBERS, self.compare())),
                "control": dict(zip(self.NUMBERS,
                                    self.compare(control=True)))}

    def _sample(self, n: int) -> np.ndarray:
        rng = np.random.default_rng(self.seed + 1)
        return np.sort(rng.choice(n, size=min(SAMPLE, n), replace=False))

    def compare(self, control: bool = False):
        """(rank mismatches, widest score error, context mismatches,
        unanswered, row mismatches) over a seeded sample of the window's
        questions, against float64 top-k over the store's rows; with
        ``control`` the served hits are replaced by the
        ``high``-precision scan's.  The reference ranks the store's own
        rows, so those are checked too (``_row_mismatch``)."""
        ix = self.cfg["index"]
        d, k = ix["embed_dim"], self.traffic["k"]
        ex = self.rag.store.export_rows()
        rows, seqs = ex["rows"][:, :d], ex["seqs"]
        ids = ex["ids"].tolist()
        pos = {nid: i for i, nid in enumerate(ids)}
        nodes = self.rag.graph.nodes
        missing = sum(1 for i in self.idx if self.kept.get(int(i)) is None)
        idx = [int(i) for i in self.idx
               if self.kept.get(int(i)) is not None]
        emb = rt.Embedder(d, ix["embedder_features"], ix["embedder_seed"])
        q = emb.encode([self.pool[int(self.which[i])] for i in idx])
        order, scores = rix.topk(rows, seqs, q, k)
        if control:
            c_vals, c_rows = rix.topk_high(rows, q, k)
        tie = self.limits["score_err"]
        mism = ctx = 0
        err = 0.0
        ranked = set(order.ravel().tolist())
        for j, i in enumerate(idx):
            r = self.kept[i]
            if control:
                got = c_rows[j][:len(r.hits)]
                got_s = c_vals[j][:len(r.hits)]
            else:
                got = [pos.get(h.node_id, -1) for h in r.hits]
                got_s = [h.score for h in r.hits]
            if min(got, default=0) < 0:
                mism += 1
                continue
            ranked.update(int(g) for g in got)
            m, e = rix.ranked(got, got_s, order[j], scores[j], tie)
            mism += m
            err = max(err, e)
            # the context: the served hits' texts under the budget rule,
            # and the budget's stop at the next reference hit
            texts = [nodes[ids[g]].text for g in got]
            kept, want = rix.budgeted(
                texts + [nodes[ids[g]].text for g in order[j][len(got):]],
                ix["token_budget"])
            ctx += (kept != len(got)) or (want != r.context)
        return mism, err, ctx, missing, self._row_mismatch(ex, ranked, emb)

    def _row_mismatch(self, ex, ranked, emb) -> int:
        """Rows that either side ranked, and ``ROWS`` more drawn from the
        seed, whose node is gone or whose stored embedding lies more
        than 1e-5 from the reference embedding of the node's text."""
        d = self.cfg["index"]["embed_dim"]
        n = len(ex["ids"])
        rng = np.random.default_rng(self.seed + 2)
        pick = sorted(ranked.union(
            rng.choice(n, size=min(ROWS, n), replace=False).tolist()))
        nodes = self.rag.graph.nodes
        live = [i for i in pick if str(ex["ids"][i]) in nodes]
        bad = len(pick) - len(live)
        for s in range(0, len(live), 1024):
            blk = live[s:s + 1024]
            ref = emb.encode([nodes[str(ex["ids"][i])].text for i in blk])
            dev = np.max(np.abs(ex["rows"][blk, :d] - ref), axis=1)
            bad += int(np.sum(dev > 1e-5))
        return bad
