"""``correct`` on tiny runs on the CPU: it comes out false when the
timed path is broken underneath, once for each fault the cells can
have, and the control (the reference one precision step below the
configuration's, in the program's place) fails a limit."""
import jax
import numpy as np
import pytest

import bench_tiny as T
from bench_tiny import bench_cache  # noqa: F401  (fixture)
from bench import control
from repro.core.graph import UpdateReport


def altered_tokens(cell):
    """A token altered where it is produced: every 10th decode launch
    puts a fixed token first."""
    step = cell.engine._decode_step
    calls = [0]

    def bad(p, t, c, n):
        logits, caches = step(p, t, c, n)
        calls[0] += 1
        if calls[0] % 10 == 0:
            logits = logits.at[:, 7].add(1e4)
        return logits, caches

    cell.engine._decode_step = bad


def unchanged_state(cell):
    """An insert that returns the state unchanged."""
    def no_insert(docs):
        rep = UpdateReport()
        rep.n_new_chunks = 5
        return rep

    cell.rag.insert_docs = no_insert


def half_summaries(cell):
    """Half of each summary batch left out: the LM serves the first
    half and the rest come back empty."""
    from repro.core.summarize import SummaryResult
    summ = cell.rag.graph.summarizer
    batch = summ.summarize_batch

    def bad(batches):
        h = max(1, len(batches) // 2)
        return batch(batches[:h]) + [SummaryResult("", 0, 0)
                                     for _ in batches[h:]]

    summ.summarize_batch = bad


def altered_answers(cell):
    """A hit altered where it is produced: the best hit of each query
    is replaced by its lowest-ranked one."""
    search = cell.rag.store.search_batch

    def bad(q, k, layer_filter=None):
        out = search(q, k, layer_filter)
        return [[h[-1]] + h[1:] if len(h) > 1 else h for h in out]

    cell.rag.store.search_batch = bad


def swapped_rows(cell):
    """Stored rows altered where the index holds them: the embeddings of
    the first 64 rows are reversed in order on the device."""
    g = cell.rag.store._group
    d = cell.cfg["index"]["embed_dim"]
    g.buf = g.buf.at[:64, :d].set(g.buf[:64, :d][::-1])


def half_batch(cell):
    """Half of each block left out."""
    qb = cell.rag.query_batch

    def bad(texts, **kw):
        return qb(texts, **kw)[:max(1, len(texts) // 2)]

    cell.rag.query_batch = bad


@pytest.mark.parametrize("workload,fault,number", [
    (T.INGEST, altered_tokens, "lm_tie_gap"),
    (T.INGEST, unchanged_state, "visible_missing"),
    (T.INGEST, half_summaries, "summary_mismatch"),
    (T.RETRIEVE, altered_answers, "hit_mismatch"),
    (T.RETRIEVE, swapped_rows, "row_mismatch"),
    (T.RETRIEVE, half_batch, "unanswered"),
])
def test_a_broken_timed_path_is_not_correct(workload, fault, number,
                                            bench_cache):
    code, res = T.run_tiny(workload, bench_cache, patch=fault,
                           seconds=1.0)
    assert code == 0
    assert res["correct"] is False
    c = res["checks"][number]
    assert c["value"] > c["limit"], res["checks"]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("workload,number", [
    (T.INGEST, "lm_tie_gap"), (T.RETRIEVE, "score_err")])
def test_the_control_fails_its_limit(workload, number, seed, bench_cache):
    [(_, r, _)] = list(control.readings(
        workload, [seed], 0.5, bench=T.bench(), configs=T.configs(),
        traffics=T.traffics(), cache_dir=bench_cache))
    limit = T.LIMITS[number]
    assert r["program"][number] <= limit < r["control"][number]


def test_int8_control_matmul_is_int8():
    from bench.reference import qwen2 as rq
    a = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    exact = np.asarray(rq.mm(a, b, "f32"))
    low = np.asarray(rq.mm(a, b, "int8"))
    err = np.abs(low - exact).max()
    # per-row int8 steps of ~|x|max/127 leave errors of about 1e-2 here
    assert 1e-4 < err < 0.2
    assert np.allclose(np.asarray(a @ b), exact, atol=1e-4)
