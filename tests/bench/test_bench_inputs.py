"""Inputs are made from ``--seed`` alone: the same seed gives the same
arrivals, questions and weights, another seed other ones, and seeds
beyond 32 bits work.  The weights have the program's layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as T
from bench import common as C
from bench.lm import arch, make_params

BIG = 2**31 + 12345


def _poisson():
    return C.kind("poisson_query")


@pytest.mark.parametrize("seed", [0, 7, BIG, 2**40 + 3])
def test_arrivals_and_questions_follow_the_seed(seed):
    sched = _poisson().schedule
    t1, q1 = sched(1200.0, 2.0, 5000, seed)
    t2, q2 = sched(1200.0, 2.0, 5000, seed)
    assert np.array_equal(t1, t2) and np.array_equal(q1, q2)
    assert np.all(np.diff(t1) >= 0) and t1[-1] < 2.0
    # a Poisson count at 1200/s over 2 s: 2400 +- 5 sigma
    assert abs(len(t1) - 2400) < 5 * 2400 ** 0.5
    t3, q3 = sched(1200.0, 2.0, 5000, seed + 1)
    assert len(t3) != len(t1) or not np.array_equal(t1, t3)


class _Store:
    def refresh(self):
        pass

    def device_buffers(self):
        return {"rows": jnp.zeros(1)}


class _Index:
    """Records the documents a window inserts."""

    def __init__(self):
        self.docs, self.store = [], _Store()

    def insert_docs(self, docs):
        from repro.core.graph import UpdateReport
        self.docs.extend(docs)
        rep = UpdateReport()
        rep.n_new_chunks = 1
        return rep


def test_ingest_documents_are_the_same_for_every_seed():
    cfg = T.configs()["era768_qwen2-7b-l16"]
    traffic = T.traffics()["single_doc_rounds"]
    pool = [(f"d{i}", f"text {i}.") for i in range(5)]
    inserted = []
    for seed in (1, BIG):
        cell = C.kind(traffic["kind"]).Cell(cfg, traffic, seed, None, {})
        cell.pool, cell.rag = pool, _Index()
        cell.window(0.0)
        inserted.append(cell.rag.docs)
    assert inserted[0] == inserted[1] == pool[:traffic["docs_per_round"]]


def test_weights_follow_the_seed_and_have_the_program_layout():
    cfg = T.configs()["era768_qwen2-7b-l16"]
    lm_arch = arch(cfg["model_type"])
    a = make_params(lm_arch.model.weights, cfg, BIG)
    b = make_params(lm_arch.model.weights, cfg, BIG)
    c = make_params(lm_arch.model.weights, cfg, BIG + 2**32)   # high word
    la, lb, lc = (jax.tree.leaves(x) for x in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[0], lc[0])
    assert all(x.dtype == jnp.bfloat16 for x in la)
    from repro.models import transformer as TR
    lm = lm_arch.model.lm_config(cfg)
    want = jax.eval_shape(
        lambda: TR.init_params(lm, jax.random.PRNGKey(0),
                               dtype=jnp.bfloat16)[0])
    got = jax.eval_shape(lambda: a)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [x.shape for x in jax.tree.leaves(got)] == \
        [x.shape for x in jax.tree.leaves(want)]
