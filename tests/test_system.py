"""End-to-end behaviour tests for the paper's system."""
import functools
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.common.config import EraRAGConfig
from repro.core.erarag import EraRAG
from repro.data.corpus import SyntheticCorpus
from repro.embed.hashing import HashingEmbedder
from repro.serving.rag_pipeline import ExtractiveReader, RAGPipeline

CFG = EraRAGConfig(embed_dim=128, n_hyperplanes=10, s_min=4, s_max=12,
                   max_layers=3, chunk_tokens=32, top_k=8,
                   token_budget=1024)


@pytest.fixture(scope="module")
def built():
    corpus = SyntheticCorpus.generate(n_docs=60, n_topics=6, seed=0)
    rag = EraRAG(CFG, HashingEmbedder(dim=CFG.embed_dim))
    init, rounds = corpus.growth_rounds(0.5, 10)
    rag.insert_docs(init)
    for r in rounds:
        rag.insert_docs(r)
    return rag, corpus


def test_e2e_qa_after_incremental_growth(built):
    rag, corpus = built
    pipeline = RAGPipeline(rag)
    detailed = [qa for qa in corpus.qa if qa.kind == "detailed"][:80]
    acc = sum(qa.answer in pipeline.answer(qa.question).answer
              for qa in detailed) / len(detailed)
    rec = sum(qa.answer in rag.query(qa.question).context
              for qa in detailed) / len(detailed)
    assert rec > 0.5, f"recall {rec}"
    assert acc > 0.4, f"accuracy {acc}"


def test_e2e_incremental_matches_static_quality(built):
    rag, corpus = built
    static = EraRAG(CFG, HashingEmbedder(dim=CFG.embed_dim))
    static.insert_docs(corpus.docs)
    detailed = [qa for qa in corpus.qa if qa.kind == "detailed"][:60]
    rec_inc = sum(qa.answer in rag.query(qa.question).context
                  for qa in detailed)
    rec_sta = sum(qa.answer in static.query(qa.question).context
                  for qa in detailed)
    # Fig 5: incremental converges to the static bound
    assert rec_inc >= rec_sta - 6


def test_e2e_update_cheaper_than_rebuild(built):
    rag, corpus = built
    extra = SyntheticCorpus.generate(n_docs=2, n_topics=2, seed=99)
    rep = rag.insert_docs(extra.docs)
    rebuild = EraRAG(CFG, HashingEmbedder(dim=CFG.embed_dim))
    rep_build = rebuild.insert_docs(corpus.docs + extra.docs)
    # 2 out-of-distribution docs (new topics -> scattered buckets):
    # still far below rebuild; the precise O(delta) scaling law is
    # asserted at scale in benchmarks/small_update.py
    assert rep.tokens_total < 0.5 * rep_build.tokens_total
    assert not rag.graph.check_integrity()


def test_e2e_state_roundtrip_serves(built, tmp_path):
    rag, corpus = built
    import numpy as np
    state = rag.state_dict()
    np.savez(tmp_path / "graph.npz", blob=np.asarray([0]))  # smoke io
    rag2 = EraRAG.from_state(state, HashingEmbedder(dim=CFG.embed_dim))
    q = corpus.qa[0]
    a = rag.query(q.question)
    b = rag2.query(q.question)
    assert [h.node_id for h in a.hits] == [h.node_id for h in b.hits]


def test_engine_generates_and_frees_slots(engine_fixture):
    eng = engine_fixture(max_batch=2, max_seq_len=64, max_new_tokens=4)
    rids = [eng.submit(f"question number {i}") for i in range(5)]
    eng.run_until_done()
    assert set(rids) == set(eng._results)
    assert all(1 <= len(v) <= 4 for v in eng._results.values())
    assert not any(s.active for s in eng.slots)


@pytest.mark.slow
def test_dryrun_entrypoint_smoke():
    """launch.dryrun compiles one small cell in a fresh process (512
    fake devices must not leak into this test process)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=512"
        from repro.launch.dryrun import lower_cell
        res = lower_cell("deepfm", "serve_p99", probe=False)
        assert res["memory"]["peak_bytes"] < 2**34
        assert res["mesh"] == {"data": 16, "model": 16}
        print("dryrun-smoke-ok")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**__import__("os").environ,
             "PYTHONPATH": "src"}, cwd=".", timeout=420)
    assert "dryrun-smoke-ok" in out.stdout, out.stderr[-2000:]


def test_shard_map_retrieval_exact():
    """Sharded top-k merge == global top-k on the local mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import local_data_mesh
    from repro.kernels.mips_topk.ops import merge_sharded_topk, \
        mips_topk
    n_dev = len(jax.devices())
    mesh = local_data_mesh(min_devices=1)
    rng = np.random.default_rng(0)
    db = rng.standard_normal((64 * n_dev, 16)).astype(np.float32)
    q = rng.standard_normal((3, 16)).astype(np.float32)
    rows = db.shape[0] // n_dev

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(None, None), P("data", None)),
                       out_specs=(P("data", None, None),
                                  P("data", None, None)))
    def search(qq, shard):
        v, i = mips_topk(qq, shard, 5)
        return v[None], (i + jax.lax.axis_index("data") * rows)[None]

    v_sh, i_sh = search(jnp.asarray(q), jnp.asarray(db))
    v, i = merge_sharded_topk(v_sh, i_sh, 5)
    v_ref, i_ref = mips_topk(jnp.asarray(q), jnp.asarray(db), 5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref),
                               rtol=1e-5)
    assert np.array_equal(np.asarray(i), np.asarray(i_ref))
