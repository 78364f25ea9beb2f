"""Sharded retrieval: the EraRAG flat index distributed with shard_map.

Demonstrates the production retrieval layout on however many devices
exist locally (the dry-run proves the 256/512-chip version): the node
embedding matrix is sharded row-wise over the data axis, every device
scans its shard with the mips kernel path, and a tiny top-k merge
produces exact global results.  The second half shows the *maintained*
version of the same layout — ``ShardedVectorStore`` hash-routes the
graph's per-version deltas to owning shards so corpus growth stays
O(delta) per chip, holding the shard buffers as ONE stacked
``(n_shards, cap, d+flags)`` array over the data axis.

With ``collective_query=True`` (``EraRAGConfig.collective_query``, the
default; ``collective=`` on the store) the whole sharded query runs as
a single jitted ``shard_map`` launch — per-device scan, candidate
``all_gather``, lowest-sequence merge — instead of one host dispatch
per shard; the loop stays available as the parity oracle and the
automatic fallback on single-device meshes.  Maintenance is off the
query path too: each ``refresh()`` compacts at most ONE over-threshold
shard (round-robin), staging the gather in a double buffer that the
next refresh swaps in, so queries between refreshes never absorb a
full-buffer gather (``store.compact()`` force-drains everything).

    PYTHONPATH=src python examples/distributed_retrieval.py
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/distributed_retrieval.py
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common.config import EraRAGConfig
from repro.core.erarag import EraRAG
from repro.core.store import ShardedVectorStore
from repro.data.corpus import SyntheticCorpus
from repro.embed.hashing import HashingEmbedder
from repro.kernels.mips_topk.ops import merge_sharded_topk, mips_topk
from repro.launch.mesh import local_data_mesh


def main() -> None:
    cfg = EraRAGConfig(embed_dim=128, n_hyperplanes=10, s_min=4,
                       s_max=12, max_layers=3, chunk_tokens=32)
    rag = EraRAG(cfg, HashingEmbedder(dim=cfg.embed_dim))
    corpus = SyntheticCorpus.generate(n_docs=50, n_topics=5, seed=0)
    rag.insert_docs(corpus.docs)
    ids, embs, _ = rag.graph.all_embeddings()
    n_dev = len(jax.devices())
    mesh = local_data_mesh(min_devices=1)
    k = 8

    # pad rows to device multiple, shard row-wise
    n = embs.shape[0]
    pad = (-n) % n_dev
    db = np.pad(embs, ((0, pad), (0, 0)))
    shard_rows = db.shape[0] // n_dev

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, None), P("data", None)),
        out_specs=(P("data", None, None), P("data", None, None)))
    def shard_search(q, db_shard):
        v, i = mips_topk(q, db_shard, k)
        base = jax.lax.axis_index("data") * shard_rows
        return v[None], (i + base)[None]

    queries = rag.embedder.encode(
        [qa.question for qa in corpus.qa[:4]])
    v_sh, i_sh = shard_search(jnp.asarray(queries), jnp.asarray(db))
    v, i = merge_sharded_topk(v_sh, i_sh, k)

    # exact-match check vs single-device search
    v_ref, i_ref = mips_topk(jnp.asarray(queries), jnp.asarray(embs), k)
    assert np.allclose(np.asarray(v), np.asarray(v_ref), atol=1e-5)
    assert np.array_equal(np.asarray(i), np.asarray(i_ref))
    print(f"sharded retrieval over {n_dev} device(s): exact match "
          f"with single-device search for {queries.shape[0]} queries")
    for qi, qa in enumerate(corpus.qa[:2]):
        top = ids[int(np.asarray(i)[qi, 0])]
        print(f"Q: {qa.question}  top-1 node: {top}")

    # --- the maintained version: incremental sharded store -----------
    sharded = ShardedVectorStore(rag.graph, mesh=mesh)
    sharded.refresh()
    staged0 = [s.rows_staged for s in sharded.shard_stats()]
    extra = SyntheticCorpus.generate(n_docs=2, n_topics=2, seed=7)
    rag.insert_docs(extra.docs)
    sharded.refresh()
    rag.store.refresh()
    staged = [s.rows_staged - b
              for s, b in zip(sharded.shard_stats(), staged0)]
    hits_flat = rag.store.search_batch(queries, k)
    hits_shard = sharded.search_batch(queries, k)
    assert all(
        [(h.node_id, h.score) for h in a]
        == [(h.node_id, h.score) for h in b]
        for a, b in zip(hits_flat, hits_shard))
    print(f"ShardedVectorStore over {sharded.n_shards} shard(s): "
          f"delta staged per shard {staged} (total "
          f"{sum(staged)} of {sharded.size} rows), exact parity with "
          f"the single-buffer store")

    # --- collective single-launch query ------------------------------
    from repro.kernels.mips_topk import ops as mips_ops
    if sharded.collective_active:
        mips_ops.reset_launch_count()
        hits_coll = sharded.search_batch(queries, k)
        n_coll = mips_ops.launch_count()
        sharded.collective = False           # the parity oracle
        mips_ops.reset_launch_count()
        hits_loop = sharded.search_batch(queries, k)
        n_loop = mips_ops.launch_count()
        sharded.collective = True
        assert all(
            [(h.node_id, h.score) for h in a]
            == [(h.node_id, h.score) for h in b]
            for a, b in zip(hits_coll, hits_loop))
        print(f"collective query: {n_coll} launch for the whole "
              f"{sharded.n_shards}-shard scan+merge vs {n_loop} on "
              f"the per-shard loop, bitwise-identical results")
    else:
        print("collective query auto-off (single-device mesh): "
              "per-shard loop dispatch")


if __name__ == "__main__":
    main()
