"""Observability layer suite: registry, tracer, inertness, drift.

Three load-bearing guarantees:

1. **Disabled means inert** — with the default counters-only config
   the tracer is the shared ``NULL_TRACER``, zero spans are recorded,
   and serving/ingest outputs are bitwise identical to an obs-enabled
   twin (observability reads, never steers).
2. **Deterministic tracing** — spans nest (parent/trace ids, depth),
   are epoch-stamped on the query/lifecycle paths (asserted across a
   real mid-traffic reshard migration via ``LiveHarness``), and under
   an injected ``ManualClock`` the recorded durations are exact.
3. **No silent telemetry** — every numeric key ``index_report()``
   surfaces must be declared in ``INDEX_REPORT_SCHEMA`` (the drift
   check), and the kernel launch counter is registry-owned with
   per-store attribution that cannot bleed between live stores.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.common.config import EraRAGConfig
from repro.core.erarag import EraRAG
from repro.data.corpus import SyntheticCorpus
from repro.embed.hashing import HashingEmbedder
from repro.kernels.mips_topk import ops as mips_ops
from repro.obs import (Histogram, ManualClock, MetricsRegistry,
                       NULL_TRACER, Observability, Tracer, timed_block,
                       use_clock)
from repro.obs.schema import (INDEX_REPORT_SCHEMA, flatten_numeric,
                              undeclared)
from repro.serving.rag_pipeline import RAGPipeline

pytestmark = pytest.mark.obs

CFG = EraRAGConfig(embed_dim=32, n_hyperplanes=8, s_min=2, s_max=4,
                   max_layers=3, chunk_tokens=16, top_k=6,
                   token_budget=512)


def _mk_emb():
    return HashingEmbedder(dim=32, n_features=512, seed=0)


def _corpus(n=10, seed=3):
    return SyntheticCorpus.generate(n_docs=n, seed=seed)


def _rag(cfg=CFG, corpus=None):
    rag = EraRAG(cfg, _mk_emb())
    rag.insert_docs((corpus or _corpus()).docs)
    rag.store.refresh()
    return rag


# -- registry instruments ----------------------------------------------
def test_registry_instruments_and_percentiles():
    reg = MetricsRegistry()
    c = reg.counter("a.b")
    c.inc()
    c.inc(4)
    assert reg.counter("a.b") is c and c.count == 5
    c.reset()
    assert c.count == 0
    g = reg.gauge("a.g")
    g.set(2.5)
    assert reg.gauge("a.g").value == 2.5

    h = reg.histogram("lat")
    rng = np.random.Generator(np.random.PCG64(0))
    xs = rng.uniform(1e-4, 2.0, size=257)
    for x in xs:
        h.observe(float(x))
    # exact: identical to np.percentile over everything observed
    for q in (50, 90, 99):
        assert h.percentile(q) == float(np.percentile(xs, q))
    assert h.count == len(xs) and sum(h.bucket_counts) == h.count
    assert h.sum == pytest.approx(float(xs.sum()))
    assert Histogram("empty").percentile(50) == 0.0


def test_registry_collectors_snapshot_and_prometheus():
    reg = MetricsRegistry()
    reg.counter("hits").inc(3)
    reg.histogram("lat").observe(0.25)
    state = {"n": 7}
    reg.register_collector("sub", lambda: {"deep": {"n": state["n"]}})
    snap = reg.snapshot()
    assert snap["hits"] == 3 and snap["sub.deep.n"] == 7
    state["n"] = 9           # collectors are live views, not copies
    assert reg.snapshot()["sub.deep.n"] == 9
    assert reg.collect("missing") == {}

    prom = reg.to_prometheus()
    assert "# TYPE hits counter\nhits 3" in prom
    assert "# TYPE lat histogram" in prom
    assert 'lat_bucket{le="+Inf"} 1' in prom and "lat_count 1" in prom
    assert "sub_deep_n 9" in prom


def test_flatten_numeric_normalizes_lists_and_skips_nonnumeric():
    flat = flatten_numeric({"a": {"b": 1}, "xs": [{"v": 2}, {"v": 3}],
                            "s": "str", "f": True, "z": None})
    assert flat == {"a.b": 1, "xs.*.v": 3}
    assert undeclared({"size": 1, "bogus": {"leaf": 2}}) == \
        ["bogus.leaf"]


# -- tracer ------------------------------------------------------------
def test_tracer_nesting_ids_and_manual_clock(tmp_path):
    tr = Tracer(clock=ManualClock(tick=1.0))
    with tr.span("root", phase="x") as r:
        with tr.span("child") as c1:
            pass
        with tr.span("child2") as c2:
            pass
    with tr.span("root2") as r2:
        pass
    assert [s.name for s in tr.roots()] == ["root", "root2"]
    assert {s.name for s in tr.children(r)} == {"child", "child2"}
    assert c1.parent_id == r.span_id and c1.trace_id == r.trace_id
    assert r2.trace_id != r.trace_id and c1.depth == r.depth + 1
    # ManualClock ticks once per now(): every span is exactly the
    # number of clock reads between its enter and exit
    assert c1.duration == 1.0 and c2.duration == 1.0
    assert r.duration == 5.0 and r.attrs == {"phase": "x"}

    path = tmp_path / "t.jsonl"
    assert tr.export_jsonl(path) == 4
    rows = [json.loads(line) for line in
            path.read_text().splitlines()]
    assert [row["name"] for row in rows] == \
        [s.name for s in tr.spans]     # completion order
    [root_row] = [row for row in rows if row["name"] == "root"]
    assert root_row["attrs"] == {"phase": "x"}
    assert root_row["end"] - root_row["start"] == 5.0


def test_tracer_span_cap_keeps_total_monotone():
    tr = Tracer(clock=ManualClock(), max_spans=3)
    for _ in range(5):
        with tr.span("s"):
            pass
    assert len(tr.spans) == 3
    assert tr.total_spans == 5 and tr.dropped == 2
    tr.reset()
    assert not tr.spans and tr.dropped == 0 and tr.total_spans == 5


def test_null_tracer_records_nothing(tmp_path):
    with NULL_TRACER.span("anything", k=1) as sp:
        assert sp is None
    assert NULL_TRACER.total_spans == 0 and not NULL_TRACER.spans
    assert NULL_TRACER.export_jsonl(tmp_path / "x.jsonl") == 0


def test_timed_block_accumulates_dict_attr_and_span():
    tr = Tracer(clock=ManualClock(tick=1.0))
    rep = {"time_embed": 0.0}

    class Obj:
        pass

    obj = Obj()
    with use_clock(ManualClock(tick=1.0)):
        with timed_block(rep, "time_embed"):
            pass
        with timed_block(rep, "time_embed"):
            pass
        with timed_block(obj, "elapsed", tr, "stage", layer=1):
            pass
    assert rep["time_embed"] == 2.0      # two enters, one tick each
    assert obj.elapsed == 1.0
    [sp] = tr.spans
    assert sp.name == "stage" and sp.attrs == {"layer": 1}


def test_config_validates_obs_knobs():
    with pytest.raises(ValueError):
        EraRAGConfig(obs_max_spans=0)


# -- kernel launch counter: registry-owned, per-store attribution ------
def test_launch_counter_shims_and_no_bleed():
    corpus = _corpus()
    before = mips_ops.launch_count()
    rag_a = _rag(corpus=corpus)
    rag_b = _rag(corpus=corpus)
    rag_a.query_batch(["What is the color of thing?"])
    a_own = rag_a.store.stats.kernel_launches
    assert a_own >= 1
    # B never searched: the process-global shim moved, B's own did not
    assert rag_b.store.stats.kernel_launches == 0
    rag_b.query_batch(["q1"])
    rag_b.query_batch(["q2"])
    b_own = rag_b.store.stats.kernel_launches
    assert b_own >= 2
    assert rag_a.store.stats.kernel_launches == a_own  # no bleed back
    assert mips_ops.launch_count() - before >= a_own + b_own
    mips_ops.reset_launch_count()
    assert mips_ops.launch_count() == 0
    # per-store counters survive the process-global reset
    assert rag_a.store.stats.kernel_launches == a_own


# -- disabled path is bitwise inert ------------------------------------
def test_obs_disabled_is_bitwise_inert():
    """Counters-only default vs full tracing: identical answers,
    identical graphs through the streaming ingest path, and the
    default records zero spans."""
    from repro.ingest import IngestService
    corpus = _corpus(n=8, seed=5)
    cfg_on = dataclasses.replace(CFG, obs_trace=True)
    rag_off, rag_on = EraRAG(CFG, _mk_emb()), EraRAG(cfg_on, _mk_emb())
    pipes = []
    for rag in (rag_off, rag_on):
        rag.insert_docs(corpus.docs[:4])
        svc = IngestService(rag)
        svc.submit_many(corpus.docs[4:])
        svc.remove([corpus.docs[4][0]])
        svc.drain()
        rag.store.refresh()
        pipes.append(RAGPipeline(rag, ingest=svc))
    assert list(rag_off.graph.nodes) == list(rag_on.graph.nodes)
    for nid in rag_off.graph.nodes:
        assert np.array_equal(rag_off.graph.nodes[nid].embedding,
                              rag_on.graph.nodes[nid].embedding)
    qs = [qa.question for qa in corpus.qa][:6]
    a_off = [(a.answer, a.context, a.hits, a.epoch)
             for a in pipes[0].answer_batch(qs)]
    a_on = [(a.answer, a.context, a.hits, a.epoch)
            for a in pipes[1].answer_batch(qs)]
    assert a_off == a_on
    assert rag_off.obs.tracer is NULL_TRACER
    assert rag_off.obs.tracer.total_spans == 0
    assert not rag_off.obs.enabled and rag_on.obs.enabled
    assert rag_on.obs.tracer.total_spans > 0
    # the obs section only appears when tracing is on
    assert "obs" not in pipes[0].index_report()
    assert pipes[1].index_report()["obs"]["spans"] > 0


# -- traced pipeline span shapes ---------------------------------------
def test_query_span_tree_and_ingest_stage_spans():
    from repro.ingest import IngestService
    corpus = _corpus(n=8, seed=5)
    rag = _rag(dataclasses.replace(CFG, obs_trace=True,
                                   query_cache=True), corpus)
    svc = IngestService(rag)
    pipe = RAGPipeline(rag, ingest=svc)
    pipe.answer_batch([qa.question for qa in corpus.qa][:4])
    tr = rag.obs.tracer
    [q] = [s for s in tr.roots() if s.name == "query"]
    kids = {s.name for s in tr.children(q)}
    assert kids == {"retrieve", "compose"}
    [ret] = [s for s in tr.spans if s.name == "retrieve"]
    rkids = {s.name for s in tr.children(ret)}
    assert {"embed", "cache_lookup", "route", "scan", "fetch", "hits",
            "context"} <= rkids
    assert ret.attrs["epoch"] == rag.store.epoch
    [scan] = [s for s in tr.spans if s.name == "scan"]
    assert scan.attrs["epoch"] == rag.store.epoch

    svc.submit("zz", "fresh doc text " * 6)
    while not svc.idle:
        svc.tick()
    svc.tick()                                   # one idle tick
    stages = [s.attrs["stage"] for s in tr.spans
              if s.name == "ingest_tick"]
    assert {"chunk", "embed", "commit", "idle"} <= set(stages)


def test_engine_prefill_decode_spans():
    from repro.serving.testing import make_test_engine
    corpus = _corpus(n=6, seed=2)
    rag = _rag(dataclasses.replace(CFG, obs_trace=True,
                                   token_budget=192), corpus)
    engine = make_test_engine(max_batch=4, max_seq_len=256,
                              max_new_tokens=3, seed=0)
    pipe = RAGPipeline(rag, engine=engine)
    pipe.answer_batch([qa.question for qa in corpus.qa][:3])
    names = [s.name for s in rag.obs.tracer.spans]
    assert "prefill" in names and "decode" in names
    [comp] = [s for s in rag.obs.tracer.spans if s.name == "compose"]
    sub = {s.name for s in rag.obs.tracer.children(comp)}
    assert "prefill" in sub and "decode" in sub
    # the host's wait for each launch's logits is its own span
    assert "fetch" in sub


@pytest.mark.parametrize("shards,collective,mode", [
    (1, True, "collapsed"), (1, True, "detailed"),
    (2, True, "collapsed"), (2, False, "collapsed")])
def test_retrieve_span_tree_same_for_every_store_kind(shards, collective,
                                                      mode):
    """The host waiting on the scan (``fetch``), the ``Hit`` lists
    (``hits``) and the budgeted composition (``context``) are direct
    children of ``retrieve`` whatever the store, as ``route`` and
    ``scan`` are."""
    corpus = _corpus(n=8, seed=5)
    rag = _rag(dataclasses.replace(CFG, obs_trace=True,
                                   index_shards=shards,
                                   collective_query=collective), corpus)
    rag.query_batch([qa.question for qa in corpus.qa][:3], mode=mode)
    tr = rag.obs.tracer
    [ret] = [s for s in tr.spans if s.name == "retrieve"]
    rkids = [s.name for s in tr.children(ret)]
    assert {"embed", "route", "scan", "fetch", "hits", "context"} <= \
        set(rkids)
    # a store search ends with the fetch of its result, then its hits
    for sp in tr.children(ret):
        assert sp.start >= ret.start and sp.end <= ret.end
    fetch = [s for s in tr.children(ret) if s.name == "fetch"][-1]
    hits = [s for s in tr.children(ret) if s.name == "hits"][-1]
    assert fetch.end <= hits.start


# -- the spans in a profiler capture -----------------------------------
def _capture(tmp_path, fn):
    """Run ``fn`` under ``jax.profiler`` and return its result and the
    capture's ``erarag.*`` host events as (name, start_ns, end_ns)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                       recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events.extend((ev.name, ev.start_ns,
                           ev.start_ns + ev.duration_ns)
                          for ev in line.events
                          if ev.name.startswith("erarag."))
    return out, events


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("obs_trace", [False, True])
def test_spans_land_in_a_profiler_capture(tmp_path, obs_trace):
    """With a capture running, one ``query_batch`` writes its spans as
    ``erarag.*`` host events, nested as the spans nest: with the
    recording tracer and with the default ``NULL_TRACER`` alike."""
    corpus = _corpus(n=8, seed=5)
    rag = _rag(dataclasses.replace(CFG, obs_trace=obs_trace), corpus)
    qs = [qa.question for qa in corpus.qa][:3]
    rag.query_batch(qs)                      # compile outside the capture
    rag.obs.tracer.reset()
    _, events = _capture(tmp_path, lambda: rag.query_batch(qs))
    names = [n for n, _, _ in events]
    for name in ("retrieve", "embed", "route", "scan", "fetch", "hits",
                 "context"):
        assert names.count("erarag." + name) == 1, (name, names)
    by = {n: ev for ev in events for n in [ev[0]]}
    for name in ("embed", "route", "scan", "fetch", "hits", "context"):
        assert _inside(by["erarag." + name], by["erarag.retrieve"])
    assert by["erarag.scan"][2] <= by["erarag.fetch"][1]
    assert by["erarag.fetch"][2] <= by["erarag.hits"][1]
    assert by["erarag.hits"][2] <= by["erarag.context"][1]
    # the recording tracer records the same spans, on the obs clock
    recorded = sorted("erarag." + sp.name for sp in rag.obs.tracer.spans)
    assert recorded == (sorted(names) if obs_trace else [])


def test_profiler_capture_leaves_answers_and_null_path_unchanged(
        tmp_path):
    """No capture: ``NULL_TRACER.span`` is the one shared no-op.  With
    and without a capture the answers are bitwise equal."""
    corpus = _corpus(n=8, seed=5)
    rag = _rag(corpus=corpus)
    qs = [qa.question for qa in corpus.qa][:4]
    first = NULL_TRACER.span("retrieve", n=1)
    assert NULL_TRACER.span("scan") is first
    with first as sp:
        assert sp is None
    off = rag.query_batch(qs)
    on, events = _capture(tmp_path, lambda: rag.query_batch(qs))
    assert events and NULL_TRACER.span("scan") is first
    key = [(r.hits, r.context, r.n_tokens, r.epoch) for r in off]
    assert key == [(r.hits, r.context, r.n_tokens, r.epoch) for r in on]
    assert rag.obs.tracer.total_spans == 0


@pytest.mark.live
def test_live_harness_epoch_stamped_spans_across_migration(tmp_path):
    """Full traced 'live day': the tracer sees the reshard migration
    (step + install spans with epoch stamps), retrieval spans carry
    BOTH the old and the new epoch, and the per-phase report rows
    count spans from the shared registry histograms."""
    from repro.serving.live_harness import LiveHarness, make_schedule
    cfg = dataclasses.replace(CFG, index_shards=2, query_cache=True,
                              obs_trace=True, obs_max_spans=200_000)
    corpus = _corpus(n=12, seed=11)
    sched = make_schedule(corpus, seed=11, query_batch=3,
                          queries_per_phase=2)
    harness = LiveHarness(cfg, _mk_emb, sched, tmp_path,
                          compact_threshold=0.1)
    report = harness.run()          # parity asserted inside
    tr = harness.rag.obs.tracer

    steps = [s for s in tr.spans if s.name == "reshard_step"]
    installs = [s for s in tr.spans if s.name == "reshard_install"]
    assert steps and installs
    mig = report["migration"]
    [inst] = [s for s in installs
              if s.attrs["new_epoch"] == mig["new_epoch"]]
    assert inst.attrs["old_epoch"] == mig["old_epoch"]
    assert all(s.attrs["total"] >= s.attrs["built"] for s in steps)

    # queries were served (and stamped) on both sides of the install
    ret_epochs = {s.attrs["epoch"] for s in tr.spans
                  if s.name == "retrieve"}
    assert {mig["old_epoch"], mig["new_epoch"]} <= ret_epochs
    # span nesting survived the store swap: scans under retrieves
    scans = [s for s in tr.spans
             if s.name in ("scan", "coarse_scan") and s.depth >= 2]
    assert scans
    # per-phase obs movement from the report: every query phase
    # recorded spans; histogram-backed percentiles are present
    for p in report["phases"]:
        assert p["obs"]["spans"] > 0
        if p["query_batches"]:
            assert p["p99_ms"] >= p["p50_ms"] >= 0.0
            assert p["obs"]["kernel_launches"] > 0
    hists = harness.rag.obs.registry.histograms
    assert any(k.startswith("serving.latency.") for k in hists)


# -- index_report schema drift -----------------------------------------
def test_index_report_schema_drift_check():
    """Every numeric key the fully-loaded report surfaces must be
    declared; an undeclared counter is exactly what this gate is for."""
    from repro.ingest import IngestService
    from repro.serving.testing import make_test_engine
    corpus = _corpus(n=8, seed=5)
    cfg = dataclasses.replace(
        CFG, index_shards=2, query_cache=True, quantized_scan=True,
        obs_trace=True, token_budget=192)
    rag = _rag(cfg, corpus)
    engine = make_test_engine(max_batch=4, max_seq_len=256,
                              max_new_tokens=3, seed=0,
                              prefix_cache_entries=4)
    svc = IngestService(rag)
    pipe = RAGPipeline(rag, engine=engine, ingest=svc)
    pipe.answer_batch([qa.question for qa in corpus.qa][:3])
    rep = pipe.index_report()
    assert undeclared(rep) == []
    assert rep["launches"]["store"]["kernel_launches"] >= 1
    assert rag.obs.registry.declared == INDEX_REPORT_SCHEMA
    # the check actually fires on a novel counter
    rep["launches"]["store"]["new_counter"] = 1
    assert undeclared(rep) == ["launches.store.new_counter"]
    # registry exposition walks the same collectors without error
    prom = rag.obs.registry.to_prometheus()
    assert "launches_store_kernel_launches" in prom


def test_index_report_values_match_live_objects():
    """The registry view must report the same numbers the owning
    objects hold — collectors are views, not copies."""
    corpus = _corpus(n=8, seed=5)
    rag = _rag(dataclasses.replace(CFG, query_cache=True), corpus)
    pipe = RAGPipeline(rag)
    qs = [qa.question for qa in corpus.qa][:4]
    pipe.answer_batch(qs)
    pipe.answer_batch(qs)              # repeat: cache hits
    rep = pipe.index_report()
    assert rep["size"] == rag.store.size
    assert rep["epoch"] == rag.store.epoch
    assert rep["retrieval_rounds"] == rag.stats["retrieval_rounds"]
    assert rep["launches"]["retrieval_rounds"] == \
        rag.stats["retrieval_rounds"]
    assert rep["query_cache"] == rag.query_cache.stats.to_dict()
    assert rep["query_cache"]["hits"] > 0
    assert rep["stats"]["kernel_launches"] == \
        rag.store.stats.kernel_launches
    assert rep["launches"]["embedder"] == rag.graph.embedder.stats
