"""A whole run of each cell at tiny sizes on the CPU: the result line's
keys, its metrics by name, the refusal without a chip, and that a new
cell, mix or metric is found from its entries and files alone."""
import json
import os
import subprocess
import sys

import pytest

import bench_tiny as T
from bench_tiny import bench_cache  # noqa: F401  (fixture)
from bench import common as C

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.mark.parametrize("workload", [T.INGEST, T.RETRIEVE])
@pytest.mark.parametrize("trace", [0, 1])
def test_result_line(workload, trace, bench_cache):
    code, res = T.run_tiny(workload, bench_cache, trace=trace)
    assert code == 0
    res = json.loads(T.dumps(res))        # the line is plain JSON
    assert KEYS <= set(res) and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(res["device"])
    bench = C.benchmark()
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        # the CPU has no device plane: the counter and host-clock
        # metrics are read, those that need device events are left out
        names = {m["name"] for m in C.per_layer_for(workload, bench)}
        want = {m["name"] for m in C.per_layer_for(workload, bench)
                if m["source"] != "device_trace"}
        assert want <= set(res["metrics"]) <= names
        assert not any(k.startswith(("device_idle", "mips_topk_roofline"))
                       for k in res["metrics"])
    else:
        want = {m["name"] for m in C.end_to_end_for(workload, bench)}
        assert set(res["metrics"]) == want
        assert "breakdown" not in res
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("start_s", [0.0, 0.2])
def test_a_traced_run_of_part_of_the_ingest_window(bench_cache,
                                                   monkeypatch, start_s):
    """A traced slice of the window starts and stops at engine steps:
    the LM's operations are those served between the two, a part of the
    window's, and the run is checked as a whole."""
    traffics = T.traffics()
    traffics["single_doc_rounds"].update(trace_start_s=start_s,
                                         trace_seconds=0.0)
    monkeypatch.setattr(T, "traffics", lambda: traffics)
    cells = []
    code, res = T.run_tiny(T.INGEST, bench_cache, trace=1,
                           patch=cells.append)
    assert code == 0 and res["correct"] is True, res["checks"]
    cell = cells[0]
    assert set(cell.at_trace) == ({"stop"} if start_s == 0
                                  else {"start", "stop"})
    tokens = [sum(n for _, n in cell.at_trace.get(k, []))
              for k in ("start", "stop")]
    assert tokens[0] < tokens[1] < sum(n for _, n in cell.served())
    assert res["metrics"]["lm_mfu.ingest"]["value"] > 0
    assert res["device"]["window_s"] < cell.elapsed


def test_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(C.BENCH / "run.py"), "--workload",
         T.RETRIEVE, "--seed", str(2**33 + 5), "--seconds", "1",
         "--trace", "0"], cwd=C.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 3
    assert out.stdout.strip() == ""
    assert "no chip" in out.stderr


def test_every_cell_runs_from_the_benchmark_file():
    """The tiny runs drive the cells as ``BENCHMARK.json`` lists them:
    no cell is held in a file of its own.  Later cells join as entries
    and files, so only these two are asked for."""
    assert not (C.BENCH / "held").exists()
    cells = {w["name"]: w["config"] for w in C.benchmark()["workloads"]}
    assert cells.items() >= {T.INGEST: "era768_qwen2-7b-l16",
                             T.RETRIEVE: "era768_index"}.items()


def test_cells_metrics_and_mixes_are_found_by_name():
    bench = C.benchmark()
    assert {w["name"] for w in bench["workloads"]} >= {T.INGEST,
                                                      T.RETRIEVE}
    for w in bench["workloads"]:
        traffic = C.traffic(w["traffic"])
        assert hasattr(C.kind(traffic["kind"]), "Cell")
        assert "limits" in C.config(w["config"], bench)
        for m in C.per_layer_for(w["name"], bench):
            assert callable(C.metric_reader(m["name"]).read)
    # a new cell of an existing kind is an entry in the benchmark and a
    # traffic file: it reports what its entries say, nothing more
    extra = json.loads(json.dumps(bench))
    extra["workloads"].append({"name": "retrieve_fast",
                               "config": "era768_index",
                               "traffic": "poisson_qa", "chips": 1,
                               "why": "x"})
    next(m for m in extra["end_to_end"]
         if m["name"] == "retrieve_p95_ms")["workloads"].append(
        "retrieve_fast")
    extra["per_layer"].append({"name": "device_idle.retrieve_fast",
                               "unit": "%", "better": "lower",
                               "source": "device_trace", "layer": "device",
                               "moves": "retrieve_p95_ms",
                               "workloads": ["retrieve_fast"]})
    assert {m["name"] for m in C.end_to_end_for("retrieve_fast", extra)} \
        == {"retrieve_p95_ms", "setup_s"}
    assert [m["name"] for m in C.per_layer_for("retrieve_fast", extra)] \
        == ["device_idle.retrieve_fast"]
    # a metric without a list follows its end-to-end metric to every
    # cell that reports it
    extra["per_layer"].append({"name": "x", "unit": "%", "better": "higher",
                               "source": "device_trace", "layer": "device",
                               "moves": "retrieve_p95_ms"})
    assert "x" in [m["name"] for m in
                   C.per_layer_for("retrieve_fast", extra)]
    assert "x" not in [m["name"] for m in C.per_layer_for(T.INGEST, extra)]
