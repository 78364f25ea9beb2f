"""Tiny versions of the benchmark's configurations and traffic, for
driving the harness on the CPU in tests."""
from __future__ import annotations

import copy
import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common as C  # noqa: E402

INGEST, RETRIEVE = "ingest_lm", "retrieve_poisson"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# limits at the tiny sizes, set on the CPU from the program's readings
# and the control's: over seeds 1-16, the served-token gaps summed per
# near tie of the program 0.00026-0.00102 against the int8 control's
# 0.0030-0.0055; over seeds 1-6, the widest score error 0.9e-7-1.7e-7
# against the high-precision scan's 2.1e-6-2.7e-6
LIMITS = {"lm_tie_gap": 0.0018, "score_err": 6e-7}
SEED = 1


def bench() -> dict:
    """``BENCHMARK.json`` with the entries of the cells held out of it
    (``bench/held/*.json``), whose harness is tested all the same."""
    out = C.benchmark()
    for p in sorted((C.BENCH / "held").glob("*.json")):
        held = C.load_json(p)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            out[key] = held[key] + out[key]
    return out


def configs() -> dict:
    """The benchmark's configurations at tiny sizes (same keys)."""
    out = {}
    for c in bench()["configs"]:
        cfg = copy.deepcopy(C.load_json(C.ROOT / c["file"]))
        cfg["corpus_docs"] = 48
        cfg["index"].update(embed_dim=64, held_out_docs=8,
                            embedder_features=512)
        if "hidden_size" in cfg:
            cfg.update(hidden_size=64, intermediate_size=128,
                       num_attention_heads=4, num_key_value_heads=2,
                       num_hidden_layers=2, vocab_size=512)
            cfg["serving"]["max_seq_len"] = 1024
        # the tiny runs compare the numbers the tiny limits were set for
        number = "lm_tie_gap" if "hidden_size" in cfg else "score_err"
        cfg["limits"] = {number: LIMITS[number]}
        out[c["name"]] = cfg
    return out


def traffics() -> dict:
    out = {}
    for w in bench()["workloads"]:
        t = copy.deepcopy(C.traffic(w["traffic"]))
        if t["kind"] == "ingest_rounds":
            t["warm_rows"] = 2
        else:
            t.update(rate_per_s=200.0, max_batch=4)
        out[w["traffic"]] = t
    return out


@pytest.fixture(scope="session")
def bench_cache(tmp_path_factory):
    """One cache directory (the tiny base indexes) for every tiny run
    of the harness in the session."""
    return tmp_path_factory.mktemp("bench_cache")


def _bench_run():
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  C.BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_tiny(workload: str, tmp_path, seed: int = SEED, seconds: float = 0.5,
             trace: int = 0, patch=None):
    """One tiny run on the CPU: (exit code, result).  JAX's persistent
    compile cache stays off, as the rest of the test session has it."""
    run = _bench_run()
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    return run.run(args, bench=bench(), configs=configs(),
                   traffics=traffics(),
                   cache_dir=Path(tmp_path), require_tpu=False,
                   peaks=PEAKS, patch=patch, compile_cache=False)


def dumps(result) -> str:
    return json.dumps(result)
