"""Shared pieces of the benchmark harness: where things live, the
cell's files found by name, the peaks table, the compile counter.

Everything a cell needs is found by the names in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json`` (its ``kind``
names its module in ``kinds/<kind>.py``) and ``metrics/<metric>.py``
for each per-layer metric.  Adding a configuration, a mix of the same
kind or a metric is adding files and entries.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
# fixed paths inside the checkout: the compile cache's key includes
# its directory, so it must never move between runs
CACHE = BENCH / ".cache"


def use_program() -> None:
    """Put the program under test (``src/``) on the import path."""
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"the program is missing: no {SRC / 'repro'}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str, bench: Optional[dict] = None) -> dict:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"unknown workload {name!r}; have "
                     f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, bench: Optional[dict] = None) -> dict:
    bench = bench or benchmark()
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise SystemExit(f"unknown config {name!r}")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def end_to_end_for(cell: str, bench: dict) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_for(cell: str, bench: dict) -> List[dict]:
    """Per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(cell, bench)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def _load_file(path: Path, modname: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise SystemExit(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> ModuleType:
    """``metrics/<name>.py``: ``read(ctx) -> float | None``."""
    return _load_file(BENCH / "metrics" / f"{name}.py",
                      "bench_metric_" + name.replace(".", "_"))


def kind(name: str) -> ModuleType:
    """``kinds/<name>.py``: the generator and window of a traffic
    kind."""
    return _load_file(BENCH / "kinds" / f"{name}.py",
                      "bench_kind_" + name)


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table[device_kind]


def src_digest() -> str:
    """Digest of the program's source: a cached base index is only
    reused by the program that built it."""
    h = hashlib.blake2b(digest_size=10)
    for p in sorted(SRC.rglob("*.py")):
        h.update(str(p.relative_to(SRC)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()


def enable_compile_cache(path: Path = CACHE / "jax") -> Path:
    """JAX's persistent compile cache, at a fixed path in the
    checkout; every program is kept so later runs compile nothing."""
    import jax
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compiles (and their seconds) through JAX's
    monitoring events, so a compile inside the window shows."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **_) -> None:
        if name == self.EVENT:
            self.n += 1
            self.seconds += secs

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
