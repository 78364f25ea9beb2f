#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process: set up (restore the base index, make the weights from the
seed, compile every shape the window uses), measure for ``--seconds``,
check what the window produced against the plain reference, and print
one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` (``--trace 1`` only) and ``checks``, each number
compared beside its limit, which also close standard error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces the window with the JAX profiler and reports its per-layer
metrics.  Without a TPU, or with fewer chips than the cell asks for,
it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common as C  # noqa: E402
from bench.trace import Profiler, top_names  # noqa: E402

NO_CHIP = 3


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, *, bench=None, configs=None, traffics=None,
        cache_dir=C.CACHE,
        require_tpu=True, peaks=None, patch=None, compile_cache=True,
        t_start=T_START):
    """One cell; returns (exit code, result dict or None).  The keyword
    arguments are for the harness's own tests: a benchmark file,
    configurations and traffic of their own, a cache directory, no chip, the
    peaks to divide by, ``patch(cell)``, called after set-up, to break
    the timed path, and no persistent compile cache."""
    bench = bench or C.benchmark()
    w = C.workload(args.workload, bench)
    cfg = (configs or {}).get(w["config"]) or C.config(w["config"], bench)
    traffic = (traffics or {}).get(w["traffic"]) or C.traffic(w["traffic"])
    C.use_program()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or
                        len(devices) < w["chips"]):
        C.log(f"no chip: JAX found {len(devices)} {dev.platform} "
              f"device(s), the cell asks for {w['chips']} TPU chip(s)")
        return NO_CHIP, None
    peaks = peaks or C.peaks(dev.device_kind)
    if compile_cache:
        C.enable_compile_cache(Path(cache_dir) / "jax")
    compiles = C.CompileCounter()
    annotate = jax.profiler.TraceAnnotation if args.trace \
        else contextlib.nullcontext

    cell = C.kind(traffic["kind"]).Cell(cfg, traffic, args.seed,
                                        cache_dir, cfg["limits"])
    cell.setup(annotate)
    if patch is not None:
        patch(cell)
    setup_s = time.perf_counter() - t_start
    setup_compiles = (compiles.n, compiles.seconds)
    C.log(f"setup: {setup_s:.3f} s, {setup_compiles[0]} backend compiles "
          f"({setup_compiles[1]:.3f} s)")

    prof = Profiler(Path(cache_dir) / "trace") if args.trace else None
    if prof:
        prof.start()
    with annotate("bench.window"):
        e2e = cell.window(args.seconds, annotate)
    red = prof.stop() if prof else None
    C.log(f"compiles inside the window: {compiles.n - setup_compiles[0]} "
          f"({compiles.seconds - setup_compiles[1]:.3f} s)")
    compiles.close()

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": w["chips"],
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    counters = cell.counters()
    cell.release()

    metrics = {}
    out = {}
    if red is None:
        for m in C.end_to_end_for(w["name"], bench):
            v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        ctx = {"trace": red, "counters": counters, "peaks": peaks,
               "e2e": e2e}
        for m in C.per_layer_for(w["name"], bench):
            v = C.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        out["breakdown"] = red.breakdown()
        C.log(f"trace: top programs {top_names(red.all_modules(), 8)}")

    checks = cell.check()
    correct = all(v <= lim for _, v, lim in checks)
    result = {"correct": correct, "attempted": int(e2e["attempted"]),
              "failed": int(e2e["failed"]), "metrics": metrics,
              "device": device, **out,
              "checks": {n: {"value": v, "limit": lim}
                         for n, v, lim in checks}}
    for n, v, lim in checks:
        C.log(f"check {n}: {v!r} (limit {lim!r}) "
              f"{'ok' if v <= lim else 'FAILED'}")
    return 0, result


def main(argv=None) -> int:
    args = parse(argv)
    code, result = run(args)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
