#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop retrieval cell, once, to find
the highest rate the program sustains; the cell's fixed rate is set
from it (about four fifths of it).

    python3 bench/sweep.py --workload retrieve_poisson --seed 1 \\
        --seconds 10 --rates 250,500,1000,2000

One process, one set-up; each rate runs the cell's window afresh and
prints one JSON line: rate, questions, p50/p95/p99 ms, mean block and
how long past the window's close the last question was answered (a
backlog that grows with the window means the rate is not sustained).
Needs the chip, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common as C  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = C.benchmark()
    w = C.workload(args.workload, bench)
    cfg, traffic = C.config(w["config"], bench), C.traffic(w["traffic"])
    C.use_program()
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        C.log("no chip")
        return 3
    C.enable_compile_cache()
    cell = C.kind(traffic["kind"]).Cell(cfg, traffic, args.seed,
                                        C.CACHE, cfg["limits"])
    cell.setup()
    for rate in [float(r) for r in args.rates.split(",")]:
        e2e = cell.window(args.seconds, rate=rate)
        lat = cell.lat_ms
        print(json.dumps({
            "rate_per_s": rate, "questions": int(e2e["attempted"]),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "mean_block": e2e["attempted"] / max(cell.batches, 1),
            "answered_past_close_s": e2e["backlog_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
