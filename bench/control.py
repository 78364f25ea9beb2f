#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: for each seed,
one cell's window, then the numbers it compares, for the program and
for the control (the reference one precision step below the
configuration's, in the program's place), in one process.

    python3 bench/control.py --workload ingest_lm --seconds 5 \\
        --seeds 11,12,13 [--dump DIR]

Prints one JSON line per seed; ``--dump`` also writes the per-position
arrays the LM numbers are taken from (served-token gaps of the program
and of the control, and the reference's top-two margins) to
``DIR/<workload>.<seed>.npz``.  The benchmark's own runs never run the
control.  Needs the chip, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common as C  # noqa: E402


def readings(workload: str, seeds, seconds: float, *, bench=None,
             configs=None, traffics=None, cache_dir=C.CACHE):
    """Yield (seed, readings, arrays) for each seed; ``arrays`` are the
    cell's per-position readings, where it keeps any."""
    bench = bench or C.benchmark()
    w = C.workload(workload, bench)
    cfg = (configs or {}).get(w["config"]) or C.config(w["config"], bench)
    traffic = (traffics or {}).get(w["traffic"]) or C.traffic(w["traffic"])
    C.use_program()
    for seed in seeds:
        cell = C.kind(traffic["kind"]).Cell(cfg, traffic, seed,
                                            cache_dir, cfg["limits"])
        cell.setup()
        cell.window(seconds)
        cell.release()
        r = cell.readings()
        yield seed, r, getattr(cell, "gap_arrays", {})
        del cell
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    C.use_program()
    import jax
    if jax.devices()[0].platform != "tpu":
        C.log("no chip")
        return 3
    C.enable_compile_cache()
    for seed, r, arrays in readings(
            args.workload, [int(s) for s in args.seeds.split(",")],
            args.seconds):
        print(json.dumps({"seed": seed, **r}), flush=True)
        if args.dump and arrays:
            Path(args.dump).mkdir(parents=True, exist_ok=True)
            np.savez(Path(args.dump) / f"{args.workload}.{seed}.npz",
                     **arrays)
    return 0


if __name__ == "__main__":
    sys.exit(main())
