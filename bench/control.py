#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: for each seed,
one cell's window, then the numbers it compares, for the program and
for the control (the reference one precision step below the
configuration's, in the program's place), in one process.

    python3 bench/control.py --workload ingest_lm --seconds 5 \\
        --seeds 11,12,13 [--dump DIR]

Prints one JSON line per seed: the numbers of the program and of the
control, and each side's verdict under the configuration's ``limits``
(``correct``, with every number compared beside its limit), the
comparison ``run.py`` makes.  It exits 1 where a seed reads the
program not correct or the control correct.  ``--dump`` also writes
the per-position arrays the LM numbers are taken from (served-token
gaps of the program and of the control, and the reference's top-two
margins) to ``DIR/<workload>.<seed>.npz``.  The benchmark's own runs
never run the control.  Needs the chip, as ``run.py`` does.
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


def readings(workload: str, seeds, seconds: float, *, configs=None,
             traffics=None, cache_dir=C.CACHE):
    """Yield (seed, readings, verdict, arrays) for each seed; ``verdict``
    is ``verdict(readings, limits)``, ``arrays`` the cell's
    per-position readings, where it keeps any."""
    bench = C.benchmark()
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
        yield seed, r, verdict(r, cfg["limits"]), \
            getattr(cell, "gap_arrays", {})
        del cell
        gc.collect()


def verdict(readings: dict, limits: dict) -> dict:
    """For the program and for the control, ``correct`` and each number
    that has a limit beside it: correct where none is over its limit."""
    out = {}
    for side, numbers in readings.items():
        checks = {n: {"value": numbers[n], "limit": lim}
                  for n, lim in limits.items() if n in numbers}
        out[side] = {"correct": all(c["value"] <= c["limit"]
                                    for c in checks.values()),
                     "checks": checks}
    return out


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
    code = 0
    for seed, r, v, arrays in readings(
            args.workload, [int(s) for s in args.seeds.split(",")],
            args.seconds):
        print(json.dumps({"seed": seed, **r, "verdict": v}), flush=True)
        for side, want in (("program", True), ("control", False)):
            C.log(f"seed {seed}: {side} correct {v[side]['correct']}, "
                  f"{v[side]['checks']}")
            if v[side]["correct"] is not want:
                code = 1
        if args.dump and arrays:
            Path(args.dump).mkdir(parents=True, exist_ok=True)
            np.savez(Path(args.dump) / f"{args.workload}.{seed}.npz",
                     **arrays)
    return code


if __name__ == "__main__":
    sys.exit(main())
