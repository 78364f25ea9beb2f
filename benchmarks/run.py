"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus section headers on
stderr).  ``python -m benchmarks.run [--fast|--smoke] [--only NAME]``.
``--smoke`` runs tiny corpora and skips the hardware-bound suites
(kernel_bench, roofline) — a seconds-scale end-to-end exercise of every
harness code path, suitable for CI and exercised by the test suite.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.common.utils import enable_compile_cache


def build_suites(n: int, smoke: bool = False) -> dict:
    from benchmarks import (
        chunk_size,
        dynamic_insertion,
        incremental_quality,
        ingest,
        initial_coverage,
        kernel_bench,
        live_serving,
        quantized_scan,
        query_batch,
        query_cache,
        reshard,
        roofline,
        segment_size,
        serving_batch,
        sharded_store,
        small_update,
        static_qa,
        update_breakdown,
    )

    half = max(40, n // 2)
    suites = {
        "static_qa": lambda: static_qa.run(n_docs=n),
        "dynamic_insertion": lambda: dynamic_insertion.run(n_docs=n),
        "incremental_quality": lambda: incremental_quality.run(
            n_docs=n),
        "small_update": lambda: small_update.run(n_docs=n),
        "initial_coverage": lambda: initial_coverage.run(n_docs=half),
        "segment_size": lambda: segment_size.run(n_docs=half),
        "update_breakdown": lambda: update_breakdown.run(n_docs=n),
        "chunk_size": lambda: chunk_size.run(n_docs=half),
        "query_batch": lambda: query_batch.run(n_docs=half),
        "serving_batch": lambda: serving_batch.run(n_docs=half),
        "sharded_store": lambda: sharded_store.run(n_docs=half),
        # lifecycle migration vs full rebuild (parity + speedup
        # asserted); below ~1000 rows the fixed dispatch overheads
        # drown the replay-vs-restack signal, so keep a 120-doc floor
        "reshard": lambda: reshard.run(n_docs=max(120, half)),
        # two-stage quantized scan vs the exact oracle: the recall
        # floor, score parity, and full-coverage bitwise equality are
        # asserted; the QPS win additionally asserted at signal scale
        "quantized_scan": lambda: quantized_scan.run(n_docs=half),
        # cached vs cold pipeline replay: bitwise answer parity across
        # a mid-replay insert + reshard, hit-rate floor, and cached-QPS
        # speedup are all asserted (AssertionError -> nonzero exit)
        "query_cache": lambda: query_cache.run(n_docs=half),
        # streaming ingest: burst-while-querying bitwise parity, the
        # batched-summarization launch/wall-clock floors, and summary-
        # cache churn savings are all asserted (nonzero exit on trip)
        "ingest": lambda: ingest.run(n_docs=half),
        # sustained-traffic "live corpus day": bursts + removals +
        # Zipf queries + checkpoint/restore + a policy-triggered
        # migration; bitwise replay parity and old-epoch availability
        # are asserted (nonzero exit on trip)
        "live_serving": lambda: live_serving.run(n_docs=half),
        "kernel_bench": kernel_bench.run,
        "roofline": roofline.run,
    }
    if smoke:
        # hardware-bound suites are meaningless at smoke scale (and
        # dominate wall time on CPU interpret mode)
        suites.pop("kernel_bench")
        suites.pop("roofline")
        suites["query_batch"] = lambda: query_batch.run(
            n_docs=24, batch_sizes=(1, 8))
        # the dispatch sweep (collective vs loop at s in {1,4,8}) runs
        # at smoke scale too, recording BENCH_sharded_query.json
        suites["sharded_store"] = lambda: sharded_store.run(
            n_docs=24, batch=8, shard_sweep=(1, 4, 8))
        # bucketed-prefill + batched-multihop sweep at smoke scale,
        # recording BENCH_serving_batch.json (parity asserted)
        suites["serving_batch"] = lambda: serving_batch.run(
            n_docs=24, n_prompts=6, batch=6)
        # the reshard-vs-rebuild wall-clock needs enough rows for the
        # signal (see above), so it keeps its 120-doc corpus in
        # smoke; still seconds-scale, recording BENCH_reshard.json
        suites["reshard"] = lambda: reshard.run(n_docs=120)
        # recall floor + score parity + full-coverage bitwise still
        # asserted at smoke scale; the QPS assert self-gates on rows
        suites["quantized_scan"] = lambda: quantized_scan.run(
            n_docs=24, rows_per_doc=50)
        # parity + invalidation + hit-rate floors hold at smoke scale;
        # the prefill-flops asymmetry shrinks with the reader shape, so
        # the speedup floor relaxes (measured ~1.2x at this scale)
        suites["query_cache"] = lambda: query_cache.run(
            n_docs=24, replay=24, token_budget=192, seq_len=256,
            min_hit=0.3, min_speedup=1.1)
        # parity + cache-churn asserts hold at smoke scale; the
        # batched-vs-serial ratios shrink with segment count, so the
        # launch/wall-clock floors relax (measured ~2.5x/~1.6x here)
        suites["ingest"] = lambda: ingest.run(
            n_docs=24, burst=12, lm_docs=10, min_launch_ratio=1.5,
            min_time_ratio=1.1, latency_ceiling=100.0)
        # parity, old-epoch availability, and the cache/compaction
        # floors hold at smoke scale; only the latency ceiling
        # relaxes (tiny batches make the percentiles jitter-bound)
        suites["live_serving"] = lambda: live_serving.run(
            n_docs=24, queries_per_phase=3,
            latency_ratio_ceiling=500.0)
    return suites


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None)
    ap.add_argument("--fast", action="store_true",
                    help="smaller corpora for CI-speed runs")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpora, skip hardware-bound suites")
    args = ap.parse_args(argv)
    enable_compile_cache()

    n = 24 if args.smoke else (40 if args.fast else 80)
    suites = build_suites(n, smoke=args.smoke)
    if args.only and args.only not in suites:
        raise SystemExit(
            f"unknown suite {args.only!r}; available: "
            f"{', '.join(suites)}")
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        if args.only and args.only != name:
            continue
        print(f"[{name}]", file=sys.stderr, flush=True)
        try:
            for row in fn():
                print(row, flush=True)
        except AssertionError:
            # a tripped parity/invariant assertion is a correctness
            # bug, not a flaky benchmark: abort with a nonzero exit
            # immediately instead of printing and continuing
            print(f"{name},0.0,ASSERTION_FAILED", flush=True)
            traceback.print_exc()
            raise SystemExit(f"parity assertion tripped in {name!r}")
        except Exception:
            failures += 1
            print(f"{name},0.0,ERROR", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} benchmark suites failed")


if __name__ == "__main__":
    main()
