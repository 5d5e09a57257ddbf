"""Benchmark entry point: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.  --quick trims sizes for CI;
--backend swaps the hash-experiment index backend (probe | scan | bucket)
-- "bucket" routes lookups through the Pallas hash_probe kernel.  The
``bench_hash`` / ``bench_shard`` / ``bench_queue`` / ``bench_recovery``
suites additionally write ``BENCH_hash.json`` / ``BENCH_shard.json`` /
``BENCH_queue.json`` / ``BENCH_recovery.json`` (ops/sec and psync/op at
the canonical configuration; shard compares flat vs S in {1, 8} shards,
queue tracks the exact SOFT psync-per-op bound, recovery tracks the
snapshot+delta hybrid vs full-scan restart cost) for cross-PR perf
tracking; CI uploads them as artifacts."""
import argparse
import inspect
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--backend", default=None,
                    help="index backend for the hash experiments (probe | "
                         "scan | bucket; default: each suite's own, "
                         "bench_shard sweeps all three).  bench_shard "
                         "accepts a comma-separated sweep, e.g. "
                         "probe,scan,bucket")
    args = ap.parse_args()
    if args.backend:
        valid = {"probe", "scan", "bucket"}
        names = args.backend.split(",")
        if set(names) - valid:
            ap.error(f"--backend must be one or more of {sorted(valid)}")
        if len(names) > 1 and args.only != "bench_shard":
            ap.error("a comma-separated --backend sweep is only supported "
                     "with --only bench_shard")

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (scalability, key_range, read_pct,
                            psync_counts, recovery, checkpoint_bench,
                            bench_hash, bench_shard, bench_queue,
                            bench_serve, bench_recovery, bench_resize)
    suites = {
        "psync_counts": psync_counts,    # paper's analytical bound first
        "bench_hash": bench_hash,        # canonical point -> BENCH_hash.json
        "bench_shard": bench_shard,      # sharded runtime -> BENCH_shard.json
        "bench_queue": bench_queue,      # durable queue -> BENCH_queue.json
        "bench_serve": bench_serve,      # open-loop tails -> BENCH_serve.json
        "bench_recovery": bench_recovery,  # hybrid -> BENCH_recovery.json
        "bench_resize": bench_resize,    # online split -> BENCH_resize.json
        "scalability": scalability,      # Fig 1
        "key_range": key_range,          # Fig 2
        "read_pct": read_pct,            # Fig 3
        "recovery": recovery,            # Sec 2.1/6
        "checkpoint": checkpoint_bench,  # framework-level (DESIGN.md §3)
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    for name, mod in suites.items():
        if only and name not in only:
            continue
        kwargs = {"quick": args.quick}
        if args.backend and "backend" in inspect.signature(mod.run).parameters:
            kwargs["backend"] = args.backend
        for row in mod.run(**kwargs):
            print(row)
            sys.stdout.flush()


if __name__ == "__main__":
    main()
