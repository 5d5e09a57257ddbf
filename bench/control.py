#!/usr/bin/env python3
"""The control of ``correct``: a cell run at its own size with buffered
durability (``bench.reference.BufferedRegistry``: one psync per epoch of
batches, a crash loses the open epoch) in the registry's place, on
several seeds in one process.  Every seed has to come out not correct;
the numbers it prints are the upper readings the limits are set below.
The benchmark's own runs never run it.

    python3 bench/control.py --workload set_uniform_r90 --seeds 1,2,3 \
        --seconds 5
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.run import execute  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    wl = harness.workload(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        line = execute(wl, seed, args.seconds, False, time.perf_counter(),
                       control=True)
        failed_all &= not line["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": line["correct"],
                          "checks": line["checks"]}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    raise SystemExit(main())
