#!/usr/bin/env python3
"""Knee sweep of the open-loop spine: one process, one spine, several
fixed offered rates, each for its own window.

    python3 bench/sweep.py --workload spine_zipf_r80 --seed 3 \
        --seconds 4 --fractions 0.3,0.5,0.6,0.7,0.8,0.9,1.0,1.1

The rates are shares of the spine's closed-loop rate, measured first in
the same process with full batches.  For each rate it prints the
requests served in the window against those offered, the backlog at the
close, and p50/p99.  The knee is the highest offered rate at which the
backlog stays bounded: the window serves at least 99% of what arrived and
less than two batches wait at its close.  The cell's traffic file takes
0.8 x the knee, written there by hand: nothing calibrates at run time.
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

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.drivers import open_loop  # noqa: E402
from bench.sut import SpineRig  # noqa: E402
from bench.traffic_gen import OpStream, PoissonArrivals, draw_ring  # noqa


def closed_rate(rig, stream, seconds: float) -> float:
    ring = draw_ring(stream, 256, rig.batch)
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        ops, keys, _ = ring[n % len(ring)]
        rig.round(keys, ops)
        n += 1
    return n * rig.batch / (time.perf_counter() - t0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="spine_zipf_r80")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fractions", required=True)
    args = ap.parse_args(argv)
    wl = harness.workload(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    ctx = harness.Ctx(wl, args.seed, args.seconds, False,
                      time.perf_counter())
    rig = SpineRig(ctx)
    stream = OpStream(ctx.traffic, rig.universe, ctx.seed)
    open_loop.warm(ctx, rig, stream)
    closed = closed_rate(rig, stream, args.seconds)
    rates = [closed * float(f) for f in args.fractions.split(",")]
    print(f"sweep: closed-loop rate {closed} req/s", flush=True)
    points = []
    for i, rate in enumerate(sorted(rates)):
        w = open_loop.serve_window(
            ctx, rig, PoissonArrivals(rate, stream, ctx.seed + i))
        lat = w["lat_ms"]
        offered = lat.size
        p = {"rate_per_s": rate, "served_share":
             w["served_in_window"] / max(offered, 1),
             "backlog_end": w["backlog_end"],
             "backlog_peak": w["backlog_peak"],
             "p50_ms": float(np.percentile(lat, 50)),
             "p99_ms": float(np.percentile(lat, 99)),
             "rounds": w["counts"]["rounds"]}
        p["bounded"] = (p["served_share"] >= 0.99
                        and p["backlog_end"] < 2 * rig.batch)
        points.append(p)
        print("sweep: " + json.dumps(p), flush=True)
    ok = [p["rate_per_s"] for p in points if p["bounded"]]
    knee = max(ok) if ok else None
    print(json.dumps({"closed_rate_per_s": closed, "knee_per_s": knee,
                      "rate_0p8_knee": None if knee is None else 0.8 * knee,
                      "points": points}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
