"""Open loop through the serving spine: Poisson arrivals at the fixed rate
of the traffic file, served in padded batches as they arrive.

A request's latency runs from its scheduled arrival to the instant its
spine round is forced (response delivered, request dequeue committed), so
a stall counts against every request that waited behind it.  Requests
still in the backlog when the window closes are served after it and their
latencies are counted too; ``p90_ms`` is the 90th percentile of all of
them (``p50_ms``, ``p95_ms`` and ``p99_ms`` ride along in ``info``).
"""
from __future__ import annotations

import time

import numpy as np

from bench.sut import SpineRig
from bench.traffic_gen import OP_NOP, OpStream, PoissonArrivals


def warm(ctx, rig: SpineRig, stream: OpStream) -> None:
    """Full rounds of the cell's own traffic: compiles the queue programs
    (every registry shape is warmed by the spine builder)."""
    for _ in range(ctx.traffic["warm_rounds"]):
        ops, keys, _ = stream.draw(rig.batch)
        rig.round(keys, ops)


def serve_window(ctx, rig: SpineRig, arrivals: PoissonArrivals) -> dict:
    """One window of ``ctx.seconds`` at the arrivals' rate, then the
    requests due by its close.  Returns latencies (ms) and counts."""
    b = rig.batch
    bt = np.empty((0,), np.float64)
    bo = np.empty((0,), np.int32)
    bk = np.empty((0,), np.int32)
    lat, rounds, lanes, round_s = [], 0, 0, 0.0
    backlog_peak, longest, slow = 0, 0.0, []

    def serve():
        nonlocal bt, bo, bk, rounds, lanes, round_s, longest
        t_start = ctx.elapsed()
        n = min(bt.size, b)
        keys = np.zeros((b,), np.int32)
        ops = np.full((b,), OP_NOP, np.int32)
        keys[:n], ops[:n] = bk[:n], bo[:n]
        t_arr = bt[:n]
        bt, bo, bk = bt[n:], bo[n:], bk[n:]
        t = time.perf_counter()
        with ctx.span("bench.spine_round"):
            rig.round(keys, ops)
        lat.append(ctx.elapsed() - t_arr)
        took = time.perf_counter() - t
        round_s += took
        longest = max(longest, took)
        if took > 0.06:
            slow.append([t_start, took * 1e3])
        rounds += 1
        lanes += n

    with ctx.window():
        while True:
            now = ctx.elapsed()
            if now >= ctx.seconds:
                break
            if bt.size < b:
                with ctx.span("bench.generate"):
                    at, ao, ak = arrivals.take(now, 4 * b)
                if at.size:
                    bt = np.concatenate([bt, at])
                    bo = np.concatenate([bo, ao])
                    bk = np.concatenate([bk, ak])
            backlog_peak = max(backlog_peak, bt.size)
            if bt.size == 0:
                wait = min(arrivals.next_arrival() - now,
                           ctx.seconds - now, 0.01)
                if wait > 0:
                    with ctx.span("bench.wait_arrival"):
                        time.sleep(wait)
                continue
            serve()
        counts = {"rounds": rounds, "lookups": lanes, "round_s": round_s}
    # arrivals due by the close that were not yet served: late, not lost
    at, ao, ak = arrivals.take(ctx.seconds, 1 << 40)
    bt, bo, bk = (np.concatenate([bt, at]), np.concatenate([bo, ao]),
                  np.concatenate([bk, ak]))
    backlog_end = int(bt.size)
    while bt.size:
        serve()
    lat_ms = np.concatenate(lat) * 1e3 if lat else np.zeros(0)
    return {"lat_ms": lat_ms, "counts": counts, "served_in_window": lanes,
            "backlog_peak": int(backlog_peak), "backlog_end": backlog_end,
            "longest_round_ms": longest * 1e3, "slow_rounds": slow[:20]}


def run(ctx) -> dict:
    rig = SpineRig(ctx)
    stream = OpStream(ctx.traffic, rig.universe, ctx.seed)
    warm(ctx, rig, stream)
    ctx.mark("warmed")
    rate = ctx.traffic["rate_per_s"]
    w = serve_window(ctx, rig, PoissonArrivals(rate, stream, ctx.seed))
    lat = w["lat_ms"]
    return {"rig": rig,
            "end_to_end": {"p90_ms": float(np.percentile(lat, 90))},
            "attempted": int(lat.size),
            "counts": w["counts"],
            "info": {"requests": int(lat.size), "offered_per_s": rate,
                     "p50_ms": float(np.percentile(lat, 50)),
                     "p95_ms": float(np.percentile(lat, 95)),
                     "p99_ms": float(np.percentile(lat, 99)),
                     "backlog_peak": w["backlog_peak"],
                     "backlog_end": w["backlog_end"],
                     "longest_round_ms": w["longest_round_ms"],
                     "slow_rounds": w["slow_rounds"],
                     "span_max_ms": rig.span_max_ms()}}
