"""Closed loop: full batches back to back, each issued when the last one's
results are on the host.  The ops come from a ring of batches drawn from
the seed before the window.  Through the spine when the configuration has
one, else straight through the registry's ``apply``.
``ops_per_s`` is every lane completed in the window over its length.
"""
from __future__ import annotations

import time

from bench.sut import Rig, SpineRig
from bench.traffic_gen import OpStream, draw_ring


def run(ctx) -> dict:
    tr = ctx.traffic
    spine = ctx.config["system"] == "spine"
    rig = SpineRig(ctx) if spine else Rig(ctx)
    b = rig.batch
    stream = OpStream(tr, rig.universe, ctx.seed)
    ring = draw_ring(stream, tr["ring_batches"], b)
    if spine:
        def step(ops, keys, vals):
            rig.round(keys, ops)
        span = "bench.spine_round"
    else:
        def step(ops, keys, vals):
            rig.registry.apply(ops, keys, vals)
        span = "bench.apply"
    for i in range(tr["warm_batches"]):
        step(*ring[i])
    ctx.mark("warmed")
    n, busy_s = tr["warm_batches"], 0.0
    with ctx.window():
        while ctx.elapsed() < ctx.seconds:
            t = time.perf_counter()
            with ctx.span(span):
                step(*ring[n % len(ring)])
            busy_s += time.perf_counter() - t
            n += 1
    batches = n - tr["warm_batches"]
    lanes = batches * b
    return {"rig": rig,
            "end_to_end": {"ops_per_s": lanes / ctx.window_s},
            "attempted": lanes,
            "counts": {"rounds": batches, "batches": batches,
                       "lookups": lanes, "round_s": busy_s},
            "info": {"batches": batches}}
