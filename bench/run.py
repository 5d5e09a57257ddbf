#!/usr/bin/env python3
"""Benchmark of the durable registry and serving spine on one TPU chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` (configuration, traffic, driver
and metrics all found by name, see ``bench/harness.py``), builds the
program's system from the seed, warms every shape the cell uses, measures
for ``--seconds`` seconds, then checks what the window produced against
the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics from a profiler trace of a short
window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: every number compared with its limit.  The same numbers end
standard error.  Without a TPU as JAX's first device it prints no result
and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def execute(wl: dict, seed: int, seconds: float, trace: bool,
            t_start: float, clock=None, control: bool = False) -> dict:
    """One run of the cell ``wl`` (from ``harness.workload``); returns the
    result line as a dict.  Looks for no chip: ``main`` does that."""
    ctx = harness.Ctx(wl, seed, seconds, trace, t_start, clock=clock,
                      control=control)
    out = harness.driver(wl["traffic"]["driver"]).run(ctx)
    device = harness.device_info()
    metrics, breakdown = {}, None
    try:
        if trace:
            from bench import trace_reduce
            tr = trace_reduce.load(ctx.trace_dir)
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s
            for m in wl["per_layer"]:
                spec = harness.load_json(harness.BENCH, "metrics",
                                         m["name"] + ".json")
                value = harness.reducer(spec["reducer"]).reduce(
                    spec, tr, out, ctx.config, device)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            breakdown = tr.breakdown()
        else:
            for m in wl["end_to_end"]:
                value = (ctx.setup_s if m["name"] == "setup_s"
                         else out["end_to_end"][m["name"]])
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    finally:
        ctx.cleanup()
    compared = out.pop("rig").finish()
    failed = sum(compared.get(k, 0) for k in ("dropped", "ack_rejected",
                                               "commit_short"))
    line = {"correct": all(v <= 0 for v in compared.values()),
            "attempted": out["attempted"], "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["info"] = dict(out.get("info", {}), setup_s=ctx.setup_s,
                        setup_marks=ctx.marks, window_s=ctx.window_s,
                        compiles_in_window=ctx.compiles_in_window,
                        gc_in_window=ctx.gc_in_window)
    line["checks"] = {k: {"value": v, "limit": 0}
                      for k, v in compared.items()}
    return line


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    wl = harness.workload(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < wl["cell"]["chips"]:
        print(f"bench: needs {wl['cell']['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    clock = harness.CompileClock()
    line = execute(wl, args.seed, args.seconds, bool(args.trace), T_START,
                   clock=clock)
    print(f"bench: compile_s={clock.seconds} compiles={clock.compiles} "
          f"cache_hits={clock.cache_hits} info={json.dumps(line['info'])}",
          flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
