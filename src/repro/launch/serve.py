"""Serving driver: batched prefill + decode with a durable request
registry (the paper's set as serving metadata).

Completed request ids are inserted into a SOFT DurableMap; a crash loses
the volatile index but not the registry, so after recovery the server
knows exactly which requests had completed (no double-billing /
re-generation) -- durable linearizability doing real work.  --backend
picks the registry's index backend ("bucket" = the Pallas hash_probe /
recovery_scan kernel path, DESIGN.md §4); --shards N > 1 swaps in the
hash-partitioned ShardedDurableMap (one vmapped dispatch over N shards,
per-shard parallel recovery, DESIGN.md §6) -- the production registry
shape for millions of request ids.

--queue upgrades the driver to the durable request/completion SPINE
(DESIGN.md §7): arrivals are acknowledged by a durable enqueue into a
request DurableQueue, the server peeks (volatile, zero psync) the batch
it serves, and after generation the completion path runs response-enqueue
-> registry-insert -> request-dequeue-commit.  The dequeue becomes
durable only AFTER the completion is recorded, so a crash at any point
loses no acknowledged request: it is either still live in the request
queue (will be re-served; the registry dedups re-delivery) or already in
the registry.  --crash drills exactly that invariant end to end.

--pipeline N (requires --shards > 1) serves the requests in waves through
the depth-N double-buffered registry (DESIGN.md §6): wave k+1's durable
ack enqueues and wave k+1's host stage-1 routing run WHILE wave k
generates on device; each wave's pipelined registry insert is flushed
durable before that wave's dequeue commit, so the spine's
no-acknowledged-request-lost ordering (and its exact 4 psyncs/request
bill) is preserved verbatim under pipelining.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b-smoke \
      --requests 8 --gen 16 [--crash] [--backend bucket] [--shards 8] \
      [--queue] [--queue-capacity 1024] [--pipeline 2]
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import (DurableMap, DurableQueue, ElasticShardedMap,
                        QueueSpec, ShardedDurableMap, SetSpec)
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.models.sharding import CPU_CTX
from repro.obs import MetricsRegistry
from repro.store.snapshot import Snapshotter, SnapshotPolicy
from repro.train import steps as TS


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--open-loop" in argv:
        # rate-driven tail-latency harness; every remaining flag is a
        # bench_serve flag (--duration, --rate, --quick, --out, ...)
        from repro.launch import bench_serve
        argv.remove("--open-loop")
        return bench_serve.main(argv)
    ap = argparse.ArgumentParser()
    ap.add_argument("--open-loop", action="store_true",
                    help="delegate to repro.launch.bench_serve: open-loop "
                         "Poisson arrivals + BENCH_serve.json (all other "
                         "flags are bench_serve flags)")
    ap.add_argument("--arch", default="qwen3-32b-smoke")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--crash", action="store_true")
    ap.add_argument("--backend", default="probe",
                    choices=("probe", "scan", "bucket"),
                    help="registry index backend (bucket = Pallas kernels)")
    ap.add_argument("--shards", type=int, default=1,
                    help="hash-partition the registry over N shards "
                         "(N > 1 = ShardedDurableMap, one routed dispatch)")
    ap.add_argument("--router", default="v2", choices=("v1", "v2"),
                    help="sharded registry router: v2 = two-stage device-"
                         "local with adaptive lane budgets (default), "
                         "v1 = legacy single-stage lane_factor router")
    ap.add_argument("--placement", default="contiguous",
                    choices=("contiguous", "strided"),
                    help="shard->device storage order when shards >> "
                         "devices (v2; see DESIGN.md §6)")
    ap.add_argument("--max-lane-budget", type=int, default=0,
                    help="cap the v2 adaptive lane budget (0 = uncapped; "
                         "a cap drops + counts over-budget lanes)")
    ap.add_argument("--queue", action="store_true",
                    help="drive traffic through the durable request/"
                         "completion spine: DurableQueue ack -> peek/serve "
                         "-> response enqueue -> registry insert -> dequeue "
                         "commit (DESIGN.md §7)")
    ap.add_argument("--queue-capacity", type=int, default=1024,
                    help="ring slots per spine queue (power of two)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="background-snapshot the registry (and, with "
                         "--queue, the spine queues) every N serving steps "
                         "(DESIGN.md §11); --crash then recovers from the "
                         "latest snapshot + the stamp delta instead of a "
                         "full-pool scan.  0 disables")
    ap.add_argument("--snapshot-dir", default=None,
                    help="snapshot store directory (default: a fresh "
                         "temp dir)")
    ap.add_argument("--autosplit", type=float, default=0.0,
                    help="fill-factor watermark in (0, 1]: the registry "
                         "becomes an ElasticShardedMap and an online "
                         "S -> 2S shard split (DESIGN.md §12) starts when "
                         "live size / capacity crosses the watermark; the "
                         "migration advances one increment per serving "
                         "step, interleaved with live traffic.  0 "
                         "disables (fixed geometry)")
    ap.add_argument("--pipeline", type=int, default=1,
                    help="registry pipeline depth (DESIGN.md §6): > 1 "
                         "serves the requests in WAVES through the "
                         "double-buffered sharded registry -- with "
                         "--queue, wave k+1's durable ack enqueues while "
                         "wave k generates on device; requires --shards "
                         "> 1")
    args = ap.parse_args(argv)
    if args.pipeline < 1:
        ap.error("--pipeline must be >= 1")
    if args.pipeline > 1 and args.shards <= 1:
        ap.error("--pipeline > 1 requires --shards > 1 (the pipelined "
                 "dispatch path lives in the sharded registry router)")
    if args.autosplit:
        if not 0 < args.autosplit <= 1:
            ap.error("--autosplit must be a fill factor in (0, 1]")
        if args.router != "v2" or args.pipeline != 1:
            ap.error("--autosplit requires --router v2 and --pipeline 1 "
                     "(the split frontier commits at dispatch boundaries)")

    use_compile_cache()
    cfg = get_config(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prefill_step, decode_step = TS.make_serve_steps(cfg, CPU_CTX)
    prefill_step = jax.jit(prefill_step)
    decode_step = jax.jit(decode_step)

    m = MetricsRegistry()     # one snapshot() reaches every structure
    spec = SetSpec(capacity=1024, mode="soft", backend=args.backend)
    if args.autosplit:        # elastic geometry: splits online under load
        registry = ElasticShardedMap(spec, n_shards=max(1, args.shards),
                                     placement=args.placement,
                                     max_lane_budget=args.max_lane_budget,
                                     metrics=m, metrics_name="registry")
        budgets = registry.precompile(args.requests)
        if budgets:
            print(f"registry router v2: pre-compiled lane budgets "
                  f"{budgets} (elastic, autosplit @ fill "
                  f">= {args.autosplit})")
    elif args.shards > 1:     # same façade API, hash-partitioned runtime
        registry = ShardedDurableMap(spec, n_shards=args.shards,
                                     router=args.router,
                                     placement=args.placement,
                                     max_lane_budget=args.max_lane_budget,
                                     pipeline_depth=args.pipeline,
                                     metrics=m, metrics_name="registry")
        # pipeline_depth > 1 makes this a PARTIAL precompile too: every
        # pow2 sub-batch bucket a padded wave can realize is traced, so
        # the first pipelined wave never pays a trace stall mid-serve
        budgets = registry.precompile(args.requests)
        if budgets:
            print(f"registry router v2: pre-compiled lane budgets "
                  f"{budgets} ({args.placement} placement)")
    else:
        registry = DurableMap(spec, metrics=m, metrics_name="registry")
    b = args.requests
    req_ids = np.arange(1000, 1000 + b, dtype=np.int32)

    req_q = resp_q = None
    if args.queue:
        qspec = QueueSpec(capacity=args.queue_capacity, mode="soft")
        req_q = DurableQueue(qspec, metrics=m, metrics_name="req_queue")
        resp_q = DurableQueue(qspec, metrics=m, metrics_name="resp_queue")

    # background snapshotters (DESIGN.md §11): capture is a host copy of
    # already-durable planes at the dispatch boundary, the build+save runs
    # off the hot path -- the serving loop's psync bill is unchanged
    snaps = {}
    if args.snapshot_every > 0:
        base = args.snapshot_dir or tempfile.mkdtemp(prefix="serve_snap_")
        pol = SnapshotPolicy(every_steps=args.snapshot_every)
        snaps["registry"] = Snapshotter(
            registry, os.path.join(base, "registry"), pol)
        if args.queue:
            snaps["req_queue"] = Snapshotter(
                req_q, os.path.join(base, "req_q"), pol)
            snaps["resp_queue"] = Snapshotter(
                resp_q, os.path.join(base, "resp_q"), pol)
        print(f"snapshotter: every {args.snapshot_every} step(s) -> {base}")
    serve_step = 0

    def snapshot_tick():
        nonlocal serve_step
        serve_step += 1
        for s in snaps.values():
            s.maybe_snapshot(serve_step)
        if args.autosplit:
            # the autosplit watermark: one migration increment rides each
            # serving step, so the split amortizes across live traffic
            if registry.migrating:
                registry.step()
            elif registry.fill_factor() >= args.autosplit:
                print(f"autosplit: fill {registry.fill_factor():.3f} >= "
                      f"{args.autosplit:g} -> online split "
                      f"S={registry.n_shards} -> {2 * registry.n_shards}")
                registry.begin_split()

    def crash_recover(structure, key):
        """Crash+recover one structure -- through its snapshotter's
        hybrid path when snapshots are on, the full-pool scan otherwise."""
        if key in snaps:
            snaps[key].wait()      # async build commits, as it would live
            snaps[key].recover()
        else:
            structure.crash_and_recover()

    @contextlib.contextmanager
    def phase(name):
        """Span-time a spine phase and bill the queue psyncs it paid to
        ``phase.<name>.psyncs`` -- what the end-of-run summary and the
        --crash drill report per phase."""
        qp0 = (req_q.psyncs + resp_q.psyncs) if args.queue else 0
        with m.span(name):
            yield
        if args.queue:
            m.counter(f"phase.{name}.psyncs").inc(
                req_q.psyncs + resp_q.psyncs - qp0)

    max_seq = args.prompt_len + args.gen
    rng = np.random.default_rng(0)
    all_toks = rng.integers(0, cfg.vocab, (b, args.prompt_len))

    def generate(tok_rows):
        """Prefill + decode one wave.  Returns the generated tokens as
        DEVICE arrays -- no host sync -- so host-side spine work (the
        next wave's durable ack) can overlap device execution."""
        caches = M.init_cache(cfg, len(tok_rows), max_seq)
        caches, logits = prefill_step(
            params, {"tokens": jnp.asarray(tok_rows, jnp.int32)}, caches)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out = [nxt]
        for _ in range(args.gen - 1):
            caches, nxt, logits = decode_step(params, caches, nxt)
            out.append(nxt)
        return jnp.concatenate(out, axis=1)

    t0 = time.time()
    if args.pipeline == 1:
        if args.queue:
            # 1. durable admission: the ack psync makes it survivable
            with phase("ack"):
                acked = np.asarray(req_q.enqueue(req_ids))
            assert acked.all(), "admission queue full"
            print(f"spine: acknowledged {int(acked.sum())} requests "
                  f"durably (req-queue psyncs={req_q.psyncs})")
            # 2. volatile peek of the batch being served (zero psync)
            served_ids, ok = req_q.peek(b)
            assert ok.all()
            np.testing.assert_array_equal(served_ids, req_ids)
        with phase("generate"):
            gen = generate(all_toks)
            jax.block_until_ready(gen)
        dt = time.time() - t0
        print(f"served {b} requests x {args.gen} tokens in {dt:.2f}s "
              f"({b * args.gen / dt:.1f} tok/s)")

        # durably record completions: one psync per request (SOFT bound).
        # Spine order (--queue): response enqueue -> registry insert ->
        # request dequeue COMMIT -- the dequeue's psync happens only after
        # the completion is durable, so no acknowledged request is lost.
        with phase("record"):
            if args.queue:
                resp_q.enqueue(req_ids)
            registry.insert(req_ids, np.asarray(gen[:, -1]))
        if args.queue:
            with phase("commit"):
                _, committed = req_q.dequeue(b)
            assert committed.all()
        snapshot_tick()
    else:
        # Depth-N pipelined waves (DESIGN.md §6): wave k generates on
        # device while the host runs wave k+1's durable ack and stage-1
        # routing.  Spine ordering survives verbatim per wave -- the
        # pipelined registry insert is FLUSHED (forced durable) before
        # that wave's dequeue commit, so a crash at any point still
        # leaves every acknowledged request in the queue or registry.
        waves = [w for w in np.array_split(np.arange(b),
                                           min(b, 2 * args.pipeline))
                 if len(w)]
        if args.queue:
            with phase("ack"):
                acked = np.asarray(req_q.enqueue(req_ids[waves[0]]))
            assert acked.all(), "admission queue full"
        for k, idx in enumerate(waves):
            ids = req_ids[idx]
            if args.queue:
                served_ids, ok = req_q.peek(len(ids))   # volatile, 0 psync
                assert np.asarray(ok).all()
                np.testing.assert_array_equal(served_ids, ids)
            gen_w = generate(all_toks[idx])             # async, on device
            if args.queue and k + 1 < len(waves):
                # wave k+1's durable ack rides wave k's device bubble
                with phase("ack"):
                    acked = np.asarray(req_q.enqueue(req_ids[waves[k + 1]]))
                assert acked.all(), "admission queue full"
            last = np.asarray(gen_w)[:, -1]             # force wave k
            with phase("record"):
                if args.queue:
                    resp_q.enqueue(ids)
                registry.insert(ids, last)              # staged, lazy
                registry.pipeline_flush()   # durable BEFORE dequeue commit
            if args.queue:
                with phase("commit"):
                    _, committed = req_q.dequeue(len(ids))
                assert np.asarray(committed).all()
            snapshot_tick()
        dt = time.time() - t0
        print(f"served {b} requests x {args.gen} tokens in {len(waves)} "
              f"waves (depth-{args.pipeline} registry pipeline) in "
              f"{dt:.2f}s ({b * args.gen / dt:.1f} tok/s)")
    # end-of-run summary: everything below reads the ONE metrics snapshot
    # (DESIGN.md §10) -- the same numbers an operator's sink would see
    snap = m.snapshot()
    coll = snap["collected"]
    reg = coll["registry"]
    if args.queue:
        by_phase = {k.split(".")[1]: v for k, v in snap["counters"].items()
                    if k.startswith("phase.") and k.endswith(".psyncs")}
        print(f"spine: {coll['resp_queue']['size']} completions enqueued, "
              f"request queue drained (len={coll['req_queue']['size']}), "
              f"psyncs by phase {by_phase}, total spine psyncs="
              f"{coll['req_queue']['psync_total'] + coll['resp_queue']['psync_total']}")
    shard_tag = f" x{args.shards} shards" if args.shards > 1 else ""
    print(f"registry[{args.backend}{shard_tag}]: {reg['size']} completed, "
          f"psyncs={reg['psyncs']} (== #requests)")
    if args.shards > 1 and reg.get("last_route"):
        lr = reg["last_route"]
        print(f"router: lane_budget={lr['lane_budget']} "
              f"groups={lr['groups']} dropped={reg['router_dropped']}")
    if args.autosplit:
        while not registry.step():      # drain an in-flight migration
            pass
        print(f"elastic registry: n_shards={registry.n_shards} "
              f"(splits={registry.splits}), fill="
              f"{registry.fill_factor():.3f}, migrated="
              f"{registry.migrated_nodes} node(s) at "
              f"{registry.migration_psyncs} migration psync(s); hot-path "
              f"psyncs={registry.psyncs} (== #requests, unchanged)")

    if args.crash:
        late_ids = None
        if args.queue:
            # acked-but-not-yet-served work at crash time: exactly the
            # requests the spine's ordering promises to redeliver
            late_ids = req_ids + b
            with phase("ack"):
                acked = np.asarray(req_q.enqueue(late_ids))
            assert acked.all(), "admission queue full"
        crash_recover(registry, "registry")
        done = np.array(registry.contains(req_ids))
        assert done.all()
        print(f"after crash+recovery: all {b} completions still registered")
        if snaps:
            g = m.snapshot()["gauges"]
            print(f"hybrid recovery: "
                  f"{int(g.get('registry.last_recovery_from_delta_slots', 0))}"
                  f" delta slot(s) re-scanned, "
                  f"{int(g.get('registry.last_recovery_from_snapshot_slots', 0))}"
                  f" restored from the snapshot")
        if args.queue:
            crash_recover(req_q, "req_queue")
            crash_recover(resp_q, "resp_queue")
            # no acknowledged request lost: each is in the registry or
            # still live in the recovered request queue
            vals, ok = resp_q.peek(b)
            assert ok.all() and set(vals.tolist()) == set(req_ids.tolist())
            redelivered = len(req_q)
            assert redelivered == len(late_ids), "acked requests lost"
            ids, ok = req_q.peek(redelivered)   # re-serve survivors
            assert np.asarray(ok).all()
            with phase("record"):
                resp_q.enqueue(ids)
                registry.insert(ids, ids)   # dedups already-completed ids
                if args.shards > 1:
                    registry.pipeline_flush()
            with phase("commit"):
                _, committed = req_q.dequeue(redelivered)
            assert np.asarray(committed).all()
            m.counter("spine.redelivered").inc(redelivered)
            assert np.array(registry.contains(late_ids)).all()
            snap = m.snapshot()
            coll = snap["collected"]
            print(f"spine after crash+recovery: "
                  f"{snap['counters']['spine.redelivered']} acked requests "
                  f"redelivered and committed, "
                  f"{coll['resp_queue']['size']} completions survive, "
                  f"request queue drained (len={coll['req_queue']['size']}); "
                  f"recovery psyncs: "
                  f"registry={coll['registry']['recovery_psyncs']} "
                  f"req_queue={coll['req_queue']['recovery_psyncs']} "
                  f"resp_queue={coll['resp_queue']['recovery_psyncs']} "
                  f"(all zero by construction)")
    for s in snaps.values():
        s.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
