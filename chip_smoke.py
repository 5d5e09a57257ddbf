#!/usr/bin/env python3
"""Bring-up smoke test of the durable serving spine on one TPU chip.

    python chip_smoke.py                # phases a-d on one chip
    python chip_smoke.py --four-chips   # only the 4-chip sharded phase

Phases (one process; all data is drawn from ``--seed``):

  a  device   fail at once unless JAX's first device is a TPU.
  b  spine    ``bench_serve.run_open_loop`` at ``ServeConfig``'s default
              geometry (2^20-slot registry, S=8 shards, 1024-lane batches,
              two 4096-slot queues, Zipf(1.1) over 4M keys, 50/25/25)
              with the probe and the bucket backend: no rejected ack, no
              short commit, no dropped or abandoned lane, no overflow,
              and exactly 1 psync per queue op.
  c  answers  the same registry geometry plus a request and a response
              queue, checked lane by lane against a plain dict / deque
              reference: load 2^19 keys, run mixed 50/25/25 batches,
              SOFT psyncs == successful updates, crash + full recovery
              (every acknowledged insert, remove, enqueue and dequeue
              survives; recovery psyncs 0), then snapshot + stamp-delta
              recovery of the same crash, bit-identical to the full scan
              (the probe backend has no O(delta) patch by design, so its
              registry takes only the full scan).
  d  kernels  the compiled programs of the registry's apply step and of
              the bucket registry's recovery contain ``tpu_custom_call``.

``--four-chips`` runs the phase-c traffic through a ``use_shard_map``
registry on a 4-device mesh and through a one-chip vmap registry, and
requires bit-equal results, state and psyncs.

Lines before the last are smoke output, not benchmark results.  The last
line is the JSON verdict; any failed phase raises and exits non-zero.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (OP_CONTAINS, OP_INSERT, OP_REMOVE,  # noqa: E402
                        DurableQueue, QueueSpec, SetSpec, ShardedDurableMap)
from repro.core import queue as Q, router as RT, shard as SH  # noqa: E402
from repro.launch import bench_serve  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.store.snapshot import Snapshotter  # noqa: E402

CAPACITY = 1 << 20       # registry slots in total (ServeConfig default)
SHARDS = 8
BATCH = 1024
QUEUE_CAPACITY = 4096
N_LOAD = 1 << 19         # keys loaded before the mixed batches
N_MIXED = 32             # mixed 50/25/25 batches
SNAPSHOT_AFTER = 8       # mixed batches before the snapshot is taken


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (cache reads included) and
    counts persistent-cache hits, process-wide."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# Traffic and the plain reference
# ---------------------------------------------------------------------------


def traffic(seed: int, n_load: int, n_mixed: int, batch: int):
    """Yield ("load" | "mixed", ops, keys, values) batches.  Loaded keys
    are distinct; mixed keys are Zipf(1.1)-popular over the loaded keys
    and as many fresh ones, so batches repeat keys, hit and miss."""
    rng = np.random.default_rng(seed)
    universe = rng.choice(1 << 30, 2 * n_load, replace=False).astype(np.int32)
    loaded, fresh = universe[:n_load], universe[n_load:]
    for i in range(0, n_load, batch):
        keys = loaded[i:i + batch]
        yield ("load", np.full(keys.shape, OP_INSERT, np.int32), keys,
               rng.integers(0, 1 << 30, keys.shape, dtype=np.int32))
    pool = rng.permutation(np.concatenate([loaded, fresh]))
    for _ in range(n_mixed):
        keys = pool[(rng.zipf(1.1, batch) - 1) % pool.size]
        u = rng.random(batch)
        ops = np.where(u < 0.5, OP_CONTAINS,
                       np.where(u < 0.75, OP_INSERT, OP_REMOVE))
        yield ("mixed", ops.astype(np.int32), keys,
               rng.integers(0, 1 << 30, batch, dtype=np.int32))


def ref_apply(ref: dict, ops, keys, vals) -> np.ndarray:
    """Plain reference of one mixed batch: contains lanes read the state
    before the batch, then inserts, then removes, each in lane order."""
    out = np.zeros(keys.shape, bool)
    for i in np.flatnonzero(ops == OP_CONTAINS):
        out[i] = int(keys[i]) in ref
    for i in np.flatnonzero(ops == OP_INSERT):
        k = int(keys[i])
        if k not in ref:
            ref[k] = int(vals[i])
            out[i] = True
    for i in np.flatnonzero(ops == OP_REMOVE):
        k = int(keys[i])
        if k in ref:
            del ref[k]
            out[i] = True
    return out


def copy_state(state):
    return jax.tree.map(jnp.array, state)


def states_equal(got, want, what: str, skip=("n_psync", "n_ops")) -> None:
    for f, a, b in zip(got._fields, got, want):
        if f not in skip:
            check(np.array_equal(np.asarray(a), np.asarray(b)),
                  f"{what}: field {f} differs")


# ---------------------------------------------------------------------------
# Phase b: the open-loop spine through its entry point
# ---------------------------------------------------------------------------


def phase_spine(backend: str, seed: int, duration: float = 10.0) -> None:
    t0 = time.perf_counter()
    p = bench_serve.run_open_loop(bench_serve.ServeConfig(
        duration=duration, backend=backend, seed=seed))
    c = p["counters"]
    for name in ("ack_rejected", "commit_short", "router_dropped",
                 "pipeline_abandoned"):
        check(c[name] == 0, f"spine[{backend}]: {name}={c[name]}")
    check(not c["registry_overflowed"], f"spine[{backend}]: registry overflow")
    check(not c["queue_overflowed"], f"spine[{backend}]: queue overflow")
    for q in ("req_queue", "resp_queue"):
        v = p["psync_per_op"][q]
        check(v == 1.0, f"spine[{backend}]: {q} psync_per_op={v}")
    check(p["requests_completed"] > 0, f"spine[{backend}]: no requests")
    check(p["meta"]["platform"] == jax.devices()[0].platform,
          f"spine[{backend}]: meta names the wrong device")
    rounds = p['spans_ms']['spine.force']['count']
    say(f"b spine[{backend}] ok: rounds={rounds} "
        f"requests={p['requests_completed']} "
        f"psync_per_op={p['psync_per_op']} wall_s={time.perf_counter() - t0}")


# ---------------------------------------------------------------------------
# Phase c: answers against the plain reference, crash, hybrid recovery
# ---------------------------------------------------------------------------


class QueueRef:
    """A DurableQueue driven with fixed 1024-lane shapes beside a deque."""

    def __init__(self, q, rng):
        self.q, self.ref, self.rng = q, collections.deque(), rng
        self.successes = 0

    def round(self, vals: np.ndarray) -> None:
        cap, b = self.q.spec.capacity, vals.shape[0]
        ok = np.asarray(self.q.enqueue(vals))
        check(ok.all(), "queue: enqueue rejected below capacity")
        self.ref.extend(vals.tolist())
        # keep room for the next full enqueue
        n_pop = int(self.rng.integers(max(0, len(self.ref) - (cap - b)),
                                      b + 1))
        want = jnp.asarray(np.arange(b) < n_pop)
        self.q.state, got, okd, _ = Q.dequeue(self.q.state, want,
                                              spec=self.q.spec)
        got, okd = np.asarray(got), np.asarray(okd)
        expect = [self.ref.popleft() for _ in range(min(n_pop, len(self.ref)))]
        check(okd.sum() == len(expect) and got[okd].tolist() == expect,
              "queue: dequeue differs from the reference")
        self.successes += b + len(expect)

    def contents(self) -> list:
        st = self.q.state
        head, tail = int(st.head), int(st.tail)
        slots = np.arange(head, tail) & (self.q.spec.capacity - 1)
        return np.asarray(st.vals)[slots].tolist()


def check_registry(m, ref: dict, touched: np.ndarray, what: str) -> None:
    """Membership of every touched key and the value of every live one."""
    check(len(m) == len(ref), f"{what}: size {len(m)} != {len(ref)}")
    want = np.array([int(k) in ref for k in touched])
    for i in range(0, touched.size, BATCH):
        k = touched[i:i + BATCH]
        got = np.asarray(m.contains(k))
        check(np.array_equal(got, want[i:i + BATCH]),
              f"{what}: membership differs from the reference")
        live = k[want[i:i + BATCH]]
        if live.size:
            vals = np.asarray(m.get(live))
            check(vals.tolist() == [ref[int(x)] for x in live],
                  f"{what}: values differ from the reference")


def phase_answers(backend: str, seed: int, n_load=N_LOAD, n_mixed=N_MIXED,
                  capacity=CAPACITY, shards=SHARDS,
                  queue_capacity=QUEUE_CAPACITY, batch=BATCH):
    """Returns the registry (for phase d)."""
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed + 7)
    m = ShardedDurableMap(SetSpec(capacity=capacity, backend=backend),
                          n_shards=shards)
    qspec = QueueSpec(capacity=queue_capacity)
    req = QueueRef(DurableQueue(qspec), rng)
    resp = QueueRef(DurableQueue(qspec), rng)
    ref, updates, touched = {}, 0, []
    hybrid = m.supports_hybrid
    with tempfile.TemporaryDirectory() as d:
        snaps = {"req": Snapshotter(req.q, os.path.join(d, "req")),
                 "resp": Snapshotter(resp.q, os.path.join(d, "resp"))}
        if hybrid:
            snaps["registry"] = Snapshotter(m, os.path.join(d, "registry"))
        mixed = 0
        for kind, ops, keys, vals in traffic(seed, n_load, n_mixed, batch):
            touched.append(keys)
            if kind == "load":
                got = np.asarray(m.insert(keys, vals))
                ref.update(zip(keys.tolist(), vals.tolist()))
                check(got.all(), f"answers[{backend}]: a load insert failed")
                updates += keys.size
                continue
            req.round(keys)
            got = np.asarray(m.apply(ops, keys, vals))
            want = ref_apply(ref, ops, keys, vals)
            check(np.array_equal(got, want),
                  f"answers[{backend}]: mixed batch {mixed} differs from "
                  f"the reference in {int((got != want).sum())} lanes")
            updates += int(got[ops != OP_CONTAINS].sum())
            resp.round(got.astype(np.int32))
            mixed += 1
            if mixed == SNAPSHOT_AFTER:
                for sn in snaps.values():
                    sn.snapshot()
                    sn.wait()
        check(m.router_dropped == 0 and not m.overflowed,
              f"answers[{backend}]: dropped lanes or overflow")
        check(m.psyncs == updates,
              f"answers[{backend}]: registry psyncs {m.psyncs} != "
              f"successful updates {updates}")
        for name, qr in (("req", req), ("resp", resp)):
            check(qr.q.psyncs == qr.successes,
                  f"answers[{backend}]: {name} queue psyncs {qr.q.psyncs} "
                  f"!= successful ops {qr.successes}")
        say(f"c answers[{backend}] ok: {n_load} loaded, {mixed} mixed "
            f"batches, {len(ref)} live keys, registry psyncs={m.psyncs}")

        # crash + full recovery
        touched = np.unique(np.concatenate(touched))
        u = {"registry": rng.random(m.state.cur.shape).astype(np.float32),
             "req": rng.random(qspec.capacity).astype(np.float32),
             "resp": rng.random(qspec.capacity).astype(np.float32)}
        structs = {"registry": m, "req": req.q, "resp": resp.q}
        pre = {k: copy_state(s.state) for k, s in structs.items()}
        full = {}
        for k, s in structs.items():
            s.crash_and_recover(u[k])
            check(s.psyncs == 0, f"recover[{backend}]: {k} psyncs {s.psyncs}")
            full[k] = copy_state(s.state)
        check_registry(m, ref, touched, f"recover[{backend}]")
        for name, qr in (("req", req), ("resp", resp)):
            check(qr.contents() == list(qr.ref),
                  f"recover[{backend}]: {name} queue lost acknowledged work")
        say(f"c recover[{backend}] ok: full scan, recovery psyncs 0, "
            f"{len(ref)} keys and both queues match the reference")

        # the same crash through snapshot + stamp delta
        for k, sn in snaps.items():
            s = structs[k]
            s.state = pre[k]
            sn.recover(u[k])
            check(s.psyncs == 0, f"hybrid[{backend}]: {k} psyncs {s.psyncs}")
            states_equal(s.state, full[k], f"hybrid[{backend}] {k}")
            sn.close()
        say(f"c hybrid[{backend}] ok: {sorted(snaps)} bit-identical to the "
            f"full scan (wall_s={time.perf_counter() - t0})")
    return m


# ---------------------------------------------------------------------------
# Phase d: compiled kernels in the HLO
# ---------------------------------------------------------------------------


def custom_calls(compiled_text: str) -> list:
    return re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                      r"\"tpu_custom_call\"", compiled_text)


def phase_kernels(registries: dict) -> None:
    i32 = jnp.int32
    for backend, m in registries.items():
        plan = m.last_route
        lanes = jax.ShapeDtypeStruct(plan.d_ops.shape, i32)
        text = RT._apply_v2.lower(
            m.state, lanes, lanes, lanes, sspec=m.sspec, groups=plan.groups,
            lane_budget=plan.lane_budget).compile().as_text()
        found = custom_calls(text)
        check(found, f"kernels[{backend}]: no tpu_custom_call in apply step")
        for name in found:
            say(f"d kernel in {backend} apply step: {name}")
    m = registries["bucket"]
    plane = jax.ShapeDtypeStruct(m.state.cur.shape, i32)
    text = SH.recover.lower(plane, plane, plane, plane,
                            sspec=m.sspec).compile().as_text()
    found = custom_calls(text)
    check(found, "kernels[bucket]: no tpu_custom_call in recovery")
    for name in found:
        say(f"d kernel in bucket recovery: {name}")


# ---------------------------------------------------------------------------
# --four-chips: shard_map over a 4-device mesh vs one-chip vmap
# ---------------------------------------------------------------------------


def phase_four_chips(seed: int, n_load=N_LOAD, n_mixed=N_MIXED,
                     capacity=CAPACITY) -> None:
    check(jax.device_count() >= 4,
          f"four-chips: {jax.device_count()} device(s), need 4")
    for backend in ("probe", "bucket"):
        t0 = time.perf_counter()
        spec = SetSpec(capacity=capacity, backend=backend)
        a = ShardedDurableMap(spec, n_shards=SHARDS, use_shard_map=True)
        b = ShardedDurableMap(spec, n_shards=SHARDS)
        check(RT.mesh_devices(a.sspec) == 4,
              f"four-chips: mesh of {RT.mesh_devices(a.sspec)} devices")
        first = True
        for _, ops, keys, vals in traffic(seed, n_load, n_mixed, BATCH):
            ra = np.asarray(a.apply(ops, keys, vals))
            rb = np.asarray(b.apply(ops, keys, vals))
            check(np.array_equal(ra, rb),
                  f"four-chips[{backend}]: results differ")
            if first:
                n_dev = len(a.state.keys.sharding.device_set)
                check(n_dev == 4,
                      f"four-chips[{backend}]: state spans {n_dev} devices")
                first = False
        psyncs = a.psyncs
        check(psyncs == b.psyncs, f"four-chips[{backend}]: psyncs differ")
        states_equal(a.state, b.state, f"four-chips[{backend}]", skip=())
        u = np.random.default_rng(seed + 11).random(
            b.state.cur.shape).astype(np.float32)
        a.crash_and_recover(u)
        b.crash_and_recover(u)
        states_equal(a.state, b.state, f"four-chips[{backend}] recovery",
                     skip=())
        check(np.array_equal(a.last_recovery_hist_shards,
                             b.last_recovery_hist_shards),
              f"four-chips[{backend}]: recovery histograms differ")
        say(f"four-chips[{backend}] ok: shard_map over 4 devices == "
            f"one-chip vmap (results, state, psyncs={psyncs} before the "
            f"crash, recovery); wall_s={time.perf_counter() - t0}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip shard_map phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    say(f"a device ok: {dev.platform} {dev.device_kind} x{jax.device_count()}")

    say(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        for backend in ("probe", "bucket"):
            phase_spine(backend, args.seed)
        registries = {b: phase_answers(b, args.seed)
                      for b in ("probe", "bucket")}
        phase_kernels(registries)
    stats = dev.memory_stats() or {}
    say(f"compile_seconds={clock.seconds} persistent_cache_hits="
        f"{clock.cache_hits} total_wall_s={time.perf_counter() - t0}")
    say(f"device bytes_in_use={stats.get('bytes_in_use')} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
