"""The system under test, as the configuration file describes it, and the
record of everything it answered.

The benchmark drives the program's served path through these entries and
no others (a later refactor keeps them callable):

  spine     ``repro.launch.bench_serve._build_spine`` and ``_spine_round``
  registry  ``repro.core.ShardedDurableMap``: ``apply``, ``get``,
            ``precompile``, ``crash_and_recover``, ``psyncs``, ``len``

``Recorder`` wraps the registry object's ``apply`` so that every batch
and its per-lane results are kept in order, with crashes and counter
readings between them.  ``replay`` runs the plain reference over that
record once the window has closed and counts every disagreement.
"""
from __future__ import annotations

import os
import sys

import numpy as np

from bench.reference import BufferedRegistry, RefRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


class Recorder:
    """Keeps every registry batch (ops, keys, values, results) in order,
    and the driver's readings (crash, psyncs, size, contents) between
    them."""

    def __init__(self, registry):
        self.log = []
        inner = registry.apply

        def apply(ops, keys, values=None):
            ops = np.asarray(ops, np.int32)
            keys = np.asarray(keys, np.int32)
            values = keys if values is None else np.asarray(values, np.int32)
            res = inner(ops, keys, values)
            self.log.append(("apply", ops.copy(), keys.copy(),
                             values.copy(), np.asarray(res, bool).copy()))
            return res

        registry.apply = apply

    def note(self, *entry) -> None:
        self.log.append(entry)


class ControlMap:
    """The control in the program's place: ``BufferedRegistry`` behind the
    registry interface that the drivers and ``_spine_round`` use."""

    router_dropped = 0
    overflowed = False

    def __init__(self, universe_ids: np.ndarray):
        self.ref = BufferedRegistry(universe_ids)

    def apply(self, ops, keys, values=None):
        values = keys if values is None else values
        return self.ref.apply(np.asarray(ops), np.asarray(keys),
                              np.asarray(values))

    def get(self, keys, default: int = 0):
        present, vals = self.ref.lookup(np.asarray(keys))
        return np.where(present, vals, default)

    def crash_and_recover(self, u=None):
        self.ref.crash()
        return self

    @property
    def psyncs(self) -> int:
        return self.ref.psyncs

    def __len__(self) -> int:
        return self.ref.size()


def build_registry(config: dict, metrics=None):
    """The sharded registry of the configuration, warmed for full batches
    of ``config["batch"]`` lanes (apply and get, every lane budget)."""
    from repro.core import SetSpec, ShardedDurableMap
    spec = SetSpec(capacity=config["capacity"], mode=config["mode"],
                   backend=config["backend"])
    m = ShardedDurableMap(spec, n_shards=config["shards"], metrics=metrics,
                          metrics_name="registry")
    m.precompile(config["batch"], partial=False)
    return m


def build_spine(config: dict, metrics):
    """(registry, request queue, response queue) through the program's own
    spine builder, which also warms every partial batch shape."""
    from repro.launch import bench_serve
    cfg = bench_serve.ServeConfig(
        batch=config["batch"], capacity=config["capacity"],
        mode=config["mode"], backend=config["backend"],
        shards=config["shards"], queue_capacity=config["queue_capacity"])
    return bench_serve._build_spine(cfg, metrics)


def prefill(registry, keys: np.ndarray, batch: int, rng) -> None:
    """Insert ``keys`` in full batches of the cell's own shape (a short
    last batch is topped up with repeats, which fail as duplicates)."""
    ops = np.full(batch, 1, np.int32)            # OP_INSERT
    for i in range(0, keys.size, batch):
        k = keys[i:i + batch]
        if k.size < batch:
            k = np.concatenate([k, keys[:batch - k.size]])
        registry.apply(ops, k, rng.integers(0, 1 << 31, batch,
                                            dtype=np.int64).astype(np.int32))


def read_contents(registry, rec: Recorder, ids: np.ndarray, batch: int):
    """Every key of the universe read back through ``get`` in full
    batches: -1 (no stored value is negative) marks an absent key."""
    for i in range(0, ids.size, batch):
        k = ids[i:i + batch]
        if k.size < batch:
            k = np.concatenate([k, ids[:batch - k.size]])
        rec.note("contents", k, np.asarray(registry.get(k, default=-1),
                                           np.int32))


def replay(log: list, universe_ids: np.ndarray) -> dict:
    """Run the plain reference over the record; count disagreements."""
    ref = RefRegistry(universe_ids)
    bad = {"lane_mismatch": 0, "psync_gap": 0, "size_gap": 0,
           "contents_mismatch": 0}
    for entry in log:
        kind = entry[0]
        if kind == "apply":
            _, ops, keys, vals, got = entry
            bad["lane_mismatch"] += int((ref.apply(ops, keys, vals)
                                         != got).sum())
        elif kind == "crash":
            ref.crash()
        elif kind == "psyncs":
            bad["psync_gap"] += abs(int(entry[1]) - ref.psyncs)
        elif kind == "size":
            bad["size_gap"] += abs(int(entry[1]) - ref.size())
        elif kind == "contents":
            present, want = ref.lookup(entry[1])
            got = entry[2]
            bad["contents_mismatch"] += int(
                ((got != -1) != present).sum()
                + (present & (got != want)).sum())
        else:
            raise ValueError(f"unknown record entry {kind!r}")
    return bad


class Rig:
    """The system of one run: the program's objects (or the control in
    the registry's place), the key universe and the record."""

    def __init__(self, ctx):
        from bench.traffic_gen import KeyUniverse, rng_for
        cfg, tr = ctx.config, ctx.traffic
        self.batch = cfg["batch"]
        ctx.mark("start")
        self.universe = KeyUniverse(ctx.seed, cfg["key_range"],
                                    tr["prefill"])
        self.registry = self._build(ctx)
        ctx.mark("built")
        if ctx.control:
            self.registry = ControlMap(self.universe.ids)
        self.rec = Recorder(self.registry)
        prefill(self.registry, self.universe.prefill, self.batch,
                rng_for(ctx.seed, 4))
        ctx.mark("prefilled")

    def _build(self, ctx):
        return build_registry(ctx.config)

    def counts(self) -> dict:
        """Program counters that have to read 0 (drops, overflow)."""
        return {"dropped": int(self.registry.router_dropped),
                "overflow": int(bool(self.registry.overflowed))}

    def finish(self) -> dict:
        """After the window: the counters, every key's membership and value
        read back, and the reference run over the whole record.  Returns
        each compared number; each has the limit 0."""
        out = self.counts()
        self.rec.note("psyncs", self.registry.psyncs)
        self.rec.note("size", len(self.registry))
        read_contents(self.registry, self.rec, self.universe.ids,
                      self.batch)
        self.registry = None
        out.update(replay(self.rec.log, self.universe.ids))
        return out


class SpineRig(Rig):
    """The serving spine: durable ack enqueue -> registry batch ->
    response enqueue -> request and response dequeue commits."""

    def _build(self, ctx):
        from repro.launch import bench_serve
        from repro.obs import MetricsRegistry
        self.metrics = MetricsRegistry()
        registry, self.req_q, self.resp_q = build_spine(ctx.config,
                                                        self.metrics)
        self._round = bench_serve._spine_round
        self.served = 0
        return registry

    def round(self, keys: np.ndarray, ops: np.ndarray) -> int:
        n = self._round(self.metrics, self.registry, self.req_q,
                        self.resp_q, self.req_q.spec, keys, ops)
        self.served += n
        return n

    def span_max_ms(self) -> dict:
        """The longest of each of ``_spine_round``'s own spans."""
        snap = self.metrics.snapshot()["histograms"]
        return {k[5:]: v["max"] * 1e3 for k, v in snap.items()
                if k.startswith("span.") and v["max"] is not None}

    def counts(self) -> dict:
        out = super().counts()
        c = self.metrics.counter
        out["ack_rejected"] = c("spine.ack_rejected").value
        out["commit_short"] = c("spine.commit_short").value
        out["queue_left"] = len(self.req_q) + len(self.resp_q)
        # SOFT: one psync per successful enqueue and per dequeue
        out["queue_psync_gap"] = (abs(self.req_q.psyncs - 2 * self.served)
                                  + abs(self.resp_q.psyncs
                                        - 2 * self.served))
        return out
