"""The one traffic generator: key universes, key draws and op mixes.

Every traffic file under ``bench/traffic/`` is parameters for this module:
the key distribution (``uniform`` or YCSB's bounded ``zipfian``), the op
mix, the prefill share and the driver's own knobs.  Everything is drawn
from the run's seed, so one seed gives one stream of operations.

Op codes are the program's input encoding (contains / insert / remove and
the padding no-op), restated here so that the reference imports nothing
of the program.
"""
from __future__ import annotations

import numpy as np

OP_CONTAINS, OP_INSERT, OP_REMOVE, OP_NOP = 0, 1, 2, 3
KEY_ID_SPACE = 1 << 30          # key ids are spread over [0, 2^30)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose; any whole ``seed`` works."""
    return np.random.default_rng([seed % (1 << 64), stream])


class KeyUniverse:
    """``key_range`` distinct key ids spread over [0, 2^30) in random order:
    rank r of the popularity law maps to ``ids[r]``, so popular keys are
    scattered over the id space and over the shards.  The ids and their
    ranks are the same for every seed (drawn from a fixed stream): which
    shards the hottest keys share sets how wide a batch's busiest shard
    is, and so the work of a zipfian cell, which must not change with the
    seed.  The prefilled half is drawn from the run's seed."""

    UNIVERSE_STREAM = 0x5EED

    def __init__(self, seed: int, key_range: int, prefill_share: float):
        self.ids = rng_for(self.UNIVERSE_STREAM, 1).choice(
            KEY_ID_SPACE, key_range, replace=False).astype(np.int32)
        n_pre = int(key_range * prefill_share)
        self.prefill = self.ids[rng_for(seed, 1).permutation(
            key_range)[:n_pre]]


class Zipfian:
    """YCSB's bounded zipfian generator (Cooper et al., SoCC 2010,
    ``ZipfianGenerator``; Gray et al., SIGMOD 1994), vectorised: rank 0
    is the most popular of ``n`` items, with constant ``theta``."""

    def __init__(self, n: int, theta: float = 0.99):
        self.n, self.theta = n, theta
        self.zetan = float(np.sum(1.0 / np.arange(1, n + 1,
                                                  dtype=np.float64) ** theta))
        zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2
                                                         / self.zetan)

    def ranks(self, u: np.ndarray) -> np.ndarray:
        uz = u * self.zetan
        r = (self.n * (self.eta * u - self.eta + 1.0) ** self.alpha)
        r = np.minimum(r.astype(np.int64), self.n - 1)
        r = np.where(uz < 1.0 + 0.5 ** self.theta, 1, r)
        return np.where(uz < 1.0, 0, r)


class OpStream:
    """Draws (ops, keys, values) in chunks from one traffic file."""

    def __init__(self, traffic: dict, universe: KeyUniverse, seed: int,
                 stream: int = 2):
        self.rng = rng_for(seed, stream)
        self.ids = universe.ids
        dist = traffic["keys"]["dist"]
        if dist == "zipfian":
            self.zipf = Zipfian(self.ids.size, traffic["keys"]["theta"])
        elif dist == "uniform":
            self.zipf = None
        else:
            raise ValueError(f"unknown key distribution {dist!r}")
        mix = traffic["mix"]
        total = mix["contains"] + mix["insert"] + mix["remove"]
        self.c_cut = mix["contains"] / total
        self.i_cut = (mix["contains"] + mix["insert"]) / total

    def draw(self, n: int):
        rng = self.rng
        if self.zipf is None:
            rank = rng.integers(0, self.ids.size, n)
        else:
            rank = self.zipf.ranks(rng.random(n))
        u = rng.random(n)
        ops = np.where(u < self.c_cut, OP_CONTAINS,
                       np.where(u < self.i_cut, OP_INSERT, OP_REMOVE))
        vals = rng.integers(0, 1 << 31, n, dtype=np.int64).astype(np.int32)
        return ops.astype(np.int32), self.ids[rank], vals


def draw_ring(stream: OpStream, n_batches: int, batch: int) -> list:
    """``n_batches`` batches of ``batch`` lanes, drawn in one go before a
    closed loop's window; the loop cycles through them."""
    ops, keys, vals = (x.reshape(n_batches, batch)
                       for x in stream.draw(n_batches * batch))
    return list(zip(ops, keys, vals))


class PoissonArrivals:
    """Open-loop arrivals at a fixed rate: exponential gaps, drawn in
    chunks with their ops, so the host generator is never the bottleneck.
    ``take(now, n)`` returns up to ``n`` arrivals due by ``now``: time
    advances whether or not the server kept up."""
    CHUNK = 1 << 14

    def __init__(self, rate: float, ops: OpStream, seed: int):
        self.rng = rng_for(seed, 3)
        self.rate, self.ops = rate, ops
        self.t = np.empty((0,), np.float64)
        self.o = np.empty((0,), np.int32)
        self.k = np.empty((0,), np.int32)
        self.clock = 0.0

    def _refill(self) -> None:
        n = self.CHUNK
        t = self.clock + np.cumsum(self.rng.exponential(1.0 / self.rate, n))
        self.clock = float(t[-1])
        o, k, _ = self.ops.draw(n)
        self.t = np.concatenate([self.t, t])
        self.o = np.concatenate([self.o, o])
        self.k = np.concatenate([self.k, k])

    def next_arrival(self) -> float:
        if self.t.size == 0:
            self._refill()
        return float(self.t[0])

    def take(self, now: float, max_n: int):
        while self.t.size < max_n and self.clock <= now:
            self._refill()
        n = min(int(np.searchsorted(self.t, now, side="right")), max_n)
        out = self.t[:n], self.o[:n], self.k[:n]
        self.t, self.o, self.k = self.t[n:], self.o[n:], self.k[n:]
        return out
