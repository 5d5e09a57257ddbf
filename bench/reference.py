"""Plain references: what the registry and the spine must answer.

``RefRegistry`` is a durable set over a fixed key universe with the
documented batch linearization (DESIGN.md §4, "Mixed batches"): every
contains lane reads the state before the batch, then inserts, then
removes, each in lane order.  SOFT durable linearizability means a crash
after a batch has completed loses nothing, and every successful update
costs exactly one psync.  It is vectorised per batch with numpy;
``ref_apply`` below is the same semantics as a dict loop, kept to check
it (bench/tests/test_reference.py).

``BufferedRegistry`` is the control: the same set with buffered
durability (one psync per epoch of batches; a crash loses the open epoch).
It breaks the configuration's stated guarantee, and every cell has to
come out not correct with it in the program's place.

The references import nothing of the program and take nothing it made.
"""
from __future__ import annotations

import numpy as np

from bench.traffic_gen import OP_CONTAINS, OP_INSERT, OP_NOP, OP_REMOVE


class RefRegistry:
    """SOFT durable set over the key ids of one universe."""

    def __init__(self, universe_ids: np.ndarray):
        self.order = np.argsort(universe_ids)
        self.sorted_ids = universe_ids[self.order]
        n = universe_ids.size
        self.present = np.zeros(n, bool)
        self.values = np.zeros(n, np.int32)
        self.psyncs = 0                    # since the last recovery

    def pos(self, keys: np.ndarray) -> np.ndarray:
        p = np.searchsorted(self.sorted_ids, keys)
        p = np.minimum(p, self.sorted_ids.size - 1)
        if not np.array_equal(self.sorted_ids[p], keys):
            raise ValueError("a key outside the universe")
        return p

    def apply(self, ops: np.ndarray, keys: np.ndarray, vals: np.ndarray
              ) -> np.ndarray:
        real = ops != OP_NOP              # padding lanes are no-ops
        p = np.zeros(keys.shape, np.int64)
        p[real] = self.pos(keys[real])
        out = np.zeros(keys.shape, bool)
        c = ops == OP_CONTAINS
        out[c] = self.present[p[c]]
        ins = self._first_lanes(ops == OP_INSERT, p)
        win = ins[~self.present[p[ins]]]
        self.present[p[win]] = True
        self.values[p[win]] = vals[win]
        out[win] = True
        rem = self._first_lanes(ops == OP_REMOVE, p)
        rwin = rem[self.present[p[rem]]]
        self.present[p[rwin]] = False
        out[rwin] = True
        self._persist(win.size + rwin.size)
        return out

    @staticmethod
    def _first_lanes(mask: np.ndarray, p: np.ndarray) -> np.ndarray:
        """Lanes of ``mask`` that are the first of their key: the lanes
        whose op takes effect (later ones of the same key see it done)."""
        lanes = np.flatnonzero(mask)
        _, first = np.unique(p[lanes], return_index=True)
        return np.sort(lanes[first])

    def _persist(self, n_updates: int) -> None:
        self.psyncs += n_updates

    def crash(self) -> None:
        """A crash after completed batches: SOFT keeps every update."""
        self.psyncs = 0

    def size(self) -> int:
        return int(self.present.sum())

    def lookup(self, keys: np.ndarray):
        """(present, value) per key, reading nothing of the program."""
        p = self.pos(keys)
        return self.present[p], self.values[p]


class BufferedRegistry(RefRegistry):
    """The control: buffered durability.  Updates are persisted by one
    psync per epoch of ``epoch`` batches, issued when the next epoch
    starts; a crash loses the updates of the open epoch."""

    def __init__(self, universe_ids: np.ndarray, epoch: int = 4):
        super().__init__(universe_ids)
        self.epoch, self.open_batches = epoch, 0
        self.durable = (self.present.copy(), self.values.copy())

    def apply(self, ops, keys, vals):
        if self.open_batches == self.epoch:
            self.durable = (self.present.copy(), self.values.copy())
            self.psyncs += 1
            self.open_batches = 0
        self.open_batches += 1
        return super().apply(ops, keys, vals)

    def _persist(self, n_updates: int) -> None:
        pass

    def crash(self) -> None:
        self.present, self.values = (a.copy() for a in self.durable)
        self.open_batches = 0
        self.psyncs = 0


def ref_apply(ref: dict, ops, keys, vals) -> np.ndarray:
    """The same batch semantics as a plain dict loop (``chip_smoke.py``'s
    reference): contains lanes read the state before the batch,
    then inserts, then removes, each in lane order."""
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
