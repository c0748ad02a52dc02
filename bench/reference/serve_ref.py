"""Plain reference of cluster-queue serving (U2U2I seeds + U2I2I union).

It imports nothing of the program.  The semantics it states:

* every ingested event, in ingest order (within one ingest call, a
  stable sort by the float32 timestamp relative to the epoch, the
  smallest timestamp of the first call), is appended to its user's
  cluster queue;
* a cluster queue holds only its last ``queue_len`` events, and of
  those only the newest occurrence of each item (an item ingested again
  hides its earlier occurrence);
* a request at time ``now`` reads its user's cluster queue newest first
  and keeps entries whose float32 relative timestamp is at least the
  float32 cutoff ``now - recency_s - epoch``; its seeds are the first
  ``n_recent`` of them, ``-1`` padded;
* the U2I2I union walks the seeds' I2I rows rank by rank (rank 0 of
  every seed, then rank 1, ...), skips ``-1``, any seed item and any item
  already taken, and keeps the first ``k``, ``-1`` padded; a seed past
  the end of the table contributes nothing.

``drop_duplicates=False`` breaks the second guarantee (earlier
occurrences stay visible): that is the control.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


class EventLog:
    """Ingested events in the order the store applies them, with the
    event count after each ingest call."""

    def __init__(self):
        self._calls: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self.call_end: List[int] = []
        self.epoch = None
        self._n = 0

    def append(self, clusters: np.ndarray, items: np.ndarray,
               ts: np.ndarray) -> None:
        ts = np.asarray(ts, np.float64)
        if self.epoch is None:
            self.epoch = float(ts.min())
        self._calls.append((np.asarray(clusters, np.int32),
                            np.asarray(items, np.int32), ts))
        self._n += len(ts)
        self.call_end.append(self._n)

    def select(self, want: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """The events of the clusters ``want`` marks, as ``(key,
        clusters, items, rel)``: within each call in stable float32
        timestamp order, and ``key`` increasing with the store's
        application order, with ``key < call_end[j - 1]`` exactly for the
        events of the first ``j`` calls."""
        keys, cl, it, rel = [], [], [], []
        start = 0
        for (c, i, ts), end in zip(self._calls, self.call_end):
            sel = np.flatnonzero(want[c])
            r = (ts[sel] - self.epoch).astype(np.float32)
            o = np.argsort(r, kind="stable")
            keys.append(start + np.arange(len(o)))
            cl.append(c[sel[o]])
            it.append(i[sel[o]])
            rel.append(r[o])
            start = end
        cat = lambda xs, dt: (np.concatenate(xs) if xs
                              else np.zeros(0, dt))
        return (cat(keys, np.int64), cat(cl, np.int32), cat(it, np.int32),
                cat(rel, np.float32))


def _queue_seeds(pos: np.ndarray, key: np.ndarray, items: np.ndarray,
                 rel: np.ndarray, end: int, cutoff: np.float32,
                 queue_len: int, n_recent: int, drop_duplicates: bool
                 ) -> np.ndarray:
    """Seeds of one cluster from its events ``pos`` (in application
    order) that had been applied before log position ``end``."""
    mine = pos[key[pos] < end][-queue_len:][::-1]   # newest first
    out = np.full(n_recent, -1, np.int64)
    seen, j = set(), 0
    for p in mine:
        it = int(items[p])
        if drop_duplicates:
            if it in seen:
                continue
            seen.add(it)
        if rel[p] >= cutoff:
            out[j] = it
            j += 1
            if j == n_recent:
                break
    return out


def union(seeds: np.ndarray, i2i: np.ndarray, k: int) -> np.ndarray:
    n = i2i.shape[0]
    seed_set = {int(s) for s in seeds if s >= 0}
    taken, out = set(), []
    for r in range(i2i.shape[1]):
        for s in seeds:
            if s < 0 or s >= n:
                continue
            c = int(i2i[s, r])
            if c < 0 or c in seed_set or c in taken:
                continue
            taken.add(c)
            out.append(c)
    row = np.full(k, -1, np.int64)
    row[:min(k, len(out))] = out[:k]
    return row


def answers(log: EventLog, clusters: np.ndarray, ends: Sequence[int],
            nows: np.ndarray, *, queue_len: int, recency_s: float,
            i2i: np.ndarray, n_recent: int, k: int,
            drop_duplicates: bool = True
            ) -> Tuple[np.ndarray, np.ndarray]:
    """Reference ``(seeds, union)`` for requests of the given clusters at
    the given times, each against the first ``ends[r]`` logged events."""
    clusters = np.asarray(clusters)
    want = np.zeros(int(max(max(int(c.max(initial=0))
                                for c, _, _ in log._calls),
                            int(clusters.max(initial=0)))) + 1, bool)
    want[clusters] = True
    key, cl, items, rel = log.select(want)
    by = np.argsort(cl, kind="stable")
    bounds: Dict[int, np.ndarray] = {}
    cl_by = cl[by]
    starts = np.flatnonzero(np.r_[True, cl_by[1:] != cl_by[:-1]])
    stops = np.r_[starts[1:], len(by)]
    for a, b in zip(starts, stops):
        bounds[int(cl_by[a])] = by[a:b]
    empty = np.zeros(0, np.int64)
    R = len(clusters)
    seeds = np.full((R, n_recent), -1, np.int64)
    uni = np.full((R, k), -1, np.int64)
    for r in range(R):
        cutoff = np.float32(float(nows[r]) - recency_s - log.epoch)
        seeds[r] = _queue_seeds(bounds.get(int(clusters[r]), empty), key,
                                items, rel, int(ends[r]), cutoff, queue_len,
                                n_recent, drop_duplicates)
        uni[r] = union(seeds[r], i2i, k)
    return seeds, uni
