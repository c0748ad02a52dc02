"""KNN-free serving (paper §4.4) — device-resident, single-dispatch engine.

U2U2I: each user carries a hierarchical cluster code (k1, k2) from the
co-learned RQ index; each cluster keeps a recency-filtered queue of items
engaged by its recently-active members.  Serving = read the target
user's cluster queue (a lookup), instead of online KNN over the active
user pool.

U2I2I: item embeddings change slowly, so I2I KNN is pre-computed offline;
serving unions the similar-item lists of the user's recent items.

``ClusterQueueStore`` keeps its ring buffers as **jax device arrays** and
collapses the whole retrieve pass — recency cutoff, validity masking,
top-k selection, and (in ``serve_batch``) the U2I2I union — into a
single jitted dispatch.  The jit releases the GIL while XLA runs, so N
serving threads scale past the interpreter wall that bounded the old
host-array engine (preserved as ``HostQueueStore`` in
``repro.core.serving_host``; it remains the bitwise oracle and the
scale-out baseline).

Design notes:

* **MVCC, not seqlocks.**  ``_state`` is a dict of immutable device
  arrays.  ``ingest`` rebinds it functionally under ``write_lock``;
  a reader grabs one GIL-atomic reference and dispatches against that
  consistent snapshot.  No generation counters, no retries, no torn
  reads — and no donation, so an in-flight reader's snapshot stays
  alive until its dispatch returns.
* **Sort-free kernels.**  Candidates are materialised newest-first by
  construction (ring order), validity is a mask, and the j-th valid
  entry is found with a cumsum prefix + unrolled binary search —
  XLA CPU sorts are an order of magnitude slower than the equivalent
  numpy sort, so the traced graph contains none.
* **Dedup at ingest.**  The ring is kept duplicate-free per
  ``(cluster, item)``: ingest tombstones the prior ring occurrence of
  each incoming item, so retrieve needs no dedup stage.  Cursor
  arithmetic still advances for *every* event, which keeps slot ages
  bitwise-identical to the host engine.
* **Two write modes.** ``delta_cap=0`` (default) scatters every ingest
  batch straight into the ring.  ``delta_cap=D`` appends to a small
  delta buffer and folds into the ring only when full (an LSM level of
  exactly one run) — retrieve scans delta-then-ring.  Delta mode makes
  per-shard ingest work scale as 1/S in ``ShardedQueueStore``.
* **Stable traces.**  Batch dims are padded to power-of-two buckets and
  ``k``/``Q``/``C``/``D`` are static, so steady state replays a handful
  of compiled traces.

``ShardedQueueStore`` partitions the cluster space into N contiguous
ranges behind the same API: ingest is split once by shard and scattered,
retrieve routes each request to its owning shard and merges.  With a
``jax.sharding.Mesh`` available, shard states are placed round-robin
across mesh devices (see ``repro.distributed.sharding``).

``ServingCostModel`` quantifies the paper's 83% claim: FLOPs + bytes per
request for online-KNN vs cluster-lookup serving at a given active-pool
size, traffic, request batch size, and shard count.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.obs import get_telemetry
from repro.core.serving_host import (   # noqa: F401  (compat re-exports)
    BufPool,
    HostQueueStore,
    ThreadLocalPools,
    _POOLS,
    dedup_topk_rows,
)


def _bucket(n: int, lo: int = 8) -> int:
    """Smallest power-of-two >= n (>= lo): pads dynamic batch dims onto a
    handful of stable jit traces."""
    b = lo
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# traced building blocks (composed inside the jitted entry points below)
# ---------------------------------------------------------------------------

def _candidate_window(st, cl, cutoff, C: int, Q: int, Deff: int):
    """Newest-first candidate window + validity mask for one row per
    (padded) cluster id.  ``cl < 0`` rows are fully invalid.  With
    ``Deff > 0`` the delta buffer (newest-first) is prepended to the
    ring window so selection order equals arrival order."""
    B = cl.shape[0]
    known = cl >= 0
    cl0 = jnp.where(known, cl, 0)
    total = st["total"][cl0]
    rtot = st["ring_total"][cl0] if Deff > 0 else total
    a = jnp.arange(Q, dtype=jnp.int32)[None, :]
    slot = jnp.mod(rtot[:, None] - 1 - a, Q)
    r_item = jnp.take_along_axis(st["items"][cl0], slot, axis=1)
    r_ts = jnp.take_along_axis(st["times"][cl0], slot, axis=1)
    r_valid = ((a < jnp.minimum(rtot, Q)[:, None])
               & (r_item >= 0) & (r_ts >= cutoff) & known[:, None])
    if Deff == 0:
        return r_item, r_valid
    r_shadow = jnp.take_along_axis(st["shadow"][cl0], slot, axis=1)
    r_age = a + (total - rtot)[:, None]        # age incl. pending deltas
    r_valid = r_valid & ~r_shadow & (r_age < Q)
    d_cl = st["d_cl"][:Deff][::-1][None, :]
    d_item = jnp.broadcast_to(st["d_item"][:Deff][::-1][None, :], (B, Deff))
    d_ts = st["d_ts"][:Deff][::-1][None, :]
    d_idx = st["d_idx"][:Deff][::-1][None, :]
    d_sh = st["d_shadow"][:Deff][::-1][None, :]
    mine = (d_cl == cl0[:, None]) & known[:, None]
    d_age = total[:, None] - 1 - d_idx
    d_valid = (mine & ~d_sh & (d_item >= 0) & (d_ts >= cutoff)
               & (d_age >= 0) & (d_age < Q))
    return (jnp.concatenate([d_item, r_item], axis=1),
            jnp.concatenate([d_valid, r_valid], axis=1))


def _select_topk(cand, valid, k: int):
    """First ``k`` valid candidates per row, in window order, ``-1``
    padded.  Sort-free: cumsum prefix + unrolled binary search for the
    j-th valid position."""
    B, W = cand.shape
    pref = jnp.cumsum(valid.astype(jnp.int32), axis=1)
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    lo = jnp.zeros((B, k), jnp.int32)
    step = 1
    while step < W:
        step *= 2
    step //= 2
    while step >= 1:
        mid = jnp.minimum(lo + step, W - 1)
        go = jnp.take_along_axis(pref, mid, axis=1) < j + 1
        lo = jnp.where(go, jnp.minimum(lo + step, W - 1), lo)
        step //= 2
    at0 = jnp.take_along_axis(pref, jnp.zeros_like(lo), axis=1) >= j + 1
    src = jnp.where(at0, 0, jnp.minimum(lo + 1, W - 1))
    got = jnp.take_along_axis(pref, src, axis=1) == j + 1
    out = jnp.where(got, jnp.take_along_axis(cand, src, axis=1), -1)
    return jnp.where(j < pref[:, -1][:, None], out, -1)


def _union_topk(seeds, i2i, k: int):
    """Traced U2I2I union: rank-major round-robin over the seeds'
    neighbor lists, seed + duplicate masking, first-k select.  Bitwise
    equal to the host ``u2i2i_retrieve_batch`` for identical seeds."""
    B, R = seeds.shape
    n, K = i2i.shape
    W = R * K
    seeded = (seeds >= 0) & (seeds < n)
    rows = jnp.take(i2i, jnp.clip(seeds, 0, n - 1), axis=0)     # (B,R,K)
    cand = jnp.where(seeded[:, :, None], rows, -1)
    flat = cand.transpose(0, 2, 1).reshape(B, W)                # rank-major
    seen = ((flat[:, :, None] == seeds[:, None, :])
            & (seeds >= 0)[:, None, :]).any(axis=2)
    valid = (flat >= 0) & ~seen
    tri = jnp.tril(jnp.ones((W, W), bool), -1)
    dup = ((flat[:, :, None] == flat[:, None, :])
           & valid[:, None, :] & tri[None]).any(axis=2)
    return _select_topk(flat, valid & ~dup, k)


# ---------------------------------------------------------------------------
# jitted entry points (one dispatch each)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("C", "Q"))
def _direct_ingest_jit(st, w_cl, slot, w_item, raw_item, rel, t_cl,
                       ucl, cnt, C, Q):
    """Direct mode: tombstone prior ring occurrences of incoming items,
    then scatter the batch's surviving writes and advance cursors.  Pad
    rows carry cluster ``C`` and fall out via ``mode="drop"``."""
    with jax.named_scope("ingest.scatter"):
        ring_rows = st["items"][jnp.clip(t_cl, 0, C - 1)]
        m = ((ring_rows == raw_item[:, None])
             & (raw_item >= 0)[:, None] & (t_cl < C)[:, None])
        q_hit = jnp.argmax(m, axis=1).astype(jnp.int32)
        has = m.any(axis=1)
        items = st["items"].at[jnp.where(has, t_cl, C), q_hit].set(
            -1, mode="drop")
        items = items.at[w_cl, slot].set(w_item, mode="drop")
        times = st["times"].at[w_cl, slot].set(rel, mode="drop")
        total = st["total"].at[ucl].add(cnt, mode="drop")
    return dict(items=items, times=times, total=total)


@functools.partial(jax.jit, static_argnames=("C", "Q", "D", "Deff"))
def _append_jit(st, cl, w_item, raw_item, rel, d_idx, d0, n_real,
                C, Q, D, Deff):
    """Delta mode: append the batch to the delta buffer at ``d0``,
    shadowing prior occurrences in both the ring (bitmap) and the delta
    run (``d_shadow``)."""
    Ep = cl.shape[0]
    ar = jnp.arange(Ep, dtype=jnp.int32)
    is_real = ar < n_real
    dst = jnp.where(is_real, d0 + ar, D)
    ring_rows = st["items"][jnp.clip(cl, 0, C - 1)]
    m = ((ring_rows == raw_item[:, None])
         & (raw_item >= 0)[:, None] & is_real[:, None])
    q_hit = jnp.argmax(m, axis=1).astype(jnp.int32)
    has = m.any(axis=1)
    shadow = st["shadow"].at[jnp.where(has, cl, C), q_hit].set(True,
                                                               mode="drop")
    dm = ((st["d_cl"][:Deff][None, :] == cl[:, None])
          & (st["d_item"][:Deff][None, :] == raw_item[:, None])
          & (raw_item >= 0)[:, None] & is_real[:, None])
    d_shadow = st["d_shadow"].at[:Deff].set(st["d_shadow"][:Deff]
                                            | dm.any(axis=0))
    # return ONLY the keys this pass writes: a jitted pass-through of
    # the untouched (C, Q) ring arrays is a full device copy of them
    # per call (no donation), which would erase the 1/S sharding win
    return dict(
        shadow=shadow,
        d_shadow=d_shadow.at[dst].set(False, mode="drop"),
        d_cl=st["d_cl"].at[dst].set(cl, mode="drop"),
        d_item=st["d_item"].at[dst].set(w_item, mode="drop"),
        d_ts=st["d_ts"].at[dst].set(rel, mode="drop"),
        d_idx=st["d_idx"].at[dst].set(d_idx, mode="drop"),
        total=st["total"].at[jnp.where(is_real, cl, C)].add(
            1, mode="drop"))


@functools.partial(jax.jit, static_argnames=("C", "Q", "D"))
def _fold_jit(st, C, Q, D):
    """Fold the delta run into the ring: apply shadow tombstones, write
    each delta event to its slot (slot-LWW via a pairwise later-matrix),
    drop already-evicted events, and reset the delta buffer."""
    items = jnp.where(st["shadow"], -1, st["items"])
    times = st["times"]
    d_cl, d_item = st["d_cl"], st["d_item"]
    d_ts, d_idx = st["d_ts"], st["d_idx"]
    live = d_cl < C
    slot = jnp.where(live, d_idx % Q, 0)
    later = ((d_cl[None, :] == d_cl[:, None])
             & (slot[None, :] == slot[:, None])
             & (d_idx[None, :] > d_idx[:, None]) & live[None, :])
    wins = live & ~later.any(axis=1)
    age = st["total"][jnp.clip(d_cl, 0, C - 1)] - 1 - d_idx
    dead = ~wins | (age >= Q)
    w_item = jnp.where(st["d_shadow"], -1, d_item)
    row = jnp.where(dead, C, d_cl)
    # modified keys only (see _append_jit): `total` passes through
    return dict(
        items=items.at[row, slot].set(w_item, mode="drop"),
        times=times.at[row, slot].set(d_ts, mode="drop"),
        shadow=jnp.zeros_like(st["shadow"]),
        ring_total=st["total"],
        d_cl=jnp.full((D,), C, jnp.int32),
        d_item=jnp.full((D,), -1, jnp.int32),
        d_ts=jnp.full((D,), -jnp.inf, jnp.float32),
        d_idx=jnp.zeros((D,), jnp.int32),
        d_shadow=jnp.zeros((D,), jnp.bool_))


@functools.partial(jax.jit, static_argnames=("k", "C", "Q", "Deff"))
def _retrieve_jit(st, cl, cutoff, k, C, Q, Deff):
    cand, valid = _candidate_window(st, cl, cutoff, C, Q, Deff)
    return _select_topk(cand, valid, k)


@functools.partial(jax.jit,
                   static_argnames=("n_recent", "k", "C", "Q", "Deff"))
def _serve_jit(st, cl, i2i, cutoff, n_recent, k, C, Q, Deff):
    """Seeds then union in one ``(B, n_recent + k)`` array, so a call
    copies one buffer back to the host."""
    # the scopes name the device operations in a profile (metadata only)
    with jax.named_scope("serve.select"):
        cand, valid = _candidate_window(st, cl, cutoff, C, Q, Deff)
        seeds = _select_topk(cand, valid, n_recent)
    with jax.named_scope("serve.union"):
        return jnp.concatenate([seeds, _union_topk(seeds, i2i, k)], axis=1)


# ---------------------------------------------------------------------------
# cluster-queue store (U2U2I) — device-resident
# ---------------------------------------------------------------------------

class ClusterQueueStore:
    """Real-time per-cluster item queues with recency filtering, resident
    on a jax device.

    Layout: ``_state`` holds dense ``(n_clusters, queue_len)``
    item/timestamp rings plus a per-cluster write counter ``total``
    (write position = ``total % queue_len``); ``delta_cap > 0`` adds a
    flat delta run that folds into the ring when full.  The ring is kept
    duplicate-free per ``(cluster, item)`` by tombstoning at ingest.

    Concurrency (MVCC): ``_state`` is immutable; writers rebind it under
    ``write_lock`` (an RLock — the swap engine's ring drain wraps
    ``ingest`` in the same lock), readers take one snapshot reference
    and dispatch a single jit against it.  The dispatch releases the
    GIL, so reader threads scale with cores.

    ``_cursor_host`` mirrors ``total`` on the host (writer-maintained, so
    ingest prep and telemetry never synchronise with the device).
    """

    def __init__(self, user_clusters: np.ndarray, *, queue_len: int = 256,
                 recency_s: float = 900.0, n_clusters: Optional[int] = None,
                 telemetry=None, delta_cap: int = 0,
                 shard: Optional[int] = None, device=None):
        self.tel = telemetry if telemetry is not None else get_telemetry()
        self.user_clusters = np.asarray(user_clusters, np.int64)
        self.queue_len = int(queue_len)
        self.recency_s = float(recency_s)
        if n_clusters is None:
            n_clusters = max(int(self.user_clusters.max()) + 1, 1) \
                if self.user_clusters.size else 1
        self.n_clusters = max(int(n_clusters), 1)
        self.delta_cap = int(delta_cap)
        C, Q, D = self.n_clusters, self.queue_len, self.delta_cap
        state = dict(
            items=jnp.full((C, Q), -1, jnp.int32),
            # timestamps are stored float32 relative to the first-seen
            # event (absolute unix-epoch seconds lose ~100s of precision
            # in f32)
            times=jnp.full((C, Q), -np.inf, jnp.float32),
            total=jnp.zeros((C,), jnp.int32),
        )
        if D > 0:
            state.update(
                shadow=jnp.zeros((C, Q), jnp.bool_),
                ring_total=jnp.zeros((C,), jnp.int32),
                d_cl=jnp.full((D,), C, jnp.int32),
                d_item=jnp.full((D,), -1, jnp.int32),
                d_ts=jnp.full((D,), -np.inf, jnp.float32),
                d_idx=jnp.zeros((D,), jnp.int32),
                d_shadow=jnp.zeros((D,), jnp.bool_),
            )
        if device is not None:
            state = jax.device_put(state, device)
        self.device = device
        self._state = state
        self._cursor_host = np.zeros(C, np.int64)
        self.d_count = 0               # filled delta slots (writer-only)
        self.epoch: Optional[float] = None
        self.write_lock = threading.RLock()
        self.ring_seen = 0     # EventRing watermark (maintained by swap)
        # a shard's metrics carry a ``.shard{i}`` suffix, and its spans are
        # children of its router's span
        self.shard = shard
        shard_tag = "" if shard is None else f".shard{shard}"
        self._span_kw = {} if shard is None else {"shard": shard}
        self._m_ingest = "serving.ingest_events" + shard_tag
        self._m_requests = "serving.retrieve_requests" + shard_tag
        self._m_latency = "serving.retrieve_latency_s" + shard_tag
        self._m_depth_max = "serving.queue_depth_max" + shard_tag
        self._m_depth_mean = "serving.queue_depth_mean" + shard_tag
        self._m_unknown_ev = "serving.unknown_user_events" + shard_tag
        self._m_unknown_rq = "serving.unknown_user_requests" + shard_tag
        self._m_serve_calls = "serving.serve_calls" + shard_tag
        self._m_serve_rows = "serving.serve_rows" + shard_tag
        self._m_serve_padded = "serving.serve_rows_padded" + shard_tag
        self._i2i_cache: Optional[Tuple[int, jnp.ndarray]] = None

    # -- cluster assignment lookup ------------------------------------------

    def clusters_of(self, user_ids: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Cluster ids for a batch of users plus a known-user mask.

        Users outside the assignment table — ids minted *after* the
        snapshot this store serves was published — and users whose table
        entry is negative (clusters owned by a different shard) map to
        cluster 0 with ``known=False``; callers must mask their rows out
        rather than crash or serve another user's cluster.
        """
        cl, known = self._lookup(np.asarray(user_ids, np.int64).ravel())
        return np.where(known, cl, 0), known

    def _lookup(self, user_ids: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
        """Table entries of flat int64 ``user_ids`` and the known-user
        mask; an entry means nothing where its user is not known."""
        uc = self.user_clusters
        cl = uc.take(user_ids, mode="clip")
        return cl, (user_ids >= 0) & (user_ids < uc.shape[0]) & (cl >= 0)

    # -- ingestion ----------------------------------------------------------

    def ingest(self, user_ids: np.ndarray, item_ids: np.ndarray,
               timestamps: np.ndarray, *, _presorted: bool = False) -> None:
        """Stream a batch of engagement events into their users' cluster
        ring buffers (oldest-to-newest so ring order is time order within
        the batch).  Events from users unknown to this snapshot's
        assignment table are dropped (they enter queues once the next
        publication assigns them a cluster).

        The device scatter happens behind ``write_lock``; readers keep
        dispatching against the previous ``_state`` snapshot and observe
        the batch atomically when the rebind lands.

        Spans: ``serving.ingest`` (attrs ``events``, ``clusters``,
        ``width``: padded event rows dispatched) over
        ``serving.ingest.lookup`` (``clusters_of``),
        ``serving.ingest.prep`` (time order, slot assignment, LWW,
        padding) and ``serving.ingest.dispatch`` (the device copies and
        the jitted write).  Under a router the router holds the parent
        and the children carry ``shard``."""
        user_ids = np.asarray(user_ids, np.int64).ravel()
        if self.shard is not None:
            self._ingest(user_ids, item_ids, timestamps, _presorted, None)
            return
        with self.tel.hot_span("serving.ingest",
                               events=int(user_ids.size)) as sp:
            self._ingest(user_ids, item_ids, timestamps, _presorted, sp)

    def _ingest(self, user_ids: np.ndarray, item_ids: np.ndarray,
                timestamps: np.ndarray, presorted: bool, sp) -> None:
        tel, kw = self.tel, self._span_kw
        item_ids = np.asarray(item_ids, np.int64).ravel()
        ts64 = np.asarray(timestamps, np.float64).ravel()
        with tel.hot_span("serving.ingest.lookup", **kw):
            cl_all, known = self.clusters_of(user_ids)
        if not known.all():
            # graceful degradation: post-snapshot users are shed, not
            # errored — the drop is surfaced as a counter so staleness
            # between publications is observable
            if self.tel.enabled:
                self.tel.counter(self._m_unknown_ev, float((~known).sum()))
            cl_all = cl_all[known]
            item_ids = item_ids[known]
            ts64 = ts64[known]
        if cl_all.size == 0:
            return
        with self.write_lock:
            with tel.hot_span("serving.ingest.prep", **kw):
                if self.epoch is None:
                    self.epoch = float(ts64.min())
                rel = (ts64 - self.epoch).astype(np.float32)
                cl = cl_all.astype(np.int32)
                it = item_ids.astype(np.int32)
                if not presorted:
                    order = np.argsort(rel, kind="stable")
                    cl, it, rel = cl[order], it[order], rel[order]
            if self.delta_cap:
                n, done, width, parts = cl.size, 0, 0, []
                while done < n:
                    take = min(n - done, self.delta_cap - self.d_count)
                    if take == 0:
                        self._fold()
                        continue
                    parts.append(self._append(cl[done:done + take],
                                              it[done:done + take],
                                              rel[done:done + take]))
                    width += _bucket(take)
                    done += take
                ucl = (parts[0] if len(parts) == 1
                       else np.unique(np.concatenate(parts)))
            else:
                ucl = self._direct_ingest(cl, it, rel)
                width = _bucket(cl.size)
        if tel.enabled:
            if sp is not None:
                sp.set("clusters", int(ucl.size))
                sp.set("width", width)
            tel.counter(self._m_ingest, float(cl.size))
            fill = np.minimum(self._cursor_host[ucl], self.queue_len)
            tel.gauge(self._m_depth_max, float(fill.max()))
            tel.gauge(self._m_depth_mean, float(fill.mean()))

    def _direct_ingest(self, cl: np.ndarray, it: np.ndarray,
                       rel: np.ndarray) -> np.ndarray:
        """Direct mode: host-side batch prep (slot assignment, in-batch
        LWW) then one jitted scatter; returns the batch's unique
        clusters.  Reentrant under ``ingest``'s lock."""
        tel, kw = self.tel, self._span_kw
        with self.write_lock:
            with tel.hot_span("serving.ingest.prep", **kw):
                E = cl.size
                C, Q = self.n_clusters, self.queue_len
                # per-event sequence index within its cluster (vectorized):
                # stable sort by cluster keeps time order inside each group
                o = np.argsort(cl, kind="stable")
                sc = cl[o]
                start = np.zeros(E, np.int64)
                if E > 1:
                    idx = np.arange(1, E)
                    start[1:] = np.where(sc[1:] == sc[:-1], 0, idx)
                    np.maximum.accumulate(start, out=start)
                rank = np.arange(E) - start
                seq = np.empty(E, np.int64)
                seq[o] = self._cursor_host[sc] + rank
                slot = (seq % Q).astype(np.int32)
                # slot LWW (in-batch ring wrap): last event per (cl, slot)
                skey = cl.astype(np.int64) * Q + slot
                _, li = np.unique(skey[::-1], return_index=True)
                keep = np.zeros(E, bool)
                keep[E - 1 - li] = True
                # in-batch item LWW: earlier duplicate of (cl, item) becomes
                # a tombstone so the ring stays duplicate-free
                ikey = cl.astype(np.int64) << 32 | it.astype(np.int64)
                _, li2 = np.unique(ikey[::-1], return_index=True)
                w_item = np.full(E, -1, np.int32)
                last = E - 1 - li2
                w_item[last] = it[last]
                ucl, cnt = np.unique(cl, return_counts=True)
                pad = _bucket(E) - E
                Cp = _bucket(ucl.size)
                args = (np.pad(np.where(keep, cl, C), (0, pad),
                               constant_values=C).astype(np.int32),
                        np.pad(slot, (0, pad)),
                        np.pad(w_item, (0, pad), constant_values=-1),
                        np.pad(it, (0, pad), constant_values=-1),
                        np.pad(rel, (0, pad), constant_values=-np.inf),
                        np.pad(cl, (0, pad), constant_values=C),
                        np.pad(ucl, (0, Cp - ucl.size),
                               constant_values=C).astype(np.int32),
                        np.pad(cnt, (0, Cp - ucl.size)).astype(np.int32))
            with tel.hot_span("serving.ingest.dispatch", **kw):
                self._state = _direct_ingest_jit(
                    self._state, *map(jnp.asarray, args), C, Q)
            self._cursor_host[ucl] += cnt
        return ucl

    def _append(self, cl: np.ndarray, it: np.ndarray,
                rel: np.ndarray) -> np.ndarray:
        """Delta mode: append ``E <= delta_cap - d_count`` events to the
        delta run; returns their unique clusters.  Reentrant under
        ``ingest``'s lock."""
        tel, kw = self.tel, self._span_kw
        with self.write_lock:
            with tel.hot_span("serving.ingest.prep", **kw):
                E = cl.size
                C, Q, D = self.n_clusters, self.queue_len, self.delta_cap
                o = np.argsort(cl, kind="stable")
                sc = cl[o]
                start = np.zeros(E, np.int64)
                if E > 1:
                    idx = np.arange(1, E)
                    start[1:] = np.where(sc[1:] == sc[:-1], 0, idx)
                    np.maximum.accumulate(start, out=start)
                rank = np.arange(E) - start
                d_idx = np.empty(E, np.int64)
                d_idx[o] = self._cursor_host[sc] + rank
                key = cl.astype(np.int64) << 32 | it.astype(np.int64)
                _, li = np.unique(key[::-1], return_index=True)
                last = E - 1 - li
                w_item = np.full(E, -1, np.int32)
                w_item[last] = it[last]
                ucl, cnt = np.unique(cl, return_counts=True)
                pad = _bucket(E) - E
                args = (np.pad(cl, (0, pad), constant_values=C),
                        np.pad(w_item, (0, pad), constant_values=-1),
                        np.pad(it, (0, pad), constant_values=-1),
                        np.pad(rel, (0, pad), constant_values=-np.inf),
                        np.pad(d_idx, (0, pad)).astype(np.int32))
            with tel.hot_span("serving.ingest.dispatch", **kw):
                self._state = {**self._state, **_append_jit(
                    self._state, *map(jnp.asarray, args),
                    jnp.int32(self.d_count), jnp.int32(E), C, Q, D, D)}
            self.d_count += E
            self._cursor_host[ucl] += cnt
        return ucl

    def _fold(self) -> None:
        """Fold the pending delta run into the ring (no-op when empty).
        Reentrant under ``ingest``'s lock."""
        with self.write_lock:
            if self.d_count == 0:
                return
            with self.tel.hot_span("serving.ingest.dispatch",
                                   **self._span_kw):
                self._state = {**self._state,
                               **_fold_jit(self._state, self.n_clusters,
                                           self.queue_len, self.delta_cap)}
            self.d_count = 0

    # -- retrieval ----------------------------------------------------------

    def rel_cutoff(self, now: float) -> float:
        """Recency cutoff in the store's internal (epoch-relative) time."""
        return now - self.recency_s - (self.epoch or 0.0)

    def _padded_clusters(self, user_ids: np.ndarray
                         ) -> Tuple[np.ndarray, np.ndarray, int,
                                    np.ndarray, np.ndarray]:
        """Dedup a request batch (flat int64 ids) down to its unique
        cluster rows (padded with ``-1`` to a power-of-two bucket) — most
        of a production batch shares clusters, and broadcasting rows back
        via the inverse is exact.  Unknown users (as in ``clusters_of``)
        get cluster ``-1``."""
        cl, known = self._lookup(user_ids)
        cl = np.where(known, cl, -1)
        ucl, inv = np.unique(cl, return_inverse=True)
        Bu = ucl.size
        cl_p = np.full(_bucket(Bu), -1, np.int32)
        cl_p[:Bu] = ucl
        return cl_p, inv, Bu, cl, known

    def retrieve_batch(self, user_ids: np.ndarray, now: float,
                       k: int) -> np.ndarray:
        """Batched U2U2I: ``(B,)`` user ids -> ``(B, k)`` item ids,
        newest-first, recency-filtered, deduped, ``-1``-padded.  One
        snapshot read + one jitted dispatch; safe to call from many
        threads at once (MVCC — no locks on this path)."""
        tel = self.tel
        t0 = tel.clock.perf() if tel.enabled else 0.0
        user_ids = np.asarray(user_ids, np.int64).ravel()
        cl_p, inv, Bu, _, known = self._padded_clusters(user_ids)
        st = self._state                 # one GIL-atomic snapshot read
        out = _retrieve_jit(st, jnp.asarray(cl_p),
                            jnp.float32(self.rel_cutoff(now)), int(k),
                            self.n_clusters, self.queue_len,
                            self.delta_cap)
        res = np.asarray(out)[:Bu][inv].astype(np.int64)
        if tel.enabled:
            tel.observe(self._m_latency, tel.clock.perf() - t0)
            tel.counter(self._m_requests)
            if not known.all():
                tel.counter(self._m_unknown_rq, float((~known).sum()))
        return res

    def retrieve(self, user_id: int, now: float, k: int) -> List[int]:
        """Legacy single-request U2U2I — a batch of one."""
        row = self.retrieve_batch(np.array([user_id]), now, k)[0]
        return [int(i) for i in row if i >= 0]

    def _i2i_device(self, i2i: np.ndarray):
        """Copy of the I2I table on this store's device, cached by
        identity (the table is rebuilt only at embedding refresh, so one
        transfer per swap)."""
        cached = self._i2i_cache
        if cached is not None and cached[0] == id(i2i):
            return cached[1]
        dev = jax.device_put(np.asarray(i2i, np.int32), self.device)
        self._i2i_cache = (id(i2i), dev)
        return dev

    def _ring_state(self) -> Dict[str, jnp.ndarray]:
        """One consistent snapshot of the device state whose ring holds
        every event (delta mode folds first)."""
        if self.delta_cap:
            with self.write_lock:
                self._fold()
                return self._state
        return self._state

    def _ring_view(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Consistent host view ``(items, times, cursor)`` of the ring."""
        st = self._ring_state()
        return (np.asarray(st["items"]), np.asarray(st["times"]),
                np.asarray(st["total"]).astype(np.int64))

    @property
    def items(self) -> np.ndarray:
        return self._ring_view()[0]

    @property
    def times(self) -> np.ndarray:
        return self._ring_view()[1]

    @property
    def cursor(self) -> np.ndarray:
        return self._cursor_host

    def serve_batch(self, user_ids: np.ndarray, now: float, *,
                    n_recent: int = 8, k: int = 32,
                    i2i: Optional[np.ndarray] = None,
                    use_kernel: bool = False
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Full serving pass: U2U2I seeds ``(B, n_recent)`` plus — when an
        ``i2i`` table is given — the U2I2I round-robin union ``(B, k)``.
        The default path fuses both stages into a single jitted dispatch;
        ``use_kernel=True`` routes through the Pallas ``queue_gather``
        kernels on the same device-resident ring snapshot.

        The fused path's span ``serving.serve_batch`` (attrs
        ``requests``, ``unique``, ``width``) has four children:
        ``serving.serve.prep`` (host only: cluster lookup, dedup, padding,
        the cutoff), ``.dispatch`` (the upload of the cluster ids and the
        cutoff and the enqueue of ``_serve_jit``, in one launch),
        ``.fetch`` (the wait for the device and the one copy back of
        seeds and union together) and ``.expand`` (rows back in request
        order; seeds and union are column slices of one array).  Under a
        router the router holds the parent and the children carry
        ``shard``."""
        if i2i is not None and use_kernel:
            from repro.kernels.queue_gather.ops import queue_gather
            user_ids = np.asarray(user_ids, np.int64).ravel()
            cl, known = self.clusters_of(user_ids)
            st = self._ring_state()
            s, u = queue_gather(st["items"], st["times"], st["total"], cl,
                                self._i2i_device(i2i),
                                cutoff=self.rel_cutoff(now),
                                n_recent=n_recent, k=k)
            seeds = np.asarray(s, np.int64)
            union = np.asarray(u, np.int64)
            if not known.all():
                seeds[~known] = -1       # unknown users: empty rows
                union[~known] = -1
                if self.tel.enabled:
                    self.tel.counter(self._m_unknown_rq,
                                     float((~known).sum()))
            return seeds, union
        if i2i is None:
            seeds = self.retrieve_batch(user_ids, now, n_recent)
            return seeds, np.full((seeds.shape[0], k), -1, np.int64)
        tel = self.tel
        user_ids = np.asarray(user_ids, np.int64).ravel()
        if self.shard is not None:
            seeds, union, known, busy = self._serve_fused(
                user_ids, now, n_recent, k, i2i, None)
        else:
            with tel.hot_span("serving.serve_batch",
                              requests=int(user_ids.size)) as sp:
                seeds, union, known, _ = self._serve_fused(
                    user_ids, now, n_recent, k, i2i, sp)
            busy = sp.duration_s
        if tel.enabled:
            tel.observe(self._m_latency, busy)
            tel.counter(self._m_requests)
            if not known.all():
                tel.counter(self._m_unknown_rq, float((~known).sum()))
        return seeds, union

    def _serve_fused(self, user_ids: np.ndarray, now: float,
                     n_recent: int, k: int, i2i: np.ndarray, sp
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, float]:
        """The fused serve pass under its four spans; returns seeds,
        union, the known-user mask and the seconds of the four spans.
        ``sp`` is the parent span (``None`` under a router)."""
        tel, kw = self.tel, self._span_kw
        with tel.hot_span("serving.serve.prep", **kw) as prep:
            cl_p, inv, Bu, _, known = self._padded_clusters(user_ids)
            # rounded as jnp.float32 rounds it; numpy arguments are
            # uploaded by the jit's own launch, with no program of their own
            cutoff = np.float32(self.rel_cutoff(now))
        with tel.hot_span("serving.serve.dispatch", **kw) as dispatch:
            out = _serve_jit(self._state, cl_p, self._i2i_device(i2i),
                             cutoff, int(n_recent), int(k), self.n_clusters,
                             self.queue_len, self.delta_cap)
        with tel.hot_span("serving.serve.fetch", **kw) as fetch:
            out = np.asarray(out)
        with tel.hot_span("serving.serve.expand", **kw) as expand:
            rows = out[:Bu][inv].astype(np.int64)
        seeds, union = rows[:, :n_recent], rows[:, n_recent:]
        if tel.enabled:
            width = int(cl_p.size)
            if sp is not None:
                sp.set("unique", Bu)
                sp.set("width", width)
            tel.counter(self._m_serve_calls)
            tel.counter(self._m_serve_rows, float(Bu))
            tel.counter(self._m_serve_padded, float(width))
            if self.shard is not None:   # the router's aggregate
                tel.counter("serving.serve_rows", float(Bu))
                tel.counter("serving.serve_rows_padded", float(width))
        busy = (prep.duration_s + dispatch.duration_s + fetch.duration_s
                + expand.duration_s)
        return seeds, union, known, busy

    # -- introspection ------------------------------------------------------

    def partitions(self) -> Tuple["ClusterQueueStore", ...]:
        """Uniform shard view: an unsharded store is its own single
        partition."""
        return (self,)

    def stats(self) -> Dict[str, float]:
        fill = np.minimum(self._cursor_host, self.queue_len)
        active = fill > 0
        return dict(n_shards=1,
                    n_clusters_active=int(active.sum()),
                    mean_queue=float(fill[active].mean())
                    if active.any() else 0.0,
                    delta_pending=float(self.d_count))


# ---------------------------------------------------------------------------
# sharded store: N contiguous cluster ranges behind one router
# ---------------------------------------------------------------------------

class ShardedQueueStore:
    """``ClusterQueueStore`` partitioned into ``n_shards`` contiguous
    cluster ranges behind the same API.

    Routing is by cluster id: ingest sorts the batch by time once, splits
    it by owning shard, and scatters; retrieve routes each request to its
    shard and merges rows back in request order.  Each shard holds a
    full-length user->cluster sub-table (out-of-range users map to
    ``-1`` = unknown), so a shard can never serve another shard's
    cluster.  The relative-time epoch is global — fixed from the first
    ingested batch and broadcast to every shard before any shard sees an
    event — so timestamps, and therefore retrieve results, are bitwise
    identical to an unsharded store over the same stream.

    With a ``jax.sharding.Mesh``, shard states are placed round-robin
    over ``mesh.devices``; on a single-device host the win comes from
    ``delta_cap``: per-shard ingest work (delta scans, fold matrices)
    shrinks as 1/S.

    Telemetry: each shard reports under a ``.shard{i}`` suffix; the
    facade emits the untagged aggregate series.
    """

    def __init__(self, user_clusters: np.ndarray, *, n_shards: int,
                 queue_len: int = 256, recency_s: float = 900.0,
                 n_clusters: Optional[int] = None, delta_cap: int = 0,
                 telemetry=None, mesh=None):
        self.tel = telemetry if telemetry is not None else get_telemetry()
        self.user_clusters = np.asarray(user_clusters, np.int64)
        if n_clusters is None:
            n_clusters = max(int(self.user_clusters.max()) + 1, 1) \
                if self.user_clusters.size else 1
        self.n_clusters = max(int(n_clusters), 1)
        self.n_shards = max(int(n_shards), 1)
        self.queue_len = int(queue_len)
        self.recency_s = float(recency_s)
        self.delta_cap = int(delta_cap)
        self.bounds = np.linspace(0, self.n_clusters,
                                  self.n_shards + 1).astype(np.int64)
        devices = None
        if mesh is not None:
            devices = list(np.asarray(mesh.devices).ravel())
        shards = []
        spans = []
        uc = self.user_clusters
        for s in range(self.n_shards):
            lo, hi = int(self.bounds[s]), int(self.bounds[s + 1])
            sub = np.where((uc >= lo) & (uc < hi), uc - lo, -1)
            shards.append(ClusterQueueStore(
                sub, queue_len=self.queue_len, recency_s=self.recency_s,
                n_clusters=max(hi - lo, 1), telemetry=self.tel,
                delta_cap=self.delta_cap, shard=s,
                device=devices[s % len(devices)] if devices else None))
            spans.append((lo, hi))
        self.shards: Tuple[ClusterQueueStore, ...] = tuple(shards)
        self._spans = tuple(spans)
        self.epoch: Optional[float] = None
        self.write_lock = threading.RLock()
        self.ring_seen = 0     # EventRing watermark (maintained by swap)

    # -- routing ------------------------------------------------------------

    def clusters_of(self, user_ids: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Global cluster ids + known mask (same contract as the
        unsharded store)."""
        user_ids = np.asarray(user_ids, np.int64).ravel()
        known = (user_ids >= 0) & (user_ids < self.user_clusters.shape[0])
        cl = self.user_clusters[np.where(known, user_ids, 0)]
        known = known & (cl >= 0)
        return np.where(known, cl, 0), known

    def _shard_of(self, cl: np.ndarray, known: np.ndarray) -> np.ndarray:
        sid = np.searchsorted(self.bounds, cl, side="right") - 1
        return np.where(known, sid, -1)

    # -- ingestion ----------------------------------------------------------

    def ingest(self, user_ids: np.ndarray, item_ids: np.ndarray,
               timestamps: np.ndarray) -> None:
        """Sort the batch by time once, split by owning shard, scatter.
        Per-shard ingests skip their own sort (``_presorted``).  The
        ``serving.ingest`` span is held here; each shard's children
        carry its ``shard``."""
        user_ids = np.asarray(user_ids, np.int64).ravel()
        with self.tel.hot_span("serving.ingest",
                               events=int(user_ids.size)) as sp:
            self._ingest(user_ids, item_ids, timestamps, sp)

    def _ingest(self, user_ids: np.ndarray, item_ids: np.ndarray,
                timestamps: np.ndarray, sp) -> None:
        tel = self.tel
        item_ids = np.asarray(item_ids, np.int64).ravel()
        ts64 = np.asarray(timestamps, np.float64).ravel()
        with tel.hot_span("serving.ingest.lookup"):
            cl, known = self.clusters_of(user_ids)
        if not known.all():
            if self.tel.enabled:
                self.tel.counter("serving.unknown_user_events",
                                 float((~known).sum()))
            user_ids = user_ids[known]
            item_ids = item_ids[known]
            ts64 = ts64[known]
            cl = cl[known]
        if cl.size == 0:
            return
        with self.write_lock:
            if self.epoch is None:
                # fix the global epoch before ANY shard ingests so every
                # shard stores identical relative timestamps
                self.epoch = float(ts64.min())
                for sh in self.shards:
                    with sh.write_lock:
                        sh.epoch = self.epoch
            # sort by the same f32 relative key the unsharded store uses
            # (stable), so per-shard ring order is bitwise-identical
            with tel.hot_span("serving.ingest.prep"):
                rel = (ts64 - self.epoch).astype(np.float32)
                order = np.argsort(rel, kind="stable")
                user_ids, item_ids = user_ids[order], item_ids[order]
                ts64, cl = ts64[order], cl[order]
                sid = np.searchsorted(self.bounds, cl, side="right") - 1
            for s, sh in enumerate(self.shards):
                m = sid == s
                if m.any():
                    sh.ingest(user_ids[m], item_ids[m], ts64[m],
                              _presorted=True)
        if tel.enabled:
            ucl = np.unique(cl)
            sp.set("clusters", int(ucl.size))
            tel.counter("serving.ingest_events", float(cl.size))
            fill = np.minimum(self.cursor[ucl], self.queue_len)
            tel.gauge("serving.queue_depth_max", float(fill.max()))
            tel.gauge("serving.queue_depth_mean", float(fill.mean()))

    # -- retrieval ----------------------------------------------------------

    def rel_cutoff(self, now: float) -> float:
        return now - self.recency_s - (self.epoch or 0.0)

    def retrieve_batch(self, user_ids: np.ndarray, now: float,
                       k: int) -> np.ndarray:
        """Route each request to its owning shard, gather, merge back in
        request order.  Unknown users get ``-1`` rows without touching
        any shard."""
        tel = self.tel
        t0 = tel.clock.perf() if tel.enabled else 0.0
        user_ids = np.asarray(user_ids, np.int64).ravel()
        cl, known = self.clusters_of(user_ids)
        sid = self._shard_of(cl, known)
        out = np.full((user_ids.size, int(k)), -1, np.int64)
        for s, sh in enumerate(self.shards):
            m = sid == s
            if m.any():
                out[m] = sh.retrieve_batch(user_ids[m], now, k)
        if tel.enabled:
            tel.observe("serving.retrieve_latency_s", tel.clock.perf() - t0)
            tel.counter("serving.retrieve_requests")
            if not known.all():
                tel.counter("serving.unknown_user_requests",
                            float((~known).sum()))
        return out

    def retrieve(self, user_id: int, now: float, k: int) -> List[int]:
        row = self.retrieve_batch(np.array([user_id]), now, k)[0]
        return [int(i) for i in row if i >= 0]

    def serve_batch(self, user_ids: np.ndarray, now: float, *,
                    n_recent: int = 8, k: int = 32,
                    i2i: Optional[np.ndarray] = None,
                    use_kernel: bool = False
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Scatter the serve pass across shards and merge both outputs,
        under the ``serving.serve_batch`` span (the shards' spans are its
        children)."""
        tel = self.tel
        user_ids = np.asarray(user_ids, np.int64).ravel()
        with tel.hot_span("serving.serve_batch",
                          requests=int(user_ids.size)) as sp:
            cl, known = self.clusters_of(user_ids)
            sid = self._shard_of(cl, known)
            seeds = np.full((user_ids.size, int(n_recent)), -1, np.int64)
            union = np.full((user_ids.size, int(k)), -1, np.int64)
            for s, sh in enumerate(self.shards):
                m = sid == s
                if m.any():
                    s_out, u_out = sh.serve_batch(user_ids[m], now,
                                                  n_recent=n_recent, k=k,
                                                  i2i=i2i,
                                                  use_kernel=use_kernel)
                    seeds[m] = s_out
                    union[m] = u_out
        if tel.enabled:
            tel.observe("serving.retrieve_latency_s", sp.duration_s)
            tel.counter("serving.retrieve_requests")
            if i2i is not None and not use_kernel:
                tel.counter("serving.serve_calls")
            if not known.all():
                tel.counter("serving.unknown_user_requests",
                            float((~known).sum()))
        return seeds, union

    # -- introspection ------------------------------------------------------

    @property
    def cursor(self) -> np.ndarray:
        """Global per-cluster write counts (shard ranges are contiguous,
        so shard cursors concatenate into the global table)."""
        return np.concatenate(
            [sh._cursor_host[:hi - lo]
             for sh, (lo, hi) in zip(self.shards, self._spans)])

    @property
    def items(self) -> np.ndarray:
        return np.concatenate(
            [sh.items[:hi - lo]
             for sh, (lo, hi) in zip(self.shards, self._spans)], axis=0)

    @property
    def times(self) -> np.ndarray:
        return np.concatenate(
            [sh.times[:hi - lo]
             for sh, (lo, hi) in zip(self.shards, self._spans)], axis=0)

    def partitions(self) -> Tuple[ClusterQueueStore, ...]:
        return self.shards

    def stats(self) -> Dict[str, float]:
        fill = np.minimum(self.cursor, self.queue_len)
        active = fill > 0
        out = dict(n_shards=self.n_shards,
                   n_clusters_active=int(active.sum()),
                   mean_queue=float(fill[active].mean())
                   if active.any() else 0.0,
                   delta_pending=float(sum(sh.d_count
                                           for sh in self.shards)))
        for s, sh in enumerate(self.shards):
            for key, v in sh.stats().items():
                if key != "n_shards":
                    out[f"shard{s}.{key}"] = v
        return out


# ---------------------------------------------------------------------------
# offline I2I KNN (U2I2I)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _topk_scorer(kk: int, exclude_self: bool):
    """Jitted chunk scorer: cosine top-k against the full item set with
    the diagonal masked.  One compile per (k, exclude_self); chunk rows
    are padded to a fixed shape so every chunk hits the same trace."""

    @jax.jit
    def score(chunk_e, all_e, row0):
        sims = chunk_e @ all_e.T                             # (C, n)
        if exclude_self:
            cols = jnp.arange(sims.shape[1])[None, :]
            own = row0 + jnp.arange(sims.shape[0])[:, None]
            sims = jnp.where(cols == own, -jnp.inf, sims)
        _, idx = jax.lax.top_k(sims, kk)
        return idx

    return score


def build_i2i_knn(item_emb: np.ndarray, k: int, *, chunk: int = 2048,
                  exclude_self: bool = True) -> np.ndarray:
    """(n_items, k) most-similar items by cosine; computed offline after
    each embedding refresh (cheap: item embeddings update infrequently).
    The chunk loop runs a single jitted top-k scorer — no per-row numpy
    argpartition/argsort passes."""
    e = item_emb / np.maximum(
        np.linalg.norm(item_emb, axis=1, keepdims=True), 1e-8)
    e = e.astype(np.float32)
    n = len(e)
    kk = min(k, n - 1)
    if kk <= 0:      # 0- or 1-item corpus: no neighbors exist at all
        return np.full((n, k), -1, np.int64)
    chunk = min(chunk, n)
    score = _topk_scorer(kk, exclude_self)
    out = np.empty((n, kk), np.int64)
    for lo in range(0, n, chunk):
        hi = min(n, lo + chunk)
        rows = e[lo:hi]
        if hi - lo < chunk:                      # pad: keep one traced shape
            rows = np.pad(rows, ((0, chunk - (hi - lo)), (0, 0)))
        out[lo:hi] = np.asarray(score(rows, e, lo))[: hi - lo]
    if kk < k:
        out = np.pad(out, ((0, 0), (0, k - kk)), constant_values=-1)
    return out


def u2i2i_retrieve_batch(i2i: np.ndarray, recent_items: np.ndarray,
                         k: int) -> np.ndarray:
    """Batched U2I2I: union the similar-item lists of each row's recent
    items ``(B, R)`` (``-1`` = padding), round-robin across ranks to
    preserve per-seed ordering, mask the seeds themselves, dedup, and
    return ``(B, k)`` ``-1``-padded candidates."""
    recent = np.asarray(recent_items, np.int64)
    B, R = recent.shape
    K = i2i.shape[1]
    nonneg = recent >= 0
    # seeds past the end of the table contribute no neighbors (queues see
    # brand-new items before the next offline I2I refresh covers them)
    seeded = nonneg & (recent < i2i.shape[0])
    cand = np.asarray(i2i, np.int32)[np.where(seeded, recent, 0)]  # (B,R,K)
    cand = np.where(seeded[:, :, None], cand, -1)
    flat = cand.reshape(B, R * K)                        # seed-major layout
    # round-robin emission priority of the seed per-request loop (rank 0
    # of every seed, then rank 1, ...) as a per-column key — no need to
    # physically transpose into rank-major order
    col = np.arange(R * K, dtype=np.int32)
    prio = (col % K) * R + col // K
    # every non-negative seed is masked from the union, including ones
    # the table does not cover (a candidate may still equal them)
    seen = (flat[:, :, None] ==
            np.where(nonneg, recent, -2)[:, None, :]).any(axis=2)
    valid = (flat >= 0) & ~seen
    return dedup_topk_rows(flat, prio[None, :], valid, k, R * K)


def u2i2i_retrieve(i2i: np.ndarray, recent_items: Sequence[int],
                   k: int) -> List[int]:
    """Legacy single-request U2I2I — a batch of one."""
    recent = np.asarray(list(recent_items), np.int64).reshape(1, -1)
    if recent.size == 0:
        return []
    row = u2i2i_retrieve_batch(i2i, recent, k)[0]
    return [int(i) for i in row if i >= 0]


# ---------------------------------------------------------------------------
# serving cost model (the 83% claim)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServingCostModel:
    """Per-request compute/memory cost of U2U2I serving strategies.

    Online KNN: every request scores the query user against the active
    pool (exact or IVF-style approximate with n_probe fraction scanned).
    Cluster index: assign-once per embedding refresh (amortized ~0) +
    O(1) queue read per request.  ``batch_size`` models the batched
    engine: per-launch fixed costs (cursor/metadata reads, dispatch) are
    amortized across the request batch.  ``n_shards`` models the sharded
    router: the single-dispatch retrieve becomes one dispatch per shard
    touched by the batch, so launch overheads scale with the shard
    count while per-request work does not.
    """
    d: int = 256
    active_pool: int = 5_000_000       # recently-active users (15 min)
    qps: float = 1e6
    n_probe_frac: float = 0.05         # ANN scans ~5% of the pool
    queue_read_items: int = 64
    rq_codes: Tuple[int, ...] = (5000, 50)
    batch_size: int = 1
    n_shards: int = 1
    launch_bytes: float = 64 * 1024.0  # per-launch metadata + dispatch
    launch_flops: float = 4 * 1024.0

    def _batch(self, batch_size: Optional[int]) -> int:
        return max(int(batch_size if batch_size is not None
                       else self.batch_size), 1)

    def knn_flops_per_req(self, exact: bool = False) -> float:
        frac = 1.0 if exact else self.n_probe_frac
        return 2.0 * self.d * self.active_pool * frac

    def knn_bytes_per_req(self, exact: bool = False) -> float:
        frac = 1.0 if exact else self.n_probe_frac
        return 4.0 * self.d * self.active_pool * frac

    def cluster_flops_per_req(self, batch_size: Optional[int] = None
                              ) -> float:
        # queue read: no dot products at request time; assignment cost is
        # amortized into the embedding-refresh batch job:
        assign = 2.0 * self.d * sum(self.rq_codes)      # per refresh
        refresh_period_s = 3 * 3600.0
        amortized = assign / max(self.qps * refresh_period_s /
                                 max(self.active_pool, 1), 1e-9)
        return amortized + (max(self.n_shards, 1) * self.launch_flops
                            / self._batch(batch_size))

    def cluster_bytes_per_req(self, batch_size: Optional[int] = None
                              ) -> float:
        # queue read + code read per request; launch cost (one dispatch
        # per shard) amortized over the batch served per dispatch
        return (8.0 * self.queue_read_items + 8.0
                + (max(self.n_shards, 1) * self.launch_bytes
                   / self._batch(batch_size)))

    def cost_reduction(self, batch_size: Optional[int] = None) -> float:
        """Fractional serving-cost reduction (bytes+flops weighted by a
        machine-cost proxy: memory-bandwidth bound at serving tier)."""
        knn = self.knn_bytes_per_req()
        cl = self.cluster_bytes_per_req(batch_size)
        return 1.0 - cl / max(knn, 1e-9)
