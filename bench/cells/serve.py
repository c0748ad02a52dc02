"""Serving cells: open-loop U2U2I + U2I2I requests against a
``SwapServer`` whose rings were filled from a seeded engagement history,
with or without a live engagement stream.

Traffic parameters (``bench/traffic/<name>.json``):

``read_rate``     requests per second, open loop;
``readers``       reader threads; each takes every due request, up to
                  ``max_batch``, into one ``serve_batch`` call;
``write_rate``    engagement events per second (0: no writes);
``max_ingest``    the most events one ``ingest`` call takes;
``check_sample``  requests compared with the reference after the window.

The writer ingests every due event as soon as its previous ingest is
ready on the device.  Latency is timed from each request's (event's)
due time to the return of the call that answered it (to the moment the
store state holding it is ready on the device).
"""
from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict, List, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import traffic as T                                   # noqa: E402
import work as W                                      # noqa: E402
from reference import serve_ref as REF                # noqa: E402

T_BASE = 1.7e9          # absolute unix-like seconds of the first event


def _steal_s() -> float:
    """Seconds of CPU that the hypervisor took from this machine, summed
    over its CPUs (``/proc/stat``; 0 where that cannot be read)."""
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return 0.0


def _bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class Cell:
    """One serving cell: ``setup``, ``measure``, ``release``, ``check``."""

    programs = ("_serve_jit", "_direct_ingest_jit")

    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, run, devices):
        self.cfg, self.tr, self.seed, self.run = cfg, traffic, seed, run
        self.devices = devices
        self.log = REF.EventLog()
        self.calls_started = 0           # ingest calls begun / ready
        self.calls_done = 0
        self.server = None
        self.stall_dump = None           # file for stacks at each stall

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        import jax
        from repro.lifecycle.snapshot import IndexSnapshot
        from repro.lifecycle.swap import SwapServer
        cfg = self.cfg
        t = time.perf_counter()
        C, nu, ni = cfg["n_clusters"], cfg["n_users"], cfg["n_items"]
        self.key = jax.random.key(self.seed)
        k_pop, k_items, k_i2i = (jax.random.fold_in(self.key, (1 << 29) + j)
                                 for j in range(3))
        pop = T.device_population(k_pop, nu, C, user_zipf=cfg["user_zipf"],
                                  cluster_zipf=cfg["cluster_zipf"])
        self.user_of_rank = pop["user_of_rank"]
        cl = np.asarray(pop["clusters"])
        self.user_clusters = cl.astype(np.int64)
        sizes = tuple(cfg["codebook_sizes"])
        codes = np.stack([cl // sizes[1], cl % sizes[1]], 1)
        del cl
        t = self.run.phase("population", t)
        self.i2i = T.device_i2i(k_i2i, ni, cfg["i2i_k"], cfg["i2i_zipf"])
        t = self.run.phase("I2I table", t)
        snap = IndexSnapshot(
            user_codes=codes, item_codes=np.zeros((ni, 2), np.int32),
            user_clusters=self.user_clusters,
            member_ptr=np.asarray(pop["member_ptr"]).astype(np.int64),
            member_ids=np.asarray(pop["member_ids"]).astype(np.int64),
            coarse_codebook=np.zeros((sizes[0], cfg["d_embed"]),
                                     np.float32),
            i2i=self.i2i, version=1, n_users=nu, n_items=ni,
            codebook_sizes=sizes)
        self.server = SwapServer(snap, queue_len=cfg["queue_len"],
                                 recency_s=cfg["recency_s"],
                                 ring_capacity=cfg["ring_capacity"])
        self.events = T.device_event_fn(
            pop, jax.random.permutation(k_items, ni).astype(np.int32),
            item_zipf=cfg["item_zipf"], n=cfg["ring_capacity"])
        del pop
        t = self.run.phase("server", t)
        if self.tr["write_rate"] > 0:
            self._warm_ingest(T.rng_for(self.seed, 1))
            t = self.run.phase("ingest warm-up", t)
        self._fill_history()
        if self.tr["write_rate"] <= 0:
            self.events = None           # the window draws no events
        t = self.run.phase("history fill", t)
        self.server.serve_batch(np.zeros(1, np.int64), self.t_end,
                                n_recent=cfg["n_recent"], k=cfg["k"])
        t = self.run.phase("first serve call (uploads the I2I table)", t)
        self._warm_serve(T.rng_for(self.seed, 4))
        T.device_zipf_keys(self.key, self.user_of_rank, cfg["user_zipf"], 1)
        self.run.phase("serve warm-up", t)

    def _ingest(self, users: np.ndarray, items: np.ndarray,
                ts: np.ndarray, wait: bool = True) -> None:
        """One logged ``SwapServer.ingest`` call, waited for on the
        device unless set-up goes straight on to the next."""
        self.log.append(self.user_clusters[users], items, ts)
        self.calls_started += 1
        self.server.ingest(users, items, ts)
        if wait:
            self._wait_store()
        self.calls_done += 1

    def _wait_store(self) -> None:
        """Block until the live store's state is ready on the device (the
        store keeps it as a pytree of device arrays in ``_state``)."""
        import jax
        store = self.server.handle.acquire().store
        jax.block_until_ready(getattr(store, "_state", None))

    def _warm_ingest(self, rng) -> None:
        """Compile the ingest programs for every batch size the stream
        can produce: pow2 event buckets, each with the cluster-count
        buckets a Zipf batch of that size reaches.  The events are older
        than the history (and logged for the reference)."""
        cap = int(self.tr["max_ingest"])
        ucl = self.user_clusters
        E = 8
        while E <= cap:
            for n_cl in sorted({_bucket(E), max(_bucket(E) // 2, 8)}):
                n_cl = min(n_cl, E)
                users = rng.integers(0, len(ucl), n_cl)
                users = users[np.unique(ucl[users], return_index=True)[1]]
                users = np.resize(users, E)
                items = rng.integers(0, self.cfg["n_items"], E)
                ts = T_BASE - 10.0 + rng.random(E)
                self._ingest(users, items, ts)
            E *= 2

    def _fill_history(self) -> None:
        """Replay the seeded history, spanning ``history_span_s`` before
        the window, in calls of ``ring_capacity`` events."""
        import jax
        cfg = self.cfg
        n_calls = cfg["history_events"] // cfg["ring_capacity"]
        span = cfg["history_span_s"] / n_calls
        for b in range(n_calls):
            u, i, o = self.events(jax.random.fold_in(self.key, b), span)
            ts = T_BASE + b * span + np.asarray(o, np.float64)
            self._ingest(np.asarray(u, np.int64), np.asarray(i, np.int64),
                         ts, wait=False)
        self._wait_store()
        self.t_end = T_BASE + cfg["history_span_s"]

    def _warm_serve(self, rng) -> None:
        """Compile the serve program for each cluster-count bucket a batch
        of up to ``max_batch`` requests reaches."""
        ucl = self.user_clusters
        top = _bucket(int(self.tr["max_batch"]))
        n = 8
        while n <= top:
            users = rng.integers(0, len(ucl), 4 * n)
            users = users[np.unique(ucl[users], return_index=True)[1]][:n]
            self.server.serve_batch(users, self.t_end,
                                    n_recent=self.cfg["n_recent"],
                                    k=self.cfg["k"])
            n *= 2

    # -- the measured window ----------------------------------------------

    def measure(self, seconds: float) -> None:
        import jax
        tr, cfg = self.tr, self.cfg
        rng = T.rng_for(self.seed, 2)
        due = T.arrivals(tr["read_rate"], seconds, rng)
        users = T.device_zipf_keys(
            jax.random.fold_in(self.key, (1 << 29) + 3), self.user_of_rank,
            cfg["user_zipf"], len(due)).astype(np.int64)
        R = len(due)
        self.req_users = users
        self.req_due = due
        self.lat = np.full(R, np.nan)
        self.picked = np.full(R, np.nan)
        self.seeds = np.full((R, cfg["n_recent"]), -2, np.int64)
        self.union = np.full((R, cfg["k"]), -2, np.int64)
        self.req_now = np.zeros(R)
        self.req_lo = np.zeros(R, np.int64)
        self.req_hi = np.zeros(R, np.int64)
        self.calls: List[Tuple[int, int]] = []
        self._next = 0
        self._lock = threading.Lock()
        wdue = (T.arrivals(tr["write_rate"], seconds, rng)
                if tr["write_rate"] > 0 else np.zeros(0))
        self.ev_due = wdue
        self.ev_lag = np.full(len(wdue), np.nan)
        self.ingest_calls: List[Tuple[int, int]] = []
        if len(wdue):
            reps = -(-len(wdue) // int(self.cfg["ring_capacity"]))
            keys = [jax.random.fold_in(self.key, (1 << 30) + r)
                    for r in range(reps)]
            parts = [self.events(k, 1.0) for k in keys]
            self.ev_users = np.concatenate(
                [np.asarray(p[0], np.int64) for p in parts])[:len(wdue)]
            self.ev_items = np.concatenate(
                [np.asarray(p[1], np.int64) for p in parts])[:len(wdue)]
        errors: List[BaseException] = []

        def guard(fn):
            def body():
                try:
                    fn()
                except BaseException as e:          # reported after join
                    errors.append(e)
            return body

        threads = [threading.Thread(target=guard(self._reader))
                   for _ in range(int(tr["readers"]))]
        self._stalls: List[Dict[str, float]] = []
        threads.append(threading.Thread(target=guard(self._watchdog),
                                        daemon=True))
        if len(wdue):
            threads.append(threading.Thread(target=guard(self._writer)))
        with jax.profiler.TraceAnnotation("bench.window"):
            self.t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            t1 = time.perf_counter()
        if errors:
            raise errors[0]
        self.run.window_s = t1 - self.t0
        worst = sorted(self._stalls, key=lambda x: -x["stall_s"])[:5]
        print(f"process stalls over 20 ms: {len(self._stalls)}, longest: "
              f"{worst}", file=sys.stderr)
        spans = self.run.spans.get("serve_batch", [])
        if spans:
            print(f"longest serve_batch calls (s): "
                  f"{sorted(spans)[-5:]}", file=sys.stderr)
        self.run.samples["serve_latency_s"] = self.lat
        self.run.samples["process_stall_s"] = np.array(
            [x["stall_s"] for x in self._stalls])
        self.run.samples["serve_pick_lag_s"] = self.picked
        self.run.counts["requests"] = R
        if len(wdue):
            self.run.samples["ingest_lag_s"] = self.ev_lag
            self.run.counts["events"] = len(wdue)

    def _watchdog(self) -> None:
        """Note every wake-up of a 5 ms sleep that came more than 20 ms
        late: a stall of the whole process (interpreter lock, host CPU),
        told apart from a slow call.  For each stall it keeps what the
        process and the host did meanwhile: the CPU seconds of all the
        process's threads, its page faults and involuntary context
        switches, and the host's steal time (CPU taken from this machine
        by its hypervisor), read every 20 ms.  With ``stall_dump`` (an
        open file) the stacks of every thread are written there whenever
        this thread oversleeps by 50 ms, by the interpreter's own timer
        thread, which needs no interpreter lock."""
        import faulthandler
        import resource
        end = self.req_due[-1] if len(self.req_due) else 0.0
        if len(self.ev_due):
            end = max(end, self.ev_due[-1])
        dump = self.stall_dump

        def usage():
            r = resource.getrusage(resource.RUSAGE_SELF)
            return (r.ru_utime + r.ru_stime, r.ru_minflt, r.ru_majflt,
                    r.ru_nivcsw)

        t, u, steal, n = time.perf_counter(), usage(), _steal_s(), 0
        while t - self.t0 < end:
            if dump is not None:
                faulthandler.dump_traceback_later(0.055, file=dump)
            time.sleep(0.005)
            t2, u2 = time.perf_counter(), usage()
            n += 1
            if t2 - t > 0.025:
                steal2 = _steal_s()
                self._stalls.append(dict(
                    at_s=t - self.t0, stall_s=t2 - t - 0.005,
                    cpu_s=u2[0] - u[0], minflt=u2[1] - u[1],
                    majflt=u2[2] - u[2], nivcsw=u2[3] - u[3],
                    host_steal_s=steal2 - steal))
                steal = steal2
            elif n % 4 == 0:
                steal = _steal_s()
            t, u = t2, u2
        if dump is not None:
            faulthandler.cancel_dump_traceback_later()

    def _reader(self) -> None:
        cfg, due = self.cfg, self.req_due
        R, cap = len(due), int(self.tr["max_batch"])
        while True:
            with self._lock:
                if self._next >= R:
                    return
                t = time.perf_counter() - self.t0
                lo = self._next
                wait = due[lo] - t
                if wait <= 0:
                    hi = min(int(np.searchsorted(due, t, side="right")),
                             lo + cap)
                    self._next = hi
            if wait > 0:
                time.sleep(min(wait, 0.002))
                continue
            self.picked[lo:hi] = t - due[lo:hi]
            now = self.t_end + (time.perf_counter() - self.t0)
            first = self.calls_done
            with self.run.span("serve_batch"):
                s, u, _ = self.server.serve_batch(
                    self.req_users[lo:hi], now, n_recent=cfg["n_recent"],
                    k=cfg["k"])
            t_ret = time.perf_counter() - self.t0
            self.lat[lo:hi] = t_ret - due[lo:hi]
            self.seeds[lo:hi], self.union[lo:hi] = s, u
            self.req_now[lo:hi] = now
            self.req_lo[lo:hi] = first
            self.req_hi[lo:hi] = self.calls_started
            with self._lock:
                self.calls.append((lo, hi))

    def _writer(self) -> None:
        due, cap = self.ev_due, int(self.tr["max_ingest"])
        E, nxt = len(due), 0
        while nxt < E:
            t = time.perf_counter() - self.t0
            if due[nxt] > t:
                time.sleep(min(due[nxt] - t, 0.002))
                continue
            hi = min(int(np.searchsorted(due, t, side="right")), nxt + cap)
            sl = slice(nxt, hi)
            with self.run.span("ingest"):
                self._ingest(self.ev_users[sl], self.ev_items[sl],
                             self.t_end + due[sl])
            self.ev_lag[sl] = time.perf_counter() - self.t0 - due[sl]
            self.ingest_calls.append((nxt, hi))
            nxt = hi

    # -- after the window -------------------------------------------------

    def failed(self) -> int:
        """Events the server dropped or shed, and requests never
        answered."""
        dropped = int(self.server.ring_dropped) if self.server else 0
        return dropped + int(np.isnan(self.lat).sum())

    def record_work(self) -> None:
        cfg = self.cfg
        ucl = self.user_clusters
        tot = {"flops": 0.0, "bytes": 0.0}
        for lo, hi in self.calls:
            w = W.serve_call(len(np.unique(ucl[self.req_users[lo:hi]])),
                             n_recent=cfg["n_recent"], k=cfg["k"],
                             k_i2i=cfg["i2i_k"])
            for key in tot:
                tot[key] += w[key]
        self.run.work["_serve_jit"] = tot
        if len(self.ev_due):
            tot = {"flops": 0.0, "bytes": 0.0}
            for lo, hi in self.ingest_calls:
                w = W.ingest_call(hi - lo, len(np.unique(
                    ucl[self.ev_users[lo:hi]])))
                for key in tot:
                    tot[key] += w[key]
            self.run.work["_direct_ingest_jit"] = tot

    def release(self) -> None:
        self.record_work()
        self.server = None
        self.events = None

    def check(self, control: bool = False) -> List[Tuple[str, float, float]]:
        """Compare a seeded sample of the answered requests with the
        reference; with ``control`` the reference that keeps duplicate
        items stands in the program's place."""
        cfg = self.cfg
        R = len(self.req_due)
        rng = T.rng_for(self.seed, 3)
        n = min(int(self.tr["check_sample"]), R)
        rows = np.sort(rng.choice(R, n, replace=False))
        ends_all = [0] + list(self.log.call_end)
        flat_rows, flat_ends = [], []
        for r in rows:
            for j in range(int(self.req_lo[r]), int(self.req_hi[r]) + 1):
                flat_rows.append(r)
                flat_ends.append(ends_all[j])
        flat_rows = np.asarray(flat_rows)
        kw = dict(queue_len=cfg["queue_len"], recency_s=cfg["recency_s"],
                  i2i=self.i2i, n_recent=cfg["n_recent"], k=cfg["k"])
        cl = self.user_clusters[self.req_users[flat_rows]]
        now = self.req_now[flat_rows]
        ref_s, ref_u = REF.answers(self.log, cl, flat_ends, now, **kw)
        if control:
            got_s, got_u = REF.answers(self.log, cl, flat_ends, now,
                                       drop_duplicates=False, **kw)
        else:
            got_s, got_u = self.seeds[flat_rows], self.union[flat_rows]
        match = ((ref_s == got_s).all(1) & (ref_u == got_u).all(1))
        ok = np.zeros(R, bool)
        np.logical_or.at(ok, flat_rows, match)
        bad = int((~ok[rows]).sum())
        unanswered = int(np.isnan(self.lat).sum())
        return [("rows_wrong", float(bad), 0.0),
                ("rows_unanswered", float(unanswered), 0.0)]
