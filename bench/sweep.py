#!/usr/bin/env python3
"""Find a serving cell's knee once: one set-up, then one window per rate.

    python3 bench/sweep.py --workload serve-read --seed 7 --seconds 10 \
        --rates 1000 2000 4000 [--refine 3] [--hold 4 --hold-seconds 50] \
        [--write-rates 1e5 4e5]

Each window reports the latency percentiles, and how far the last
requests lagged behind their due times (a growing backlog).
``--refine`` then bisects between the highest request rate sustained and
the lowest one not sustained that many times.  ``--hold`` then runs that
many windows at four fifths of the highest rate sustained, the rate a
cell runs at, and reports each window's percentiles over its whole
length and over its first ``--head-seconds``, with the process stalls the
window met (``--stall-dump`` writes every thread's stack at each).  With
``--write-rates`` it first sweeps engagement events per second with no
requests, then sweeps the request rates with half the highest write
rate sustained: the two sweeps a mixed cell's rates come from.  The rates a
cell runs at are then fixed in its traffic file.
"""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import numpy as np                                           # noqa: E402

import harness as H                                          # noqa: E402


def report(what: str, lat, window_s: float) -> bool:
    """Print one window's latencies; True where the backlog did not grow
    (the last tenth of the window waited no longer than the first)."""
    lat = np.asarray(lat)
    n = max(len(lat) // 10, 1)
    first, last = np.median(lat[:n]), np.median(lat[-n:])
    ok = bool(last <= 2 * first + 0.005 and H.nearest_rank(lat, 99) < 1.0)
    pct = " ".join(f"p{q}_ms={H.nearest_rank(lat, q) * 1e3:.3f}"
                   for q in (50, 95, 99))
    print(f"{what}: n={len(lat)} {pct} first_tenth_ms="
          f"{first * 1e3:.3f} last_tenth_ms={last * 1e3:.3f} "
          f"window_s={window_s:.3f} sustained={ok}", flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--refine", type=int, default=0)
    ap.add_argument("--hold", type=int, default=0)
    ap.add_argument("--hold-seconds", type=float, default=0.0)
    ap.add_argument("--head-seconds", type=float, default=30.0)
    ap.add_argument("--stall-dump", default=None,
                    help="file for every thread's stack at each stall of "
                         "the --hold windows")
    ap.add_argument("--write-rates", type=float, nargs="*", default=[],
                    help="first sweep these event rates with no reads, "
                         "then sweep --rates with half the highest one "
                         "sustained")
    args = ap.parse_args(argv)
    entry = H.workload(H.load_benchmark(), args.workload)
    cfg, tr = H.load_config(entry["config"]), H.load_traffic(entry["traffic"])
    devices = H.require_accelerator(int(entry["chips"]))
    from repro.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run = H.RunRecord(args.workload, args.seed, args.seconds, False)
    cell = H.load_driver(tr["driver"]).Cell(cfg, tr, args.seed, run,
                                            devices)
    cell.setup()
    print(f"after set-up: {devices[0].memory_stats()}", flush=True)
    best = 0.0
    for rate in args.write_rates:
        cell.tr = dict(tr, write_rate=rate, read_rate=0)
        cell.measure(args.seconds)
        ok = report(f"writes {rate:g}", run.samples["ingest_lag_s"],
                    run.window_s)
        if ok:
            best = max(best, rate)
    write_rate = best / 2 if args.write_rates else tr["write_rate"]
    if args.write_rates:
        print(f"highest sustained write rate {best:g}; reads are swept "
              f"with {write_rate:g} events/s", flush=True)
    ok_rates, bad_rates = [], []

    def reads(rate: float, seconds: float) -> None:
        cell.tr = dict(tr, read_rate=rate, write_rate=write_rate)
        cell.measure(seconds)
        ok = report(f"reads {rate:g}", run.samples["serve_latency_s"],
                    run.window_s)
        (ok_rates if ok else bad_rates).append(rate)

    for rate in args.rates:
        reads(rate, args.seconds)
    for _ in range(args.refine):
        lo = max(ok_rates, default=0.0)
        hi = min((r for r in bad_rates if r > lo), default=None)
        if hi is None:
            break
        reads(round((lo + hi) / 2), args.seconds)
    knee = max(ok_rates, default=0.0)
    print(f"highest sustained read rate {knee:g} (not sustained: "
          f"{sorted(r for r in bad_rates if r > knee)})", flush=True)
    if args.hold:
        rate = round(0.8 * knee)
        dump = open(args.stall_dump, "w") if args.stall_dump else None
        cell.stall_dump = dump
        for i in range(args.hold):
            cell.tr = dict(tr, read_rate=rate, write_rate=write_rate)
            cell.measure(args.hold_seconds or args.seconds)
            lat = run.samples["serve_latency_s"]
            head = lat[cell.req_due < args.head_seconds]
            ms = lambda v, q: H.nearest_rank(v, q) * 1e3
            print(f"hold {i} at {rate:g}/s: whole p50_ms={ms(lat, 50)!r} "
                  f"p99_ms={ms(lat, 99)!r}; first {args.head_seconds:g} s "
                  f"p50_ms={ms(head, 50)!r} p99_ms={ms(head, 99)!r}; stalls "
                  f"{sorted(cell._stalls, key=lambda x: -x['stall_s'])[:4]}",
                  flush=True)
        if dump is not None:
            dump.close()
    print(f"at the end: {devices[0].memory_stats()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
