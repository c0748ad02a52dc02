#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (data and weights from the seed, the program's state, every shape
the window uses compiled or loaded from the persistent compilation cache
in ``<checkout>/.jax_cache``) is reported as ``setup_s``.  The window
then runs for ``--seconds``; with ``--trace 1`` it runs for at most
``TRACE_SECONDS`` under the profiler, and the run reports the cell's
per-layer metrics instead of its end-to-end ones.  After the window the
program's state is freed and what the window produced is compared with
the plain reference.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last the numbers compared, each with its
limit).  The run exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                          # noqa: E402
import gc                                                # noqa: E402
import os                                                # noqa: E402
import shutil                                            # noqa: E402
import sys                                               # noqa: E402
import tempfile                                          # noqa: E402
from typing import Any, Dict, List, Optional, Sequence   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# A traced second of serve-read costs about 3 s of the profiler's
# writing and reading on a one-chip TPU v5e host: a 51 s traced window
# took a run to 407-411 s, and the device buffer dropped 13.6 s of it.
TRACE_SECONDS = 15.0
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np                                       # noqa: E402

import harness as H                                      # noqa: E402


def parse(argv: Optional[Sequence[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(args, *, devices=None, config: Optional[Dict] = None,
             traffic: Optional[Dict] = None, t_start: float = T_START,
             control: bool = False, use_cache: bool = True,
             out=sys.stdout) -> int:
    """One run of one cell.  ``devices``, ``config`` and ``traffic``
    replace the accelerator check and the cell's files (the CPU tests
    drive the rest of a run this way, also of a mix no cell runs yet)."""
    bench = H.load_benchmark()
    if config is None or traffic is None or devices is None:
        entry = H.workload(bench, args.workload)
        cfg = H.load_config(entry["config"])
        tr = H.load_traffic(entry["traffic"])
    cfg = config if config is not None else cfg
    tr = traffic if traffic is not None else tr
    if devices is None:
        try:
            devices = H.require_accelerator(int(entry["chips"]))
        except H.NoAccelerator as e:
            H.log(f"refused: {e}")
            return 2
    import jax
    cache = None
    if use_cache:
        from repro.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = H.CompileCounter()
    driver = H.load_driver(tr["driver"])
    run = H.RunRecord(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    run.device_kind = devices[0].device_kind
    cell = driver.Cell(cfg, tr, args.seed, run, devices)
    cell.setup()
    run.setup_s = time.perf_counter() - t_start
    low0, comp0 = counter.totals()
    run.spans.clear()
    pauses = H.GcPauses()
    trace_dir = None
    if args.trace:
        from devtrace import profiler_options
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=profiler_options())
    try:
        cell.measure(min(args.seconds, TRACE_SECONDS) if args.trace
                     else args.seconds)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    low1, comp1 = counter.totals()
    pauses.stop()
    device = H.device_record(devices)
    failed = cell.failed()
    breakdown = None
    if trace_dir:
        from devtrace import find_xplane, load_planes, reduce_planes
        planes = load_planes(find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace = reduce_planes(planes, cell.programs)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        breakdown = run.trace["breakdown"]
    t_ref = time.perf_counter()
    cell.release()
    gc.collect()
    checks = cell.check(control=control)
    H.log(f"reference check: {time.perf_counter() - t_ref!r} s")
    metrics: Dict[str, Any] = {}
    for m in H.metrics_for(bench, args.workload, bool(args.trace)):
        v = H.metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = (v, m["unit"])
    H.log(f"device: {device}; compile cache {cache}")
    H.log(f"setup_s {run.setup_s!r}; window_s {run.window_s!r}")
    H.log(f"in the window: {low1 - low0} programs lowered, "
          f"{comp1 - comp0} compiled")
    H.log(f"peak_bytes_in_use {device['memory_peak_bytes']}")
    H.log(f"garbage collections in the window: {pauses.summary()}")
    for name, v in sorted(run.samples.items()):
        v = np.asarray(v, np.float64)
        v = v[np.isfinite(v)]
        if v.size:
            H.log(f"{name}: n={v.size} p50={H.nearest_rank(v, 50)!r} "
                  f"p99={H.nearest_rank(v, 99)!r} max={float(v.max())!r}")
    H.log(f"counts: {run.counts}")
    for name, v in sorted(run.spans.items()):
        H.log(f"span {name}: n={len(v)} median={float(np.median(v))!r} "
              f"total={float(np.sum(v))!r}")
    if run.trace:
        H.log(f"device program seconds: {run.trace['program_s']}; the "
              f"last operation ended {run.trace['quiet_end_s']!r} s "
              f"before the window")
    H.print_checks(checks)
    attempted = int(sum(run.counts.get(k, 0)
                        for k in ("requests", "events", "steps")))
    print(H.result_line(correct=H.checks_correct(checks),
                        attempted=attempted, failed=failed,
                        metrics=metrics, device=device, checks=checks,
                        breakdown=breakdown), file=out, flush=True)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    return run_cell(parse(argv))


if __name__ == "__main__":
    sys.exit(main())
