"""What every cell shares: finding its pieces by name, the device check,
the run record that metric readers read, and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its pieces are
found by name, so a later change adds a cell, a traffic mix or a metric
by adding files and entries only:

* ``bench/configs/<config>.json``  — the deployment's sizes;
* ``bench/traffic/<traffic>.json`` — the traffic parameters, and the
  ``driver`` (``bench/cells/<driver>.py``) that generates them;
* ``bench/metrics/<metric>.py``    — a ``read(run)`` that returns the
  metric's value from a :class:`RunRecord`, or ``None`` where it finds
  nothing to read.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "bench")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding pieces by name
# ---------------------------------------------------------------------------

def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def _load_json(kind: str, name: str, bench_dir: str) -> Dict[str, Any]:
    path = os.path.join(bench_dir, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def load_config(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    return _load_json("configs", name, bench_dir)


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    return _load_json("traffic", name, bench_dir)


def _load_module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str, bench_dir: str = BENCH_DIR):
    """The traffic driver module ``bench/cells/<name>.py``."""
    return _load_module(os.path.join(bench_dir, "cells", f"{name}.py"),
                        f"bench_cell_{name}")


def metric_reader(name: str, bench_dir: str = BENCH_DIR
                  ) -> Callable[["RunRecord"], Optional[float]]:
    """``read`` of ``bench/metrics/<name>.py``."""
    mod = _load_module(os.path.join(bench_dir, "metrics", f"{name}.py"),
                       "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))
    return mod.read


def metrics_for(bench: Dict[str, Any], cell: str, trace: bool
                ) -> List[Dict[str, Any]]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced; a metric with a ``workloads`` list is reported
    only in those cells."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if "workloads" not in m or cell in m["workloads"]]


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json") from None


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_accelerator(chips: int) -> List[Any]:
    """The first ``chips`` TPU devices; raises :class:`NoAccelerator`
    where JAX finds no TPU or too few of them."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform if devs else "none"
    if platform != "tpu":
        raise NoAccelerator(f"JAX platform is {platform!r}; this benchmark "
                            "measures a TPU and runs nowhere else")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips; JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def device_record(devices: Sequence[Any]) -> Dict[str, Any]:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts programs this process lowered, and of those the ones
    compiled rather than found in the persistent compilation cache."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring
        self.lowered = 0
        self.requests = 0
        self.hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_kw) -> None:
        with self._lock:
            if event == self.LOWER:
                self.lowered += 1
            elif event == self.COMPILE:
                self.requests += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            with self._lock:
                self.hits += 1

    def totals(self) -> Tuple[int, int]:
        """``(lowered, compiled)`` so far."""
        with self._lock:
            return self.lowered, self.requests - self.hits


class GcPauses:
    """Collections of the garbage collector while it is installed: count
    and longest pause per generation."""

    def __init__(self):
        import gc
        self._gc = gc
        self._t0 = 0.0
        self.stats: Dict[int, List[float]] = {}
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.stats.setdefault(info["generation"], []).append(
                time.perf_counter() - self._t0)

    def stop(self) -> None:
        if self._cb in self._gc.callbacks:
            self._gc.callbacks.remove(self._cb)

    def summary(self) -> str:
        return ", ".join(f"gen{g}: n={len(v)} max_s={max(v)!r} "
                         f"total_s={sum(v)!r}"
                         for g, v in sorted(self.stats.items())) or "none"


# ---------------------------------------------------------------------------
# the run record that metric readers read
# ---------------------------------------------------------------------------

def nearest_rank(values: Sequence[float], q: float) -> float:
    """The q-th percentile by nearest rank: the smallest sample with at
    least q% of all samples at or below it (no interpolation, so a tail
    is a sample that was measured)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no samples")
    rank = max(int(math.ceil(q / 100.0 * v.size)), 1)
    return float(v[rank - 1])


class RunRecord:
    """What one run measured, for the metric readers.

    ``spans``   host durations (s) the benchmark timed around calls into
                the program, by name;
    ``samples`` per-item measurements (request latencies, ...) by name;
    ``counts``  totals (requests, events, steps, edges, ...);
    ``work``    required operations and bytes per program, from
                ``bench/work.py``;
    ``trace``   the reduced profiler trace of a ``--trace 1`` run.
    """

    def __init__(self, cell: str, seed: int, seconds: float, trace: bool):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.traced = trace
        self.spans: Dict[str, List[float]] = {}
        self.samples: Dict[str, np.ndarray] = {}
        self.counts: Dict[str, float] = {}
        self.work: Dict[str, Dict[str, float]] = {}
        self.trace: Optional[Dict[str, Any]] = None
        self.device_kind: Optional[str] = None
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        """Time one call into the program on the host clock, and mark it
        in the profiler's trace under ``bench.<name>``."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        dt = time.perf_counter() - t0
        with self._lock:
            self.spans.setdefault(name, []).append(dt)

    def phase(self, name: str, t0: float) -> float:
        """Log a set-up phase that began at ``t0``; returns the time."""
        t = time.perf_counter()
        log(f"setup phase {name}: {t - t0!r} s")
        return t


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def checks_correct(checks: Sequence[Tuple[str, float, float]]) -> bool:
    """Every compared number at or under its limit (NaN fails)."""
    return all(np.isfinite(v) and v <= lim for _, v, lim in checks)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Tuple[float, str]],
                device: Dict[str, Any],
                checks: Sequence[Tuple[str, float, float]],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    out: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": float(v), "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # the numbers compared, each beside its limit, come last
    out["checks"] = {name: {"value": float(v), "limit": float(lim)}
                     for name, v, lim in checks}
    return json.dumps(out)


def print_checks(checks: Sequence[Tuple[str, float, float]]) -> None:
    for name, v, lim in checks:
        ok = "ok" if (np.isfinite(v) and v <= lim) else "FAIL"
        print(f"check {name}: {v!r} limit {lim!r} {ok}", file=sys.stderr)
    sys.stderr.flush()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
