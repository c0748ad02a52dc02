"""Profiler trace of the measured window, and its reduction to numbers.

The run marks its window with the host annotation ``bench.window`` and
each call into the program with ``bench.<call>`` (``RunRecord.span``).
The reduction reads the ``.xplane.pb`` the JAX profiler writes:

* device planes are those named ``/device:TPU:<n>``; on each, the
  ``XLA Ops`` line holds one event per operation that ran, and the
  ``XLA Modules`` line one event per program execution;
* busy time is the union of operation intervals inside the window,
  averaged over the chips used; the idle share is 1 - busy / window;
* a program's device time is the sum of its module events whose name
  contains the program's name (``_serve_jit``, ...), inside the window;
* ``quiet_end_s`` is how long before the window's end the last
  operation ended: seconds long where the profiler's device buffer
  filled and dropped the rest of the window's events;
* each idle gap is named by the innermost ``bench.*`` host annotation
  that covers its midpoint, and by the host event of the runtime (any
  other host event of 1 ms or more: a dispatch, a wait, a transfer, a
  collection of Python references) that overlaps at least half of it
  the most, where there is one: ``bench.serve_batch during <event>``.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


def profiler_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # no per-call Python events
    opts.host_tracer_level = 1          # the bench.* annotations
    return opts


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def union_length(intervals: Iterable[Interval], lo: float, hi: float
                 ) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def load_planes(path: str) -> Dict[str, Any]:
    """Device ops and modules per chip, and host annotations, from an
    xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    runtime: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices[plane.name] = {"ops": lines.get("XLA Ops", []),
                                   "modules": lines.get("XLA Modules", [])}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in _events(ln):
                    if ev[0].startswith("bench."):
                        host.append(ev)
                    elif ev[2] - ev[1] >= 1e-3:
                        runtime.append(ev)
    return {"devices": devices, "host": host, "runtime": runtime}


def reduce_planes(planes: Dict[str, Any], programs: Sequence[str],
                  top: int = 10) -> Dict[str, Any]:
    """Busy, idle and per-program device seconds inside ``bench.window``,
    with the breakdown of the longest operations and idle gaps."""
    host = planes["host"]
    windows = [(s, e) for n, s, e in host if n == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window annotation")
    lo, hi = windows[0]
    window_s = hi - lo
    devices = planes["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy, prog_s = [], {p: 0.0 for p in programs}
    last_op = lo
    op_total: Dict[str, float] = {}
    all_gaps: List[Interval] = []
    for dev in devices.values():
        ops = [(s, e) for _, s, e in dev["ops"]]
        busy.append(union_length(ops, lo, hi))
        mods = sorted(dev["modules"], key=lambda m: m[1])
        for name, s, e in mods:
            d = max(min(e, hi) - max(s, lo), 0.0)
            for p in programs:
                if p in name:
                    prog_s[p] += d
        starts = [m[1] for m in mods]
        for name, s, e in dev["ops"]:
            d = max(min(e, hi) - max(s, lo), 0.0)
            if d <= 0:
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][0] if i >= 0 and mods[i][2] >= e else "?"
            key = f"{_short(mod)}/{_short(name)}"
            op_total[key] = op_total.get(key, 0.0) + d
        all_gaps.extend(gaps(ops, lo, hi))
        last_op = max([last_op] + [min(e, hi) for _, e in ops])
    n = len(devices)
    busy_s = sum(busy) / n
    top_ops = sorted(op_total.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    runtime = planes.get("runtime", [])
    named_gaps = [(_host_at(host, (s + e) / 2)
                   + _runtime_over(runtime, s, e), e - s)
                  for s, e in longest]
    return {"busy_s": busy_s, "window_s": window_s,
            "quiet_end_s": hi - last_op,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "program_s": {p: v / n for p, v in prog_s.items()},
            "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                          "idle_gaps": [[k, v] for k, v in named_gaps]}}


def _short(name: str) -> str:
    """An operation's name without its HLO text (``%fusion.25 = ...``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _runtime_over(runtime: List[Tuple[str, float, float]], lo: float,
                  hi: float) -> str:
    """`` during <event>`` for the runtime host event that overlaps the
    most of ``[lo, hi]``, where it covers at least half; else ``""``."""
    best, cover = "", 0.5 * (hi - lo)
    for name, s, e in runtime:
        c = min(e, hi) - max(s, lo)
        if c >= cover:
            best, cover = name, c
    return f" during {_short(best)}" if best else ""


def _host_at(host: List[Tuple[str, float, float]], t: float) -> str:
    """The innermost bench.* annotation (other than the window) covering
    time ``t``."""
    best: Optional[Tuple[str, float, float]] = None
    for name, s, e in host:
        if name == "bench.window" or not (s <= t <= e):
            continue
        if best is None or e - s < best[2] - best[1]:
            best = (name, s, e)
    return best[0] if best else "bench.window"
