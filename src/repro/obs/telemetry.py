"""Telemetry facade: spans + metrics + sink behind one object.

One :class:`Telemetry` instance owns a clock, a metrics registry, and a
sink. Spans are context managers that always *measure* (callers rely on
``span.elapsed()`` for report fields like ``build_seconds``) but are
only *recorded* when the instance is enabled. A recorded span goes into
its thread's private buffer, like the metric shards: nothing is
serialized and no shared lock is taken on the emitting thread.
:meth:`Telemetry.flush` serializes the buffered spans (in the order they
closed), then the cumulative counters/gauges/histograms of the
per-thread shards (see :mod:`repro.obs.metrics`).

Spans are timed on the monotonic ``perf`` clock only. Their ``t_wall``
is derived at flush from one wall/perf offset taken when the instance
is configured, so every span of a process shares one clock.

``annotate`` (optional) is a factory ``annotate(name, **attrs)`` that
returns a context manager; every recorded span also enters one, and
attrs set later through :meth:`Span.set` reach it through its
``set_metadata`` where it has one. An entry point that profiles passes
its profiler's host annotation here, so program spans land in the
profiler's trace on the profiler's own clock. The package itself never
imports a profiler.

A module-level singleton (:func:`get_telemetry` / :func:`configure`)
lets instrumented library code default to the process-wide instance
while tests inject private ones. ``configure`` mutates the singleton
*in place* so references captured at construction time (e.g. a store
built before the benchmark configured telemetry) observe the change.

JSONL schema (one object per line, sorted keys, compact separators):

* ``{"type": "span", "name", "span_id", "parent_id", "thread",
  "t_wall", "dur_s", "attrs"}``
* ``{"type": "counter"|"gauge", "name", "value", "t_wall"}``
* ``{"type": "hist", "name", "t_wall", "n", "sum", "min", "max",
  "counts", "base", "growth"}`` — cumulative at flush time.
"""
from __future__ import annotations

import itertools
import json
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from .clock import Clock, SystemClock
from .metrics import Histogram, MetricsRegistry
from .sink import JsonlSink, NullSink, Sink


_KEEP = object()      # reconfigure(): leave the span hook as it is


def _dumps(obj: dict) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"),
                      default=float)


class Span:
    """Context-manager timer. Measures always; records only when enabled."""

    __slots__ = ("name", "attrs", "span_id", "parent_id",
                 "duration_s", "_tel", "_t0", "_ann")

    def __init__(self, tel: "Telemetry", name: str,
                 attrs: Optional[dict] = None) -> None:
        self._tel = tel
        self.name = name
        self.attrs = dict(attrs) if attrs else {}
        self.span_id: Optional[int] = None
        self.parent_id: Optional[int] = None
        self.duration_s = 0.0
        self._t0 = 0.0
        self._ann = None

    def set(self, key: str, value) -> "Span":
        self.attrs[key] = value
        ann = self._ann
        if ann is not None and hasattr(ann, "set_metadata"):
            ann.set_metadata(**{key: value})
        return self

    def elapsed(self) -> float:
        """Seconds since span entry (usable before and after exit)."""
        if self.duration_s:
            return self.duration_s
        return self._tel._clock.perf() - self._t0

    def __enter__(self) -> "Span":
        tel = self._tel
        self.span_id = next(tel._span_ids)
        stack = tel._local().stack
        self.parent_id = stack[-1].span_id if stack else None
        stack.append(self)
        if tel.enabled and tel._annotate is not None:
            ann = tel._annotate(self.name, **self.attrs)
            ann.__enter__()
            self._ann = ann
        self._t0 = tel._clock.perf()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        tel = self._tel
        self.duration_s = tel._clock.perf() - self._t0
        local = tel._local()
        stack = local.stack
        if stack and stack[-1] is self:
            stack.pop()
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(exc_type, exc, tb)
        if tel.enabled:
            if local.index is None:
                tel._register(local)
            local.spans.append(self)


class _NullSpan:
    """What :meth:`Telemetry.hot_span` returns while disabled: a shared
    span that reads no clock, takes no id and records nothing."""

    __slots__ = ()
    name = ""
    span_id = parent_id = None
    duration_s = 0.0

    def set(self, key: str, value) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


_NULL_SPAN = _NullSpan()


class _ThreadSpans:
    """One thread's open-span stack and closed-span buffer; ``index`` is
    given when the thread records its first span."""

    __slots__ = ("index", "stack", "spans")

    def __init__(self) -> None:
        self.index: Optional[int] = None
        self.stack: List[Span] = []
        self.spans: List[Span] = []


class Telemetry:
    """Facade over clock + metrics registry + sink."""

    def __init__(self, *, sink: Optional[Sink] = None,
                 clock: Optional[Clock] = None,
                 enabled: bool = True,
                 annotate: Optional[Callable[..., Any]] = None) -> None:
        self._sink: Sink = sink if sink is not None else NullSink()
        self._clock: Clock = clock if clock is not None else SystemClock()
        self.enabled = bool(enabled)
        self._annotate = annotate
        self.metrics = MetricsRegistry()
        self._span_ids = itertools.count(1)
        self._tls = threading.local()
        self._threads_lock = threading.Lock()
        self._threads: List[_ThreadSpans] = []
        self._take_offset()
        self._sink.bind(self._write_spans)

    # -- internals -------------------------------------------------------
    def _take_offset(self) -> None:
        self._wall0 = self._clock.wall()
        self._perf0 = self._clock.perf()

    def _local(self) -> _ThreadSpans:
        loc = getattr(self._tls, "spans", None)
        if loc is None:
            loc = self._tls.spans = _ThreadSpans()
        return loc

    def _register(self, loc: _ThreadSpans) -> None:
        with self._threads_lock:
            loc.index = len(self._threads)
            self._threads.append(loc)

    def _closed(self, drain: bool) -> List[Tuple[int, Span]]:
        """Every buffered span with its thread index, in closing order;
        ``drain`` empties the buffers (a span closing meanwhile stays)."""
        with self._threads_lock:
            threads = list(self._threads)
        out = []
        for loc in threads:
            buf = loc.spans
            n = len(buf)
            out.extend((loc.index, sp) for sp in buf[:n])
            if drain:
                del buf[:n]
        out.sort(key=lambda r: r[1]._t0 + r[1].duration_s)
        return out

    def _write_spans(self) -> None:
        """Serialize and drain the buffered spans into the sink."""
        offset = self._wall0 - self._perf0
        for ti, sp in self._closed(drain=True):
            self._sink.write_line(_dumps({
                "type": "span",
                "name": sp.name,
                "span_id": sp.span_id,
                "parent_id": sp.parent_id,
                "thread": ti,
                "t_wall": offset + sp._t0,
                "dur_s": sp.duration_s,
                "attrs": sp.attrs,
            }))

    # -- public API ------------------------------------------------------
    @property
    def clock(self) -> Clock:
        return self._clock

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs or None)

    def hot_span(self, name: str, **attrs):
        """A span for a per-request path: :meth:`span` while enabled;
        while disabled one shared no-op that measures nothing, so the
        path pays no clock read and no allocation for it."""
        if self.enabled:
            return Span(self, name, attrs or None)
        return _NULL_SPAN

    def spans(self) -> List[dict]:
        """The buffered (not yet flushed) spans, in closing order:
        ``name``, ``span_id``, ``parent_id``, ``thread``, ``start`` (on
        the ``perf`` clock), ``dur_s`` and ``attrs``."""
        return [{"name": sp.name, "span_id": sp.span_id,
                 "parent_id": sp.parent_id, "thread": ti,
                 "start": sp._t0, "dur_s": sp.duration_s,
                 "attrs": sp.attrs}
                for ti, sp in self._closed(drain=False)]

    def reset_spans(self) -> None:
        """Drop the buffered spans."""
        self._closed(drain=True)

    def counter(self, name: str, delta: float = 1.0) -> None:
        if self.enabled:
            self.metrics.counter(name, delta)

    def gauge(self, name: str, value: float) -> None:
        if self.enabled:
            self.metrics.gauge(name, float(value))

    def observe(self, name: str, value: float) -> None:
        if self.enabled:
            self.metrics.observe(name, float(value))

    def snapshot(self) -> dict:
        """Merged metric state: counters, gauges, histogram summaries."""
        counters, gauges, hists = self.metrics.merged()
        return {
            "counters": dict(sorted(counters.items())),
            "gauges": dict(sorted(gauges.items())),
            "hists": {k: h.to_dict() for k, h in sorted(hists.items())},
        }

    def percentiles(self, name: str,
                    qs: Tuple[float, ...] = (0.5, 0.95, 0.99)
                    ) -> Dict[str, float]:
        _, _, hists = self.metrics.merged()
        h = hists.get(name, Histogram())
        return {f"p{int(q * 100)}": h.percentile(q) for q in qs}

    def flush(self) -> None:
        """Serialize the buffered spans and the cumulative metric state
        to the sink, then flush it."""
        self._write_spans()
        if self.enabled:
            counters, gauges, hists = self.metrics.merged()
            t = self._clock.wall()
            for name in sorted(counters):
                self._sink.write_line(_dumps({
                    "type": "counter", "name": name,
                    "value": counters[name], "t_wall": t}))
            for name in sorted(gauges):
                self._sink.write_line(_dumps({
                    "type": "gauge", "name": name,
                    "value": gauges[name], "t_wall": t}))
            for name in sorted(hists):
                rec = {"type": "hist", "name": name, "t_wall": t}
                rec.update(hists[name].to_dict())
                self._sink.write_line(_dumps(rec))
        self._sink.flush()

    def reset_metrics(self) -> None:
        self.metrics.reset()

    def reconfigure(self, *, sink: Optional[Sink] = None,
                    clock: Optional[Clock] = None,
                    enabled: Optional[bool] = None,
                    annotate: Any = _KEEP) -> "Telemetry":
        """Mutate this instance in place (late-bound refs see the change).
        ``annotate`` left out keeps the current hook; ``None`` drops it."""
        if sink is not None:
            old = self._sink
            self._sink = sink
            old.bind(None)
            old.close()
            sink.bind(self._write_spans)
        if clock is not None:
            self._clock = clock
        if enabled is not None:
            self.enabled = bool(enabled)
        if annotate is not _KEEP:
            self._annotate = annotate
        self._take_offset()
        return self


# Process-wide singleton. Disabled by default: library code is
# instrumented unconditionally and pays ~one attribute check until an
# entry point (benchmark, example, test) calls ``configure``.
_GLOBAL = Telemetry(enabled=False)


def get_telemetry() -> Telemetry:
    return _GLOBAL


def configure(*, path: Optional[str] = None, sink: Optional[Sink] = None,
              clock: Optional[Clock] = None, enabled: bool = True,
              max_bytes: int = 64 * 1024 * 1024,
              max_files: int = 4,
              annotate: Optional[Callable[..., Any]] = None) -> Telemetry:
    """(Re)configure the process-wide telemetry singleton in place.
    ``annotate`` is the span hook (see the module docstring); every call
    sets it, so leaving it out removes a previous one."""
    if sink is None and path is not None:
        sink = JsonlSink(path, max_bytes=max_bytes, max_files=max_files)
    if sink is None and not enabled:
        sink = NullSink()
    return _GLOBAL.reconfigure(sink=sink, clock=clock, enabled=enabled,
                               annotate=annotate)
