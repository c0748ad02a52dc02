"""Telemetry core: spans, sharded metrics, histograms, sinks, clock.

Covers the ISSUE-8 telemetry contract: multi-threaded counter/histogram
emission with no lost or torn records, span nesting/parentage, JSONL
schema round-trip, disabled-sink no-op semantics, injectable-clock
determinism (fixed clock -> byte-stable JSONL), sink rotation, and the
report renderer.
"""
import json
import os
import threading

import pytest

from repro.obs import (FixedClock, Histogram, JsonlSink, MemorySink,
                       MetricsRegistry, NullSink, Telemetry)
from repro.obs import report as report_mod
from repro.obs.metrics import HIST_BUCKETS, bucket_index, bucket_mid
from tests._hypothesis_fallback import given, settings, st


def make_tel(enabled=True):
    sink = MemorySink()
    tel = Telemetry(sink=sink, clock=FixedClock(), enabled=enabled)
    return tel, sink


def records(sink):
    return [json.loads(ln) for ln in sink.lines]


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class TestSpans:
    def test_nesting_and_parentage(self):
        tel, sink = make_tel()
        with tel.span("outer") as outer:
            with tel.span("mid") as mid:
                with tel.span("inner") as inner:
                    pass
            with tel.span("mid2") as mid2:
                pass
        recs = {r["name"]: r for r in records(sink)}
        assert recs["outer"]["parent_id"] is None
        assert recs["mid"]["parent_id"] == outer.span_id
        assert recs["inner"]["parent_id"] == mid.span_id
        assert recs["mid2"]["parent_id"] == outer.span_id
        assert mid2.span_id != mid.span_id
        # children exit (and are emitted) before their parents
        names = [r["name"] for r in records(sink)]
        assert names == ["inner", "mid", "mid2", "outer"]

    def test_duration_and_attrs(self):
        tel, sink = make_tel()
        with tel.span("work", stage="x") as sp:
            sp.set("extra", 3)
        rec = records(sink)[0]
        assert rec["dur_s"] > 0
        assert rec["attrs"] == {"stage": "x", "extra": 3}
        assert sp.elapsed() == rec["dur_s"]   # cached after exit

    def test_exception_annotates_and_emits(self):
        tel, sink = make_tel()
        with pytest.raises(ValueError):
            with tel.span("boom"):
                raise ValueError("x")
        rec = records(sink)[0]
        assert rec["attrs"]["error"] == "ValueError"

    def test_elapsed_live_before_exit(self):
        tel, _ = make_tel()
        with tel.span("s") as sp:
            assert sp.elapsed() > 0

    def test_per_thread_stacks(self):
        """Parentage never crosses threads: a thread with no open span
        emits a root even while another thread is inside one."""
        tel, sink = make_tel()
        done = threading.Event()
        go = threading.Event()

        def other():
            go.wait(5)
            with tel.span("other_root"):
                pass
            done.set()

        t = threading.Thread(target=other)
        t.start()
        with tel.span("main_root"):
            go.set()
            assert done.wait(5)
        t.join()
        recs = {r["name"]: r for r in records(sink)}
        assert recs["other_root"]["parent_id"] is None
        assert recs["main_root"]["parent_id"] is None
        assert (recs["other_root"]["thread"]
                != recs["main_root"]["thread"])


# ---------------------------------------------------------------------------
# metrics: counters / gauges / histograms across threads
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_basic(self):
        reg = MetricsRegistry()
        reg.counter("a")
        reg.counter("a", 2.5)
        reg.gauge("g", 1.0)
        reg.gauge("g", 7.0)
        counters, gauges, _ = reg.merged()
        assert counters == {"a": 3.5}
        assert gauges == {"g": 7.0}

    def test_multithreaded_counters_no_lost_records(self):
        reg = MetricsRegistry()
        N_THREADS, N_INCR = 8, 5000

        def work():
            for _ in range(N_INCR):
                reg.counter("hits")
                reg.observe("lat", 0.001)

        threads = [threading.Thread(target=work)
                   for _ in range(N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        counters, _, hists = reg.merged()
        assert counters["hits"] == N_THREADS * N_INCR
        assert hists["lat"].n == N_THREADS * N_INCR

    def test_merged_readable_while_writing(self):
        """A scraper merging concurrently with writers sees monotonically
        growing, untorn state (never more than the true total)."""
        reg = MetricsRegistry()
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                reg.counter("c")

        threads = [threading.Thread(target=writer) for _ in range(4)]
        for t in threads:
            t.start()
        last = 0.0
        for _ in range(50):
            counters, _, _ = reg.merged()
            cur = counters.get("c", 0.0)
            assert cur >= last
            last = cur
        stop.set()
        for t in threads:
            t.join()
        final = reg.merged()[0]["c"]
        assert final == int(final)     # whole number: no torn adds

    def test_gauge_last_write_wins_across_threads(self):
        reg = MetricsRegistry()
        barrier = threading.Barrier(2)

        def setter(v):
            barrier.wait(5)
            reg.gauge("g", v)

        t1 = threading.Thread(target=setter, args=(1.0,))
        t1.start()
        barrier.wait(5)
        t1.join()
        reg.gauge("g", 2.0)            # strictly later than thread 1
        assert reg.merged()[1]["g"] == 2.0


class TestHistogram:
    def test_bucket_monotone(self):
        idx = [bucket_index(v) for v in
               (0.0, 1e-7, 1e-6, 1e-5, 1e-3, 0.1, 10.0, 1e9)]
        assert idx == sorted(idx)
        assert idx[-1] == HIST_BUCKETS - 1
        assert bucket_mid(3) > bucket_mid(2)

    def test_percentiles_uniform(self):
        h = Histogram()
        for i in range(1000):
            h.observe(0.001 * (i + 1))     # 1ms .. 1s uniform
        p50 = h.percentile(0.5)
        p95 = h.percentile(0.95)
        p99 = h.percentile(0.99)
        assert 0.3 < p50 < 0.75            # log buckets: ~10% resolution
        assert p50 <= p95 <= p99 <= h.max
        assert h.percentile(0.0) >= h.min
        assert h.n == 1000
        assert abs(h.mean - 0.5005) < 1e-9

    def test_merge_matches_combined(self):
        a, b, c = Histogram(), Histogram(), Histogram()
        for i in range(100):
            v = 10.0 ** (-(i % 6))
            (a if i % 2 else b).observe(v)
            c.observe(v)
        a.merge(b)
        assert a.n == c.n
        assert a.counts == c.counts
        assert a.min == c.min and a.max == c.max
        assert a.percentile(0.5) == c.percentile(0.5)

    def test_round_trip_dict(self):
        h = Histogram()
        for v in (1e-6, 3e-4, 0.02, 5.0):
            h.observe(v)
        h2 = Histogram.from_dict(
            json.loads(json.dumps(h.to_dict())))
        assert h2.n == h.n and h2.counts == h.counts
        assert h2.min == h.min and h2.max == h.max
        assert h2.percentile(0.95) == h.percentile(0.95)

    def test_empty(self):
        h = Histogram()
        assert h.percentile(0.5) == 0.0
        assert h.mean == 0.0

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.floats(min_value=1e-9, max_value=1e6,
                              allow_nan=False), min_size=1, max_size=200),
           st.floats(min_value=0.01, max_value=0.99))
    def test_percentile_within_range(self, values, q):
        h = Histogram()
        for v in values:
            h.observe(v)
        p = h.percentile(q)
        assert h.min <= p <= h.max


# ---------------------------------------------------------------------------
# telemetry facade: schema, flush, disabled semantics, determinism
# ---------------------------------------------------------------------------

class TestTelemetry:
    def test_jsonl_schema_round_trip(self):
        tel, sink = make_tel()
        with tel.span("s", k="v"):
            pass
        tel.counter("c", 2)
        tel.gauge("g", 1.5)
        tel.observe("h", 0.01)
        tel.flush()
        recs = records(sink)
        by_type = {}
        for r in recs:
            by_type.setdefault(r["type"], []).append(r)
        assert set(by_type) == {"span", "counter", "gauge", "hist"}
        sp = by_type["span"][0]
        assert set(sp) == {"type", "name", "span_id", "parent_id",
                           "thread", "t_wall", "dur_s", "attrs"}
        assert by_type["counter"][0]["value"] == 2.0
        assert by_type["gauge"][0]["value"] == 1.5
        h = Histogram.from_dict(by_type["hist"][0])
        assert h.n == 1

    def test_fixed_clock_byte_stable(self):
        def run():
            tel, sink = make_tel()
            with tel.span("a", k=1):
                with tel.span("b"):
                    pass
            tel.counter("c.x", 2)
            tel.observe("h.lat", 0.0123)
            tel.gauge("g", 4.0)
            tel.flush()
            return sink.text()

        assert run() == run()
        assert run()                       # non-empty

    def test_disabled_is_noop(self):
        tel, sink = make_tel(enabled=False)
        with tel.span("s") as sp:
            tel.counter("c")
            tel.gauge("g", 1.0)
            tel.observe("h", 0.5)
        tel.flush()
        assert sink.lines == []
        assert tel.snapshot() == {"counters": {}, "gauges": {},
                                  "hists": {}}
        # spans still measure even when not emitting
        assert sp.duration_s > 0

    def test_null_sink(self):
        tel = Telemetry(sink=NullSink(), clock=FixedClock())
        with tel.span("s"):
            tel.counter("c")
        tel.flush()                        # no crash, nowhere to look
        assert tel.snapshot()["counters"] == {"c": 1.0}

    def test_reconfigure_in_place(self):
        tel, _ = make_tel(enabled=False)
        tel.counter("c")
        sink2 = MemorySink()
        tel.reconfigure(sink=sink2, enabled=True)
        tel.counter("c")
        tel.flush()
        assert tel.snapshot()["counters"] == {"c": 1.0}   # pre-enable lost
        assert any(json.loads(ln)["type"] == "counter"
                   for ln in sink2.lines)

    def test_percentiles_api(self):
        tel, _ = make_tel()
        for i in range(100):
            tel.observe("lat", 0.001 * (i + 1))
        p = tel.percentiles("lat")
        assert set(p) == {"p50", "p95", "p99"}
        assert p["p50"] <= p["p95"] <= p["p99"]
        assert tel.percentiles("missing") == {"p50": 0.0, "p95": 0.0,
                                              "p99": 0.0}

    def test_reset_metrics(self):
        tel, _ = make_tel()
        tel.counter("c")
        tel.reset_metrics()
        assert tel.snapshot()["counters"] == {}
        tel.counter("c")                   # shard re-registers
        assert tel.snapshot()["counters"] == {"c": 1.0}

    def test_numpy_values_serialize(self):
        np = pytest.importorskip("numpy")
        tel, sink = make_tel()
        tel.counter("c", np.float32(2.0))
        tel.gauge("g", np.int64(3))
        with tel.span("s", n=np.int32(7)):
            pass
        tel.flush()
        for r in records(sink):            # default=float coerces all
            json.dumps(r)


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

class TestJsonlSink:
    def test_write_flush_read_back(self, tmp_path):
        p = str(tmp_path / "t.jsonl")
        sink = JsonlSink(p)
        sink.write_line('{"a":1}')
        sink.flush()
        assert json.loads(open(p).read()) == {"a": 1}
        sink.close()

    def test_rotation_bounded(self, tmp_path):
        p = str(tmp_path / "r.jsonl")
        sink = JsonlSink(p, max_bytes=200, max_files=3)
        for i in range(100):
            sink.write_line(json.dumps({"i": i, "pad": "x" * 20}))
        sink.flush()
        files = sorted(os.listdir(tmp_path))
        assert "r.jsonl" in files
        assert len(files) <= 3
        total = sum(os.path.getsize(tmp_path / f) for f in files)
        assert total <= 3 * (200 + 64)     # bounded despite 100 writes
        # newest record is in the active file
        last = open(p).read().strip().splitlines()[-1]
        assert json.loads(last)["i"] == 99
        sink.close()

    def test_concurrent_writers_no_torn_lines(self, tmp_path):
        p = str(tmp_path / "c.jsonl")
        sink = JsonlSink(p, max_bytes=1 << 20)

        def work(tid):
            for i in range(500):
                sink.write_line(json.dumps({"t": tid, "i": i}))

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sink.flush()
        lines = open(p).read().strip().splitlines()
        assert len(lines) == 2000
        seen = set()
        for ln in lines:
            r = json.loads(ln)             # every line parses: no tears
            seen.add((r["t"], r["i"]))
        assert len(seen) == 2000           # and none lost
        sink.close()


# ---------------------------------------------------------------------------
# report renderer
# ---------------------------------------------------------------------------

class TestReport:
    def _emit(self, tmp_path, name="t.jsonl"):
        p = str(tmp_path / name)
        tel = Telemetry(sink=JsonlSink(p), clock=FixedClock())
        with tel.span("lifecycle.cycle"):
            with tel.span("lifecycle.train"):
                pass
            with tel.span("lifecycle.swap"):
                with tel.span("swap.flip"):
                    pass
        tel.counter("serving.seqlock_retries", 5)
        tel.gauge("serving.queue_depth_max", 12.0)
        for i in range(50):
            tel.observe("serving.retrieve_latency_s", 0.001 * (i + 1))
        tel.flush()
        return p

    def test_render_tree_and_metrics(self, tmp_path):
        p = self._emit(tmp_path)
        out = report_mod.render([p])
        assert "lifecycle.cycle" in out
        # nested children are indented under their parents
        assert "\n  lifecycle.train" in out
        assert "\n    swap.flip" in out
        assert "serving.seqlock_retries" in out and "5" in out
        assert "serving.queue_depth_max" in out
        assert "p50=" in out and "p95=" in out
        assert "serving.retrieve_latency_s" in out

    def test_multi_file_counters_sum(self, tmp_path):
        p1 = self._emit(tmp_path, "a.jsonl")
        p2 = self._emit(tmp_path, "b.jsonl")
        counters, _, hists = report_mod.metric_summary(
            report_mod.load_records([p1, p2]))
        assert counters["serving.seqlock_retries"] == 10.0
        assert hists["serving.retrieve_latency_s"].n == 100

    def test_cli_main(self, tmp_path, capsys):
        p = self._emit(tmp_path)
        assert report_mod.main([p]) == 0
        assert "span tree" in capsys.readouterr().out

    def test_skips_garbage_lines(self, tmp_path):
        p = self._emit(tmp_path)
        with open(p, "a") as fh:
            fh.write("not json\n\n{\"type\":\"counter\",\"name\":\"x\","
                     "\"value\":1,\"t_wall\":0}\n")
        counters, _, _ = report_mod.metric_summary(
            report_mod.load_records([p]))
        assert counters["x"] == 1


# ---------------------------------------------------------------------------
# buffered spans: per-thread buffers, one clock offset, the annotate hook
# ---------------------------------------------------------------------------

class _CountingClock(FixedClock):
    def __init__(self):
        super().__init__(wall=5000.0)
        self.wall_reads = 0

    def wall(self):
        self.wall_reads += 1
        return super().wall()


class _Hook:
    """An annotate factory that records every name, attr and exit."""

    def __init__(self):
        self.calls = []

    def __call__(self, name, **attrs):
        hook = self

        class _Ann:
            def __init__(self):
                self.rec = {"name": name, "attrs": dict(attrs),
                            "exited": False}
                hook.calls.append(self.rec)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                self.rec["exited"] = True

            def set_metadata(self, **kw):
                self.rec["attrs"].update(kw)

        return _Ann()


class TestBufferedSpans:
    def test_four_threads_reach_flush_with_the_schema(self, tmp_path):
        p = str(tmp_path / "s.jsonl")
        tel = Telemetry(sink=JsonlSink(p), clock=FixedClock())
        barrier = threading.Barrier(4)

        def work(tid):
            barrier.wait(5)
            for i in range(50):
                with tel.span("outer", t=tid):
                    with tel.span("inner", i=i):
                        pass

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not os.path.exists(p)       # nothing written before flush
        assert len(tel.spans()) == 400
        tel.flush()
        recs = [json.loads(ln) for ln in open(p)]
        spans = [r for r in recs if r["type"] == "span"]
        assert len(spans) == 400
        for r in spans:
            assert set(r) == {"type", "name", "span_id", "parent_id",
                              "thread", "t_wall", "dur_s", "attrs"}
        outer = {r["span_id"]: r for r in spans if r["name"] == "outer"}
        for r in spans:
            if r["name"] == "inner":
                parent = outer[r["parent_id"]]
                assert parent["thread"] == r["thread"]
        assert len({r["thread"] for r in spans}) == 4
        assert tel.spans() == []           # flush drained the buffers

    def test_t_wall_comes_from_one_offset(self):
        clock = _CountingClock()
        sink = MemorySink()
        tel = Telemetry(sink=sink, clock=clock)
        reads = clock.wall_reads
        for i in range(5):
            with tel.span("s", i=i):
                pass
        assert clock.wall_reads == reads   # spans never read the wall
        buffered = tel.spans()
        recs = records(sink)
        offsets = {round(r["t_wall"] - b["start"], 9)
                   for r, b in zip(recs, buffered)}
        assert len(offsets) == 1
        assert [r["dur_s"] for r in recs] == [b["dur_s"] for b in buffered]
        assert [r["attrs"]["i"] for r in recs] == list(range(5))

    def test_annotate_hook_sees_names_and_attrs(self):
        hook = _Hook()
        tel = Telemetry(sink=MemorySink(), clock=FixedClock(),
                        annotate=hook)
        with tel.span("outer", requests=3) as sp:
            with tel.span("inner"):
                pass
            sp.set("width", 8)
        assert [c["name"] for c in hook.calls] == ["outer", "inner"]
        assert hook.calls[0]["attrs"] == {"requests": 3, "width": 8}
        assert hook.calls[1]["attrs"] == {}
        assert all(c["exited"] for c in hook.calls)
        tel.reconfigure(enabled=True)      # leaving annotate out keeps it
        with tel.span("again"):
            pass
        assert hook.calls[-1]["name"] == "again"
        tel.reconfigure(annotate=None)
        with tel.span("no_hook"):
            pass
        assert hook.calls[-1]["name"] == "again"

    def test_disabled_buffers_nothing_and_never_calls_the_hook(self):
        hook = _Hook()
        tel = Telemetry(sink=MemorySink(), clock=FixedClock(),
                        enabled=False, annotate=hook)
        with tel.span("s", a=1) as sp:
            sp.set("b", 2)
        assert tel.spans() == []
        assert hook.calls == []
        assert sp.duration_s > 0           # still measures

    def test_hot_span_costs_nothing_while_disabled(self):
        class Counting(FixedClock):
            reads = 0

            def perf(self):
                Counting.reads += 1
                return super().perf()

        hook = _Hook()
        tel = Telemetry(sink=MemorySink(), clock=Counting(), enabled=False,
                        annotate=hook)
        before = Counting.reads
        with tel.hot_span("a", k=1) as a:
            with tel.hot_span("b") as b:
                b.set("x", 2)
        assert a is b                      # one shared no-op
        assert Counting.reads == before and hook.calls == []
        assert tel.spans() == []
        tel.reconfigure(enabled=True)
        with tel.hot_span("a", k=1) as a:
            with tel.hot_span("b") as b:
                b.set("x", 2)
        recs = {s["name"]: s for s in tel.spans()}
        assert recs["b"]["parent_id"] == recs["a"]["span_id"]
        assert recs["a"]["attrs"] == {"k": 1} and recs["b"]["dur_s"] > 0
        assert [c["name"] for c in hook.calls] == ["a", "b"]

    def test_reset_spans_drops_the_buffer(self):
        tel, sink = make_tel()
        with tel.span("old"):
            pass
        tel.reset_spans()
        with tel.span("new"):
            pass
        assert [s["name"] for s in tel.spans()] == ["new"]
        assert [r["name"] for r in records(sink)] == ["new"]
