"""The program's own telemetry beside the benchmark: the device time per
serve call, the count of program calls it rests on, and telemetry left
off by a benchmark run."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import devtrace  # noqa: E402
import harness as H  # noqa: E402


def _record(calls=3, device_s=0.006):
    run = H.RunRecord("serve-read", 0, 15.0, True)
    run.spans["serve_batch"] = [0.005] * calls
    run.trace = {"program_s": {"_serve_jit": device_s}}
    return run


def test_device_ms_is_device_time_per_serve_call():
    assert H.metric_reader("serve.device_ms")(_record()) == \
        pytest.approx(2.0)


@pytest.mark.parametrize("case", ["untraced", "no calls", "no program"])
def test_device_ms_finds_nothing_to_read(case):
    run = _record()
    if case == "untraced":
        run.trace = None
    elif case == "no calls":
        run.spans.clear()
    else:
        run.trace = {"program_s": {"_direct_ingest_jit": 0.5}}
    assert H.metric_reader("serve.device_ms")(run) is None


def test_an_untraced_run_leaves_the_program_telemetry_off():
    import bench_tiny as B
    from repro.obs import get_telemetry
    assert not get_telemetry().enabled
    res = B.run("serve-read")
    assert res["correct"], res["checks"]
    assert not get_telemetry().enabled
    assert get_telemetry().spans() == []


def test_a_traced_run_counts_one_program_call_per_serve_call(monkeypatch):
    """The traced path on the CPU, with the device trace's reduction
    (which needs a TPU plane) replaced and the program's telemetry turned
    on around it: the window's ``serving.serve_calls`` equals its
    ``serve_batch`` spans, which ``serve.device_ms`` divides by, and each
    call is one ``serving.serve_batch`` span of the program."""
    import io
    import json
    import time
    import types

    import jax

    import bench_tiny as B
    import run as R
    from repro import obs

    def reduce(planes, programs, top=10):
        return {"busy_s": 0.5, "window_s": 1.0, "quiet_end_s": 0.0,
                "idle_share": 0.5, "program_s": {"_serve_jit": 0.25},
                "breakdown": {"device_ops": [], "idle_gaps": []}}

    monkeypatch.setattr(devtrace, "reduce_planes", reduce)
    monkeypatch.setattr(H, "peaks", lambda kind, *a: {
        "bf16_flops": 1e15, "hbm_bytes_per_s": 1e12})
    runs, window = [], {}
    real_record = H.RunRecord

    def record(*a, **kw):
        runs.append(real_record(*a, **kw))
        return runs[-1]

    monkeypatch.setattr(H, "RunRecord", record)
    tel = obs.get_telemetry()
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def start_trace(*a, **kw):
        window["calls0"] = tel.snapshot()["counters"].get(
            "serving.serve_calls", 0.0)
        tel.reset_spans()
        return start(*a, **kw)

    def stop_trace():
        window["calls1"] = tel.snapshot()["counters"].get(
            "serving.serve_calls", 0.0)
        window["spans"] = [s for s in tel.spans()
                           if s["name"] == "serving.serve_batch"]
        return stop()

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
    cfg = dict(H.load_config("rankgraph2-serve"), **B.SERVE)
    tr = dict(H.load_traffic("serve-read"), **B.SERVE_TRAFFIC)
    args = types.SimpleNamespace(workload="serve-read", seed=2 ** 31 + 3,
                                 seconds=1.0, trace=1)
    out = io.StringIO()
    obs.configure(enabled=True, sink=obs.MemorySink(),
                  annotate=jax.profiler.TraceAnnotation)
    try:
        assert R.run_cell(args, devices=jax.devices()[:1], config=cfg,
                          traffic=tr, t_start=time.perf_counter(),
                          use_cache=False, out=out) == 0
    finally:
        obs.configure(enabled=False)
        tel.reset_spans()
        tel.reset_metrics()
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    calls = len(runs[0].spans["serve_batch"])
    assert calls > 0
    assert window["calls1"] - window["calls0"] == calls
    assert len(window["spans"]) == calls
    assert res["metrics"]["serve.device_ms"]["value"] == \
        pytest.approx(250.0 / calls)
