"""The chip benchmark's harness on the CPU: pieces found by name, the
contract of BENCHMARK.json, the trace reduction, the percentile and rate
arithmetic, the generators, and the refusal of a CPU platform."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import harness as H  # noqa: E402
import traffic as T  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return H.load_benchmark()


def test_every_piece_is_found_by_name(bench):
    for cell in bench["workloads"]:
        H.load_config(cell["config"])
        tr = H.load_traffic(cell["traffic"])
        assert hasattr(H.load_driver(tr["driver"]), "Cell")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(H.metric_reader(m["name"]))


def test_benchmark_json_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    names = [c["name"] for c in bench["configs"]]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for cell in bench["workloads"]:
        assert cell["config"] in names and cell["chips"] in (1, 4)
        assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
        reported = [m["name"] for m in H.metrics_for(bench, cell["name"],
                                                     False)]
        assert "setup_s" in reported and len(reported) >= 2
        layers = H.metrics_for(bench, cell["name"], True)
        assert layers
        for m in layers:
            assert m["moves"] in reported
    assert len(json.dumps(bench)) < 64 * 1024


def test_a_new_cell_needs_only_new_files(tmp_path, bench):
    root = tmp_path / "repo"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = json.loads(json.dumps(bench))
    b["workloads"].append({"name": "serve-burst", "config":
                           "rankgraph2-serve", "traffic": "serve-burst",
                           "chips": 1, "why": "on/off bursts"})
    b["per_layer"].append({"name": "serve.burst_ms", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "serving store",
                           "moves": "serve_p95_ms",
                           "workloads": ["serve-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    (root / "bench" / "traffic" / "serve-burst.json").write_text(
        json.dumps(dict(H.load_traffic("serve-read"), read_rate=123)))
    (root / "bench" / "metrics" / "serve.burst_ms.py").write_text(
        "def read(run):\n    return 4.0\n")
    bdir = str(root / "bench")
    got = H.load_benchmark(str(root))
    assert H.workload(got, "serve-burst")["traffic"] == "serve-burst"
    assert H.load_traffic("serve-burst", bdir)["read_rate"] == 123
    layer = [m["name"] for m in H.metrics_for(got, "serve-burst", True)]
    assert layer == ["serve.burst_ms"]
    assert H.metric_reader("serve.burst_ms", bdir)(None) == 4.0
    # nothing that was there changed
    for sub in ("cells", "configs", "metrics", "reference"):
        for f in os.listdir(os.path.join(BENCH, sub)):
            p = os.path.join(BENCH, sub, f)
            if os.path.isfile(p):
                assert open(p).read() == (root / "bench" / sub / f
                                          ).read_text()


def _planes():
    """A recorded trace in miniature: two programs on one chip, host
    spans, a window from 0 to 10 s."""
    s = 1e9
    ops = [("fusion.1", 1 * s, 2 * s), ("fusion.2", 1.5 * s, 3 * s),
           ("copy.3", 6 * s, 7 * s), ("fusion.4", 9.5 * s, 11 * s)]
    mods = [("jit__serve_jit(7)", 0.9 * s, 3.1 * s),
            ("jit__direct_ingest_jit(3)", 5.9 * s, 7.1 * s),
            ("jit__serve_jit(7)", 9.4 * s, 11.2 * s)]
    host = [("bench.window", 0.0, 10 * s), ("bench.serve_batch", 3 * s,
                                            5.5 * s),
            ("bench.ingest", 7 * s, 9 * s)]
    ev = lambda xs: [(n, a * 1e-9, b * 1e-9) for n, a, b in xs]
    return {"devices": {"/device:TPU:0": {"ops": ev(ops),
                                          "modules": ev(mods)}},
            "host": ev(host)}


def test_trace_reduction_on_a_small_trace():
    r = devtrace.reduce_planes(_planes(), ("_serve_jit",
                                           "_direct_ingest_jit"))
    assert r["window_s"] == pytest.approx(10.0)
    # union of [1,3], [6,7], [9.5,10] inside the window
    assert r["busy_s"] == pytest.approx(3.5)
    assert r["idle_share"] == pytest.approx(0.65)
    assert r["quiet_end_s"] == pytest.approx(0.0)
    assert r["program_s"]["_serve_jit"] == pytest.approx(2.2 + 0.6)
    assert r["program_s"]["_direct_ingest_jit"] == pytest.approx(1.2)
    gaps = dict((round(d, 6), n) for n, d in r["breakdown"]["idle_gaps"])
    assert gaps[3.0] == "bench.serve_batch"     # 3..6
    assert gaps[2.5] == "bench.ingest"          # 7..9.5
    assert gaps[1.0] == "bench.window"          # 0..1
    top = r["breakdown"]["device_ops"][0]
    assert top[0] == "jit__serve_jit(7)/fusion.2"
    # a runtime host event over most of a gap names what held it
    planes = _planes()
    planes["runtime"] = [("PythonRefManager::CollectGarbage", 3.2, 5.9),
                         ("PjitFunction(_serve_jit)", 7.0, 7.1)]
    r = devtrace.reduce_planes(planes, ("_serve_jit",))
    gaps = dict((round(d, 6), n) for n, d in r["breakdown"]["idle_gaps"])
    assert gaps[3.0] == ("bench.serve_batch during "
                         "PythonRefManager::CollectGarbage")
    assert gaps[2.5] == "bench.ingest"
    # a trace whose device events stop early shows a quiet end
    planes["devices"]["/device:TPU:0"]["ops"].pop()
    r = devtrace.reduce_planes(planes, ("_serve_jit",))
    assert r["quiet_end_s"] == pytest.approx(3.0)
    assert devtrace.union_length([(0, 2), (1, 3), (5, 6)], 0, 10) == 4
    assert devtrace.gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]


def test_percentiles_and_rates_see_a_stall():
    rng = np.random.default_rng(0)
    due = np.sort(rng.random(2000) * 10.0)
    service = 0.002
    lat = np.full(due.size, service)
    assert H.nearest_rank(lat, 99) == pytest.approx(service)
    assert H.nearest_rank([1, 2, 3, 4], 50) == 2
    assert H.nearest_rank([1, 2, 3, 4], 99) == 4
    # a 0.5 s stall at t=5: requests due in it wait until it ends
    stalled = np.where((due >= 5.0) & (due < 5.5), 5.5 - due + service,
                       service)
    assert H.nearest_rank(stalled, 99) > 50 * service
    # a stall that holds more than a twentieth of the requests moves p95
    p95 = H.metric_reader("serve_p95_ms")
    run = H.RunRecord("serve-read", 0, 10.0, False)
    run.samples["serve_latency_s"] = lat
    assert p95(run) == pytest.approx(service * 1e3)
    run.samples["serve_latency_s"] = np.where(
        (due >= 5.0) & (due < 5.7), 5.7 - due + service, service)
    assert p95(run) > 10 * service * 1e3
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(BENCH, "metrics", "train_edges_per_s.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    run = H.RunRecord("train-burst", 0, 10.0, False)
    run.counts["edges"] = 1000.0
    run.window_s = 10.0
    fast = mod.read(run)
    run.window_s = 10.5                   # the same work with a stall
    assert mod.read(run) < fast


def test_generators_replay_per_seed():
    a = T.arrivals(500.0, 4.0, T.rng_for(7, 2))
    b = T.arrivals(500.0, 4.0, T.rng_for(7, 2))
    c = T.arrivals(500.0, 4.0, T.rng_for(8, 2))
    assert np.array_equal(a, b) and len(a) == len(c) == 2000
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0) and a.max() < 4.0
    import jax
    key = jax.random.key(2 ** 31 + 5)
    p1 = T.device_population(key, 1000, 50, user_zipf=0.99,
                             cluster_zipf=0.6)
    p2 = T.device_population(key, 1000, 50, user_zipf=0.99,
                             cluster_zipf=0.6)
    for name in p1:
        assert np.array_equal(p1[name], p2[name]), name
    cl = np.asarray(p1["clusters"])
    assert set(np.unique(cl)) == set(range(50))
    assert sorted(np.asarray(p1["user_of_rank"])) == list(range(1000))
    ptr, mids = np.asarray(p1["member_ptr"]), np.asarray(p1["member_ids"])
    for c in (0, 17, 49):
        assert np.all(cl[mids[ptr[c]:ptr[c + 1]]] == c)
    t1 = T.device_i2i(jax.random.key(9), 300, 4, 0.8, rows=128)
    t2 = T.device_i2i(jax.random.key(9), 300, 4, 0.8, rows=128)
    assert np.array_equal(t1, t2) and t1.shape == (300, 4)
    assert t1.min() >= 0 and t1.max() < 300
    assert not np.any(t1 == np.arange(300)[:, None])
    f = T.device_event_fn(p1, jax.numpy.arange(30), item_zipf=0.99, n=64)
    u1, i1, o1 = f(jax.random.key(3), 2.0)
    u2, i2, o2 = f(jax.random.key(3), 2.0)
    assert np.array_equal(u1, u2) and np.array_equal(i1, i2)
    assert np.all(np.asarray(o1) < 2.0) and np.all(np.asarray(i1) < 30)
    # every event's user belongs to a cluster: members map back
    assert np.all(np.asarray(u1) < 1000)
    r1 = T.device_zipf_keys(jax.random.key(4), p1["user_of_rank"], 0.99, 50)
    r2 = T.device_zipf_keys(jax.random.key(4), p1["user_of_rank"], 0.99, 50)
    assert np.array_equal(r1, r2)


def test_zipf_tail_ranks_are_all_reachable():
    """Float32 alone would leave all but every few hundredth rank of a
    hundred million keys unreachable; the spread draws reach them all,
    and the head keeps the Zipf law."""
    import jax
    r = np.asarray(T.zipf_ranks(jax.random.key(11), 10 ** 8, 0.99,
                                1 << 20))
    tail = r[r > 10 ** 7]
    assert len(tail) > 1 << 16
    counts = np.bincount(tail % 64, minlength=64)
    assert counts.min() > 0.7 * counts.mean()
    head = np.bincount(r[r < 4], minlength=4).astype(float)
    assert 0.4 < head[1] / head[0] < 0.6 and 0.2 < head[3] / head[0] < 0.3


def test_the_command_refuses_a_cpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "serve-read", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "cpu" in p.stderr
