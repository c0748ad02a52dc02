"""The serving cells at tiny sizes on the CPU: the reference agrees with
the program, and the control and each planted fault come out not
correct."""
import bench_tiny as B


def test_serve_read_agrees_with_the_reference():
    res = B.run("serve-read")
    assert res["correct"], res["checks"]
    assert res["attempted"] == 300 and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_p50_ms", "serve_p95_ms",
                                   "setup_s"}


def test_serve_control_keeping_duplicates_is_not_correct():
    res = B.run("serve-read", control=True)
    assert not res["correct"]
    assert res["checks"]["rows_wrong"]["value"] > 0


def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.core import serving
    real = serving.ClusterQueueStore.serve_batch

    def altered(self, *a, **kw):
        seeds, union = real(self, *a, **kw)
        union = union.copy()
        union[:, 0] = union[:, 0] + 1
        return seeds, union

    monkeypatch.setattr(serving.ClusterQueueStore, "serve_batch", altered)
    res = B.run("serve-read")
    assert not res["correct"]


def test_serve_mixed_agrees_with_the_reference():
    """The mixed mix (no cell runs it yet): reads checked against every
    state the concurrent ingests could have shown them."""
    res = B.run("serve-mixed", 1.5, traffic={"write_rate": 2000})
    assert res["correct"], res["checks"]
    assert res["attempted"] == 450 + 3000        # requests + events


def test_an_ingest_that_leaves_the_state_unchanged_is_caught(monkeypatch):
    import harness as H
    real_load = H.load_driver

    def load(name, *a, **kw):
        mod = real_load(name, *a, **kw)

        class Broken(mod.Cell):
            def measure(self, seconds):
                store = self.server.handle.acquire().store
                store._direct_ingest = lambda cl, it, rel: None
                return super().measure(seconds)

        mod.Cell = Broken
        return mod

    monkeypatch.setattr(H, "load_driver", load)
    res = B.run("serve-mixed", 1.5, traffic={"write_rate": 2000})
    assert not res["correct"]
