"""Device-resident serving engine vs the preserved host engine.

The device ``ClusterQueueStore`` (MVCC snapshots + one jitted dispatch
per request batch) must be an observably identical replacement for the
seqlock ``HostQueueStore`` on every retrieval it serves.  For
non-decreasing-timestamp streams the contract is *bitwise* equality —
pinned here across seeds, ring wraps, dup-heavy streams, unknown and
post-snapshot user ids, recency-cutoff edges, and empty queues — in
direct mode, delta (LSM) mode, and through the sharded router.

The one documented tolerance: the engines dedup at different times
(device at ingest, latest-ingest-wins; host at retrieve,
newest-timestamp-wins), so a duplicate ``(cluster, item)`` re-ingested
in a *later batch* with an *older timestamp* diverges iff the recency
cutoff falls between the two timestamps.  That exact window is pinned
below too.
"""
import numpy as np
import pytest

import jax

from repro.core.serving import (ClusterQueueStore, HostQueueStore,
                                ServingCostModel, ShardedQueueStore,
                                u2i2i_retrieve_batch)
from repro.obs.telemetry import Telemetry


# ---------------------------------------------------------------------------
# stream + comparison helpers
# ---------------------------------------------------------------------------

N_USERS, N_CLUSTERS, N_ITEMS = 32, 6, 10      # tiny item space: dup-heavy


def _clusters(rng):
    return rng.integers(0, N_CLUSTERS, N_USERS).astype(np.int64)


def _batches(rng, n_batches, t0=0.0, span=10.0, id_hi=N_USERS + 4):
    """Batched event stream with globally non-decreasing timestamps.
    ``id_hi`` past the table size mixes in post-snapshot (unknown) ids;
    empty batches exercise the no-op ingest path."""
    out, t = [], t0
    for b in range(n_batches):
        n = int(rng.integers(0, 40))          # 0 => empty-batch edge
        u = rng.integers(0, id_hi, n)
        it = rng.integers(0, N_ITEMS, n)
        ts = t + np.sort(rng.random(n)) * span
        t += span
        out.append((u, it, ts))
    return out


# probe users: known, repeated, never-ingested clusters, post-snapshot
# ids, and a negative id — every row class the engines must agree on
_PROBES = np.array([0, 1, 1, 5, 17, 31, N_USERS, N_USERS + 9, -1])


def _assert_parity(dev, host, now, ks=(4, 8)):
    for k in ks:
        np.testing.assert_array_equal(
            dev.retrieve_batch(_PROBES, now, k),
            host.retrieve_batch(_PROBES, now, k))
    np.testing.assert_array_equal(dev.cursor, host.cursor)


def _run_stream_parity(dev, host, rng):
    """Ingest the same stream into both engines, checking parity after
    every batch at recency-edge ``now`` values (cutoff before, inside,
    and after the retained window)."""
    for u, it, ts in _batches(rng, 7):
        dev.ingest(u, it, ts)
        host.ingest(u, it, ts)
        t_end = float(ts[-1]) if ts.size else 70.0
        for now in (t_end, t_end + 25.0, t_end + 49.9, t_end + 200.0):
            _assert_parity(dev, host, now)


@pytest.mark.parametrize("seed", range(4))
def test_direct_mode_matches_host_bitwise(seed):
    rng = np.random.default_rng(seed)
    flat = _clusters(rng)
    # queue_len 8 << events per cluster: every cluster wraps repeatedly
    dev = ClusterQueueStore(flat, queue_len=8, recency_s=50.0)
    host = HostQueueStore(flat, queue_len=8, recency_s=50.0)
    _run_stream_parity(dev, host, rng)


@pytest.mark.parametrize("seed", range(3))
def test_delta_mode_matches_host_bitwise(seed):
    """LSM writes: small ``delta_cap`` forces mid-stream folds; reads
    that see a part-filled delta must still match the host."""
    rng = np.random.default_rng(100 + seed)
    flat = _clusters(rng)
    dev = ClusterQueueStore(flat, queue_len=8, recency_s=50.0,
                            delta_cap=16)
    host = HostQueueStore(flat, queue_len=8, recency_s=50.0)
    _run_stream_parity(dev, host, rng)


@pytest.mark.parametrize("seed", range(3))
def test_sharded_router_matches_host_bitwise(seed):
    """3 shards over 6 clusters: scatter-ingest + gather-merge retrieve
    must be transparent — bitwise equal to the unsharded host."""
    rng = np.random.default_rng(200 + seed)
    flat = _clusters(rng)
    dev = ShardedQueueStore(flat, n_shards=3, queue_len=8,
                            recency_s=50.0)
    host = HostQueueStore(flat, queue_len=8, recency_s=50.0)
    assert len(dev.partitions()) == 3
    _run_stream_parity(dev, host, rng)


def test_empty_store_unknown_users_and_retrieve_list_api():
    flat = _clusters(np.random.default_rng(0))
    dev = ClusterQueueStore(flat, queue_len=8, recency_s=50.0)
    host = HostQueueStore(flat, queue_len=8, recency_s=50.0)
    # nothing ingested: every row is all -1 on both engines
    _assert_parity(dev, host, now=10.0)
    assert (dev.retrieve_batch(_PROBES, 10.0, 4) == -1).all()
    dev.ingest(np.array([0]), np.array([3]), np.array([1.0]))
    host.ingest(np.array([0]), np.array([3]), np.array([1.0]))
    assert dev.retrieve(0, 2.0, 4) == host.retrieve(0, 2.0, 4)
    assert dev.retrieve(N_USERS + 1, 2.0, 4) == []   # post-snapshot id


def test_ts_regression_cross_batch_is_the_documented_tolerance():
    """The one permitted divergence, pinned to its exact window: a
    duplicate re-ingested in a later batch with an older timestamp.
    Device keeps the re-ingested (older) stamp, host keeps the newest;
    they disagree iff the cutoff lands between the two stamps."""
    flat = np.zeros(1, np.int64)
    dev = ClusterQueueStore(flat, queue_len=8, recency_s=50.0)
    host = HostQueueStore(flat, queue_len=8, recency_s=50.0)
    for s in (dev, host):
        s.ingest(np.array([0]), np.array([7]), np.array([10.0]))
        s.ingest(np.array([0]), np.array([7]), np.array([5.0]))  # older!
    u = np.array([0])
    # cutoff below both stamps (now=54 -> cutoff 4): both return it
    np.testing.assert_array_equal(dev.retrieve_batch(u, 54.0, 4),
                                  host.retrieve_batch(u, 54.0, 4))
    # cutoff between the stamps (now=57 -> cutoff 7): the divergence
    assert host.retrieve_batch(u, 57.0, 4)[0, 0] == 7
    assert (dev.retrieve_batch(u, 57.0, 4) == -1).all()
    # cutoff above both (now=61 -> cutoff 11): both empty again
    np.testing.assert_array_equal(dev.retrieve_batch(u, 61.0, 4),
                                  host.retrieve_batch(u, 61.0, 4))


def _ingest_both(stores, rng, n_batches=5):
    for u, it, ts in _batches(rng, n_batches):
        for s in stores:
            s.ingest(u, it, ts)


def test_fused_serve_matches_host_u2i2i():
    """The single-dispatch serve (retrieve + U2I2I union in one jit)
    must be bitwise equal to the host's two-step path."""
    rng = np.random.default_rng(7)
    flat = _clusters(rng)
    dev = ClusterQueueStore(flat, queue_len=8, recency_s=1e9)
    shd = ShardedQueueStore(flat, n_shards=2, queue_len=8, recency_s=1e9)
    host = HostQueueStore(flat, queue_len=8, recency_s=1e9)
    _ingest_both((dev, shd, host), rng)
    i2i = rng.integers(0, N_ITEMS, (N_ITEMS, 3)).astype(np.int64)
    hs, hu = host.serve_batch(_PROBES, 100.0, n_recent=4, k=8, i2i=i2i)
    for store in (dev, shd):
        seeds, union = store.serve_batch(_PROBES, 100.0, n_recent=4,
                                         k=8, i2i=i2i)
        np.testing.assert_array_equal(seeds, hs)
        np.testing.assert_array_equal(union, hu)
        np.testing.assert_array_equal(
            union, u2i2i_retrieve_batch(i2i, seeds, 8))
    # no i2i table: seeds only, union all -1
    seeds, union = dev.serve_batch(_PROBES, 100.0, n_recent=4, k=8)
    np.testing.assert_array_equal(seeds, hs)
    assert (union == -1).all()


def test_kernel_serve_path_matches_fused():
    """``use_kernel=True`` routes the device store's ring view through
    the fused Pallas ``queue_gather`` kernel — same answers."""
    rng = np.random.default_rng(9)
    flat = _clusters(rng)
    dev = ClusterQueueStore(flat, queue_len=8, recency_s=1e9)
    _ingest_both((dev,), rng)
    i2i = rng.integers(0, N_ITEMS, (N_ITEMS, 3)).astype(np.int64)
    s0, u0 = dev.serve_batch(_PROBES, 100.0, n_recent=4, k=8, i2i=i2i)
    s1, u1 = dev.serve_batch(_PROBES, 100.0, n_recent=4, k=8, i2i=i2i,
                             use_kernel=True)
    np.testing.assert_array_equal(s1, s0)
    np.testing.assert_array_equal(u1, u0)


# ---------------------------------------------------------------------------
# stats, telemetry, cost model, mesh placement
# ---------------------------------------------------------------------------

def test_stats_per_shard_and_delta_pending():
    rng = np.random.default_rng(3)
    flat = _clusters(rng)
    shd = ShardedQueueStore(flat, n_shards=3, queue_len=8,
                            recency_s=1e9, delta_cap=64)
    _ingest_both((shd,), rng, n_batches=3)
    st = shd.stats()
    assert st["n_shards"] == 3.0
    for s in range(3):
        assert f"shard{s}.n_clusters_active" in st
        assert f"shard{s}.mean_queue" in st
    assert sum(st[f"shard{s}.n_clusters_active"] for s in range(3)) \
        == st["n_clusters_active"]
    # folding drains the pending delta
    pending = [p.stats()["delta_pending"] for p in shd.partitions()]
    for p in shd.partitions():
        p._fold()
    assert any(x > 0 for x in pending) or shd.cursor.sum() == 0
    assert all(p.stats()["delta_pending"] == 0.0
               for p in shd.partitions())


def test_sharded_telemetry_tagged_counters_and_gauges():
    """Shards emit ``.shardN``-tagged metrics; the facade emits the
    untagged aggregates — tagged ingest counts must sum to the
    aggregate, and every shard publishes its own depth gauges."""
    rng = np.random.default_rng(5)
    flat = _clusters(rng)
    tel = Telemetry()
    shd = ShardedQueueStore(flat, n_shards=2, queue_len=8,
                            recency_s=1e9, telemetry=tel)
    u = rng.integers(0, N_USERS, 64)
    it = rng.integers(0, N_ITEMS, 64)
    shd.ingest(u, it, np.sort(rng.random(64) * 10.0))
    shd.retrieve_batch(np.arange(8), 20.0, 4)
    snap = tel.snapshot()
    c, g = snap["counters"], snap["gauges"]
    assert c["serving.ingest_events"] == 64.0
    assert (c.get("serving.ingest_events.shard0", 0.0)
            + c.get("serving.ingest_events.shard1", 0.0)) == 64.0
    assert c["serving.retrieve_requests"] == 1.0
    assert "serving.queue_depth_max" in g
    for s in range(2):
        if c.get(f"serving.ingest_events.shard{s}", 0.0):
            assert f"serving.queue_depth_max.shard{s}" in g
    assert snap["hists"]["serving.retrieve_latency_s"].get("n", 0) >= 1


# ---------------------------------------------------------------------------
# spans and counters inside serve and ingest
# ---------------------------------------------------------------------------

SERVE_CHILDREN = ["serving.serve.prep", "serving.serve.dispatch",
                  "serving.serve.fetch", "serving.serve.expand"]


def _children(spans, parent):
    return [s for s in spans if s["parent_id"] == parent["span_id"]]


def test_serve_batch_spans_and_counters_agree_with_padding():
    rng = np.random.default_rng(21)
    flat = _clusters(rng)
    tel = Telemetry()
    dev = ClusterQueueStore(flat, queue_len=8, recency_s=1e9,
                            telemetry=tel)
    _ingest_both((dev,), rng)
    i2i = rng.integers(0, N_ITEMS, (N_ITEMS, 3)).astype(np.int64)
    tel.reset_spans()
    tel.reset_metrics()
    batches = [_PROBES, np.arange(N_USERS), np.array([3])]
    for users in batches:
        dev.serve_batch(users, 100.0, n_recent=4, k=8, i2i=i2i)
    spans = tel.spans()
    parents = [s for s in spans if s["name"] == "serving.serve_batch"]
    assert len(parents) == len(batches)
    rows = padded = 0
    for users, parent in zip(batches, parents):
        assert parent["parent_id"] is None
        kids = _children(spans, parent)
        assert [k["name"] for k in kids] == SERVE_CHILDREN
        assert sum(k["dur_s"] for k in kids) <= parent["dur_s"]
        cl_p, _, Bu, _, _ = dev._padded_clusters(users)
        assert parent["attrs"] == {"requests": users.size, "unique": Bu,
                                   "width": cl_p.size}
        rows += Bu
        padded += cl_p.size
    c = tel.snapshot()["counters"]
    assert c["serving.serve_calls"] == len(batches)
    assert c["serving.serve_rows"] == rows
    assert c["serving.serve_rows_padded"] == padded
    lat = tel.snapshot()["hists"]["serving.retrieve_latency_s"]
    assert lat["n"] == len(batches)
    assert lat["sum"] == pytest.approx(sum(p["dur_s"] for p in parents))


@pytest.mark.parametrize("delta_cap", [0, 16])
def test_ingest_spans_nest_under_swap_and_store(delta_cap):
    from repro.lifecycle.snapshot import IndexSnapshot
    from repro.lifecycle.swap import SwapServer
    rng = np.random.default_rng(22)
    flat = _clusters(rng)
    snap = IndexSnapshot(
        user_codes=np.stack([flat // 3, flat % 3], 1),
        item_codes=np.zeros((N_ITEMS, 2), np.int32), user_clusters=flat,
        member_ptr=np.zeros(N_CLUSTERS + 1, np.int64),
        member_ids=np.zeros(0, np.int64),
        coarse_codebook=np.zeros((2, 4), np.float32),
        i2i=np.zeros((N_ITEMS, 3), np.int64), version=1, n_users=N_USERS,
        n_items=N_ITEMS, codebook_sizes=(2, 3))
    tel = Telemetry()
    srv = SwapServer(snap, queue_len=8, recency_s=1e9, telemetry=tel,
                     delta_cap=delta_cap)
    u = rng.integers(0, N_USERS, 40)
    srv.ingest(u, rng.integers(0, N_ITEMS, 40), np.sort(rng.random(40)))
    spans = tel.spans()
    by = {s["name"]: s for s in spans}
    assert by["swap.ingest"]["parent_id"] is None
    assert by["swap.ingest"]["attrs"] == {"events": 40}
    assert [k["name"] for k in _children(spans, by["swap.ingest"])] == \
        ["swap.ring_push", "swap.drain"]
    ing = by["serving.ingest"]
    assert ing["parent_id"] == by["swap.drain"]["span_id"]
    names = [k["name"] for k in _children(spans, ing)]
    assert names[:2] == ["serving.ingest.lookup", "serving.ingest.prep"]
    assert "serving.ingest.dispatch" in names
    assert set(names) == {"serving.ingest.lookup", "serving.ingest.prep",
                          "serving.ingest.dispatch"}
    E = 40
    width = 64 if delta_cap == 0 else 16 + 16 + 8
    assert ing["attrs"] == {"events": E,
                            "clusters": np.unique(flat[u]).size,
                            "width": width}
    # every span of the call is accounted for
    assert {s["name"] for s in spans} == {
        "swap.ingest", "swap.ring_push", "swap.drain", "serving.ingest",
        "serving.ingest.lookup", "serving.ingest.prep",
        "serving.ingest.dispatch"}


def test_sharded_spans_hang_under_the_router_with_a_shard():
    rng = np.random.default_rng(23)
    flat = _clusters(rng)
    tel = Telemetry()
    shd = ShardedQueueStore(flat, n_shards=2, queue_len=8, recency_s=1e9,
                            telemetry=tel)
    _ingest_both((shd,), rng, n_batches=2)
    i2i = rng.integers(0, N_ITEMS, (N_ITEMS, 3)).astype(np.int64)
    shd.serve_batch(np.arange(N_USERS), 100.0, n_recent=4, k=8, i2i=i2i)
    spans = tel.spans()
    serve = [s for s in spans if s["name"] == "serving.serve_batch"]
    assert len(serve) == 1 and serve[0]["parent_id"] is None
    kids = _children(spans, serve[0])
    assert sorted({k["attrs"]["shard"] for k in kids}) == [0, 1]
    for shard in (0, 1):
        assert [k["name"] for k in kids
                if k["attrs"]["shard"] == shard] == SERVE_CHILDREN
    for ing in [s for s in spans if s["name"] == "serving.ingest"]:
        assert ing["parent_id"] is None
        for k in _children(spans, ing):
            assert k["name"].startswith("serving.ingest.")
    shard_kids = [s for s in spans if "shard" in s["attrs"]]
    assert {s["name"] for s in shard_kids} >= {
        "serving.ingest.lookup", "serving.ingest.prep",
        "serving.ingest.dispatch", *SERVE_CHILDREN}
    c = tel.snapshot()["counters"]
    assert c["serving.serve_calls"] == 1.0
    assert c["serving.serve_rows"] == (c["serving.serve_rows.shard0"]
                                       + c["serving.serve_rows.shard1"])


def test_cost_model_shard_and_batch_scaling():
    """Launch overheads scale with the shard count and amortize with
    the dispatch batch; per-request queue work does neither."""
    one = ServingCostModel(batch_size=1, n_shards=1)
    four = ServingCostModel(batch_size=1, n_shards=4)
    per_req_bytes = 8.0 * one.queue_read_items + 8.0
    assert four.cluster_bytes_per_req() - per_req_bytes \
        == pytest.approx(4 * (one.cluster_bytes_per_req()
                              - per_req_bytes))
    assert four.cluster_flops_per_req() > one.cluster_flops_per_req()
    # batching amortizes the extra dispatches away
    assert four.cluster_bytes_per_req(batch_size=256) \
        < one.cluster_bytes_per_req(batch_size=1)
    assert four.cost_reduction(batch_size=256) \
        > four.cost_reduction(batch_size=1)
    assert one.cost_reduction(batch_size=256) > 0.99


def test_mesh_placement_smoke():
    """With a mesh, shard state is placed round-robin over its devices
    and answers are unchanged."""
    rng = np.random.default_rng(13)
    flat = _clusters(rng)
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("shards",))
    shd = ShardedQueueStore(flat, n_shards=2, queue_len=8,
                            recency_s=1e9, mesh=mesh)
    host = HostQueueStore(flat, queue_len=8, recency_s=1e9)
    _ingest_both((shd, host), rng, n_batches=3)
    np.testing.assert_array_equal(
        shd.retrieve_batch(_PROBES, 100.0, 8),
        host.retrieve_batch(_PROBES, 100.0, 8))
    devs = set(np.asarray(mesh.devices).ravel().tolist())
    for p in shd.partitions():
        arr = p._state["items"]
        assert set(arr.devices()) <= devs
