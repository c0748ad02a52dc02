"""End-to-end lifecycle: construction -> training -> publish -> refresh
-> atomic hot-swap -> serving, on the synthetic world.

This is the paper's co-design loop closed for the first time: the graph
built from the engagement log feeds training; training co-learns the RQ
cluster index; publication pushes every embedding through the trained
codebooks into a versioned ``IndexSnapshot``; the serving tier flips to
the new version atomically while ingesting live events — no online KNN
anywhere.  The published index must retain >= 0.8x of exact-KNN
Recall@100 (the CI gate threshold), checked via ``core/evaluation``.

    PYTHONPATH=src python examples/lifecycle_e2e.py

Every stage emits telemetry (spans + counters + latency histograms) to
``$OBS_JSONL`` (default ``/tmp/rankgraph2_obs/lifecycle_e2e.jsonl``);
render the per-stage latency breakdown afterwards with

    PYTHONPATH=src python -m repro.obs.report \
        /tmp/rankgraph2_obs/lifecycle_e2e.jsonl
"""
import os

import numpy as np

from repro import obs
from repro.configs.base import RankGraph2Config, RQConfig
from repro.core.graph_builder import EngagementLog, build_graph
from repro.data.edge_dataset import build_neighbor_tables
from repro.data.synthetic import make_world
from repro.lifecycle import LifecycleConfig, LifecycleRuntime
from repro.compile_cache import enable_compile_cache


def main(snapshot_dir="/tmp/rankgraph2_snapshots"):
    trace_path = os.environ.get(
        "OBS_JSONL", "/tmp/rankgraph2_obs/lifecycle_e2e.jsonl")
    os.makedirs(os.path.dirname(trace_path), exist_ok=True)
    if os.path.exists(trace_path):
        os.remove(trace_path)            # one run per trace file
    tel = obs.configure(path=trace_path)
    world = make_world(n_users=500, n_items=800, events_per_user=20.0,
                       seed=1)
    cfg = RankGraph2Config(
        d_user_feat=64, d_item_feat=64, d_embed=32, n_heads=2, d_hidden=96,
        k_imp=10, k_train=4, n_negatives=24, n_pool_neg=8,
        rq=RQConfig(codebook_sizes=(16, 4), hist_len=50), dtype="float32")
    lcfg = LifecycleConfig(steps_per_cycle=150, batch_per_type=64,
                           i2i_k=12, recency_s=2 * 86400.0,
                           recall_k=100, recall_queries=300,
                           min_recall_ratio=0.8)

    # --- construction: the "yesterday" build on the first 23 hours ----------
    log = world.day0
    m = log.timestamp <= 82800.0
    old = EngagementLog(log.user_id[m], log.item_id[m], log.event_type[m],
                        log.timestamp[m], log.n_users, log.n_items)
    with tel.span("e2e.construct") as sp:
        g = build_graph(old, k_cap=16, hub_cap=24, keep_state=True)
        tables = build_neighbor_tables(g, k_imp=10, n_walks=16,
                                       walk_len=3, backend="jax",
                                       keep_state=True)
    print(f"construction: {g.n_edges} edges in {sp.elapsed():.2f}s")

    # --- cycle 0: train -> publish v1 -> bring serving up -------------------
    rt = LifecycleRuntime(cfg, lcfg, g, tables, world.user_feat,
                          world.item_feat, world=world,
                          snapshot_dir=snapshot_dir, seed=0)
    rep = rt.run_cycle(now=86400.0)
    pub = rep["publish"]
    print(f"cycle 0: published v{pub['version']}  "
          f"recall@100 index={pub['recall_index']:.3f} "
          f"exact={pub['recall_exact']:.3f} "
          f"(ratio {pub['recall_ratio']:.3f})")

    # --- live traffic against v1 --------------------------------------------
    d1 = world.day1
    with tel.span("e2e.serve", n_requests=512):
        rt.server.ingest(d1.user_id, d1.item_id, d1.timestamp)
        now = float(d1.timestamp.max())
        users = np.random.default_rng(0).integers(0, world.n_users, 512)
        seeds, union, ver = rt.server.serve_batch(users, now,
                                                  n_recent=8, k=32)
    print(f"serving v{ver}: {int((union >= 0).sum())} U2I2I candidates "
          f"for {len(users)} requests")

    # --- cycle 1: the trailing hour splices in, with brand-new users AND
    # --- items joining — both flow through publication into the index ------
    delta = log.window(86400.0, 3600.0)
    nu_new, ni_new = log.n_users + 5, log.n_items + 5
    rng = np.random.default_rng(2)
    du = np.r_[delta.user_id, np.arange(log.n_users, nu_new),
               rng.integers(0, log.n_users, 5)]
    di = np.r_[delta.item_id, rng.integers(0, log.n_items, 5),
               np.arange(log.n_items, ni_new)]
    delta = EngagementLog(du.astype(np.int64), di.astype(np.int64),
                          np.zeros(len(du), np.int32),
                          np.full(len(du), 86400.0), nu_new, ni_new)
    uf = np.r_[world.user_feat,
               rng.normal(0, 1, (5, 64)).astype(np.float32)]
    itf = np.r_[world.item_feat,
                rng.normal(0, 1, (5, 64)).astype(np.float32)]
    rep = rt.run_cycle(delta, now=now, user_feat=uf, item_feat=itf,
                       backend="jax")
    r, p, s = rep["refresh"], rep["publish"], rep["swap"]
    assert not s.get("skipped"), \
        f"published index lost too much recall: {p['recall_ratio']:.3f}"
    print(f"cycle 1: re-walked {r['affected_nodes']} nodes in "
          f"{r['refresh_seconds']:.2f}s; published v{p['version']} "
          f"(ratio {p['recall_ratio']:.3f}); swap stall "
          f"{s['stall_ms']:.3f}ms, {int(s['replayed_events'])} events "
          f"re-keyed")

    # --- the new version serves the users that did not exist at v1 ---------
    fresh = np.arange(log.n_users, nu_new)
    res, ver = rt.server.retrieve_batch(fresh, now, 16)
    snap = rt.store.load()
    print(f"v{ver} serves {len(fresh)} brand-new users; "
          f"their clusters: {snap.user_clusters[fresh].tolist()}")

    # --- the acceptance gate -------------------------------------------------
    assert p["recall_ratio"] >= 0.8, \
        f"published index lost too much recall: {p['recall_ratio']:.3f}"
    assert ver == p["version"]

    # --- telemetry out -------------------------------------------------------
    tel.flush()
    pct = tel.percentiles("serving.retrieve_latency_s")
    print(f"telemetry: {trace_path}  retrieve p50={pct['p50']*1e3:.2f}ms "
          f"p95={pct['p95']*1e3:.2f}ms")
    print("lifecycle e2e OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
