"""Hour-level incremental graph refresh (paper §4.2).

Builds the construction-stage artifacts on a 23h window, then splices
the trailing hour in with ``incremental_refresh`` — including items that
did not exist when the graph was built — instead of rebuilding from
scratch.  Fresh items without same-type co-engagement route through the
Group-2 KNN fallback over previous-run embeddings.

    PYTHONPATH=src python examples/refresh_graph.py
"""
import time

import numpy as np

from repro.core.graph_builder import EngagementLog, build_graph
from repro.data.edge_dataset import build_neighbor_tables, \
    incremental_refresh
from repro.data.synthetic import make_world
from repro.compile_cache import enable_compile_cache


def main():
    world = make_world(n_users=2000, n_items=4000, events_per_user=6.0,
                       seed=0)
    log = world.day0

    # 1) the "yesterday" build: first 23 hours
    m = log.timestamp <= 82800.0
    old = EngagementLog(log.user_id[m], log.item_id[m], log.event_type[m],
                        log.timestamp[m], log.n_users, log.n_items)
    t0 = time.perf_counter()
    g = build_graph(old, k_cap=16, hub_cap=24, keep_state=True)
    tables = build_neighbor_tables(g, k_imp=10, n_walks=16, walk_len=3,
                                   backend="jax", keep_state=True)
    t_build = time.perf_counter() - t0
    print(f"initial build: {g.n_edges} edges in {t_build:.2f}s")

    # 2) the trailing hour, with 5 brand-new items joining the catalog
    delta = log.window(86400.0, 3600.0)
    ni_new = log.n_items + 5
    rng = np.random.default_rng(1)
    fresh_u = rng.integers(0, log.n_users, 5).astype(np.int64)
    fresh_i = (log.n_items + np.arange(5)).astype(np.int64)
    delta = EngagementLog(
        np.r_[delta.user_id, fresh_u], np.r_[delta.item_id, fresh_i],
        np.r_[delta.event_type, np.zeros(5, np.int32)],
        np.r_[delta.timestamp, np.full(5, 86400.0)],
        log.n_users, ni_new)

    # previous-run embeddings for the Group-2 KNN fallback (in a live
    # deployment: yesterday's trained embeddings + content embeddings
    # for never-seen items; features here)
    fresh_feat = rng.normal(0, 1, (5, world.item_feat.shape[1])
                            ).astype(np.float32)
    prev_emb = np.r_[world.user_feat, world.item_feat, fresh_feat]

    t0 = time.perf_counter()
    g2, tables2, report = incremental_refresh(g, tables, delta,
                                              prev_emb=prev_emb,
                                              backend="jax")
    t_refresh = time.perf_counter() - t0
    n = g2.n_users + g2.n_items
    print(f"refresh: {len(delta.user_id)} delta events, "
          f"{len(report['affected_nodes'])}/{n} nodes re-walked "
          f"in {t_refresh:.2f}s ({t_refresh / t_build:.2f}x of the "
          f"initial build)")

    # 3) the new items are fully served by the refreshed tables
    for i in fresh_i:
        gid = g2.n_users + int(i)
        nbrs = tables2.item_nbrs[gid]
        print(f"  new item {int(i)}: group1={bool(g2.group1_items[i])} "
              f"same-type neighbors {[int(x) - g2.n_users for x in nbrs[:5] if x >= 0]}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
