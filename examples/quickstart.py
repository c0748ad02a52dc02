"""Quickstart: the full RankGraph-2 lifecycle in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.configs.base import RankGraph2Config, RQConfig
from repro.core import evaluation as EV
from repro.core.pipeline import run_pipeline
from repro.core.serving import ClusterQueueStore
from repro.data.synthetic import make_world
from repro.compile_cache import enable_compile_cache


def main():
    # 1) a synthetic engagement world (stand-in for the production log)
    world = make_world(n_users=500, n_items=800, seed=0)

    # 2) lifecycle: construct -> PPR -> co-train model + RQ index -> embed
    cfg = RankGraph2Config(
        d_user_feat=64, d_item_feat=64, d_embed=32, n_heads=2, d_hidden=96,
        k_imp=12, k_train=4, n_negatives=24, n_pool_neg=8, k_cap=24,
        rq=RQConfig(codebook_sizes=(32, 8), hist_len=50), dtype="float32")
    res = run_pipeline(world, cfg, steps=150, batch_per_type=64,
                       log_every=50)
    print(f"built graph: {res.graph.n_edges} edges "
          f"({res.seconds['construct']:.1f}s construct, "
          f"{res.seconds['ppr']:.1f}s PPR, {res.seconds['train']:.1f}s "
          f"train)")

    # 3) offline quality (paper §5.2 protocol)
    rec = EV.user_recall(res.user_emb, world, n_queries=200)
    print("user Recall@K:", {k: round(v, 3) for k, v in rec.items()})

    # 4) KNN-free serving: cluster queues keyed by the co-learned index
    store = ClusterQueueStore(res.user_codes, recency_s=86400.0)
    d1 = world.day1
    store.ingest(d1.user_id, d1.item_id, d1.timestamp)
    items = store.retrieve(user_id=7, now=float(d1.timestamp.max()), k=10)
    print(f"U2U2I retrieval for user 7 (cluster "
          f"{res.user_codes[7]}): {items}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
