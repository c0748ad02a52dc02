"""Serving example: KNN-free batched retrieval with the cluster index.

Simulates the production serving tier: a stream of engagement events
feeds the array-backed cluster ring buffers in real time; retrieval
requests are answered in batches by (a) U2U2I cluster-queue lookups and
(b) U2I2I via the offline I2I KNN table — no online nearest-neighbor
search anywhere.  Reports batched vs per-request-loop throughput, the
fused Pallas queue_gather path, and the production-scale cost model.

    PYTHONPATH=src python examples/serve_retrieval.py
"""
import time

import numpy as np

from repro.configs.base import RankGraph2Config, RQConfig
from repro.core.pipeline import run_pipeline
from repro.core.serving import (ClusterQueueStore, ServingCostModel,
                                build_i2i_knn, u2i2i_retrieve_batch)
from repro.data.synthetic import make_world
from repro.compile_cache import enable_compile_cache


def main():
    world = make_world(n_users=600, n_items=900, seed=1)
    cfg = RankGraph2Config(
        d_user_feat=64, d_item_feat=64, d_embed=32, n_heads=2, d_hidden=96,
        k_imp=12, k_train=4, n_negatives=24, n_pool_neg=8,
        rq=RQConfig(codebook_sizes=(32, 8), hist_len=50), dtype="float32")
    print("training (offline stage)...")
    res = run_pipeline(world, cfg, steps=150, batch_per_type=64)

    # --- offline artifacts the serving tier loads ---------------------------
    store = ClusterQueueStore(res.user_codes, queue_len=256,
                              recency_s=86400.0)
    i2i = build_i2i_knn(res.item_emb, k=20)    # refreshed per embed cycle

    # --- real-time ingestion (one vectorized pass) --------------------------
    d1 = world.day1
    t0 = time.perf_counter()
    store.ingest(d1.user_id, d1.item_id, d1.timestamp)
    print(f"ingested {len(d1.user_id)} events in "
          f"{time.perf_counter()-t0:.3f}s; {store.stats()}")

    # --- batched request path ------------------------------------------------
    now = float(d1.timestamp.max())
    rng = np.random.default_rng(0)
    users = rng.integers(0, world.n_users, 2048)

    store.retrieve_batch(users, now, 32)                     # warm
    t0 = time.perf_counter()
    seeds = store.retrieve_batch(users, now, 8)              # U2U2I
    u2u2i = store.retrieve_batch(users, now, 32)
    t_batch = (time.perf_counter() - t0) / len(users) / 2

    t0 = time.perf_counter()
    union = u2i2i_retrieve_batch(i2i, seeds, 32)             # U2I2I
    t_u2i2i = (time.perf_counter() - t0) / len(users)

    # same pass through the fused Pallas kernel (interpret mode on CPU)
    sk, uk = store.serve_batch(users[:64], now, n_recent=8, k=32, i2i=i2i,
                               use_kernel=True)
    sr, ur = store.serve_batch(users[:64], now, n_recent=8, k=32, i2i=i2i)
    assert (sk == sr).all() and (uk == ur).all(), "kernel disagrees"

    # --- the per-request loop this replaces ---------------------------------
    t0 = time.perf_counter()
    for u in users[:256]:
        store.retrieve(int(u), now, 32)
    t_loop = (time.perf_counter() - t0) / 256

    # --- and the system KNN-free serving replaces: online KNN ---------------
    emb = res.user_emb
    t0 = time.perf_counter()
    for u in users[:200]:
        sims = emb[int(u)] @ emb.T
        np.argpartition(-sims, 32)[:32]
    t_knn = (time.perf_counter() - t0) / 200

    cm = ServingCostModel(batch_size=len(users))
    print(f"\nper-request latency:  batched U2U2I {t_batch*1e6:.1f}us | "
          f"batched U2I2I {t_u2i2i*1e6:.1f}us | per-request loop "
          f"{t_loop*1e6:.0f}us | online-KNN {t_knn*1e6:.0f}us")
    print(f"batched-vs-loop speedup: {t_loop/max(t_batch, 1e-12):.1f}x   "
          f"(union served {int((union >= 0).sum())} candidates)")
    print(f"modeled production-scale serving cost reduction at batch="
          f"{cm.batch_size}: {cm.cost_reduction()*100:.1f}% (paper: 83%)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
