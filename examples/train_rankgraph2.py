"""End-to-end training driver with production plumbing.

Continuous-training loop with everything a cluster deployment needs:
host-side prefetch overlap, periodic async checkpointing, preemption
(SIGTERM) handling, crash-resume from the latest checkpoint, periodic
graph rebuild (the 3h refresh cycle, scaled down), eval, and RQ-index
health monitoring.

    PYTHONPATH=src python examples/train_rankgraph2.py --steps 300
    PYTHONPATH=src python examples/train_rankgraph2.py --steps 600 \
        --ckpt-dir /tmp/rg2 --resume          # crash-resume
"""
import argparse
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import RankGraph2Config, RQConfig
from repro.core import evaluation as EV
from repro.core import rq_index as RQ
from repro.core import trainer as T
from repro.core.graph_builder import build_graph
from repro.data.edge_dataset import (EdgeDataset, Prefetcher,
                                     build_neighbor_tables)
from repro.data.synthetic import make_world
from repro.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--users", type=int, default=800)
    ap.add_argument("--items", type=int, default=1200)
    ap.add_argument("--batch", type=int, default=96)
    ap.add_argument("--ckpt-dir", default="/tmp/rankgraph2_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--rebuild-every", type=int, default=200,
                    help="graph-refresh cadence (the 3h cycle, scaled)")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = RankGraph2Config(
        d_user_feat=64, d_item_feat=64, d_embed=48, n_heads=2,
        d_hidden=128, k_imp=16, k_train=6, n_negatives=32, n_pool_neg=8,
        rq=RQConfig(codebook_sizes=(64, 16), hist_len=100),
        dtype="float32")

    world = make_world(n_users=args.users, n_items=args.items, seed=0)

    def build(window_end):
        g = build_graph(world.day0.window(window_end, 86400.0),
                        k_cap=cfg.k_cap)
        tables = build_neighbor_tables(g, k_imp=cfg.k_imp,
                                       n_walks=cfg.ppr_walks,
                                       walk_len=cfg.ppr_len)
        # id-only batches: the prefetch thread ships ids + masks only;
        # features stay device-resident in the step's FeatureStore
        return EdgeDataset(g, tables, world.user_feat, world.item_feat,
                           k_train=cfg.k_train, batch_format="dedup_ids")

    ds = build(86400.0)
    state, specs, optimizer = T.init_state(jax.random.key(0), cfg,
                                           pool_size=4096)
    step_fn = T.make_train_step(cfg, optimizer)
    feats = T.make_feature_store(world.user_feat, world.item_feat)

    ck = Checkpointer(args.ckpt_dir, keep=3)
    start = 0
    if args.resume and ck.latest_step() is not None:
        state, meta = ck.restore(state)
        start = int(meta["step"])
        print(f"resumed from step {start}")

    # preemption: cooperative SIGTERM — the step is donated, so while a
    # step is in flight the previous state's buffers are already gone
    # and a save from inside the signal handler could read dead memory.
    # The handler only sets a flag; the loop saves right after the next
    # step returns (a fully-materialized state) and exits 143.
    preempted = {"flag": False}
    signal.signal(signal.SIGTERM,
                  lambda *_: preempted.update(flag=True))

    per_type = {"uu": args.batch, "ui": args.batch, "ii": args.batch}
    prefetch = Prefetcher(ds.iter_batches(0, per_type, start_step=start),
                          depth=2)
    t0 = time.perf_counter()
    for t in range(start, args.steps):
        if t and t % args.rebuild_every == 0:
            # hour-level refresh: rebuild on the shifted window and swap
            # the dataset under the same model (self-contained data!)
            prefetch.close()
            ds = build(86400.0)
            prefetch = Prefetcher(ds.iter_batches(0, per_type,
                                                  start_step=t), depth=2)
            print(f"[{t}] graph rebuilt in {ds.g.build_seconds:.1f}s")
        batch = jax.tree.map(jnp.asarray, next(prefetch))
        state, m = step_fn(state, batch, jax.random.key(7000 + t), feats)
        if preempted["flag"]:
            ck.save(int(state.step), state,
                    metadata={"data_seed": 0, "preempted": True,
                              "preempted_at": time.time()}, blocking=True)
            prefetch.close()
            raise SystemExit(143)
        if t % 50 == 0:
            util = RQ.codebook_utilization(state.rq_state)
            print(f"[{t}] total={float(m['total']):.3f} "
                  f"infonce_ui={float(m['infonce_ui']):.3f} "
                  f"codebook_util={[round(u, 2) for u in util]} "
                  f"({(t - start + 1) / (time.perf_counter() - t0):.1f} "
                  f"steps/s)")
        if t and t % args.ckpt_every == 0:
            ck.save(t, state, metadata={"data_seed": 0}, blocking=False)
    ck.save(args.steps, state,
            metadata={"data_seed": 0, "preempted": preempted["flag"]},
            blocking=True)
    prefetch.close()
    if preempted["flag"]:   # SIGTERM after the last in-loop check
        raise SystemExit(143)

    # embedding refresh + eval
    from repro.core import model as M
    user_emb = T.embed_all(state.params, cfg, ds, node_type=M.USER,
                           ids=np.arange(world.n_users))
    rec = EV.user_recall(user_emb, world, n_queries=300)
    print("final user Recall@K:", {k: round(v, 3) for k, v in rec.items()})
    print(f"checkpoints in {args.ckpt_dir}: steps {ck.all_steps()}")
    if preempted["flag"]:   # SIGTERM during embed/eval: still exit 143
        raise SystemExit(143)


if __name__ == "__main__":
    enable_compile_cache()
    main()
