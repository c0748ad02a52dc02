"""Train-step throughput: PR-3 baseline vs the reworked hot path.

    PYTHONPATH=src python -m benchmarks.train_throughput
    PYTHONPATH=src TRAIN_MIN_SPEEDUP=1.5 python -m benchmarks.train_throughput

Three configurations of the *same* model / edge draws:

  baseline   — PR-3 semantics: legacy per-endpoint batches (every
               endpoint occurrence host-gathered and re-encoded),
               double negative draws for L', undonated jit;
  dedup      — packed unique-node batches: every referenced node
               encoded once, negatives reused between L and L',
               donated step;
  dedup_ids  — dedup + id-only batches: features gathered inside the
               jitted step from a device-resident FeatureStore (host
               ships int32 ids + masks instead of (B, K, d) float32).

End-to-end per-step time is measured (host batch construction + device
step), since the host gather is exactly what the id-only path removes.
Asserts dedup_ids >= TRAIN_MIN_SPEEDUP x baseline (default 1.5).
"""
from __future__ import annotations

import os
import time
from typing import Dict

import numpy as np

from benchmarks.common import write_result


def _bench_cfg():
    from repro.configs.base import RankGraph2Config, RQConfig
    return RankGraph2Config(
        d_user_feat=64, d_item_feat=64, d_embed=48, n_heads=2,
        d_hidden=128, k_imp=20, k_train=10, n_negatives=50, n_pool_neg=16,
        k_cap=32, ppr_walks=32, ppr_len=4, ppr_restart=0.3,
        rq=RQConfig(codebook_sizes=(64, 16), hist_len=100),
        dtype="float32")


def _time_mode(name: str, cfg, ds, fmt: str, *, steps: int,
               batch_per_type: int, features=None, donate: bool = True,
               seed: int = 0) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    from repro.core import trainer as T

    state, _, opt = T.init_state(jax.random.key(seed), cfg, pool_size=2048)
    step_fn = T.make_train_step(cfg, opt, donate=donate)
    per_type = {et: batch_per_type for et in ("uu", "ui", "ii")}

    def one(t):
        batch = jax.tree.map(jnp.asarray,
                             ds.sample_batch(t, seed, per_type, format=fmt))
        return step_fn(state_box[0], batch, jax.random.key(1000 + t),
                       features)

    # warmup pass over the *same* (seed, step) range the measurement
    # will replay: every pack-size bucket the measured pass can hit is
    # compiled here, so the timing contains no trace/compile events
    state_box = [state]
    m = None
    for t in range(steps):
        state_box[0], m = one(t)
    jax.block_until_ready(m["total"])

    t0 = time.perf_counter()
    for t in range(steps):
        state_box[0], m = one(t)
    jax.block_until_ready(m["total"])
    dt = time.perf_counter() - t0

    edges = 3 * batch_per_type
    out = dict(seconds_per_step=dt / steps,
               edges_per_second=edges * steps / dt,
               total=float(m["total"]))
    print(f"  {name:<10s} {out['seconds_per_step']*1e3:8.1f} ms/step  "
          f"{out['edges_per_second']:9.0f} edges/s  "
          f"(total={out['total']:.3f})")
    return out


def run(full: bool = False) -> Dict:
    import dataclasses
    from repro.core.graph_builder import build_graph
    from repro.core import trainer as T
    from repro.data.edge_dataset import EdgeDataset, build_neighbor_tables
    from repro.data.synthetic import make_world

    cfg = _bench_cfg()
    n_users, n_items = (1200, 3000) if full else (600, 1500)
    steps = 30 if full else 16
    batch_per_type = 256
    world = make_world(n_users=n_users, n_items=n_items,
                       events_per_user=14.0, pop_strength=0.7, seed=7)
    g = build_graph(world.day0, k_cap=cfg.k_cap, seed=7)
    tables = build_neighbor_tables(g, k_imp=cfg.k_imp,
                                   n_walks=cfg.ppr_walks,
                                   walk_len=cfg.ppr_len, seed=7)
    ds = EdgeDataset(g, tables, world.user_feat, world.item_feat,
                     k_train=cfg.k_train)
    feats = T.make_feature_store(world.user_feat, world.item_feat)

    # batch stats: how much work dedup actually removes
    b = ds.sample_batch(0, 7, {et: batch_per_type
                               for et in ("uu", "ui", "ii")})
    slots = 3 * batch_per_type          # endpoint slots per node type
    enc_rows_legacy = 2 * slots * (1 + 2 * cfg.k_train)
    enc_rows_dedup = sum(b["nodes"][t]["feat"].shape[0]
                         for t in ("user", "item"))
    print(f"  encoder rows/step: legacy={enc_rows_legacy} "
          f"dedup={enc_rows_dedup} "
          f"({enc_rows_legacy / enc_rows_dedup:.1f}x dedup)")

    cfg_pr3 = dataclasses.replace(cfg, reuse_lprime_negatives=False)
    kw = dict(steps=steps, batch_per_type=batch_per_type)
    res = {
        "baseline": _time_mode("baseline", cfg_pr3, ds, "legacy",
                               donate=False, **kw),
        "dedup": _time_mode("dedup", cfg, ds, "dedup", **kw),
        "dedup_ids": _time_mode("dedup_ids", cfg, ds, "dedup_ids",
                                features=feats, **kw),
    }
    base = res["baseline"]["seconds_per_step"]
    out = dict(
        config=dict(n_users=n_users, n_items=n_items, steps=steps,
                    batch_per_type=batch_per_type,
                    k_train=cfg.k_train, n_negatives=cfg.n_negatives),
        encoder_rows=dict(legacy=enc_rows_legacy, dedup=enc_rows_dedup),
        modes=res,
        speedup_dedup=base / res["dedup"]["seconds_per_step"],
        speedup_dedup_ids=base / res["dedup_ids"]["seconds_per_step"],
    )
    print(f"  speedup: dedup={out['speedup_dedup']:.2f}x  "
          f"dedup+id-only={out['speedup_dedup_ids']:.2f}x")
    write_result("train_throughput", out)

    # CI gate: the reworked hot path must beat the PR-3 baseline.
    # Shared runners are noisy — tune via TRAIN_MIN_SPEEDUP.
    min_speedup = float(os.environ.get("TRAIN_MIN_SPEEDUP", "1.5"))
    assert out["speedup_dedup_ids"] >= min_speedup, \
        (f"dedup+id-only step only {out['speedup_dedup_ids']:.2f}x over "
         f"baseline (< {min_speedup}x)")
    return out


if __name__ == "__main__":
    run(full=os.environ.get("FULL") == "1")
