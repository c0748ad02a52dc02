"""Tiny sizes of the benchmark's cells, and one run of a cell through
the harness on the CPU (the accelerator check skipped)."""
import io
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness as H  # noqa: E402

SERVE = dict(n_clusters=400, codebook_sizes=[20, 20], n_users=3000,
             n_items=2000, queue_len=16, ring_capacity=512,
             history_events=512 * 12)
SERVE_TRAFFIC = dict(read_rate=300, max_batch=64, max_ingest=512,
                     check_sample=200)
TRAIN = dict(d_user_feat=32, d_item_feat=32, d_embed=16, n_heads=2,
             d_hidden=24, k_imp=6, k_train=3, n_negatives=16, n_pool_neg=4,
             codebook_sizes=[16, 8], hist_len=20, reset_probe=32,
             batch_per_type=64, n_users=400, n_items=300, edges_ui=3000,
             edges_uu=2000, edges_ii=2000, min_fill=2, dtype="float32",
             limits={"loss_gap": 1e-3, "grad_gap": 1e-3,
                     "change_gap": 1e-3})
TRAIN_TRAFFIC = dict(steps_per_burst=3, warm_bursts=6, pack_margin=0.0)


def run(workload, seconds=1.0, *, seed=2 ** 31 + 17, control=False,
        config=None, traffic=None):
    """One tiny run of ``workload``; returns the parsed result line."""
    import jax
    serve = workload.startswith("serve")
    cfg = dict(H.load_config("rankgraph2-serve" if serve
                             else "rankgraph2-train"),
               **(SERVE if serve else TRAIN), **(config or {}))
    tr = dict(H.load_traffic(workload),
              **(SERVE_TRAFFIC if cfg.get("queue_len") else TRAIN_TRAFFIC),
              **(traffic or {}))
    import run as R
    args = types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=0)
    out = io.StringIO()
    rc = R.run_cell(args, devices=jax.devices()[:1], config=cfg,
                    traffic=tr, t_start=time.perf_counter(),
                    control=control, use_cache=False, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
