"""RankGraph-2 training loop (paper §4.3 + §4.4 co-learning).

One jit'd ``train_step`` consumes an edge-centric batch (all edge types),
computes per-type contrastive losses in both U-I directions, co-learns
the RQ index (reconstruction + contrastive-on-recon + balance
regularizer) and combines everything with learned uncertainty weights.
State (params, optimizer, RQ histograms, negative pool) is one pytree —
checkpointable and donated into the step.

Two batch layouts are supported (see ``data.edge_dataset``):

* **legacy** — per-(edge_type, side) feature tensors; each endpoint
  occurrence is re-encoded (the PR-3 reference path);
* **dedup / dedup_ids** — packed unique-node sub-batches per node type:
  every referenced node (endpoint *or* sampled neighbor) runs through
  the type encoder exactly once, endpoints are aggregated once, and
  per-edge heads/primaries are pure gathers.  With ``dedup_ids`` the
  batch is id-only and raw features are gathered inside the jitted step
  from a device-resident ``FeatureStore`` — the host ships int32 ids
  and masks instead of (B, K, d) float32 neighbor features.

Both layouts produce the same losses (up to float reduction order) on
the same edge draws; ``EdgeDataset.expand_batch`` materializes the
legacy view of a dedup batch for the equivalence tests.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RankGraph2Config
from repro.core import losses as L
from repro.core import model as M
from repro.core import negatives as N
from repro.core import rq_index as RQ
from repro.data.edge_dataset import pack_bucket
from repro.distributed.sharding import ShardingCtx, NULL_CTX
from repro.optim import optimizers as opt_lib


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    rq_state: RQ.RQState
    pool: N.NegPoolState
    step: jnp.ndarray


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt_state", "rq_state", "pool",
                             "step"], meta_fields=[])


@dataclasses.dataclass(frozen=True)
class FeatureStore:
    """Device-resident raw feature tables for id-only batches.

    Passed to the jitted step as an argument (never donated): the tables
    stay on device between steps, per-step host->device traffic is just
    the id / mask integers of the batch, and the compiled program holds
    no copy of the tables, whatever the corpus size."""
    user_feat: jnp.ndarray     # (n_users, d_user_feat) float32
    item_feat: jnp.ndarray     # (n_items, d_item_feat) float32


jax.tree_util.register_dataclass(
    FeatureStore, data_fields=["user_feat", "item_feat"], meta_fields=[])


def make_feature_store(user_feat: np.ndarray, item_feat: np.ndarray
                       ) -> FeatureStore:
    """Upload the tables, zero-padding their rows to a ``pack_bucket``
    size: ids never reach the pad, and an id space that grows by a few
    percent keeps the table shapes — and so the compiled step."""
    def put(x):
        x = np.asarray(x, np.float32)
        pad = pack_bucket(x.shape[0], 64) - x.shape[0]
        return jnp.asarray(np.pad(x, ((0, pad), (0, 0))))

    return FeatureStore(put(user_feat), put(item_feat))


def init_state(key, cfg: RankGraph2Config, *, pool_size: int = 8192,
               optimizer: Optional[opt_lib.Optimizer] = None
               ) -> Tuple[TrainState, Any, opt_lib.Optimizer]:
    k1, k2 = jax.random.split(key)
    params, specs = M.init_params(k1, cfg)
    rq_params, rq_specs, rq_state = RQ.init_rq(k2, cfg.rq, cfg.d_embed)
    params["rq"] = rq_params
    specs["rq"] = rq_specs
    params["uncertainty"] = L.init_uncertainty()
    specs["uncertainty"] = {k: None for k in params["uncertainty"]}
    optimizer = optimizer or opt_lib.rankgraph2_optimizer()
    opt_state = optimizer.init(params)
    pool = N.init_pool(pool_size, cfg.d_embed)
    state = TrainState(params, opt_state, rq_state, pool,
                       jnp.zeros((), jnp.int32))
    # the step is donated: jax's constant cache can alias identical
    # zero-init leaves and XLA rejects donating one buffer twice, so
    # give every leaf its own buffer once at init
    return jax.tree.map(jnp.copy, state), specs, optimizer


# edge type -> (src node type, dst node type)
_ET_TYPES = {"uu": (M.USER, M.USER), "ui": (M.USER, M.ITEM),
             "ii": (M.ITEM, M.ITEM)}
_NODE_TYPES = (("user", M.USER), ("item", M.ITEM))


def _dedup_per_type(params, cfg: RankGraph2Config, batch,
                    ctx: ShardingCtx, features: Optional[FeatureStore]):
    """Unique-node forward: encode each pack row once, aggregate each
    endpoint-unique node once, gather per-(edge_type, side) views.

    Returns {et: (src_heads, src_prim, dst_heads, dst_prim)} exactly as
    the legacy per-endpoint forward would."""
    nodes, edges = batch["nodes"], batch["edges"]
    enc: Dict[str, jnp.ndarray] = {}
    for tname, ntype in _NODE_TYPES:
        side = nodes[tname]
        if "feat" in side:
            feat = side["feat"]
        else:
            if features is None:
                raise ValueError(
                    "id-only batch but no FeatureStore; pass features "
                    "to the train step")
            table = (features.user_feat if ntype == M.USER
                     else features.item_feat)
            feat = jnp.take(table, side["ids"], axis=0)
        enc[tname] = M.encode_nodes(params, cfg, ntype, feat, ctx)

    heads, prims = {}, {}
    for tname, ntype in _NODE_TYPES:
        side = nodes[tname]
        e_pad = side["unbr_idx"].shape[0]    # endpoint-unique rows first
        h = M.aggregate_nodes(
            params, cfg, ntype, enc[tname][:e_pad],
            jnp.take(enc["user"], side["unbr_idx"], axis=0),
            side["unbr_mask"],
            jnp.take(enc["item"], side["inbr_idx"], axis=0),
            side["inbr_mask"], ctx)
        heads[tname] = h
        prims[tname] = M.primary_embedding(h)

    per_type = {}
    for et, e in edges.items():
        st, dt = _ET_TYPES[et]
        sn = "user" if st == M.USER else "item"
        dn = "user" if dt == M.USER else "item"
        per_type[et] = (jnp.take(heads[sn], e["src_map"], axis=0),
                        jnp.take(prims[sn], e["src_map"], axis=0),
                        jnp.take(heads[dn], e["dst_map"], axis=0),
                        jnp.take(prims[dn], e["dst_map"], axis=0))
    return per_type


def _forward_losses(params, cfg: RankGraph2Config, batch, pool, rq_state,
                    key, ctx: ShardingCtx, train: bool,
                    features: Optional[FeatureStore] = None):
    """Returns (task_losses, aux) where aux carries pool/rq updates."""
    tasks: Dict[str, jnp.ndarray] = {}

    if "nodes" in batch:   # dedup layout
        per_type = _dedup_per_type(params, cfg, batch, ctx, features)
    else:                  # legacy layout: re-encode every endpoint
        per_type = {}
        for et, sub in batch.items():
            st, dt = _ET_TYPES[et]
            src_heads, src_prim = M.embed_side(params, cfg, sub["src"],
                                               st, ctx)
            dst_heads, dst_prim = M.embed_side(params, cfg, sub["dst"],
                                               dt, ctx)
            per_type[et] = (src_heads, src_prim, dst_heads, dst_prim)

    user_embs, item_embs = [], []
    endpoint_prims, endpoint_splits = [], []
    for et, (sh, sp, dh, dp) in per_type.items():
        st, dt = _ET_TYPES[et]
        (user_embs if st == M.USER else item_embs).append(sp)
        (user_embs if dt == M.USER else item_embs).append(dp)
        endpoint_prims += [sp, dp]
        endpoint_splits += [(et, "src"), (et, "dst")]

    dp_size = ctx.axis_size("batch")

    def _neg(k, prim, heads, node_type):
        buf = pool.user if node_type == M.USER else pool.item
        fill = pool.user_fill if node_type == M.USER else pool.item_fill
        blk = prim.shape[0] // dp_size if dp_size > 1 and \
            prim.shape[0] % dp_size == 0 else 0
        return N.sample_negatives(k, prim, heads, buf, fill,
                                  cfg.n_negatives, cfg.n_pool_neg,
                                  shard_block=blk)

    def _pair(src, dst, negs):
        return L.pair_losses(src, dst, negs, margin=cfg.margin,
                             tau=cfg.tau,
                             use_kernel=cfg.use_fused_contrastive)

    keys = jax.random.split(key, 8)
    ki = 0
    loss_dirs = []   # (task_suffix, src_prim, dst_prim, dst_heads, dst_type)
    for et, (sh, sp, dh, dp) in per_type.items():
        st, dt = _ET_TYPES[et]
        loss_dirs.append((et, sp, dp, dh, dt))
        if et == "ui":  # bidirectional U-I (paper computes L_UI and L_IU)
            loss_dirs.append(("iu", dp, sp, sh, st))

    dir_negs = {}
    for suffix, sp_, dp_, dh_, dt_ in loss_dirs:
        negs = _neg(keys[ki], dp_, dh_, dt_)
        ki += 1
        dir_negs[suffix] = negs
        marg, info = _pair(sp_, dp_, negs)
        tasks[f"margin_{suffix}"] = jnp.mean(marg)
        tasks[f"infonce_{suffix}"] = jnp.mean(info)

    # --- RQ co-learning on all endpoint embeddings -------------------------
    all_prim = jnp.concatenate(endpoint_prims, axis=0)
    rq_out = RQ.rq_forward(params["rq"], rq_state, all_prim, cfg.rq,
                           train=train)
    tasks["rq_recon"] = rq_out["l_recon"]
    tasks["rq_reg"] = rq_out["l_reg"]
    if cfg.rq.util_coef > 0:
        # utilization balance rides as its own uncertainty-weighted task
        # (a constant-zero task would drive its learned log-var to -inf)
        tasks["rq_util"] = rq_out["l_util"]
    # contrastive on reconstructed embeddings (L'): recompute the positive
    # pair similarity with straight-through recon endpoints.
    recon_st = rq_out["recon_st"]
    sizes = [p.shape[0] for p in endpoint_prims]
    offs = np.cumsum([0] + sizes)
    recon_parts = {}
    for (et, side), lo, hi in zip(endpoint_splits, offs[:-1], offs[1:]):
        recon_parts[(et, side)] = recon_st[lo:hi]
    lprime = []
    for et, (sh, sp, dh, dp) in per_type.items():
        st, dt = _ET_TYPES[et]
        rs = recon_parts[(et, "src")]
        rd = recon_parts[(et, "dst")]
        # the per-direction negative bank is i.i.d. of the recon
        # endpoints — reuse it for L' instead of a second pool gather
        # (reuse_lprime_negatives=False restores the PR-3 double draw)
        if cfg.reuse_lprime_negatives:
            negs = dir_negs[et]
        else:
            negs = _neg(keys[ki], dp, dh, dt)
            ki += 1
        marg, info = _pair(rs, rd, negs)
        lprime.append(jnp.mean(0.5 * marg + 0.5 * info))
    tasks["rq_contrastive"] = jnp.mean(jnp.stack(lprime))

    aux = dict(rq_state=rq_out["state"],
               user_emb=jnp.concatenate(user_embs, axis=0)
               if user_embs else None,
               item_emb=jnp.concatenate(item_embs, axis=0)
               if item_embs else None,
               codes=rq_out["codes"])
    return tasks, aux


def make_train_step(cfg: RankGraph2Config, optimizer: opt_lib.Optimizer,
                    ctx: ShardingCtx = NULL_CTX, *,
                    grad_clip: float = 1.0,
                    jit: bool = True, donate: bool = True):
    """Builds train_step(state, batch, key, features=None) ->
    (state, metrics).

    By default the step comes back jitted with ``donate_argnums=0`` —
    the incoming ``TrainState`` buffers are reused for the outgoing
    state, halving peak state memory.  Callers that lower/compile the
    raw function themselves (dry-run, roofline) pass ``jit=False``.
    ``features`` is the ``FeatureStore`` required by id-only
    (``dedup_ids``) batches; it is an argument, not a closure, so new
    tables of the same shape reuse the compiled step.
    """

    def train_step(state: TrainState, batch, key,
                   features: Optional[FeatureStore] = None):
        def loss_fn(params):
            tasks, aux = _forward_losses(params, cfg, batch, state.pool,
                                         state.rq_state, key, ctx, True,
                                         features)
            total = L.uncertainty_combine(tasks, params["uncertainty"])
            return total, (tasks, aux)

        (total, (tasks, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        grads, gnorm = opt_lib.clip_by_global_norm(grads, grad_clip)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = opt_lib.apply_updates(state.params, updates)
        pool = N.update_pool(state.pool, aux["user_emb"], aux["item_emb"])
        new_state = TrainState(params, opt_state, aux["rq_state"], pool,
                               state.step + 1)
        metrics = {k: v for k, v in tasks.items()}
        metrics["total"] = total
        metrics["grad_norm"] = gnorm
        return new_state, metrics

    if not jit:
        return train_step
    return jax.jit(train_step, donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# self-healing: dead-code reset over the whole TrainState
# ---------------------------------------------------------------------------

def reset_dead_codes(state: TrainState, probe_emb: np.ndarray,
                     cfg: RankGraph2Config, *, seed: int, step: int = 0,
                     usage=None) -> Tuple[TrainState, Dict[str, int]]:
    """Run ``rq_index.dead_code_reset`` against a TrainState.

    Host-side and functional: only the dead codebook rows and the RQ
    usage counters change, the rest of the state (optimizer moments,
    histograms, pool, step) is carried through untouched, so the
    donated jitted step keeps its compiled trace.  ``probe_emb`` is a
    (P, d_embed) sample of current embeddings supplying the donor
    residuals; ``usage`` optionally overrides the EMA counters with
    published corpus occupancy (the repair path).
    """
    new_rq, new_rq_state, report = RQ.dead_code_reset(
        state.params["rq"], state.rq_state, probe_emb, cfg.rq,
        seed=seed, step=step, usage=usage)
    params = dict(state.params)
    params["rq"] = new_rq
    return (TrainState(params, state.opt_state, new_rq_state,
                       state.pool, state.step), report)


# ---------------------------------------------------------------------------
# embedding generation (paper: embeddings regenerated after each rebuild)
# ---------------------------------------------------------------------------

def embed_all(params, cfg: RankGraph2Config, dataset, *, node_type: int,
              ids: np.ndarray, batch: int = 4096,
              ctx: ShardingCtx = NULL_CTX) -> np.ndarray:
    """Generate primary embeddings for nodes (global ids)."""
    fn = jax.jit(functools.partial(_embed_batch, cfg=cfg,
                                   node_type=node_type, ctx=ctx))
    out = []
    for lo in range(0, len(ids), batch):
        chunk = ids[lo:lo + batch]
        # always pad to the fixed batch size: a ragged tail (or a corpus
        # smaller than one batch) would otherwise retrace per size
        pad = batch - len(chunk)
        if pad:
            chunk = np.r_[chunk, np.repeat(chunk[-1:], pad)]
        side = dataset.node_inference_batch(chunk)
        emb = np.asarray(fn(params, {k: jnp.asarray(v)
                                     for k, v in side.items()}))
        out.append(emb[: len(emb) - pad] if pad else emb)
    return np.concatenate(out, axis=0)


def _embed_batch(params, side, *, cfg, node_type, ctx):
    _, prim = M.embed_side(params, cfg, side, node_type, ctx)
    return prim
