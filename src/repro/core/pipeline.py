"""End-to-end RankGraph-2 pipeline: log -> graph -> PPR -> train -> embed.

One entry point used by the examples, the paper-table benchmarks and the
ablations; every ablation knob of §5.3 is a parameter:

    edge_types         subset of ("uu", "ui", "ii")          (Table 5)
    neighbor_strategy  "ppr" | "topweight" | "random"        (Table 6)
    popbias            Eq. 3 correction on/off               (Table 7)
    rq_regularize      RQ balance regularizer on/off         (Table 4)
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RankGraph2Config, RQConfig
from repro.core import graph_builder as GB
from repro.core import trainer as T
from repro.core import rq_index as RQ
from repro.data.edge_dataset import (EdgeDataset, NeighborTables,
                                     build_neighbor_tables)
from repro.data.synthetic import SyntheticWorld
from repro.obs import get_telemetry


@contextlib.contextmanager
def _timed(times: Dict[str, float], name: str):
    """Record a stage's duration in the run report via an obs span
    (``pipeline.<stage>``) — the pipeline never reads the clock raw."""
    with get_telemetry().span(f"pipeline.{name}") as sp:
        yield
    times[name] = sp.duration_s


@dataclasses.dataclass
class PipelineResult:
    user_emb: np.ndarray
    item_emb: np.ndarray
    user_codes: np.ndarray
    state: T.TrainState
    cfg: RankGraph2Config
    graph: GB.HeteroGraph
    tables: NeighborTables
    metrics: Dict[str, float]
    seconds: Dict[str, float]


def _strip_edge_types(g: GB.HeteroGraph, keep: Sequence[str]
                      ) -> GB.HeteroGraph:
    empty = GB.EdgeSet(np.zeros(0, np.int64), np.zeros(0, np.int64),
                       np.zeros(0, np.float32))
    return GB.HeteroGraph(
        g.n_users, g.n_items,
        ui=g.ui if "ui" in keep else empty,
        uu=g.uu if "uu" in keep else empty,
        ii=g.ii if "ii" in keep else empty,
        group1_users=g.group1_users, group1_items=g.group1_items,
        build_seconds=g.build_seconds)


def _fallback_tables(g: GB.HeteroGraph, k_imp: int, strategy: str,
                     seed: int) -> NeighborTables:
    """Table 6 alternatives: per-node neighbors by random sampling or
    top edge weight (single hop), in PPR-table format."""
    rng = np.random.default_rng(seed)
    nu, ni = g.n_users, g.n_items
    n = nu + ni
    user_nbrs = np.full((n, k_imp), -1, np.int64)
    item_nbrs = np.full((n, k_imp), -1, np.int64)

    def fill(edges, src_off, dst_off, table):
        if len(edges) == 0:
            return
        if strategy == "topweight":
            nbrs, _ = GB.padded_adjacency(edges, (nu if src_off == 0 else ni),
                                          k_imp)
            rows = np.flatnonzero((nbrs >= 0).any(axis=1))
            table[rows + src_off] = np.where(nbrs[rows] >= 0,
                                             nbrs[rows] + dst_off, -1)
        else:  # random: uniform neighbors among all edges of the node
            order = np.argsort(edges.src, kind="stable")
            s, d = edges.src[order], edges.dst[order]
            starts = np.searchsorted(s, np.arange(
                nu if src_off == 0 else ni))
            ends = np.searchsorted(s, np.arange(
                nu if src_off == 0 else ni) + 1)
            deg = ends - starts
            rows = np.flatnonzero(deg > 0)
            pick = (rng.random((len(rows), k_imp))
                    * deg[rows][:, None]).astype(np.int64)
            table[rows + src_off] = d[starts[rows][:, None] + pick] + dst_off

    fill(g.uu, 0, 0, user_nbrs)
    fill(g.ui, 0, nu, item_nbrs)
    iu = GB.EdgeSet(g.ui.dst, g.ui.src, g.ui.weight)
    fill(iu, nu, 0, user_nbrs)
    fill(g.ii, nu, nu, item_nbrs)
    return NeighborTables(user_nbrs, item_nbrs, nu, ni)


def run_pipeline(world: SyntheticWorld, cfg: RankGraph2Config, *,
                 edge_types: Sequence[str] = ("uu", "ui", "ii"),
                 neighbor_strategy: str = "ppr",
                 popbias: bool = True,
                 steps: int = 300,
                 batch_per_type: int = 128,
                 pool_size: int = 2048,
                 seed: int = 0,
                 ppr_backend: str = "numpy",
                 log_every: int = 0) -> PipelineResult:
    times: Dict[str, float] = {}
    with _timed(times, "construct"):
        g = GB.build_graph(world.day0, alpha_pop=cfg.alpha_pop if popbias
                           else 0.0, c_u=cfg.c_u, c_i=cfg.c_i,
                           k_cap=cfg.k_cap, seed=seed)
        g = _strip_edge_types(g, edge_types)

    with _timed(times, "ppr"):
        if neighbor_strategy == "ppr":
            tables = build_neighbor_tables(
                g, k_imp=cfg.k_imp, n_walks=cfg.ppr_walks,
                walk_len=cfg.ppr_len, restart=cfg.ppr_restart, seed=seed,
                backend=ppr_backend)
        else:
            tables = _fallback_tables(g, cfg.k_imp, neighbor_strategy,
                                      seed)

    # id-only batches: features live on device in a FeatureStore and the
    # jitted step gathers them; the host ships ids + masks only
    ds = EdgeDataset(g, tables, world.user_feat, world.item_feat,
                     k_train=cfg.k_train, batch_format="dedup_ids")
    state, specs, optimizer = T.init_state(jax.random.key(seed), cfg,
                                           pool_size=pool_size)
    step_fn = T.make_train_step(cfg, optimizer)
    feats = T.make_feature_store(world.user_feat, world.item_feat)

    per_type = {et: batch_per_type for et in ("uu", "ui", "ii")
                if et in edge_types or et == "ui"}
    with _timed(times, "train"):
        m = None
        for t in range(steps):
            batch = jax.tree.map(jnp.asarray,
                                 ds.sample_batch(t, seed, per_type))
            state, m = step_fn(state, batch, jax.random.key(1000 + t), feats)
            if log_every and t % log_every == 0:
                print(f"  step {t}: total={float(m['total']):.3f} "
                      f"infonce_ui={float(m.get('infonce_ui', 0.0)):.3f}")
        # steps=0 (embed-only runs): no train metrics, not an
        # UnboundLocalError
        metrics = {} if m is None else {k: float(v) for k, v in m.items()}

    with _timed(times, "embed"):
        from repro.core import model as M
        nu = g.n_users
        user_emb = T.embed_all(state.params, cfg, ds, node_type=M.USER,
                               ids=np.arange(nu), batch=2048)
        item_emb = T.embed_all(state.params, cfg, ds, node_type=M.ITEM,
                               ids=np.arange(nu, nu + g.n_items),
                               batch=2048)
        codes = np.asarray(RQ.assign_codes(
            state.params["rq"], jnp.asarray(user_emb), cfg.rq))

    return PipelineResult(user_emb, item_emb, codes, state, cfg, g, tables,
                          metrics, times)
