"""Edge-centric self-contained training data (paper §4.2 'Data format').

Each record = edge (n_i, n_j, w) + features and pre-sampled neighbors for
both endpoints, partitioned by edge type.  Training therefore needs *no*
online graph access — the dataset below materializes neighbor tables
once (construction output) and every batch is a pure gather.

Deterministic, resumable iteration: batch t of run (seed) is a pure
function of (seed, t), so a restored checkpoint resumes mid-epoch
exactly (fault-tolerance requirement).

A small prefetch thread overlaps host-side gather/negative-pool work
with device compute (paper 'Efficiency optimizations').
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from repro.core.graph_builder import HeteroGraph
from repro.core import ppr as ppr_mod
from repro.obs import get_telemetry


@dataclasses.dataclass
class NeighborTables:
    """Pre-computed K_IMP neighbors per node, unified global id space
    (users [0, n_users), items [n_users, n_users+n_items))."""
    user_nbrs: np.ndarray    # (n_nodes, k_imp) global ids, -1 pad
    item_nbrs: np.ndarray    # (n_nodes, k_imp)
    n_users: int
    n_items: int
    ppr: Optional["ppr_mod.PPRState"] = None   # refresh splice state


def _fill_group2(g: HeteroGraph, user_nbrs: np.ndarray,
                 item_nbrs: np.ndarray, prev_emb: np.ndarray, k_imp: int,
                 only: Optional[np.ndarray] = None) -> None:
    """Group-2 fallback: same-type neighbors via previous-run KNN
    (in-place; ``only`` restricts to a node-id subset, e.g. the nodes an
    incremental refresh actually touched)."""
    nu = g.n_users
    g2u = np.flatnonzero(~g.group1_users)
    g1u = np.flatnonzero(g.group1_users)
    g2i = np.flatnonzero(~g.group1_items)
    g1i = np.flatnonzero(g.group1_items)
    if only is not None:
        g2u = g2u[np.isin(g2u, only)]
        g2i = g2i[np.isin(g2i + nu, only)]
    if len(g2u) and len(g1u):
        knn = ppr_mod.group2_neighbors(prev_emb[:nu], g1u, g2u, k_imp)
        user_nbrs[g2u] = np.where(knn >= 0, knn, user_nbrs[g2u])
    if len(g2i) and len(g1i):
        knn = ppr_mod.group2_neighbors(prev_emb[nu:], g1i, g2i, k_imp)
        item_nbrs[nu + g2i] = np.where(knn >= 0, nu + knn,
                                       item_nbrs[nu + g2i])


def build_neighbor_tables(g: HeteroGraph, *, k_imp: int = 50,
                          n_walks: int = 64, walk_len: int = 5,
                          restart: float = 0.15, seed: int = 0,
                          prev_emb: Optional[np.ndarray] = None,
                          backend: str = "numpy",
                          keep_state: bool = False) -> NeighborTables:
    """PPR tables on the backbone + Group-2 fallback (paper §4.2).

    ``backend`` selects the walker (numpy / jax / pallas — identical
    output); ``keep_state`` retains the visit traces that power
    ``incremental_refresh`` (opt-in: (n_nodes, n_walks*walk_len) int64
    plus an adjacency snapshot).
    """
    with get_telemetry().span("construction.ppr_walk", backend=backend,
                              n_walks=int(n_walks),
                              walk_len=int(walk_len)):
        user_nbrs, item_nbrs, state = ppr_mod.precompute_ppr_neighbors(
            g, k_imp=k_imp, n_walks=n_walks, walk_len=walk_len,
            restart=restart, seed=seed, backend=backend,
            return_state=True)
    # Group-2 fallback: same-type neighbors via previous-run KNN; item
    # neighbors from top-weight U-I edges (already what PPR finds for
    # 1-hop starts, but fill explicitly where PPR returned nothing).
    if prev_emb is not None:
        _fill_group2(g, user_nbrs, item_nbrs, prev_emb, k_imp)
    return NeighborTables(user_nbrs, item_nbrs, g.n_users, g.n_items,
                          ppr=state if keep_state else None)


def incremental_refresh(g: HeteroGraph, tables: NeighborTables,
                        new_log_window, *,
                        prev_emb: Optional[np.ndarray] = None,
                        backend: Optional[str] = None
                        ) -> Tuple[HeteroGraph, NeighborTables, Dict]:
    """Hour-level lifecycle refresh (paper §4.2): splice a trailing log
    window into an existing graph + PPR tables without a full rebuild.

    Edges are re-derived only for co-engagement pairs reachable from the
    delta (``graph_builder.refresh_graph``); walks re-run only for nodes
    whose walk-length neighborhood changed, and new nodes — *both* id
    spaces may grow — are spliced into the padded adjacencies and
    tables (``ppr.refresh_ppr_neighbors``; user growth additionally
    remaps the unified id space, shifting item global ids).  Fresh nodes
    that still lack same-type neighbors route through the Group-2 KNN
    fallback when ``prev_emb`` (previous-run embeddings sized for the
    *new* space, [users; items]) is given.

    Affected rows match a from-scratch build on the merged window
    bit-for-bit — including when ``hub_cap`` triggers: hub-subsample
    draws are keyed per anchor and persisted in ``RefreshState`` (see
    ``refresh_graph``).  Unaffected rows are left untouched (modulo the
    id remap).  Returns ``(new_graph, new_tables, report)``.
    """
    from repro.core.graph_builder import refresh_graph
    if tables.ppr is None:
        raise ValueError("tables were built without keep_state=True; "
                         "no refresh state retained")
    with get_telemetry().span("construction.refresh") as sp:
        g_new, report = refresh_graph(g, new_log_window)
        with get_telemetry().span("construction.ppr_refresh"):
            user_nbrs, item_nbrs, state, affected = \
                ppr_mod.refresh_ppr_neighbors(
                    g_new, tables.user_nbrs, tables.item_nbrs,
                    tables.ppr, backend=backend)
        if prev_emb is not None and len(affected):
            _fill_group2(g_new, user_nbrs, item_nbrs, prev_emb,
                         tables.ppr.k_imp, only=affected)
        report["affected_nodes"] = affected
        report["refresh_seconds"] = sp.elapsed()
    return (g_new,
            NeighborTables(user_nbrs, item_nbrs, g_new.n_users,
                           g_new.n_items, ppr=state),
            report)


EDGE_KEYS = ("uu", "ui", "ii")

# batch formats (see sample_batch):
#   legacy    — PR-3 layout: per (edge_type, side) feature tensors, every
#               endpoint occurrence re-materialized (and re-encoded);
#   dedup     — packed unique-node sub-batch per node type (features +
#               pack-relative sampled-neighbor indices) plus int32 gather
#               maps per (edge_type, side): each referenced node is
#               encoded exactly once;
#   dedup_ids — same packs but id-only (no feature tensors): the trainer
#               gathers features inside the jitted step from a
#               device-resident FeatureStore, so the host ships ~K*d
#               fewer bytes per row.
BATCH_FORMATS = ("legacy", "dedup", "dedup_ids")

# edge type -> (src, dst) node-type names
_ET_SIDES = {"uu": ("user", "user"), "ui": ("user", "item"),
             "ii": ("item", "item")}


def _round_up(n: int, m: int) -> int:
    """Round n up to a multiple of m (min m)."""
    return max(m, -(-n // m) * m)


def pack_bucket(n: int, m: int) -> int:
    """Bucket a pack size so jit traces are reused across batches instead
    of recompiling per unique-node count: a multiple of m, coarsened to
    about n/32 for large packs, so the few-per-mille batch-to-batch
    jitter of a 30k-edge batch stays in one bucket (<= ~3% padding)."""
    step = m * max(1, (1 << max(n.bit_length() - 5, 0)) // m)
    return _round_up(n, step)


@dataclasses.dataclass
class EdgeDataset:
    g: HeteroGraph
    tables: NeighborTables
    user_feat: np.ndarray
    item_feat: np.ndarray
    k_train: int = 10
    # importance-sample training edges proportionally to their Eq.1/2
    # weights (construction's premise: weight == relevance; uniform
    # sampling would train on the spurious-tie tail)
    sample_by_weight: bool = True
    batch_format: str = "dedup"
    pad_multiple: int = 64        # unique-pack size bucketing

    def _cumw(self, et):
        cache = getattr(self, "_cumw_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_cumw_cache", cache)
        if et not in cache:
            es = getattr(self.g, et)
            w = np.maximum(es.weight.astype(np.float64), 1e-9)
            cache[et] = np.cumsum(w) / w.sum()
        return cache[et]

    def _gather_side(self, gids: np.ndarray, rng: np.random.Generator
                     ) -> Dict[str, np.ndarray]:
        """Features + sampled neighbor features for global node ids."""
        nu = self.tables.n_users
        # batches are partitioned by edge type so each side is one type
        if (gids < nu).all():
            feat = self.user_feat[gids]
        else:
            feat = self.item_feat[gids - nu]
        # sample k_train of the K_IMP pre-computed neighbors (paper)
        k_imp = self.tables.user_nbrs.shape[1]
        k = self.k_train
        cols = rng.integers(0, k_imp, (len(gids), k))
        unbr = self.tables.user_nbrs[gids[:, None], cols]
        cols = rng.integers(0, k_imp, (len(gids), k))
        inbr = self.tables.item_nbrs[gids[:, None], cols]
        umask = unbr >= 0
        imask = inbr >= nu
        unbr_feat = self.user_feat[np.clip(unbr, 0, nu - 1)]
        inbr_feat = self.item_feat[np.clip(inbr - nu, 0,
                                           self.tables.n_items - 1)]
        unbr_feat = unbr_feat * umask[..., None]
        inbr_feat = inbr_feat * imask[..., None]
        return dict(feat=feat.astype(np.float32),
                    unbr_feat=unbr_feat.astype(np.float32),
                    unbr_mask=umask.astype(np.float32),
                    inbr_feat=inbr_feat.astype(np.float32),
                    inbr_mask=imask.astype(np.float32))

    def _draw_edges(self, rng: np.random.Generator, et: str, n: int
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Draw n (src_gid, dst_gid, weight) samples of one edge type."""
        nu = self.tables.n_users
        es = getattr(self.g, et)
        if len(es) == 0:   # degenerate graphs: self-pairs as fallback
            src = rng.integers(0, nu, n)
            dst = src.copy()
            w = np.ones(n, np.float32)
        else:
            if self.sample_by_weight:
                idx = np.searchsorted(self._cumw(et), rng.random(n))
                idx = np.minimum(idx, len(es) - 1)
            else:
                idx = rng.integers(0, len(es), n)
            src, dst, w = es.src[idx], es.dst[idx], es.weight[idx]
        if et == "uu":
            sg, dg = src, dst
        elif et == "ui":
            sg, dg = src, dst + nu
        else:  # ii
            sg, dg = src + nu, dst + nu
        return sg, dg, w.astype(np.float32)

    def sample_batch(self, step: int, seed: int, per_type: Dict[str, int],
                     format: Optional[str] = None) -> Dict[str, Dict]:
        """Batch t is a pure function of (seed, step, format) — resumable.

        ``format`` (default: ``self.batch_format``) selects the layout —
        see ``BATCH_FORMATS``.  The legacy path keeps PR-3's exact rng
        consumption order (edge draw, then src/dst neighbor draws, per
        edge type) so old runs stay reproducible bit-for-bit.
        """
        fmt = format or self.batch_format
        if fmt not in BATCH_FORMATS:
            raise ValueError(f"unknown batch format {fmt!r}")
        rng = np.random.default_rng((seed, step))
        if fmt == "legacy":
            batch: Dict[str, Dict] = {}
            for et in EDGE_KEYS:
                n = per_type.get(et, 0)
                if n == 0:
                    continue
                sg, dg, w = self._draw_edges(rng, et, n)
                batch[et] = dict(src=self._gather_side(sg, rng),
                                 dst=self._gather_side(dg, rng),
                                 weight=w,
                                 src_ids=sg.astype(np.int32),
                                 dst_ids=dg.astype(np.int32))
            return batch
        edges = {et: self._draw_edges(rng, et, n) for et in EDGE_KEYS
                 if (n := per_type.get(et, 0))}
        return self._dedup_batch(rng, edges, id_only=(fmt == "dedup_ids"))

    def _dedup_batch(self, rng: np.random.Generator, edges: Dict[str, Tuple],
                     id_only: bool) -> Dict[str, Dict]:
        """Packed unique-node batch: every node referenced by any
        endpoint or sampled neighbor appears exactly once per node type.

        Pack layout per type: ``[endpoint uniques (E, sorted) | pad to
        E_pad | neighbor-only extras (sorted) | pad to U_pad]``; sizes
        are bucketed to multiples of ``pad_multiple`` (coarser for large
        packs, see ``pack_bucket``) so jit traces are shared across
        batches.  Endpoint rows [0, E) are the only ones aggregated;
        extras exist only to be feature-encoded and gathered as
        neighbors.
        """
        nu, ni = self.tables.n_users, self.tables.n_items
        mult = self.pad_multiple
        k_imp = self.tables.user_nbrs.shape[1]
        k = self.k_train

        ep = {"user": [], "item": []}
        for et, (sg, dg, w) in edges.items():
            st, dt = _ET_SIDES[et]
            ep[st].append(sg)
            ep[dt].append(dg)

        sides: Dict[str, Dict[str, np.ndarray]] = {}
        uniq: Dict[str, np.ndarray] = {}
        nbr_gids: Dict[str, Dict[str, np.ndarray]] = {}
        for t in ("user", "item"):
            u = (np.unique(np.concatenate(ep[t])) if ep[t]
                 else np.zeros(0, np.int64))
            uniq[t] = u
            # one neighbor draw per unique endpoint node (the legacy
            # format draws per occurrence; dedup makes the draw — like
            # the encode — a per-node event)
            cols = rng.integers(0, k_imp, (len(u), k))
            unbr = self.tables.user_nbrs[u[:, None], cols] if len(u) else \
                np.zeros((0, k), np.int64)
            cols = rng.integers(0, k_imp, (len(u), k))
            inbr = self.tables.item_nbrs[u[:, None], cols] if len(u) else \
                np.zeros((0, k), np.int64)
            nbr_gids[t] = dict(
                unbr=np.clip(unbr, 0, nu - 1), umask=unbr >= 0,
                inbr=np.clip(inbr, nu, nu + ni - 1), imask=inbr >= nu)

        # neighbor-only extras per pack (valid neighbors not already
        # endpoint uniques of that type)
        extras, e_pad = {}, {}
        for t, key_m in (("user", "umask"), ("item", "imask")):
            key_g = "unbr" if t == "user" else "inbr"
            valid = [nbr_gids[s][key_g][nbr_gids[s][key_m]]
                     for s in ("user", "item")]
            allv = (np.unique(np.concatenate(valid)) if valid
                    else np.zeros(0, np.int64))
            extras[t] = np.setdiff1d(allv, uniq[t], assume_unique=True)
            e_pad[t] = pack_bucket(len(uniq[t]), mult)

        def pack_index(t: str, gids: np.ndarray, mask: np.ndarray
                       ) -> np.ndarray:
            """Pack-relative index of global ids (masked entries -> 0)."""
            u, ex = uniq[t], extras[t]
            if len(u) == 0:   # a type with no endpoints: extras only
                idx = e_pad[t] + np.searchsorted(ex, gids)
            else:
                pos = np.minimum(np.searchsorted(u, gids), len(u) - 1)
                idx = np.where(u[pos] == gids, pos,
                               e_pad[t] + np.searchsorted(ex, gids))
            return np.where(mask, idx, 0).astype(np.int32)

        for t in ("user", "item"):
            E, Ep = len(uniq[t]), e_pad[t]
            u_pad = pack_bucket(Ep + len(extras[t]), mult)
            local = np.zeros(u_pad, np.int64)
            off, hi = (0, nu - 1) if t == "user" else (nu, ni - 1)
            local[:E] = np.clip(uniq[t] - off, 0, hi)
            local[Ep:Ep + len(extras[t])] = np.clip(extras[t] - off, 0, hi)
            n = nbr_gids[t]
            unbr_idx = np.zeros((Ep, k), np.int32)
            inbr_idx = np.zeros((Ep, k), np.int32)
            umask = np.zeros((Ep, k), np.float32)
            imask = np.zeros((Ep, k), np.float32)
            unbr_idx[:E] = pack_index("user", n["unbr"], n["umask"])
            inbr_idx[:E] = pack_index("item", n["inbr"], n["imask"])
            umask[:E] = n["umask"].astype(np.float32)
            imask[:E] = n["imask"].astype(np.float32)
            side = dict(unbr_idx=unbr_idx, unbr_mask=umask,
                        inbr_idx=inbr_idx, inbr_mask=imask)
            if id_only:
                side["ids"] = local.astype(np.int32)
            else:
                table = self.user_feat if t == "user" else self.item_feat
                side["feat"] = table[local].astype(np.float32)
            sides[t] = side

        out_edges = {}
        for et, (sg, dg, w) in edges.items():
            st, dt = _ET_SIDES[et]
            out_edges[et] = dict(
                src_map=np.searchsorted(uniq[st], sg).astype(np.int32),
                dst_map=np.searchsorted(uniq[dt], dg).astype(np.int32),
                weight=w,
                src_ids=sg.astype(np.int32), dst_ids=dg.astype(np.int32))
        return {"nodes": sides, "edges": out_edges}

    def expand_batch(self, batch: Dict[str, Dict]) -> Dict[str, Dict]:
        """Re-materialize a dedup batch in the legacy per-endpoint layout
        (same neighbor draws — the dedup forward on ``batch`` and the
        legacy forward on the expansion must produce the same losses)."""
        if "nodes" not in batch:
            return batch
        nu = self.tables.n_users
        feats = {}
        for t, table in (("user", self.user_feat), ("item", self.item_feat)):
            side = batch["nodes"][t]
            feats[t] = (np.asarray(side["feat"]) if "feat" in side
                        else table[np.asarray(side["ids"])])
        out: Dict[str, Dict] = {}
        for et, e in batch["edges"].items():
            st, dt = _ET_SIDES[et]
            sub = {}
            for side_name, t, m in (("src", st, e["src_map"]),
                                    ("dst", dt, e["dst_map"])):
                nd = batch["nodes"][t]
                m = np.asarray(m)
                umask = np.asarray(nd["unbr_mask"])[m]
                imask = np.asarray(nd["inbr_mask"])[m]
                sub[side_name] = dict(
                    feat=feats[t][m],
                    unbr_feat=feats["user"][np.asarray(nd["unbr_idx"])[m]]
                    * umask[..., None],
                    unbr_mask=umask,
                    inbr_feat=feats["item"][np.asarray(nd["inbr_idx"])[m]]
                    * imask[..., None],
                    inbr_mask=imask)
            out[et] = dict(weight=np.asarray(e["weight"]),
                           src_ids=np.asarray(e["src_ids"]),
                           dst_ids=np.asarray(e["dst_ids"]), **sub)
        return out

    def iter_batches(self, seed: int, per_type: Dict[str, int],
                     start_step: int = 0) -> Iterator[Dict]:
        step = start_step
        while True:
            yield self.sample_batch(step, seed, per_type)
            step += 1

    def node_inference_batch(self, gids: np.ndarray, seed: int = 0
                             ) -> Dict[str, np.ndarray]:
        """Inference-side gather for embedding generation."""
        rng = np.random.default_rng(seed)
        return self._gather_side(gids, rng)


class Prefetcher:
    """Host-side pipeline overlap: data fetching / preprocessing runs in a
    background thread while the device executes train_step."""

    def __init__(self, it: Iterator, depth: int = 2):
        self.q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()

        def work():
            for item in it:
                if self._stop.is_set():
                    return
                self.q.put(item)

        self.t = threading.Thread(target=work, daemon=True)
        self.t.start()

    def __iter__(self):
        return self

    def __next__(self):
        return self.q.get()

    def close(self):
        self._stop.set()
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass
