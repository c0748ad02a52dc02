"""Operations and bytes that the semantics of a call require.

Each count is what any correct implementation has to do for the call's
inputs, whatever its padding or layout, so a share of the roofline built
on it cannot pass 100% unless the time leaves out part of the work.

Serve dispatch (``SwapServer.serve_batch``, one device program): per
distinct known cluster of the batch, read its cluster id and write
cursor (4 + 4 B), at least ``n_recent`` newest ring slots (item and
timestamp, 4 + 4 B each), the I2I rows of its ``n_recent`` seeds
(``n_recent * k_i2i * 4`` B), and write its ``n_recent + k`` answers
(4 B each).  No floating-point arithmetic is required.

Ingest scatter (``SwapServer.ingest``): per event, read its cluster,
item and timestamp (12 B) and write its item and timestamp into a slot
(8 B); per distinct cluster, read and write its cursor (8 B).  A ring
the size of the store need not be copied.

Train step (``LifecycleRuntime.train_burst``, one step): the encoder
``f_t`` (``d_feat x d_hidden`` then ``d_hidden x H*d``) once per
distinct node of the batch, the aggregator (``H`` matrices of
``3d x d``) once per distinct endpoint, the contrastive logits
(``1 + n_negatives`` dot products of width ``d``) once per edge and
loss direction (uu, ui, iu, ii, plus the three on reconstructed
embeddings), and the RQ distances (``sum(codebook_sizes)`` dot products
of width ``d``) once per endpoint occurrence.  A multiply-add is two
operations; the backward pass is counted as twice the forward, except
for the RQ distances, whose input carries no gradient (once more).
"""
from __future__ import annotations

from typing import Dict


def serve_call(n_clusters: int, *, n_recent: int, k: int, k_i2i: int
               ) -> Dict[str, float]:
    per = 8 + 8 * n_recent + 4 * n_recent * k_i2i + 4 * (n_recent + k)
    return {"flops": 0.0, "bytes": float(n_clusters * per)}


def ingest_call(n_events: int, n_clusters: int) -> Dict[str, float]:
    return {"flops": 0.0, "bytes": float(20 * n_events + 8 * n_clusters)}


def train_step(*, nodes: int, endpoints: int, edges_per_type: int,
               d_feat: int, d_hidden: int, d: int, heads: int,
               n_negatives: int, codebook_sizes) -> Dict[str, float]:
    """``nodes``: distinct nodes encoded; ``endpoints``: distinct endpoint
    nodes aggregated; ``edges_per_type``: edges of each of uu, ui, ii."""
    enc = 2.0 * (d_feat * d_hidden + d_hidden * heads * d) * nodes
    agg = 2.0 * heads * 3 * d * d * endpoints
    directions = 4 + 3
    con = 2.0 * directions * edges_per_type * (1 + n_negatives) * d
    rq = 2.0 * (2 * 3 * edges_per_type) * sum(codebook_sizes) * d
    flops = 3.0 * (enc + agg + con) + 2.0 * rq
    return {"flops": flops, "bytes": 0.0}
