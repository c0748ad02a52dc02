"""Pallas TPU kernels: cluster-queue gather + U2I2I round-robin union.

The batched serving path answers each request by (1) reading the user's
cluster ring buffer newest-first with a recency filter and (2) unioning
the I2I lists of the surviving seed items.  Two ``pallas_call``s inside
one jitted dispatch, one grid program per request each:

  * **seeds** — the request's cluster id and write count arrive by
    scalar prefetch (SMEM); the ``(8, Q)`` ring tile holding the
    cluster's row is DMA'd by the BlockSpec index map and the row is
    selected in-register (the TPU tiling wants 8-row blocks).  Recency
    masking, newest-first ranking and dedup are mask/compare ops on the
    ``(1, Q)`` row — selection is a one-hot reduction, not a serial scan;
  * **union** — the seeds from the first call are scalar-prefetched, so
    each seed's I2I row is DMA'd from HBM as an ``(8, K)`` tile by its own
    BlockSpec: the table never has to fit in VMEM, whatever the item
    corpus.  The round-robin union (rank-major priority, seeds masked,
    first-k dedup) reuses the priority-rank-scatter pattern on the R*K
    candidates.

Pairwise masks need each vector as a row and as a column
(``row_to_col``/``col_to_row``: Mosaic cannot transpose such shapes).

The per-request cursor is gathered by XLA before the first call, so the
``(C,)`` cursor array never has to fit in SMEM either.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import col_to_row, row_to_col, should_interpret

_ROWS = 8           # sublane tile: blocks of a (rows, lanes) array


def _any(x, axis):
    return jnp.max(x.astype(jnp.int32), axis=axis, keepdims=True) > 0


def _dedup_prio(vals_c, vals_r, prio_c, prio_r, big):
    """Mask (set to ``big``) the priority of every entry whose value
    already appears with a strictly smaller priority.  Takes and returns
    both orientations: column (M, 1) and row (1, M)."""
    eq = vals_c == vals_r                                 # (M, M)
    dup_r = _any(eq & (prio_c < prio_r), 0)               # (1, M)
    dup_c = _any(eq & (prio_r < prio_c), 1)               # (M, 1)
    return jnp.where(dup_c, big, prio_c), jnp.where(dup_r, big, prio_r)


def _rank_select(vals_c, prio_c, prio_r, big, n_out):
    """Shared priority machinery: given (M, 1) values with priorities
    (``big`` = masked), return the ``n_out`` smallest-priority values as
    a (1, n_out) row, -1-padded.  Rank = count of strictly smaller
    priorities (priorities are unique below ``big``); the scatter to
    output position is a one-hot reduction."""
    rank = jnp.sum((prio_r < prio_c).astype(jnp.int32), axis=1,
                   keepdims=True)                         # (M, 1)
    live = (prio_c < big) & (rank < n_out)
    sel = (jax.lax.broadcasted_iota(jnp.int32, (vals_c.shape[0], n_out), 1)
           == rank) & live                                # (M, n_out)
    picked = jnp.sum(jnp.where(sel, vals_c, 0), axis=0, keepdims=True)
    return jnp.where(_any(sel, 0), picked, -1)            # (1, n_out)


def _pick_row(block, row, fill):
    """Row ``row`` of an ``(8, W)`` tile as ``(1, W)``: a masked max over
    the sublanes (``fill`` lies below every stored value)."""
    hit = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0) == row
    return jnp.max(jnp.where(hit, block, fill), axis=0, keepdims=True)


def _seeds_kernel(cl_ref, tot_ref, cutoff_ref, items_ref, times_ref,
                  seeds_out, *, Q: int, R: int):
    b = pl.program_id(0)
    row = cl_ref[b] % _ROWS
    total = tot_ref[b]
    items = _pick_row(items_ref[...], row, jnp.int32(-2))         # (1, Q)
    ts = _pick_row(times_ref[...], row, jnp.float32(-jnp.inf))    # (1, Q)

    # slot age, newest = 0, as (newest slot - slot) mod Q: the modulo
    # stays on the scalar unit
    d = (jnp.mod(total - 1, Q)
         - jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1))
    age = jnp.where(d < 0, d + Q, d)
    valid = (age < jnp.minimum(total, Q)) & (ts >= cutoff_ref[0]) \
        & (items >= 0)
    big = jnp.int32(Q + 1)
    prio = jnp.where(valid, age, big)
    items_c = row_to_col(items)
    prio_c, prio = _dedup_prio(items_c, items, row_to_col(prio), prio, big)
    seeds_out[...] = _rank_select(items_c, prio_c, prio, big, R)  # (1, R)


def _union_kernel(sidx_ref, seeds_ref, *refs, R: int, k: int, N: int):
    i2i_refs, union_out = refs[:R], refs[R]
    b = pl.program_id(0)
    seeds = seeds_ref[...]                                # (1, R)
    seeds_c = row_to_col(seeds)                           # (R, 1)
    K = i2i_refs[0].shape[1]
    M = R * K
    # candidate m = r*K + c is rank c of seed r; laid out as an (M, 1)
    # column with its round-robin (rank-major) priority c*R + r
    mi = jax.lax.broadcasted_iota(jnp.int32, (M, K), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (M, K), 1)
    flat = jnp.full((M, K), -2, jnp.int32)
    rr = jnp.full((M, K), -1, jnp.int32)
    for r in range(R):
        row = _pick_row(i2i_refs[r][...], sidx_ref[b * R + r] % _ROWS,
                        jnp.int32(-2))                    # (1, K)
        # seeds past the table end gather nothing (new items can reach
        # the queues before the next offline I2I refresh covers them)
        s = seeds[:, r:r + 1]
        row = jnp.where((s >= 0) & (s < N), row, -1)
        at = mi - r * K == ci
        flat = jnp.where(at, row, flat)
        rr = jnp.where(at, ci * R + r, rr)
    flat_c = jnp.max(flat, axis=1, keepdims=True)         # (M, 1)
    rr_c = jnp.max(rr, axis=1, keepdims=True)
    flat_r = col_to_row(flat_c)
    seen_c = _any((flat_c == seeds) & (seeds >= 0), 1)    # (M, 1)
    seen_r = _any((seeds_c == flat_r) & (seeds_c >= 0), 0)  # (1, M)
    bigm = jnp.int32(M + 1)
    prio_c = jnp.where((flat_c >= 0) & ~seen_c, rr_c, bigm)
    prio_r = jnp.where((flat_r >= 0) & ~seen_r, col_to_row(rr_c), bigm)
    prio_c, prio_r = _dedup_prio(flat_c, flat_r, prio_c, prio_r, bigm)
    union_out[...] = _rank_select(flat_c, prio_c, prio_r, bigm, k)


@functools.partial(jax.jit,
                   static_argnames=("n_recent", "k", "interpret"))
def _run(items, times, cursor, clusters, i2i, cutoff, *, n_recent: int,
         k: int, interpret: bool):
    C, Q = items.shape
    N, K = i2i.shape
    B = clusters.shape[0]
    R = n_recent
    totals = cursor[clusters]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    ring = pl.BlockSpec((_ROWS, Q), lambda b, cl, tot: (cl[b] // _ROWS, 0))
    seeds = pl.pallas_call(
        functools.partial(_seeds_kernel, Q=Q, R=R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[smem, ring, ring],
            out_specs=pl.BlockSpec((None, 1, R),
                                   lambda b, cl, tot: (b, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((B, 1, R), jnp.int32),
        interpret=interpret)(clusters, totals, cutoff, items, times)

    sidx = jnp.clip(seeds, 0, N - 1).reshape(B * R)
    # one (8, K) I2I tile per seed slot, picked by the prefetched seed id
    in_specs = [pl.BlockSpec((None, 1, R), lambda b, s: (b, 0, 0))]
    in_specs += [pl.BlockSpec((_ROWS, K), functools.partial(
        lambda r, b, s: (s[b * R + r] // _ROWS, 0), r)) for r in range(R)]
    union = pl.pallas_call(
        functools.partial(_union_kernel, R=R, k=k, N=N),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,), in_specs=in_specs,
            out_specs=pl.BlockSpec((None, 1, k), lambda b, s: (b, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((B, 1, k), jnp.int32),
        interpret=interpret)(sidx, seeds, *([i2i] * R))
    return seeds.reshape(B, R), union.reshape(B, k)


def queue_gather(items, times, cursor, clusters, i2i, *, cutoff: float,
                 n_recent: int, k: int, interpret: bool = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Serving gather.  items/times (C, Q) ring buffers, cursor (C,)
    total writes, clusters (B,) request cluster ids, i2i (N, K).

    Returns (seeds (B, n_recent) int32, union (B, k) int32), -1-padded.
    """
    if interpret is None:
        interpret = should_interpret()
    return _run(jnp.asarray(items, jnp.int32),
                jnp.asarray(times, jnp.float32),
                jnp.asarray(cursor, jnp.int32),
                jnp.asarray(clusters, jnp.int32),
                jnp.asarray(i2i, jnp.int32),
                jnp.full((1,), cutoff, jnp.float32),
                n_recent=int(n_recent), k=int(k),
                interpret=bool(interpret))
