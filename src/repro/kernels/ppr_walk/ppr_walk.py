"""Pallas TPU kernel: fused PPR Monte-Carlo walk + visit-count pass.

Construction's hot loop walks R restart-walks of length L from every
backbone node and then counts visits per start (paper §4.2).  Done
naively that is L round-trips through HBM for the (m, D2) adjacency-row
gathers plus a host-side sort/run-length pass.  The fusion keeps each
start's whole workload in VMEM:

  * the padded adjacency (``nbrs``/``cum``, (N, D2)) stays VMEM-resident
    across the whole grid: production shards starts over cores so the
    hot subgraph fits (the v5e compile is checked at N = 8192, D2 = 64;
    the HBM-streamed variant for larger subgraphs is a ROADMAP item);
    node ids must stay below 2^24 for the f32 MXU gather to be exact;
  * one grid program walks all R walkers of one start (its id arrives
    by scalar prefetch): the row gather is a one-hot (R, N) @ (N, D2)
    MXU matmul, the inverse-CDF draw is a compare/count over the
    gathered (R, D2) cumulative row, and the trailing-pad clamp (f32
    cumsums can top out below 1.0) picks the first column holding the
    row's total;
  * per-start visit counting is an equality reduction over the finished
    (R, L) trace — multiplicity at first occurrence, zero elsewhere — so
    the host goes straight to top-k selection with no sort or run-length
    pass;
  * the transition/restart draws stream in as a host-generated (R, 2L)
    f32 block: the uniform stream is the cross-backend contract (numpy /
    jax / pallas walk bit-identical traces), so the kernel consumes it
    rather than owning a PRNG.

grid = (n_starts,): one program per start node, mirroring
``queue_gather``'s one-program-per-request layout.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import VMEM_LIMIT, col_to_row, should_interpret

# full-f32 MXU passes: the one-hot row gathers must return cumulative
# masses and node ids bit-exactly
_EXACT = jax.lax.Precision.HIGHEST


def _kernel(starts_ref, u_ref, nbrs_ref, cum_ref, vis_ref, cnt_ref, *,
            n_walks: int, walk_len: int, restart: float):
    W, L = n_walks, walk_len
    N, D2 = cum_ref.shape
    home = starts_ref[pl.program_id(0)]
    u = u_ref[...]                                 # (W, 2L) f32
    nbrs = nbrs_ref[...].astype(jnp.float32)       # ids < 2^24: f32-exact
    cum = cum_ref[...]

    col_n = jax.lax.broadcasted_iota(jnp.int32, (W, N), 1)
    col_d = jax.lax.broadcasted_iota(jnp.int32, (W, D2), 1)
    col_l = jax.lax.broadcasted_iota(jnp.int32, (W, L), 1)
    pos = jnp.full((W, 1), home, jnp.int32)
    trace = jnp.zeros((W, L), jnp.int32)
    for t in range(L):
        onehot = (col_n == pos).astype(jnp.float32)
        rc = jax.lax.dot_general(onehot, cum, (((1,), (0,)), ((), ())),
                                 precision=_EXACT,
                                 preferred_element_type=jnp.float32)
        rn = jax.lax.dot_general(onehot, nbrs, (((1,), (0,)), ((), ())),
                                 precision=_EXACT,
                                 preferred_element_type=jnp.float32)
        us = u[:, 2 * t:2 * t + 1]                 # (W, 1)
        col = jnp.sum((rc < us).astype(jnp.int32), axis=1, keepdims=True)
        # clamp overflow draws (f32 cum[-1] < 1) to the last column with
        # positive mass — never onto a trailing -1 pad.  A cumulative row
        # is non-decreasing, so that column is the first one holding the
        # row's total.
        total = jnp.max(rc, axis=1, keepdims=True)
        lastc = jnp.min(jnp.where(rc == total, col_d, D2), axis=1,
                        keepdims=True)
        col = jnp.minimum(col, lastc)
        nxt = jnp.sum(jnp.where(col_d == col, rn, 0.0), axis=1,
                      keepdims=True).astype(jnp.int32)
        dead = (nxt < 0) | (total <= 0)
        nxt = jnp.where(dead, pos, nxt)
        rst = u[:, 2 * t + 1:2 * t + 2] < jnp.float32(restart)
        pos = jnp.where(rst, home, nxt)
        trace = jnp.where(col_l == t, pos, trace)
    vis_ref[...] = trace

    # fused visit counting over the walker-major trace (entry (w, t) is
    # visit w*L + t): multiplicity at first occurrence, 0 at later ones
    w_row = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
    w_col = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
    rows = [col_to_row(trace[:, t:t + 1]) for t in range(L)]   # (1, W)
    cnt = jnp.zeros((W, L), jnp.int32)
    for t1 in range(L):
        v = trace[:, t1:t1 + 1]                    # (W, 1)
        mult = jnp.zeros((W, 1), jnp.int32)
        earlier = jnp.zeros((W, 1), jnp.int32)
        for t2 in range(L):
            eq = v == rows[t2]                     # (W, W): [w1, w2]
            before = (w_col < w_row) | ((w_col == w_row) & (t2 < t1))
            mult = mult + jnp.sum(eq.astype(jnp.int32), axis=1,
                                  keepdims=True)
            earlier = jnp.maximum(earlier, jnp.max(
                (eq & before).astype(jnp.int32), axis=1, keepdims=True))
        cnt = jnp.where(col_l == t1, jnp.where(earlier > 0, 0, mult), cnt)
    cnt_ref[...] = cnt


@functools.partial(jax.jit, static_argnames=("n_walks", "walk_len",
                                             "restart", "interpret"))
def _run(starts, u, nbrs, cum, *, n_walks: int, walk_len: int,
         restart: float, interpret: bool):
    n = starts.shape[0]
    N, D2 = nbrs.shape
    S = n_walks * walk_len
    kernel = functools.partial(_kernel, n_walks=n_walks, walk_len=walk_len,
                               restart=restart)
    trace = pl.BlockSpec((None, n_walks, walk_len), lambda b, st: (b, 0, 0))
    # The (N, D2) adjacency is VMEM-resident by contract: production
    # shards starts over cores so the hot subgraph fits, and the HBM
    # double-buffered variant for larger subgraphs is a ROADMAP item.
    # repro: disable=vmem-budget — deliberate resident adjacency (sharded to fit); HBM double-buffer variant tracked in ROADMAP
    vis, cnt = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((n_walks, 2 * walk_len),
                             lambda b, st: (b, 0)),              # uniforms
                pl.BlockSpec((N, D2), lambda b, st: (0, 0)),     # nbrs
                pl.BlockSpec((N, D2), lambda b, st: (0, 0)),     # cum
            ],
            out_specs=(trace, trace)),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        out_shape=(jax.ShapeDtypeStruct((n, n_walks, walk_len), jnp.int32),
                   jax.ShapeDtypeStruct((n, n_walks, walk_len), jnp.int32)),
        interpret=interpret)(starts, u, nbrs, cum)
    return vis.reshape(n, S), cnt.reshape(n, S)


def ppr_walk(nbrs, cum, starts, uniforms, *, restart: float,
             interpret: bool = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused PPR walk.  ``nbrs``/``cum`` (N, D2) padded adjacency,
    ``starts`` (n,) node ids, ``uniforms`` (n, n_walks, 2*walk_len) f32
    (column 2t: step draw, 2t+1: restart draw).

    Returns (visited (n, S) int32, counts (n, S) int32) with
    S = n_walks*walk_len; counts holds each node's multiplicity at its
    first occurrence in the row, 0 elsewhere.
    """
    if interpret is None:
        interpret = should_interpret()
    n, n_walks, two_l = uniforms.shape
    walk_len = two_l // 2
    u = jnp.asarray(uniforms, jnp.float32).reshape(n * n_walks, two_l)
    return _run(jnp.asarray(starts, jnp.int32), u,
                jnp.asarray(nbrs, jnp.int32),
                jnp.asarray(cum, jnp.float32), n_walks=int(n_walks),
                walk_len=int(walk_len), restart=float(restart),
                interpret=bool(interpret))
