"""Pallas TPU kernel: fused contrastive losses (margin + InfoNCE).

Training hot loop: every positive edge scores against ~100 negatives
(paper §4.3) at batch 32,768 — a (B, N) similarity matrix.  Unfused, XLA
materializes the logits in HBM twice (margin path + log-softmax path);
fused, the (Bt, N) tile lives only in VMEM and both reductions happen in
the same pass right after the MXU batched dot.

grid over batch tiles; per tile: sims via dot_general with a batched
contraction, then margin sum + numerically-stable logsumexp.

The op is differentiable: ``fused_contrastive_diff`` carries a
``jax.custom_vjp`` whose forward additionally emits the per-row positive
similarity and logsumexp (cheap (B, 1) columns) so the backward kernel
only recomputes the (Bt, N) similarity tile — both loss gradients
(margin indicator + softmax) are formed in the same VMEM pass and
contracted back onto src/dst/negs without the logits ever hitting HBM.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import cdiv, should_interpret


def _fwd_kernel(src_ref, dst_ref, neg_ref, marg_ref, info_ref, pos_ref,
                lse_ref, *, margin: float, tau: float):
    src = src_ref[...].astype(jnp.float32)          # (Bt, d)
    dst = dst_ref[...].astype(jnp.float32)          # (Bt, d)
    negs = neg_ref[...].astype(jnp.float32)         # (Bt, N, d)
    s_pos = jnp.sum(src * dst, axis=-1)             # (Bt,)
    s_neg = jnp.sum(src[:, None, :] * negs, axis=-1)  # (Bt, N)
    marg_ref[...] = jnp.sum(
        jnp.maximum(s_neg - s_pos[:, None] + margin, 0.0), axis=-1,
        keepdims=True)
    # stable log-softmax over [pos, negs] picking the pos slot
    m = jnp.maximum(jnp.max(s_neg, axis=-1), s_pos) / tau
    lse = m + jnp.log(jnp.sum(jnp.exp(s_neg / tau - m[:, None]), axis=-1)
                      + jnp.exp(s_pos / tau - m))
    info_ref[...] = (lse - s_pos / tau)[:, None]
    pos_ref[...] = s_pos[:, None]
    lse_ref[...] = lse[:, None]


def _bwd_kernel(src_ref, dst_ref, neg_ref, gm_ref, gi_ref, pos_ref, lse_ref,
                dsrc_ref, ddst_ref, dneg_ref, *, margin: float, tau: float):
    """Fused backward tile: recompute s_neg, form both loss gradients.

    marg = sum_n relu(s_neg - s_pos + margin):
        d/ds_neg[n] = 1{active_n},   d/ds_pos = -sum_n 1{active_n}
    info = lse - s_pos / tau with softmax p = exp(s/tau - lse):
        d/ds_neg[n] = p_n / tau,     d/ds_pos = (p_pos - 1) / tau
    """
    src = src_ref[...].astype(jnp.float32)          # (Bt, d)
    dst = dst_ref[...].astype(jnp.float32)          # (Bt, d)
    negs = neg_ref[...].astype(jnp.float32)         # (Bt, N, d)
    gm = gm_ref[...].astype(jnp.float32)            # (Bt, 1)
    gi = gi_ref[...].astype(jnp.float32)            # (Bt, 1)
    s_pos = pos_ref[...].astype(jnp.float32)        # (Bt, 1)
    lse = lse_ref[...].astype(jnp.float32)          # (Bt, 1)
    s_neg = jnp.sum(src[:, None, :] * negs, axis=-1)  # (Bt, N)
    active = (s_neg - s_pos + margin > 0.0).astype(jnp.float32)
    p_neg = jnp.exp(s_neg / tau - lse)
    a = gm * active + gi * (p_neg / tau)             # (Bt, N) dL/ds_neg
    p_pos = jnp.exp(s_pos / tau - lse)
    c = -gm * jnp.sum(active, axis=-1, keepdims=True) \
        + gi * (p_pos - 1.0) / tau                   # (Bt, 1) dL/ds_pos
    dsrc_ref[...] = c * dst + jnp.sum(a[:, :, None] * negs, axis=1)
    ddst_ref[...] = c * src
    dneg_ref[...] = a[:, :, None] * src[:, None, :]  # (Bt, N, d)


@functools.partial(jax.jit, static_argnames=("margin", "tau", "block_b",
                                             "interpret"))
def _run_fwd(src, dst, negs, *, margin, tau, block_b, interpret):
    B, d = src.shape
    N = negs.shape[1]
    grid = (cdiv(B, block_b),)
    kern = functools.partial(_fwd_kernel, margin=margin, tau=tau)
    col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        kern, grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((block_b, N, d), lambda i: (i, 0, 0)),
        ],
        out_specs=(col, col, col, col),
        out_shape=tuple(jax.ShapeDtypeStruct((B, 1), jnp.float32)
                        for _ in range(4)),
        interpret=interpret)(src, dst, negs)
    return out


@functools.partial(jax.jit, static_argnames=("margin", "tau", "block_b",
                                             "interpret"))
def _run_bwd(src, dst, negs, gm, gi, s_pos, lse, *, margin, tau, block_b,
             interpret):
    B, d = src.shape
    N = negs.shape[1]
    grid = (cdiv(B, block_b),)
    kern = functools.partial(_bwd_kernel, margin=margin, tau=tau)
    row = pl.BlockSpec((block_b, d), lambda i: (i, 0))
    col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    neg = pl.BlockSpec((block_b, N, d), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        kern, grid=grid,
        in_specs=[row, row, neg, col, col, col, col],
        out_specs=(row, row, neg),
        out_shape=(jax.ShapeDtypeStruct((B, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, d), jnp.float32)),
        interpret=interpret)(src, dst, negs, gm, gi, s_pos, lse)
    return out


def _pad_rows(x, pad):
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


# 32-row tiles: the (Bt, N, d) negative block is the VMEM driver, and at
# production dims (N=100, d=256) the backward pass double-buffers it both
# in and out — 128-row tiles blow the ~16 MiB budget (vmem-budget rule).


def _padded_fwd(src, dst, negs, margin, tau, interpret, block_b=32):
    if interpret is None:
        interpret = should_interpret()
    B = src.shape[0]
    bb = min(block_b, B)
    pad = (-B) % bb
    if pad:
        src, dst, negs = (_pad_rows(a, pad) for a in (src, dst, negs))
    marg, info, s_pos, lse = _run_fwd(src, dst, negs, margin=margin,
                                      tau=tau, block_b=bb,
                                      interpret=bool(interpret))
    return marg[:B, 0], info[:B, 0], s_pos[:B, 0], lse[:B, 0]


def fused_contrastive(src, dst, negs, *, margin: float = 0.1,
                      tau: float = 0.06, block_b: int = 32,
                      interpret=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Forward-only fused losses (no VJP); see ``fused_contrastive_diff``
    for the differentiable op used on the training path."""
    marg, info, _, _ = _padded_fwd(src, dst, negs, margin, tau, interpret,
                                   block_b=block_b)
    return marg, info


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def fused_contrastive_diff(margin: float, tau: float, src, dst, negs
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Differentiable fused (margin, infonce) losses, each (B,).

    margin/tau lead (nondiff static args); src/dst (B, d) and
    negs (B, N, d) are the differentiable operands.
    """
    marg, info, _, _ = _padded_fwd(src, dst, negs, margin, tau, None)
    return marg, info


def _diff_fwd(margin, tau, src, dst, negs):
    marg, info, s_pos, lse = _padded_fwd(src, dst, negs, margin, tau, None)
    return (marg, info), (src, dst, negs, s_pos, lse)


def _diff_bwd(margin, tau, res, g):
    src, dst, negs, s_pos, lse = res
    gm, gi = g
    interpret = should_interpret()
    B = src.shape[0]
    bb = min(32, B)
    pad = (-B) % bb
    cols = tuple(a[:, None].astype(jnp.float32)
                 for a in (gm, gi, s_pos, lse))
    if pad:
        src_p, dst_p, negs_p = (_pad_rows(a, pad)
                                for a in (src, dst, negs))
        cols = tuple(_pad_rows(a, pad) for a in cols)
    else:
        src_p, dst_p, negs_p = src, dst, negs
    d_src, d_dst, d_negs = _run_bwd(src_p, dst_p, negs_p, *cols,
                                    margin=margin, tau=tau, block_b=bb,
                                    interpret=bool(interpret))
    return (d_src[:B].astype(src.dtype), d_dst[:B].astype(dst.dtype),
            d_negs[:B].astype(negs.dtype))


fused_contrastive_diff.defvjp(_diff_fwd, _diff_bwd)
