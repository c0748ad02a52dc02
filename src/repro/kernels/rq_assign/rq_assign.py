"""Pallas TPU kernel: fused residual-quantization code assignment.

The serving index assigns a cluster code to every user at every embedding
refresh (hundreds of millions of rows): per row, L sequential
nearest-code searches with residual subtraction.  The fusion win on TPU:

  * codebooks stay resident in VMEM across the whole batch tile
    (production 5000x256 fp32 = 5.1 MiB + 50x256 = 51 KiB, well under
    the ~16 MiB VMEM budget);
  * distances are computed with the MXU (||r||^2 - 2 r.C^T + ||C||^2 —
    the cross term is a (Bt,d)@(d,n) matmul);
  * the selected-code gather is a one-hot (Bt,n)@(n,d) matmul — again
    MXU — avoiding an HBM gather round-trip between layers;
  * codes + reconstruction leave the kernel in one pass (the pure-jnp
    version round-trips the residual through HBM per layer).

Block layout: grid over batch tiles; x tile (Bt, d) in VMEM, codebooks
replicated per tile (index_map -> block 0).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import VMEM_LIMIT, cdiv, pad_to, should_interpret

# full-f32 MXU passes: the one-hot gather must return codebook rows
# exactly, and the distances must rank like the f32 reference
_EXACT = jax.lax.Precision.HIGHEST


def _kernel(x_ref, *refs, n_layers: int, n_codes: Tuple[int, ...]):
    code_refs = refs[:n_layers]      # codebooks (n_l, d)
    codes_out = refs[n_layers]       # (Bt, L) int32
    recon_out = refs[n_layers + 1]   # (Bt, d) f32

    x = x_ref[...].astype(jnp.float32)
    resid = x
    recon = jnp.zeros_like(x)
    for l in range(n_layers):
        C = code_refs[l][...].astype(jnp.float32)            # (n, d)
        # squared distances via MXU: ||r||^2 - 2 rC^T + ||C||^2
        cross = jax.lax.dot_general(
            resid, C, (((1,), (1,)), ((), ())), precision=_EXACT,
            preferred_element_type=jnp.float32)              # (Bt, n)
        d2 = (jnp.sum(resid * resid, axis=1, keepdims=True)
              - 2.0 * cross + jnp.sum(C * C, axis=1)[None, :])
        k = jnp.argmin(d2, axis=1).astype(jnp.int32)         # (Bt,)
        onehot = (k[:, None] ==
                  jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
                  ).astype(jnp.float32)
        sel = jax.lax.dot_general(                            # (Bt, d) MXU
            onehot, C, (((1,), (0,)), ((), ())), precision=_EXACT,
            preferred_element_type=jnp.float32)
        resid = resid - sel
        recon = recon + sel
        codes_out[:, l] = k
    recon_out[...] = recon


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _run(x, codebooks, *, block_b: int, interpret: bool):
    B, d = x.shape
    L = len(codebooks)
    grid = (cdiv(B, block_b),)
    kernel = functools.partial(_kernel, n_layers=L,
                               n_codes=tuple(c.shape[0] for c in codebooks))
    out_shapes = (jax.ShapeDtypeStruct((B, L), jnp.int32),
                  jax.ShapeDtypeStruct((B, d), jnp.float32))
    in_specs = [pl.BlockSpec((block_b, d), lambda i: (i, 0))]
    in_specs += [pl.BlockSpec(c.shape, lambda i: (0, 0)) for c in codebooks]
    out_specs = (pl.BlockSpec((block_b, L), lambda i: (i, 0)),
                 pl.BlockSpec((block_b, d), lambda i: (i, 0)))
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shapes, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT))(x, *codebooks)


def rq_assign(x: jnp.ndarray, codebooks: Sequence[jnp.ndarray], *,
              block_b: int = 256, interpret: bool = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused RQ assignment.  x (B, d) -> (codes (B, L), recon (B, d))."""
    if interpret is None:
        interpret = should_interpret()
    B, d = x.shape
    xp, orig_b = pad_to(x, 0, block_b)
    codes, recon = _run(xp, tuple(codebooks), block_b=block_b,
                        interpret=bool(interpret))
    return codes[:orig_b], recon[:orig_b]
