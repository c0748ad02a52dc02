"""Shared kernel utilities."""
from __future__ import annotations

import jax
import jax.numpy as jnp


# Scoped-VMEM limit for kernels that keep a large operand resident and
# split it for exact-f32 MXU passes (v5e: 128 MiB of VMEM per core; the
# compiler's default scope is 16 MiB).
VMEM_LIMIT = 96 * 1024 * 1024


def should_interpret() -> bool:
    """Pallas TPU kernels run in interpret mode off-TPU (CPU container);
    on real TPU they compile to Mosaic."""
    return jax.default_backend() != "tpu"


def pad_to(x: jnp.ndarray, axis: int, multiple: int, value=0.0):
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value), n


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _eye(m: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (m, m), 1))


def row_to_col(row):
    """(1, M) -> (M, 1) inside a kernel.  Mosaic has no transpose for
    such unaligned shapes: keep the diagonal of the broadcast square and
    sum the lanes (exact for integers)."""
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0), axis=1,
                   keepdims=True)


def col_to_row(col):
    """(M, 1) -> (1, M): the sublane-sum twin of ``row_to_col``."""
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0), axis=0,
                   keepdims=True)
