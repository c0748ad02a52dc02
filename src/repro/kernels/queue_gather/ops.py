"""Public op: fused queue-gather + I2I-union with kernel/oracle dispatch."""
from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.kernels.queue_gather.queue_gather import (
    queue_gather as queue_gather_kernel)
from repro.kernels.queue_gather.ref import queue_gather_ref


def queue_gather(items, times, cursor, clusters, i2i, *, cutoff: float,
                 n_recent: int, k: int, use_kernel: bool = True
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched serving gather: U2U2I seeds + U2I2I round-robin union.

    items/times (C, Q) ring buffers, cursor (C,) total writes, clusters
    (B,) per-request cluster ids, i2i (N, K) offline KNN table — host or
    device arrays (the kernel path reads device arrays in place).
    Returns (seeds (B, n_recent), union (B, k)), both ``-1``-padded.
    """
    if use_kernel:
        return queue_gather_kernel(items, times, cursor, clusters, i2i,
                                   cutoff=cutoff, n_recent=n_recent, k=k)
    return queue_gather_ref(np.asarray(items), np.asarray(times),
                            np.asarray(cursor), np.asarray(clusters),
                            np.asarray(i2i), cutoff=cutoff,
                            n_recent=n_recent, k=k)
