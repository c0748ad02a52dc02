"""The one traffic generator: a seeded population and the arrivals, users
and items that a traffic file's parameters ask for.

Keys are Zipf-skewed (YCSB's constant 0.99 by default): the key of rank
``r`` (1-based) has weight ``r ** -s``, and ranks are mapped to ids by a
seeded permutation so hot ids are scattered.  Keys are drawn on the
device by the closed-form inverse of ``zipf_ranks``, so populations and
tables of hundreds of millions of keys take one call (or one call a
block), never a host loop.

Arrivals are open loop: a window of ``seconds`` at ``rate`` per second
holds exactly ``round(rate * seconds)`` arrivals at sorted uniform
offsets (a Poisson process given its count), so every seed offers the
same amount of work in another order.
"""
from __future__ import annotations

import functools

import numpy as np


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng((int(seed) % (1 << 63),) + tags)


def zipf_ranks(key, n: int, s: float, count: int):
    """``count`` 0-based Zipf(s) ranks over ``n`` keys drawn on the device
    by the closed-form inverse of the density ``x ** -s`` on
    ``[0.5, n + 0.5)`` (the midpoint rule: rank ``r`` gets the mass of
    ``[r + 0.5, r + 1.5)``; the head ranks come within 10% of the discrete
    law for ``s`` near 1).  Float32 resolves the inverse only to a
    relative ``2 ** -23 / (1 - s)``, which in the tail of a hundred
    million keys spans hundreds of ranks; each draw is spread uniformly,
    in integers, over twice that width, so every rank can come up.
    Needs ``s != 1``; a traced function."""
    import jax
    import jax.numpy as jnp
    a = 1.0 - float(s)
    lo, hi = 0.5 ** a, (n + 0.5) ** a
    u = jax.random.uniform(key, (count,))
    x = (lo + u * (hi - lo)) ** (1.0 / a)
    w = jnp.maximum(jnp.floor(x * (2.0 ** -22 / abs(a))), 1.0)
    v = jax.random.uniform(jax.random.fold_in(key, 1), (count,))
    r = (jnp.floor(x + 0.5).astype(jnp.int32) - 1
         + jnp.floor(v * w).astype(jnp.int32) - w.astype(jnp.int32) // 2)
    return jnp.clip(r, 0, n - 1)


def arrivals(rate: float, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Sorted due offsets (s) of an open-loop window."""
    n = int(round(rate * seconds))
    return np.sort(rng.random(n) * seconds)


def device_population(key, n_users: int, n_clusters: int, *,
                      user_zipf: float, cluster_zipf: float):
    """A seeded user population drawn on the device in one jitted call,
    as a dict of device arrays: ``clusters`` (each user's flat cluster
    id; cluster sizes follow Zipf(``cluster_zipf``) over a seeded cluster
    order, and every cluster has at least one member), ``user_of_rank``
    (a seeded permutation: the user of each 0-based request-weight
    rank), ``cluster_weight`` (the summed Zipf(``user_zipf``) request
    weight of each cluster's users) and the cluster -> members CSR
    ``member_ptr`` / ``member_ids`` (users in cluster order, stable).
    Needs ``n_users >= n_clusters``."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
    def draw_(key, n, C, su, sc):
        k1, k2, k3 = jax.random.split(key, 3)
        order = jax.random.permutation(k1, C).astype(jnp.int32)
        cl = order[zipf_ranks(k2, C, sc, n)]
        by_rank = jax.random.permutation(k3, n).astype(jnp.int32)
        # one user of every cluster, spread evenly over the ranks
        cl = cl.at[by_rank[jnp.arange(C) * (n // C)]].set(
            jnp.arange(C, dtype=jnp.int32))
        rank = jnp.zeros(n, jnp.int32).at[by_rank].set(
            jnp.arange(n, dtype=jnp.int32))
        weight = (rank.astype(jnp.float32) + 1.0) ** -float(su)
        cw = jax.ops.segment_sum(weight, cl, num_segments=C)
        ptr = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(
            jnp.bincount(cl, length=C)).astype(jnp.int32)])
        mids = jnp.argsort(cl, stable=True).astype(jnp.int32)
        return dict(clusters=cl, user_of_rank=by_rank, cluster_weight=cw,
                    member_ptr=ptr, member_ids=mids)

    return draw_(key, int(n_users), int(n_clusters), float(user_zipf),
                 float(cluster_zipf))


def device_zipf_keys(key, table, s: float, count: int, *,
                     block: int = 1 << 18) -> np.ndarray:
    """``count`` entries of the device array ``table`` (the key of each
    0-based rank) drawn by Zipf(``s``) rank on the device, ``block`` a
    call, so one compiled program serves every count."""
    import jax
    take = _zipf_take()
    parts = [np.asarray(take(jax.random.fold_in(key, j), table, float(s),
                             int(block)))
             for j in range(max(-(-int(count) // block), 1))]
    return np.concatenate(parts)[:count]


@functools.lru_cache(maxsize=None)
def _zipf_take():
    import jax
    return jax.jit(lambda key, table, s, count: table[zipf_ranks(
        key, table.shape[0], s, count)], static_argnums=(2, 3))


def device_i2i(key, n: int, k: int, s: float, *, rows: int = 1 << 20
               ) -> np.ndarray:
    """Offline I2I table ``(n, k)`` int32 in host memory: ``k``
    neighbours per item drawn Zipf(``s``) over a seeded item order
    (popular items sit in many lists), never the item itself.  Drawn on
    the device ``rows`` rows a call, so the device never holds the whole
    table, each block flat (a flat array leaves the device with no change
    of layout); the last call ends at row ``n`` (it may redraw rows of
    the one before)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
    def block(key, order, start, rows, n, k, s):
        nb = order[zipf_ranks(key, n, s, rows * k)]
        own = start + jnp.arange(rows * k, dtype=jnp.int32) // k
        return jnp.where(nb == own, (nb + 1) % n, nb)

    k0, k1 = jax.random.split(key)
    order = jax.random.permutation(k0, n).astype(jnp.int32)
    rows = min(int(rows), n)
    out = np.empty((n, k), np.int32)
    flat = out.reshape(-1)
    starts = list(range(0, n - rows, rows)) + [n - rows]
    ahead = None
    for j, st in enumerate(starts):
        cur = ahead if ahead is not None else block(
            jax.random.fold_in(k1, j), order, jnp.int32(st), rows, n, k,
            float(s))
        if j + 1 < len(starts):          # keep the device one block ahead
            ahead = block(jax.random.fold_in(k1, j + 1), order,
                          jnp.int32(starts[j + 1]), rows, n, k, float(s))
        flat[st * k:(st + rows) * k] = np.asarray(cur)
    return out


def device_event_fn(pop, item_perm, *, item_zipf: float, n: int):
    """``f(key, span) -> (users, items, offsets)`` drawing ``n``
    engagement events on the device in one jitted call: clusters by their
    users' summed request weight (``pop`` from ``device_population``), a
    uniform member of that cluster as the user, a Zipf(``item_zipf``)
    item by rank through the device array ``item_perm``, and a uniform
    offset in ``[0, span)``."""
    import jax
    import jax.numpy as jnp
    cw = pop["cluster_weight"]
    ccdf = jnp.cumsum(cw) / jnp.sum(cw)
    tables = (ccdf, item_perm, pop["member_ptr"], pop["member_ids"])

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def events(tabs, key, span, n, s):
        ccdf, iperm, ptr, mids = tabs
        k1, k2, k3, k4 = jax.random.split(key, 4)
        c = jnp.minimum(jnp.searchsorted(ccdf, jax.random.uniform(k1, (n,)),
                                         side="right"), ccdf.shape[0] - 1)
        lo, hi = ptr[c], ptr[c + 1]
        m = lo + jnp.floor(jax.random.uniform(k2, (n,))
                           * (hi - lo)).astype(jnp.int32)
        users = mids[jnp.minimum(m, hi - 1)]
        items = iperm[zipf_ranks(k3, iperm.shape[0], s, n)]
        offs = jax.random.uniform(k4, (n,)) * span
        return users, items, offs

    return lambda key, span: events(tables, key, jnp.float32(span), int(n),
                                    float(item_zipf))
