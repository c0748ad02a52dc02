#!/usr/bin/env python3
"""Bring-up run of the RankGraph-2 lifecycle on one TPU chip.

    python3 chip_smoke.py                # one chip: lifecycle + kernels
    python3 chip_smoke.py --four-chips   # four chips: sharded serving only

One chip, at the widths of ``src/repro/configs/rankgraph2.py`` (§5.1):
construction (``build_graph`` + the jax PPR walker), cycle 0 through
``LifecycleRuntime.run_cycle`` (a 32,768-edge training burst, publish
through the 5000x50 codebooks, ``SwapServer`` bring-up with its
250,000-cluster x 256-slot rings on the device), a seeded engagement
stream plus U2U2I + U2I2I requests against v1, then cycle 1 (refresh
with new users and items, train, publish, hot-swap) and requests
against v2.  A kernels phase then runs each Pallas kernel against its
plain counterpart on the same chip.

``--four-chips`` runs only sharded serving: ``SwapServer(n_shards=4)``
with shard i on device i, against the unsharded store on one chip, on
the same stream and requests; the results must be bitwise equal.

Every time printed is one cold run (compilation included), not a
benchmark number.  The run fails — non-zero exit, no result line — on
any failed, skipped or degraded stage, any shed or dropped event, a
non-finite loss, an empty serving answer or a kernel that disagrees with
its reference.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The script refuses to run where JAX finds no TPU.  ``run_lifecycle``,
``run_kernels`` and ``run_four_chips`` take their sizes as arguments, so
the CPU tests drive the same path at tiny widths.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
from typing import Any, Callable, Dict, Iterator, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(AssertionError):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Scale of the run; widths come from the model config."""
    n_users: int = 100_000
    n_items: int = 50_000
    events_per_user: float = 5.0
    batch_per_type: int = 10_923     # 32,768 edges per step over uu/ui/ii
    steps: int = 3
    new_users: int = 1_000
    new_items: int = 500
    n_requests: int = 512
    request_batch: int = 128
    ingest_batch: int = 16_384
    i2i_k: int = 16
    ppr_nodes: int = 8_192           # the Pallas walker's resident size
    ppr_starts: int = 1_024


FULL = Sizes()


class Phases:
    """Wall-clock seconds of each phase, printed as they finish."""

    def __init__(self, log: Callable[[str], None]):
        self.log = log
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        yield
        self.seconds[name] = dt = time.perf_counter() - t0
        self.log(f"phase {name}: {dt:.3f} s (one cold run, compilation "
                 "included)")


def _batches(n: int, size: int) -> Iterator[slice]:
    for lo in range(0, n, size):
        yield slice(lo, min(n, lo + size))


def check_report(rep: Dict[str, Any], where: str) -> None:
    """No stage of a cycle report may have failed, been skipped or run
    degraded."""
    def walk(d, path):
        for k, v in d.items():
            if isinstance(v, dict):
                walk(v, f"{path}.{k}")
            elif k in ("failed", "skipped", "degraded", "rolled_back"):
                check(not v, f"{where}: {path}.{k} = {v!r}")
    walk(rep, where)
    train = rep.get("train", {})
    check(bool(train), f"{where}: no training report")
    bad = {k: v for k, v in train.items() if not np.isfinite(v)}
    check(not bad, f"{where}: non-finite training metrics {bad}")


def serve(server, users: np.ndarray, now: float, sz: Sizes,
          want_version: int, **kw) -> Tuple[np.ndarray, np.ndarray]:
    seeds, union = [], []
    for sl in _batches(len(users), sz.request_batch):
        s, u, ver = server.serve_batch(users[sl], now, n_recent=8, k=32,
                                       **kw)
        check(ver == want_version,
              f"served version {ver}, want {want_version}")
        seeds.append(s)
        union.append(u)
    seeds, union = np.concatenate(seeds), np.concatenate(union)
    check(int((union >= 0).any(axis=1).sum()) > 0,
          f"v{want_version}: no request got a U2I2I candidate")
    check(int((seeds >= 0).any(axis=1).sum()) > 0,
          f"v{want_version}: no request got a U2U2I seed")
    return seeds, union


def run_lifecycle(cfg, sz: Sizes, *, seed: int = 0,
                  log: Callable[[str], None] = print) -> Dict[str, Any]:
    """Construction, cycle 0, traffic on v1, cycle 1 and traffic on v2
    through the lifecycle's own entry points.  Returns the runtime and
    the request users for the kernels phase."""
    from repro.core.graph_builder import EngagementLog, build_graph
    from repro.data.edge_dataset import build_neighbor_tables
    from repro.data.synthetic import make_world
    from repro.lifecycle import LifecycleConfig, LifecycleRuntime
    from repro.obs import Telemetry

    tel = Telemetry()                    # in-memory counters, no sink
    phase = Phases(log)
    rng = np.random.default_rng((seed, 11))

    with phase("construction"):
        world = make_world(n_users=sz.n_users, n_items=sz.n_items,
                           d_user_feat=cfg.d_user_feat,
                           d_item_feat=cfg.d_item_feat,
                           events_per_user=sz.events_per_user, seed=seed)
        day0 = world.day0
        old = day0.window(82800.0, 86400.0)       # the first 23 hours
        g = build_graph(old, alpha_pop=cfg.alpha_pop, c_u=cfg.c_u,
                        c_i=cfg.c_i, k_cap=cfg.k_cap, seed=seed,
                        keep_state=True)
        tables = build_neighbor_tables(
            g, k_imp=cfg.k_imp, n_walks=cfg.ppr_walks, walk_len=cfg.ppr_len,
            restart=cfg.ppr_restart, seed=seed, backend="jax",
            keep_state=True)
    log(f"world: {sz.n_users} users, {sz.n_items} items, "
        f"{len(day0.user_id)} events; graph: {len(g.uu)} uu, "
        f"{len(g.ui)} ui, {len(g.ii)} ii edges")

    lcfg = LifecycleConfig(steps_per_cycle=sz.steps,
                           batch_per_type=sz.batch_per_type,
                           i2i_k=sz.i2i_k, recency_s=2 * 86400.0)
    rt = LifecycleRuntime(cfg, lcfg, g, tables, world.user_feat,
                          world.item_feat, seed=seed, telemetry=tel)
    with phase("cycle0"):
        rep0 = rt.run_cycle(now=82800.0)
    check_report(rep0, "cycle0")
    check(rt.server is not None and rt.server.version == 1,
          "cycle 0 did not bring serving up on v1")
    store = rt.server.handle.acquire().store
    log(f"cycle0: train total={rep0['train']['total']:.6f}; serving v1 "
        f"with {store.n_clusters} clusters x {store.queue_len} slots")

    stream = day0.window(86400.0, 3600.0)         # the trailing hour
    now = 86400.0
    active = np.unique(stream.user_id)
    users = rng.choice(active, min(sz.n_requests, len(active)),
                       replace=False)
    with phase("traffic_v1"):
        for sl in _batches(len(stream.user_id), sz.ingest_batch):
            rt.server.ingest(stream.user_id[sl], stream.item_id[sl],
                             stream.timestamp[sl])
        serve(rt.server, users, now, sz, 1)
    log(f"traffic v1: {len(stream.user_id)} events ingested, "
        f"{len(users)} requests served")

    nu, ni = day0.n_users, day0.n_items
    nu2, ni2 = nu + sz.new_users, ni + sz.new_items
    du = np.r_[stream.user_id, np.arange(nu, nu2),
               rng.integers(0, nu, sz.new_items)]
    di = np.r_[stream.item_id, rng.integers(0, ni, sz.new_users),
               np.arange(ni, ni2)]
    ts = np.r_[stream.timestamp, np.full(sz.new_users + sz.new_items, now)]
    delta = EngagementLog(du.astype(np.int64), di.astype(np.int64),
                          np.zeros(len(du), np.int32), ts, nu2, ni2)
    uf = np.r_[world.user_feat, rng.normal(
        0, 1, (sz.new_users, cfg.d_user_feat)).astype(np.float32)]
    itf = np.r_[world.item_feat, rng.normal(
        0, 1, (sz.new_items, cfg.d_item_feat)).astype(np.float32)]
    with phase("cycle1"):
        rep1 = rt.run_cycle(delta, now=now, user_feat=uf, item_feat=itf,
                            backend="jax")
    check_report(rep1, "cycle1")
    check(rt.server.version == 2, "cycle 1 did not swap serving to v2")
    log(f"cycle1: re-walked {rep1['refresh']['affected_nodes']} nodes, "
        f"train total={rep1['train']['total']:.6f}, swap replayed "
        f"{int(rep1['swap']['replayed_events'])} events")

    users2 = np.r_[users, np.arange(nu, min(nu2, nu + 8))]
    with phase("traffic_v2"):
        serve(rt.server, users2, now, sz, 2)

    counters = tel.snapshot()["counters"]
    for name in ("lifecycle.stage_failures", "swap.ingest_shed_batches",
                 "swap.ring_dropped"):
        check(counters.get(name, 0.0) == 0.0,
              f"counter {name} = {counters.get(name)}")
    check(rt.server.ring_dropped == 0, "the event ring dropped events")
    return dict(runtime=rt, users=users2, now=now, phases=phase.seconds)


def _first_occurrence_counts(visited: np.ndarray) -> np.ndarray:
    """Per row: each value's multiplicity at its first occurrence, 0
    elsewhere (the Pallas walker's count layout)."""
    out = np.zeros(visited.shape, np.int64)
    for r, row in enumerate(visited):
        _, first, cnt = np.unique(row, return_index=True,
                                  return_counts=True)
        out[r, first] = cnt
    return out


def run_kernels(ctx: Dict[str, Any], sz: Sizes, *, seed: int = 0,
                log: Callable[[str], None] = print) -> Dict[str, float]:
    """Each Pallas kernel against its plain counterpart, on the state the
    lifecycle left behind, at each family's test tolerance.  References
    run at full f32 matmul precision, as the kernels do."""
    import jax
    import jax.numpy as jnp
    from repro.core import trainer as T
    from repro.core.ppr import (build_padded_hetero_adj, ppr_walk_jax,
                                walk_uniforms)
    from repro.kernels.ppr_walk.ops import ppr_walk
    from repro.lifecycle.publish import build_snapshot

    rt = ctx["runtime"]
    cfg = rt.cfg
    phase = Phases(log)
    exact = jax.default_matmul_precision("highest")

    with phase("kernel_rq_assign"):
        args = (3, rt._last_user_emb, rt._last_item_emb,
                rt.state.params["rq"], cfg)
        kw = dict(i2i_k=sz.i2i_k, chunk=rt.lcfg.encode_chunk,
                  want_user_recon=True)
        with exact:
            ref, ref_recon = build_snapshot(*args, use_kernel=False, **kw)
        ker, ker_recon = build_snapshot(*args, use_kernel=True, **kw)
        same = (ker.user_codes == ref.user_codes).all(axis=1)
        agree_u = float(same.mean())
        agree_i = float((ker.item_codes == ref.item_codes).all(
            axis=1).mean())
        log(f"rq_assign: code agreement users {agree_u:.6f} items "
            f"{agree_i:.6f}")
        check(agree_u > 0.99 and agree_i > 0.99,
              f"rq_assign code agreement {agree_u}, {agree_i}")
        np.testing.assert_allclose(ker_recon[same], ref_recon[same],
                                   rtol=1e-5, atol=1e-5)

    with phase("kernel_fused_contrastive"):
        cfg32 = dataclasses.replace(cfg, dtype="float32")
        batch = jax.tree.map(jnp.asarray, rt.dataset.sample_batch(
            10_000, seed, {et: sz.batch_per_type
                           for et in ("uu", "ui", "ii")}))
        key = jax.random.key(7)
        metrics = {}
        for fused in (False, True):
            c = dataclasses.replace(cfg32, use_fused_contrastive=fused)
            step = T.make_train_step(c, rt.optimizer, donate=False)
            with exact:
                _, m = step(rt.state, batch, key, rt._features)
            metrics[fused] = {k: float(v) for k, v in m.items()}
        ref, ker = metrics[False], metrics[True]
        for k in ref:
            tol = 2e-4 if k == "grad_norm" else 1e-5
            np.testing.assert_allclose(ker[k], ref[k], rtol=tol, atol=1e-5,
                                       err_msg=k)
        log(f"fused_contrastive: train step total {ker['total']:.7f} vs "
            f"{ref['total']:.7f}, grad_norm {ker['grad_norm']:.7f} vs "
            f"{ref['grad_norm']:.7f}")

    with phase("kernel_queue_gather"):
        users, now = ctx["users"], ctx["now"]
        s_ref, u_ref = serve(rt.server, users, now, sz, rt.server.version)
        s_ker, u_ker = serve(rt.server, users, now, sz, rt.server.version,
                             use_kernel=True)
        check(np.array_equal(s_ker, s_ref) and np.array_equal(u_ker, u_ref),
              "queue_gather kernel disagrees with the default dispatch")
        log(f"queue_gather: {len(users)} requests bitwise equal to the "
            "default dispatch")

    with phase("kernel_ppr_walk"):
        adj = build_padded_hetero_adj(rt.g)
        n = min(sz.ppr_nodes, adj.n_nodes)
        nbrs = np.where(adj.nbrs[:n] < n, adj.nbrs[:n], -1)
        cum = adj.cum[:n]
        starts = np.arange(min(sz.ppr_starts, n), dtype=np.int64)
        u = walk_uniforms(seed, starts, cfg.ppr_walks, cfg.ppr_len)
        vk, ck = ppr_walk(nbrs, cum, starts, u, restart=cfg.ppr_restart,
                          use_kernel=True)
        vj = ppr_walk_jax(nbrs, cum, starts, u, n_walks=cfg.ppr_walks,
                          walk_len=cfg.ppr_len, restart=cfg.ppr_restart)
        vk, ck = np.asarray(vk, np.int64), np.asarray(ck, np.int64)
        check(np.array_equal(vk, vj),
              "ppr_walk kernel walks differ from the jax walker")
        check(np.array_equal(ck, _first_occurrence_counts(vj)),
              "ppr_walk kernel visit counts are wrong")
        log(f"ppr_walk: {len(starts)} starts x {cfg.ppr_walks} walks on a "
            f"{n}-node subgraph equal to the jax walker")
    return phase.seconds


def run_four_chips(cfg, sz: Sizes, *, seed: int = 0,
                   log: Callable[[str], None] = print) -> Dict[str, float]:
    """Sharded serving, shard i on device i, against the unsharded store
    on one device: the same snapshot, stream and requests must give
    bitwise-equal answers."""
    import jax
    from jax.sharding import Mesh
    from repro.lifecycle import SwapServer, build_snapshot

    devices = jax.devices()
    check(len(devices) == 4, f"four devices wanted, found {len(devices)}")
    phase = Phases(log)
    rng = np.random.default_rng((seed, 12))
    with phase("snapshot"):
        d = cfg.d_embed
        books = {f"layer{l}": rng.normal(0, 1, (n, d)).astype(np.float32)
                 for l, n in enumerate(cfg.rq.codebook_sizes)}
        snap = build_snapshot(
            1, rng.normal(0, 1, (sz.n_users, d)).astype(np.float32),
            rng.normal(0, 1, (sz.n_items, d)).astype(np.float32),
            {"codebooks": books}, cfg, i2i_k=sz.i2i_k)
    mesh = Mesh(np.array(devices), ("shard",))
    servers = {}
    with phase("bring_up"):
        for name, kw in (("one_chip", {}),
                         ("four_chips", dict(n_shards=4, mesh=mesh))):
            servers[name] = SwapServer(snap, recency_s=2 * 86400.0, **kw)
    n_ev = int(sz.n_users * sz.events_per_user) // 4
    ev_u = rng.integers(0, sz.n_users, n_ev)
    ev_i = rng.integers(0, sz.n_items, n_ev)
    ev_t = np.sort(rng.uniform(0.0, 3600.0, n_ev))
    users = rng.choice(np.unique(ev_u), sz.n_requests, replace=False)
    answers = {}
    for name, server in servers.items():
        with phase(f"traffic_{name}"):
            for sl in _batches(n_ev, sz.ingest_batch):
                server.ingest(ev_u[sl], ev_i[sl], ev_t[sl])
            answers[name] = serve(server, users, 3600.0, sz, 1)
    (s1, u1), (s4, u4) = answers["one_chip"], answers["four_chips"]
    check(np.array_equal(s1, s4) and np.array_equal(u1, u4),
          "sharded serving differs from the one-chip store")
    store = servers["four_chips"].handle.acquire().store
    for i, sh in enumerate(store.shards):
        check(sh._state["items"].devices() == {devices[i]},
              f"shard {i} ring is not on device {i}")
        check(sh._i2i_cache is not None
              and sh._i2i_cache[1].devices() == {devices[i]},
              f"shard {i} I2I table is not on device {i}")
    log(f"four chips: {n_ev} events, {len(users)} requests bitwise equal "
        "to the one-chip store; shard i on device i")
    return phase.seconds


def require_tpu():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found platform "
                 f"{devices[0].platform!r}")
    return devices


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run only sharded serving, on four chips")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    devices = require_tpu()
    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.configs.rankgraph2 import CONFIG
    from repro.kernels.common import should_interpret

    cache = enable_compile_cache()
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    def log(msg: str) -> None:
        print(msg, flush=True)

    log(f"device: {devices[0].device_kind} x {len(devices)}; "
        f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(CONFIG, FULL, seed=args.seed, log=log)
    else:
        check(not should_interpret(), "Pallas kernels would interpret")
        log(f"reduced: {FULL.n_users} users, {FULL.n_items} items, "
            f"{FULL.events_per_user} events per user, {FULL.steps} train "
            "steps per cycle (a scale cut; every §5.1 width is kept)")
        ctx = run_lifecycle(CONFIG, FULL, seed=args.seed, log=log)
        run_kernels(ctx, FULL, seed=args.seed, log=log)
    stats = devices[0].memory_stats() or {}
    log(f"total: {time.perf_counter() - t0:.3f} s (one cold run); backend "
        f"compile {sum(compile_s):.3f} s over {len(compile_s)} programs; "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
