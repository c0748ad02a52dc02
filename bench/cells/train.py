"""Training cell: back-to-back ``LifecycleRuntime.train_burst`` calls on a
seeded heterogeneous graph at the configuration's widths.

Traffic parameters (``bench/traffic/<name>.json``):

``steps_per_burst``  steps of one ``train_burst`` call (its closing
                     dead-code reset pass included);
``checked_steps``    set-up steps driven through ``train_burst(1)`` and
                     compared with the reference;
``warm_bursts``      bursts whose reset-probe shapes set-up compiles;
``pack_margin``      share of each checked batch's pack size by which
                     the window's batches may differ: set-up compiles
                     every padded shape within it.

The window runs whole bursts until ``--seconds`` have passed; the rate is
the edges of every step over the wall time of the window.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import time
from typing import Any, Dict, List, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import traffic as T                                   # noqa: E402
import work as W                                      # noqa: E402
from reference import train_ref as REF                # noqa: E402

EDGE_TYPES = ("uu", "ui", "ii")
MODEL_KEYS = ("d_user_feat", "d_item_feat", "d_embed", "n_heads",
              "d_hidden", "k_imp", "k_train", "n_negatives", "n_pool_neg",
              "margin", "tau", "dtype", "param_dtype")
RQ_KEYS = ("zeta1", "zeta2", "hist_len", "commit_coef", "usage_ema",
           "dead_floor", "reset_every", "reset_probe", "util_coef")


def program_config(cfg: Dict[str, Any]):
    """The program's rankgraph2 configuration with this file's values
    (they are the same at the published widths; a difference is
    printed)."""
    from repro.configs.rankgraph2 import CONFIG
    rq = dataclasses.replace(
        CONFIG.rq, codebook_sizes=tuple(cfg["codebook_sizes"]),
        **{k: cfg[k] for k in RQ_KEYS})
    out = dataclasses.replace(CONFIG, rq=rq,
                              **{k: cfg[k] for k in MODEL_KEYS})
    if out != CONFIG:
        print(f"note: running {out} in place of the registered {CONFIG}",
              file=sys.stderr)
    if not (rq.biased_selection and rq.regularize):
        raise ValueError("the reference states biased, regularised RQ")
    return out


def make_graph(cfg: Dict[str, Any], seed: int):
    """Edges, K_IMP neighbour tables and features drawn from the seed on
    the device in one jitted call, Zipf-skewed over seeded id orders."""
    import functools
    import jax
    import jax.numpy as jnp
    nu, ni, K = cfg["n_users"], cfg["n_items"], cfg["k_imp"]
    rng = T.rng_for(seed, 21)
    perm_u = jnp.asarray(rng.permutation(nu), jnp.int32)
    perm_i = jnp.asarray(rng.permutation(ni), jnp.int32)
    n_ui, n_uu, n_ii = (cfg["edges_ui"], cfg["edges_uu"] // 2,
                        cfg["edges_ii"] // 2)
    dz, nz = cfg["degree_zipf"], cfg["neighbour_zipf"]

    @functools.partial(jax.jit, static_argnums=(0,))
    def gen(shapes, key, pu, pi):
        ks = iter(jax.random.split(key, 16))
        z = lambda perm, n, s, c: perm[T.zipf_ranks(next(ks), n, s, c)]
        n_ui, n_uu, n_ii, n_nodes = shapes
        ui = (z(pu, nu, dz, n_ui), z(pi, ni, dz, n_ui))
        uu = (z(pu, nu, dz, n_uu), z(pu, nu, dz, n_uu))
        ii = (z(pi, ni, dz, n_ii), z(pi, ni, dz, n_ii))
        fill = jax.random.randint(next(ks), (n_nodes, 1), cfg["min_fill"],
                                  K + 1)
        col = jnp.arange(K)[None, :]
        un = z(pu, nu, nz, n_nodes * K).reshape(n_nodes, K)
        inn = z(pi, ni, nz, n_nodes * K).reshape(n_nodes, K) + nu
        un = jnp.where(col < fill, un, -1)
        inn = jnp.where(col < fill, inn, -1)
        w = jnp.exp(jax.random.normal(next(ks), (n_ui + 2 * n_uu
                                                 + 2 * n_ii,)))
        uf = jax.random.normal(next(ks), (nu, cfg["d_user_feat"]))
        itf = jax.random.normal(next(ks), (ni, cfg["d_item_feat"]))
        return ui, uu, ii, un, inn, w, uf, itf

    out = gen((n_ui, n_uu, n_ii, nu + ni), jax.random.key(seed), perm_u,
              perm_i)
    ui, uu, ii, un, inn, w, uf, itf = jax.tree.map(np.asarray, out)
    from repro.core.graph_builder import EdgeSet, HeteroGraph
    from repro.data.edge_dataset import NeighborTables
    a, b = n_ui, n_ui + 2 * n_uu
    both = lambda s, d: (np.r_[s, d].astype(np.int64),
                         np.r_[d, s].astype(np.int64))
    uu_s, uu_d = both(*uu)
    ii_s, ii_d = both(*ii)
    g = HeteroGraph(
        n_users=nu, n_items=ni,
        ui=EdgeSet(ui[0].astype(np.int64), ui[1].astype(np.int64), w[:a]),
        uu=EdgeSet(uu_s, uu_d, w[a:b]), ii=EdgeSet(ii_s, ii_d, w[b:]),
        group1_users=np.ones(nu, bool), group1_items=np.ones(ni, bool))
    return g, NeighborTables(un, inn, nu, ni), uf, itf


def _pack_shape(batch) -> Tuple[int, int, int, int]:
    n = batch["nodes"]
    return (n["user"]["unbr_idx"].shape[0], n["user"]["ids"].shape[0],
            n["item"]["unbr_idx"].shape[0], n["item"]["ids"].shape[0])


def _pack_counts(batch) -> Tuple[int, int, int, int]:
    """Real rows of each pack: distinct endpoints, and the pack's padded
    endpoint block plus its distinct neighbour-only nodes."""
    n, e = batch["nodes"], batch["edges"]
    out = []
    for t, key in (("user", "unbr"), ("item", "inbr")):
        ends = [m["src_map"] for et, m in e.items() if _SIDES[et][0] == t]
        ends += [m["dst_map"] for et, m in e.items() if _SIDES[et][1] == t]
        E = int(np.concatenate(ends).max()) + 1
        Ep = n[t]["unbr_idx"].shape[0]
        refs = np.concatenate([n[s][key + "_idx"][n[s][key + "_mask"] > 0]
                               for s in ("user", "item")])
        out += [E, Ep + int(np.unique(refs[refs >= Ep]).size)]
    return tuple(out)


def _repad(batch, shape):
    """A copy of ``batch`` with its packs padded or cut to ``shape`` (for
    compiling that shape only: cut rows make its numbers meaningless)."""
    def fit(a, rows):
        a = np.asarray(a)
        if a.shape[0] >= rows:
            return a[:rows]
        return np.pad(a, [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1))
    nodes = {}
    for i, t in enumerate(("user", "item")):
        ep, up = shape[2 * i], shape[2 * i + 1]
        nodes[t] = {k: fit(v, up if k == "ids" else ep)
                    for k, v in batch["nodes"][t].items()}
    return {"nodes": nodes, "edges": batch["edges"]}


_SIDES = {"uu": ("user", "user"), "ui": ("user", "item"),
          "ii": ("item", "item")}


def edge_keys(g, nu: int) -> Dict[str, np.ndarray]:
    """Sorted ``src * 2**32 + dst`` of every edge, in global node ids."""
    key = lambda s, d: np.unique(s * (1 << 32) + d)
    return {"uu": key(g.uu.src, g.uu.dst),
            "ui": key(g.ui.src, g.ui.dst + nu),
            "ii": key(g.ii.src + nu, g.ii.dst + nu)}


def leaf_norms(tree) -> Dict[str, float]:
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        out[name] = float(np.linalg.norm(np.asarray(leaf, np.float64)))
    return out


def _tree_np(tree):
    import jax
    return jax.tree.map(lambda x: np.asarray(x, np.float32).copy(), tree)


class Cell:
    programs = ("train_step",)

    def __init__(self, cfg, traffic, seed, run, devices):
        self.cfg, self.tr, self.seed, self.run = cfg, traffic, seed, run
        self.devices = devices
        self.pseed = int(seed) % (1 << 31)     # the program's own seed
        self.m = REF.freeze(cfg)

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.lifecycle import LifecycleConfig, LifecycleRuntime
        cfg = self.cfg
        t = time.perf_counter()
        pcfg = program_config(cfg)
        g, tables, uf, itf = make_graph(cfg, self.seed)
        t = self.run.phase("graph, tables, features", t)
        self.g, self.tables, self.features = g, tables, (uf, itf)
        lcfg = LifecycleConfig(steps_per_cycle=self.tr["steps_per_burst"],
                               batch_per_type=cfg["batch_per_type"])
        rt = LifecycleRuntime(pcfg, lcfg, g, tables, uf, itf,
                              seed=self.pseed)
        params = REF.make_params(self.seed, self.m)
        want = jax.tree.map(lambda x: (x.shape, x.dtype), rt.state.params)
        have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        if want != have:
            raise ValueError("the benchmark's weights do not match the "
                             "program's parameter tree")
        self.params0 = _tree_np(params)
        rt.state = dataclasses.replace(
            rt.state, params=jax.tree.map(jnp.copy, params))
        self.rt = rt
        t = self.run.phase("runtime and weights", t)
        ds = rt.dataset
        self._sample = ds.sample_batch
        run = self.run

        def timed_sample(*a, **kw):
            with run.span("sample_batch"):
                return self._sample(*a, **kw)

        object.__setattr__(ds, "sample_batch", timed_sample)
        self.readings = self._checked_steps()
        t = self.run.phase("checked steps", t)
        self._warm_probes()
        t = self.run.phase("reset-probe warm-up", t)
        self._warm_pack_shapes()
        self.run.phase("pack-shape warm-up", t)

    def _checked_steps(self) -> Dict[str, Any]:
        """Drive the first steps through the window's own call, reading
        each step's loss, the first gradient from the optimizer state and
        the parameters after the last."""
        rt = self.rt
        losses, first = [], None
        for t in range(int(self.tr["checked_steps"])):
            out = rt.train_burst(1)
            losses.append(out["total"])
            if t == 0:
                first = self._first_grad(rt.state)
        return {"loss": losses, "grad": first,
                "after": _tree_np(rt.state.params)}

    @staticmethod
    def _first_grad(state) -> Dict[str, float]:
        """Per-leaf norm of the gradient the optimizer took at step 1:
        AdamW's first moment is ``(1 - b1) g``, AdaGrad's accumulator
        ``g ** 2``."""
        import jax
        opt = state.opt_state
        mu = leaf_norms(opt["false"].mu)
        acc = jax.tree_util.tree_flatten_with_path(opt["true"])[0]
        out = {}
        for path, leaf in acc:
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            if "codebooks" in name:
                out[name] = float(np.sqrt(np.asarray(leaf, np.float64).sum()))
            else:
                out[name] = mu[name] / 0.1
        return out

    def _warm_pack_shapes(self) -> None:
        """Compile the train step for every padded batch shape the window
        can reach.  The program pads each node pack to a bucket
        (``edge_dataset.pack_bucket``); where the checked batches' counts
        lie within a margin of a bucket's edge, the next bucket is
        compiled too, on a padded copy of a checked batch and a copy of
        the state (the step donates its state), so no step of the window
        meets a new shape."""
        import itertools
        import jax
        import jax.numpy as jnp
        from repro.data.edge_dataset import pack_bucket
        per = {et: self.cfg["batch_per_type"] for et in EDGE_TYPES}
        self.batches = [self._sample(t, self.pseed, per)
                        for t in range(int(self.tr["checked_steps"]))]
        counts = np.array([_pack_counts(b) for b in self.batches], float)
        margin = float(self.tr["pack_margin"]) * counts.mean(0)
        lo, hi = counts.min(0) - margin, counts.max(0) + margin
        dims = []
        for t in range(2):                      # user, item packs
            e_b = {pack_bucket(int(lo[2 * t]), 64),
                   pack_bucket(int(hi[2 * t]), 64)}
            u_b = {pack_bucket(int(e + x), 64) for e in e_b
                   for x in (lo[2 * t + 1], hi[2 * t + 1])}
            dims += [sorted(e_b), sorted(u_b)]
        seen = {_pack_shape(b) for b in self.batches}
        rt = self.rt
        for shape in itertools.product(*dims):
            if shape in seen:
                continue
            batch = jax.tree.map(jnp.asarray, _repad(self.batches[0], shape))
            state, _ = rt._step_fn(jax.tree.map(jnp.copy, rt.state), batch,
                                   jax.random.key(0), rt._features)
            jax.block_until_ready(state)
            print(f"warmed pack shape {shape}", file=sys.stderr)

    def _probe_sizes(self, bursts: int) -> List[Tuple[int, int]]:
        """Users and items in the reset probe closing each of the next
        bursts (drawn as ``LifecycleRuntime`` draws it)."""
        nu, ni = self.cfg["n_users"], self.cfg["n_items"]
        n_probe = self.cfg["reset_probe"]
        base = int(self.tr["checked_steps"])
        steps = int(self.tr["steps_per_burst"])
        out = []
        for b in range(bursts):
            s = base + steps * (b + 1)
            ids = np.random.default_rng((self.pseed, 91, s)).choice(
                nu + ni, min(n_probe, nu + ni), replace=False)
            n_u = int((ids < nu).sum())
            out.append((n_u, len(ids) - n_u))
        return out

    def _warm_probes(self) -> None:
        """Compile the reset pass's embedding programs for the probe
        shapes the window's bursts will use."""
        from repro.core import model as M
        from repro.core import trainer as TR
        rt = self.rt
        seen = set()
        for n_u, n_i in self._probe_sizes(int(self.tr["warm_bursts"])):
            for ntype, n, off in ((M.USER, n_u, 0),
                                  (M.ITEM, n_i, self.cfg["n_users"])):
                b = min(rt.lcfg.embed_batch, n)
                if n == 0 or (ntype, b) in seen:
                    continue
                seen.add((ntype, b))
                TR.embed_all(rt.state.params, rt.cfg, rt.dataset,
                             node_type=ntype, ids=off + np.arange(n),
                             batch=b)

    # -- the measured window ----------------------------------------------

    def measure(self, seconds: float) -> None:
        import jax
        rt, run = self.rt, self.run
        steps = int(self.tr["steps_per_burst"])
        edges = 3 * self.cfg["batch_per_type"]
        self.bad_losses = 0
        n_steps = 0
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                with run.span("train_burst"):
                    out = rt.train_burst(steps)
                n_steps += steps
                self.bad_losses += sum(not np.isfinite(v)
                                       for v in out.values())
            t1 = time.perf_counter()
        run.window_s = t1 - t0
        run.counts["steps"] = n_steps
        run.counts["edges"] = n_steps * edges
        run.counts["bursts"] = n_steps // steps

    # -- after the window -------------------------------------------------

    def failed(self) -> int:
        return int(getattr(self, "bad_losses", 0))

    def release(self) -> None:
        """Count the checked batches' required work and free the
        program's device state."""
        self.rt = None
        gc.collect()
        nu = self.cfg["n_users"]
        keys = edge_keys(self.g, nu)
        self.expanded = []
        for b in self.batches:
            self.expanded.append(REF.expand(b, nu, keys,
                                            self.tables.user_nbrs,
                                            self.tables.item_nbrs))
        meta = [e[0]["meta"] for e in self.expanded]
        m = self.cfg
        flops = np.mean([W.train_step(
            nodes=x["user"]["nodes"] + x["item"]["nodes"],
            endpoints=x["user"]["endpoints"] + x["item"]["endpoints"],
            edges_per_type=m["batch_per_type"], d_feat=m["d_user_feat"],
            d_hidden=m["d_hidden"], d=m["d_embed"], heads=m["n_heads"],
            n_negatives=m["n_negatives"],
            codebook_sizes=m["codebook_sizes"])["flops"] for x in meta])
        steps = self.run.counts.get("steps", 0)
        self.run.work["train_step"] = {"flops": float(flops * steps),
                                       "bytes": 0.0}
        self.g = None

    def _reference(self, dtype, edge_share: float = 1.0):
        """Readings of the reference's checked steps: losses, the first
        clipped gradient and the parameters after the last step."""
        import jax
        import jax.numpy as jnp
        feats = {"user": jnp.asarray(self.features[0]),
                 "item": jnp.asarray(self.features[1])}
        state = REF.init_state(jax.tree.map(jnp.asarray, self.params0),
                               self.m)
        losses, first = [], None
        for t, (batch, _) in enumerate(self.expanded):
            if edge_share < 1.0:
                batch = dict(batch, edges={
                    et: {k: v[:int(len(v) * edge_share)]
                         for k, v in e.items()}
                    for et, e in batch["edges"].items()})
            state, loss, grads = REF.step(state, batch, feats,
                                          jax.random.key(1000 + t), self.m,
                                          dtype=dtype)
            losses.append(loss)
            if t == 0:
                first = leaf_norms(grads)
        return {"loss": losses, "grad": first,
                "after": _tree_np(state["params"])}

    def check(self, control: bool = False) -> List[Tuple[str, float, float]]:
        import jax.numpy as jnp
        ref = self._reference(jnp.float32)
        got = (self._reference(jnp.float8_e4m3fn) if control
               else self.readings)
        bad = sum(e[1] for e in self.expanded)
        return compare(got, ref, self.params0, bad, self.cfg["limits"])


def gaps(got, ref, params0) -> Dict[str, float]:
    """The compared numbers: the first step's relative loss gap (later
    steps' gaps are returned beside it); the worst per-leaf gap between
    the program's and the reference's norms of the first gradient and of
    the parameters' change, each over the larger of that leaf's reference
    norm and the median leaf's.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's are left out."""
    steps = [abs(a - b) / max(abs(b), 1e-12)
             for a, b in zip(got["loss"], ref["loss"])]
    g_ref = ref["grad"]
    med_g = float(np.median(list(g_ref.values())))
    keep = [k for k, v in g_ref.items() if v >= 1e-3 * med_g]
    grad = max(abs(got["grad"][k] - g_ref[k]) / max(g_ref[k], med_g)
               for k in keep)
    d_got = leaf_norms(_diff(got["after"], params0))
    d_ref = leaf_norms(_diff(ref["after"], params0))
    med_d = float(np.median([d_ref[k] for k in keep]))
    change = max(abs(d_got[k] - d_ref[k]) / max(d_ref[k], med_d)
                 for k in keep)
    return {"loss_gap": steps[0], "grad_gap": grad, "change_gap": change,
            "left_out": float(len(g_ref) - len(keep)),
            "loss_gap_steps": steps}


def _diff(a, b):
    import jax
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64) - y, a, b)


def compare(got, ref, params0, bad: int, limits: Dict[str, float]
            ) -> List[Tuple[str, float, float]]:
    g = gaps(got, ref, params0)
    print(f"loss gap per checked step {g['loss_gap_steps']}; leaves left "
          f"out {g['left_out']}", file=sys.stderr)
    return [("loss_gap", g["loss_gap"], limits["loss_gap"]),
            ("grad_gap", g["grad_gap"], limits["grad_gap"]),
            ("change_gap", g["change_gap"], limits["change_gap"]),
            ("feed_mismatches", float(bad), 0.0)]
