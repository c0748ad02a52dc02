"""Plain reference of the RankGraph-2 co-training step (paper §4.3-4.4).

It imports nothing of the program and computes in float32 at
``Precision.HIGHEST`` (``dtype`` lowers the operands of every matrix
product for the control).  What it states, per step:

* encoder ``f_t(x) = W2 gelu(W1 x + b1) + b2``, reshaped to ``H`` heads of
  width ``d`` (gelu in its tanh form), once per distinct node;
* aggregator ``AGG_t``: per head, ``gelu([self, mean of user neighbours,
  mean of item neighbours] @ W_h + b_h)``, l2-normalised (masked
  neighbours count zero, the mean divides by at least one); the primary
  embedding is the l2-normalised mean over heads;
* negatives per loss direction (ii, ui, iu, uu): 56 in-batch primaries of
  other rows, 32 from the out-of-batch pool (in-batch while the pool is
  empty) and 12 single heads of other rows, drawn by threefry from the
  step key ``key(1000 + step)`` split eight ways, one split per
  direction in that order;
* margin (0.1) and InfoNCE (tau 0.06) losses per direction; two-layer RQ
  with biased code selection (Eq. 13), reconstruction plus 0.25
  commitment, balance regulariser (Eq. 11-12) and utilisation gap; the
  contrastive loss on straight-through reconstructions, reusing each
  edge type's negatives; learned uncertainty weights over all twelve
  tasks;
* gradients clipped to global norm 1; AdaGrad (lr 0.02) on codebooks,
  AdamW (lr 0.004, wd 0.01) on the rest; the pool takes the step's user
  and item primaries as a FIFO ring (a later row wins a slot); the RQ
  histograms and usage counters advance.

The encoder is evaluated and differentiated in blocks of rows, so a
step at 32,768 edges fits next to nothing else on one chip.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
TASK_ORDER = ("margin_ii", "infonce_ii", "margin_ui", "infonce_ui",
              "margin_iu", "infonce_iu", "margin_uu", "infonce_uu",
              "rq_recon", "rq_reg", "rq_util", "rq_contrastive")
ET_SIDES = {"ii": ("item", "item"), "ui": ("user", "item"),
            "uu": ("user", "user")}


class Frozen(dict):
    """A configuration dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                                 for k, v in self.items())))


def freeze(cfg: Dict[str, Any]) -> Frozen:
    return Frozen({k: tuple(v) if isinstance(v, list) else v
                   for k, v in cfg.items()
                   if isinstance(v, (int, float, str, list, tuple))})


# ---------------------------------------------------------------------------
# weights, made from the seed
# ---------------------------------------------------------------------------

def init_params(key, m: Dict[str, Any]) -> Dict[str, Any]:
    """The benchmark's weights: dense matrices N(0, 1/fan_in), biases 0,
    codebook layer l N(0, (0.1/(l+1))^2), uncertainty log-variances 0."""
    d, H, dh = m["d_embed"], m["n_heads"], m["d_hidden"]
    ks = iter(jax.random.split(key, 16))

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    def enc(d_in):
        return {"l1": {"w": dense(next(ks), (d_in, dh), d_in),
                       "b": jnp.zeros((dh,), jnp.float32)},
                "l2": {"w": dense(next(ks), (dh, H * d), dh),
                       "b": jnp.zeros((H * d,), jnp.float32)}}

    def agg():
        return {"w": dense(next(ks), (H, 3 * d, d), 3 * d),
                "b": jnp.zeros((H, d), jnp.float32)}

    books = {f"layer{l}": jax.random.normal(next(ks), (n, d), jnp.float32)
             * (0.1 / (l + 1)) for l, n in enumerate(m["codebook_sizes"])}
    return {"f_user": enc(m["d_user_feat"]), "f_item": enc(m["d_item_feat"]),
            "agg_user": agg(), "agg_item": agg(),
            "rq": {"codebooks": books},
            "uncertainty": {t: jnp.zeros((), jnp.float32)
                            for t in TASK_ORDER}}


def make_params(seed: int, m: Dict[str, Any]) -> Dict[str, Any]:
    """All weights on the device in one jitted call from the seed."""
    return jax.jit(functools.partial(init_params, m=m))(
        jax.random.key(int(seed) % (1 << 62)))


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------

def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def l2n(x, axis=-1):
    return x / (jnp.linalg.norm(x, axis=axis, keepdims=True) + 1e-8)


def _mm(a, b, dtype):
    """Matrix product with operands in ``dtype``, accumulated in f32."""
    return jnp.matmul(a.astype(dtype), b.astype(dtype), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def encode(p, x, m, dtype):
    h = gelu(_mm(x, p["l1"]["w"], dtype) + p["l1"]["b"])
    y = _mm(h, p["l2"]["w"], dtype) + p["l2"]["b"]
    return y.reshape(x.shape[0], m["n_heads"], m["d_embed"])


def _blocks(ids: np.ndarray, block: int, bucket: int = 1
            ) -> Tuple[np.ndarray, int]:
    """Row ids in ``(blocks, block)``, the block count a multiple of
    ``bucket`` so that batches of similar size share compiled shapes."""
    n = len(ids)
    nb = max(-(-n // (block * bucket)), 1) * bucket
    out = np.zeros(nb * block, np.int32)
    out[:n] = ids
    return out.reshape(nb, block), n


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _encode_blocks(p, table, ids, m, dtype):
    return jax.lax.map(lambda b: encode(p, table[b], m, dtype), ids)


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _encode_vjp(p, table, ids, cot, m, dtype):
    def body(acc, xs):
        b, c = xs
        _, vjp = jax.vjp(lambda q: encode(q, table[b], m, dtype), p)
        g, = vjp(c)
        return jax.tree.map(jnp.add, acc, g), None
    zero = jax.tree.map(jnp.zeros_like, p)
    return jax.lax.scan(body, zero, (ids, cot))[0]


def _other_rows(k, B, n):
    off = jax.random.randint(k, (B, n), 1, max(B, 2))
    i = jnp.arange(B)[:, None]
    return (i + off) % B


def negatives(key, prim, heads, pool, fill, m):
    B, H = prim.shape[0], heads.shape[1]
    n_neg = m["n_negatives"]
    n_aug = max(n_neg // 8, 1) if H > 1 else 0
    n_pool = min(m["n_pool_neg"], n_neg - n_aug)
    n_inb = n_neg - n_pool - n_aug
    k1, k2, k3 = jax.random.split(key, 3)
    inb = prim[_other_rows(k1, B, n_inb)]
    idx = jax.random.randint(k2, (B, n_pool), 0, jnp.maximum(fill, 1))
    pneg = jnp.where(fill > 0, pool[idx], prim[_other_rows(k3, B, n_pool)])
    parts = [inb, pneg]
    if n_aug:
        rows = _other_rows(jax.random.fold_in(key, 7), B, n_aug)
        hh = jax.random.randint(jax.random.fold_in(key, 8), (B, n_aug), 0, H)
        parts.append(heads[rows, hh])
    return jnp.concatenate(parts, axis=1)


def pair_losses(src, dst, negs, m, dtype):
    s_pos = jnp.sum(src * dst, axis=-1)
    s_neg = jnp.einsum("bd,bnd->bn", src.astype(dtype), negs.astype(dtype),
                       precision=HIGHEST, preferred_element_type=jnp.float32)
    marg = jnp.sum(jax.nn.relu(s_neg - s_pos[:, None] + m["margin"]), -1)
    logits = jnp.concatenate([s_pos[:, None], s_neg], 1) / m["tau"]
    return marg, -jax.nn.log_softmax(logits, axis=-1)[:, 0]


def rq_forward(books, rq, h, m, dtype):
    sg = jax.lax.stop_gradient
    resid, recon = h, jnp.zeros_like(h)
    regs, utils, hard, routed = [], [], [], []
    B = h.shape[0]
    for l, n in enumerate(m["codebook_sizes"]):
        C = books[f"layer{l}"]
        r = sg(resid)
        d2 = (jnp.sum(r * r, 1, keepdims=True) - 2.0 * _mm(r, C.T, dtype)
              + jnp.sum(C * C, 1)[None, :])
        dist = jnp.sqrt(jnp.maximum(d2, 0.0) + 1e-12)
        p_soft = jax.nn.softmax(m["zeta1"] / (m["zeta2"] + dist), axis=-1)
        tot = jnp.sum(rq["hists"][l], 0)
        phat = (tot + 1e-6) / (jnp.sum(tot) + 1e-6 * n)
        k_hard = jnp.argmin(dist, 1)
        k = jnp.argmax(p_soft / phat[None, :], 1)
        sel = C[k]
        recon, resid = recon + sel, resid - sel
        pb = jnp.sum(p_soft, 0)
        pb = pb / jnp.maximum(jnp.sum(pb), 1e-12)
        regs.append(jnp.dot(sg(phat), pb) * n)
        f_hard = jnp.zeros(n).at[k_hard].add(1.0) / B
        pm = jnp.mean(p_soft, 0)
        pm = pm / jnp.maximum(jnp.sum(pm), 1e-12)
        utils.append(jnp.maximum((n * jnp.dot(sg(f_hard), pm) - 1.0)
                                 / (n - 1.0), 0.0))
        hard.append(f_hard * B)
        routed.append(jnp.zeros(n).at[k].add(1.0))
    l_recon = (jnp.mean(jnp.sum((sg(h) - recon) ** 2, 1))
               + m["commit_coef"] * jnp.mean(jnp.sum((h - sg(recon)) ** 2, 1)))
    p = rq["ptr"] % m["hist_len"]
    new = {"hists": tuple(hh.at[p].set(c) for hh, c in zip(rq["hists"],
                                                         routed)),
           "usage": tuple(m["usage_ema"] * u + (1 - m["usage_ema"]) * c / B
                          for u, c in zip(rq["usage"], hard)),
           "ptr": rq["ptr"] + 1,
           "filled": jnp.minimum(rq["filled"] + 1, m["hist_len"])}
    return (h + sg(recon - h), l_recon, jnp.mean(jnp.stack(regs)),
            m["util_coef"] * jnp.mean(jnp.stack(utils)), new)


def _mmean(enc, idx, msk):
    """Masked mean of ``enc`` rows ``idx`` (E, K), one neighbour column at
    a time so no (E, K, H, d) block is held."""
    acc = jnp.zeros((idx.shape[0],) + enc.shape[1:], enc.dtype)
    for j in range(idx.shape[1]):
        acc = acc + enc[idx[:, j]] * msk[:, j, None, None]
    return acc / jnp.maximum(jnp.sum(msk, 1), 1.0)[:, None, None]


def _agg(p, self_e, u_mean, i_mean, dtype):
    x = jnp.concatenate([self_e, u_mean, i_mean], -1)
    y = jnp.einsum("bhk,hkd->bhd", x.astype(dtype), p["w"].astype(dtype),
                   precision=HIGHEST, preferred_element_type=jnp.float32)
    return l2n(gelu(y + p["b"]))


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _head_grads(enc_u, enc_i, rest, batch, pool, rq, key, m, dtype):
    """Loss, new pool/RQ state and gradients w.r.t. the encodings and
    every non-encoder leaf."""

    def loss_fn(enc_u, enc_i, rest):
        enc = {"user": enc_u, "item": enc_i}
        heads, prims = {}, {}
        for t in ("user", "item"):
            n = batch[t]
            h = _agg(rest["agg_" + t], enc[t][n["self"]],
                     _mmean(enc_u, n["unbr"], n["umask"]),
                     _mmean(enc_i, n["inbr"], n["imask"]), dtype)
            heads[t], prims[t] = h, l2n(jnp.mean(h, 1))
        per = {}
        for et in ("ii", "ui", "uu"):
            st, dt = ET_SIDES[et]
            e = batch["edges"][et]
            per[et] = (heads[st][e["src"]], prims[st][e["src"]],
                       heads[dt][e["dst"]], prims[dt][e["dst"]])
        keys = jax.random.split(key, 8)
        dirs = []
        for et, (sh, sp, dh, dp) in per.items():
            dirs.append((et, sp, dp, dh, ET_SIDES[et][1]))
            if et == "ui":
                dirs.append(("iu", dp, sp, sh, "user"))
        tasks, dnegs = {}, {}
        for i, (name, sp, dp, dh, dt) in enumerate(dirs):
            negs = negatives(keys[i], dp, dh, pool[dt], pool[dt + "_fill"],
                             m)
            dnegs[name] = negs
            mg, info = pair_losses(sp, dp, negs, m, dtype)
            tasks["margin_" + name] = jnp.mean(mg)
            tasks["infonce_" + name] = jnp.mean(info)
        prim_all = jnp.concatenate([x for et in per
                                    for x in (per[et][1], per[et][3])])
        rst, l_recon, l_reg, l_util, new_rq = rq_forward(
            rest["rq"]["codebooks"], rq, prim_all, m, dtype)
        tasks["rq_recon"], tasks["rq_reg"] = l_recon, l_reg
        tasks["rq_util"] = l_util
        lp, off = [], 0
        for et in per:
            n = per[et][1].shape[0]
            rs, rd = rst[off:off + n], rst[off + n:off + 2 * n]
            off += 2 * n
            mg, info = pair_losses(rs, rd, dnegs[et], m, dtype)
            lp.append(jnp.mean(0.5 * mg + 0.5 * info))
        tasks["rq_contrastive"] = jnp.mean(jnp.stack(lp))
        unc = rest["uncertainty"]
        total = sum(jnp.exp(-unc[t]) * tasks[t] + unc[t] for t in TASK_ORDER)
        users = jnp.concatenate([per["ui"][1], per["uu"][1], per["uu"][3]])
        items = jnp.concatenate([per["ii"][1], per["ii"][3], per["ui"][3]])
        return total, (users, items, new_rq)

    (total, aux), grads = jax.value_and_grad(
        loss_fn, argnums=(0, 1, 2), has_aux=True)(enc_u, enc_i, rest)
    return total, aux, grads


def _push(buf, ptr, fill, emb):
    """FIFO ring push; where a batch wraps the ring, the later row wins."""
    P, B = buf.shape[0], emb.shape[0]
    keep = emb[-min(B, P):]
    idx = (ptr + (B - keep.shape[0]) + jnp.arange(keep.shape[0])) % P
    return buf.at[idx].set(jax.lax.stop_gradient(keep)), (ptr + B) % P, \
        jnp.minimum(fill + B, P)


def init_state(params, m, pool_size: int = 8192):
    n = m["codebook_sizes"]
    z = lambda: jax.tree.map(jnp.zeros_like, params)
    return {"params": params,
            "opt": {"acc": z(), "mu": z(), "nu": z(), "count": 0},
            "pool": {"user": jnp.zeros((pool_size, m["d_embed"])),
                     "item": jnp.zeros((pool_size, m["d_embed"])),
                     "user_ptr": jnp.int32(0), "item_ptr": jnp.int32(0),
                     "user_fill": jnp.int32(0), "item_fill": jnp.int32(0)},
            "rq": {"hists": tuple(jnp.zeros((m["hist_len"], k)) for k in n),
                   "usage": tuple(jnp.full((k,), 1.0 / k) for k in n),
                   "ptr": jnp.int32(0), "filled": jnp.int32(0)}}


def _is_sparse(path) -> bool:
    return "codebooks" in "/".join(str(getattr(p, "key", p)) for p in path)


def step(state, batch, features, key, m, *, dtype=jnp.float32,
         block: int = 16384, bucket: int = 4):
    """One reference step.  ``batch`` is from :func:`expand`.  Returns the
    new state, the total loss and the clipped gradient."""
    p = state["params"]
    ids_u, n_u = _blocks(batch["user"]["nodes"], block, bucket)
    ids_i, n_i = _blocks(batch["item"]["nodes"], block, bucket)
    enc_u = _encode_blocks(p["f_user"], features["user"], ids_u, m, dtype)
    enc_i = _encode_blocks(p["f_item"], features["item"], ids_i, m, dtype)
    shp = lambda e: e.reshape(-1, m["n_heads"], m["d_embed"])
    rest = {k: v for k, v in p.items() if k not in ("f_user", "f_item")}
    dev = jax.tree.map(jnp.asarray, {k: v for k, v in batch.items()
                                     if k != "meta"})
    for t in ("user", "item"):
        dev[t].pop("nodes")
    pool = state["pool"]
    total, (users, items, new_rq), (g_u, g_i, g_rest) = _head_grads(
        shp(enc_u), shp(enc_i), rest, dev, pool, state["rq"], key, m, dtype)
    grads = dict(g_rest)
    grads["f_user"] = _encode_vjp(p["f_user"], features["user"], ids_u,
                                  g_u.reshape(enc_u.shape), m, dtype)
    grads["f_item"] = _encode_vjp(p["f_item"], features["item"], ids_i,
                                  g_i.reshape(enc_i.shape), m, dtype)
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, 1.0 / (gn + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    opt = state["opt"]
    c = opt["count"] + 1
    b1, b2 = 0.9, 0.999

    def upd(path, g, acc, mu, nu, w):
        if _is_sparse(path):
            acc = acc + g * g
            return w - 0.02 * g / (jnp.sqrt(acc) + 1e-8), acc, mu, nu
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        s = (mu / (1 - b1 ** c)) / (jnp.sqrt(nu / (1 - b2 ** c)) + 1e-8)
        return w - 0.004 * (s + 0.01 * w), acc, mu, nu

    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    tdef = jax.tree.structure(grads)
    leaves = {k: jax.tree.leaves(opt[k]) for k in ("acc", "mu", "nu")}
    new_w, new_acc, new_mu, new_nu = [], [], [], []
    wl = jax.tree.leaves(p)
    for j, (path, g) in enumerate(flat):
        w2, a2, m2, n2 = upd(path, g, leaves["acc"][j], leaves["mu"][j],
                             leaves["nu"][j], wl[j])
        new_w.append(w2)
        new_acc.append(a2)
        new_mu.append(m2)
        new_nu.append(n2)
    ub, up, uf = _push(pool["user"], pool["user_ptr"], pool["user_fill"],
                       users)
    ib, ip, if_ = _push(pool["item"], pool["item_ptr"], pool["item_fill"],
                        items)
    new = {"params": tdef.unflatten(new_w),
           "opt": {"acc": tdef.unflatten(new_acc),
                   "mu": tdef.unflatten(new_mu),
                   "nu": tdef.unflatten(new_nu), "count": c},
           "pool": {"user": ub, "item": ib, "user_ptr": up, "item_ptr": ip,
                    "user_fill": uf, "item_fill": if_},
           "rq": new_rq}
    return new, float(total), grads


# ---------------------------------------------------------------------------
# the feed, checked against the graph
# ---------------------------------------------------------------------------

def _pad_rows(o: Dict[str, Any], mult: int) -> Dict[str, Any]:
    """Endpoint rows padded to a multiple of ``mult`` (masked, never
    referenced by an edge) so that batches share compiled shapes."""
    E = len(o["self_g"])
    pad = -(-E // mult) * mult - E
    if pad == 0:
        return o
    rows = lambda a, v: np.concatenate(
        [a, np.full((pad,) + a.shape[1:], v, a.dtype)])
    return dict(o, self_g=rows(o["self_g"], o["uniq"][0]),
                un_g=rows(o["un_g"], 0), in_g=rows(o["in_g"], 0),
                um=rows(o["um"], False), im=rows(o["im"], False),
                real=E)


def expand(batch: Dict[str, Any], n_users: int, edge_keys: Dict[str, Any],
           user_nbrs: np.ndarray, item_nbrs: np.ndarray, *,
           pad_to: int = 4096) -> Tuple[Dict[str, Any], int]:
    """The reference's view of one program batch: endpoints, their
    sampled neighbours and the edges, as global ids mapped onto the
    reference's own distinct-node lists.  Returns it with the count of
    entries that disagree with the graph: an edge that is not in it, an
    endpoint map that points at another node, or a neighbour that is not
    in its node's table row."""
    bad = 0
    nodes = batch["nodes"]
    gid, ep = {}, {}
    for t, off in (("user", 0), ("item", n_users)):
        ids = np.asarray(nodes[t]["ids"], np.int64) + off
        gid[t] = ids
    out: Dict[str, Any] = {"edges": {}}
    for et, e in batch["edges"].items():
        st, dt = ET_SIDES[et]
        src, dst = np.asarray(e["src_ids"], np.int64), np.asarray(
            e["dst_ids"], np.int64)
        sm, dm = np.asarray(e["src_map"]), np.asarray(e["dst_map"])
        bad += int((gid[st][sm] != src).sum() + (gid[dt][dm] != dst).sum())
        keys = src * (1 << 32) + dst
        known = edge_keys[et]
        at = np.minimum(np.searchsorted(known, keys), len(known) - 1)
        bad += int((known[at] != keys).sum())
        ep.setdefault(st, []).append(sm)
        ep.setdefault(dt, []).append(dm)
        out["edges"][et] = {"src": sm, "dst": dm}
    for t in ("user", "item"):
        n = nodes[t]
        rows = np.unique(np.concatenate(ep[t]))
        E = int(rows[-1]) + 1            # endpoint rows lead the pack
        self_g = gid[t][:E]
        un_g = gid["user"][np.asarray(n["unbr_idx"])[:E]]
        in_g = gid["item"][np.asarray(n["inbr_idx"])[:E]]
        um = np.asarray(n["unbr_mask"])[:E] > 0
        im = np.asarray(n["inbr_mask"])[:E] > 0
        for g, msk, table in ((un_g, um, user_nbrs), (in_g, im, item_nbrs)):
            r = table[self_g[rows]]
            hit = (r[:, None, :] == g[rows][:, :, None]).any(-1)
            bad += int((msk[rows] & ~hit).sum())
            bad += int((~msk[rows] & (r >= 0).all(-1)[:, None]).sum())
        out[t] = {"self_g": self_g, "un_g": un_g, "in_g": in_g,
                  "um": um, "im": im}
    # distinct nodes per type, and every reference onto them
    for t, off in (("user", 0), ("item", n_users)):
        refs = [gid[t][np.asarray(np.concatenate(ep[t]))]]
        for s in ("user", "item"):
            g = out[s]["un_g"] if t == "user" else out[s]["in_g"]
            msk = out[s]["um"] if t == "user" else out[s]["im"]
            refs.append(g[msk])
        out[t]["uniq"] = np.unique(np.concatenate(refs))
        out[t]["off"] = off
    res: Dict[str, Any] = {"edges": out["edges"], "meta": {}}
    for t in ("user", "item"):
        o = _pad_rows(out[t], pad_to)
        pos = lambda g, tt: np.searchsorted(out[tt]["uniq"], g).astype(
            np.int32)
        E = len(o["self_g"])
        res[t] = {"nodes": (o["uniq"] - o["off"]).astype(np.int32),
                  "self": np.clip(pos(o["self_g"], t), 0,
                                  len(o["uniq"]) - 1),
                  "unbr": np.where(o["um"], np.clip(pos(o["un_g"], "user"),
                                   0, len(out["user"]["uniq"]) - 1), 0),
                  "umask": o["um"].astype(np.float32),
                  "inbr": np.where(o["im"], np.clip(pos(o["in_g"], "item"),
                                   0, len(out["item"]["uniq"]) - 1), 0),
                  "imask": o["im"].astype(np.float32)}
        res["meta"][t] = {"nodes": len(o["uniq"]),
                          "endpoints": o.get("real", E)}
    return res, bad
