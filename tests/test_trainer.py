"""Training-loop behaviour: convergence, determinism, state plumbing,
dedup/id-only forward equivalence, donated-step checkpointing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import trainer as T
from repro.core import rq_index as RQ
from repro.distributed.sharding import NULL_CTX


def _step_n(state, step_fn, ds, per_type, seed, n, start=0, format=None,
            features=None):
    m = None
    for t in range(start, start + n):
        batch = jax.tree.map(jnp.asarray,
                             ds.sample_batch(t, seed, per_type,
                                             format=format))
        state, m = step_fn(state, batch, jax.random.key(500 + t),
                           features)
    return state, m


def test_loss_decreases(tiny_cfg, tiny_dataset):
    state, specs, optimizer = T.init_state(jax.random.key(0), tiny_cfg,
                                           pool_size=256)
    step = T.make_train_step(tiny_cfg, optimizer)
    per_type = {"uu": 32, "ui": 32, "ii": 32}
    state, m0 = _step_n(state, step, tiny_dataset, per_type, 0, 3)
    state, m1 = _step_n(state, step, tiny_dataset, per_type, 0, 40, start=3)
    assert float(m1["infonce_ui"]) < float(m0["infonce_ui"])
    assert np.isfinite(float(m1["total"]))


def test_state_advances_and_pool_fills(tiny_cfg, tiny_dataset):
    state, _, optimizer = T.init_state(jax.random.key(0), tiny_cfg,
                                       pool_size=256)
    step = T.make_train_step(tiny_cfg, optimizer)
    per_type = {"uu": 16, "ui": 16, "ii": 16}
    state, _ = _step_n(state, step, tiny_dataset, per_type, 0, 2)
    assert int(state.step) == 2
    assert int(state.pool.user_fill) > 0
    assert int(state.pool.item_fill) > 0
    assert int(state.rq_state.ptr) == 2


def test_deterministic_resume(tiny_cfg, tiny_dataset):
    """batch(seed, t) purity + identical keys => identical training —
    the checkpoint-resume invariant."""
    per_type = {"uu": 16, "ui": 16, "ii": 16}

    def run(n, state=None):
        if state is None:
            state, _, opt = T.init_state(jax.random.key(0), tiny_cfg,
                                         pool_size=128)
        else:
            _, _, opt = T.init_state(jax.random.key(0), tiny_cfg,
                                     pool_size=128)
        step = T.make_train_step(tiny_cfg, opt)
        start = int(state.step)
        return _step_n(state, step, tiny_dataset, per_type, 0, n,
                       start=start)[0]

    s_full = run(8)
    s_half = run(4)
    s_resumed = run(4, state=s_half)
    for a, b in zip(jax.tree.leaves(s_full.params),
                    jax.tree.leaves(s_resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_uncertainty_weights_move(tiny_cfg, tiny_dataset):
    state, _, optimizer = T.init_state(jax.random.key(0), tiny_cfg,
                                       pool_size=128)
    before = {k: float(v) for k, v in
              state.params["uncertainty"].items()}
    step = T.make_train_step(tiny_cfg, optimizer)
    state, _ = _step_n(state, step, tiny_dataset,
                       {"uu": 16, "ui": 16, "ii": 16}, 0, 10)
    after = {k: float(v) for k, v in state.params["uncertainty"].items()}
    assert any(abs(after[k] - before[k]) > 1e-4 for k in after)


def test_embed_all_shapes(tiny_cfg, tiny_dataset, tiny_graph):
    state, _, _ = T.init_state(jax.random.key(0), tiny_cfg, pool_size=64)
    from repro.core import model as M
    emb = T.embed_all(state.params, tiny_cfg, tiny_dataset,
                      node_type=M.USER, ids=np.arange(50), batch=32)
    assert emb.shape == (50, tiny_cfg.d_embed)
    norms = np.linalg.norm(emb, axis=1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-3)
    # corpus smaller than one batch: the first chunk pads too (one trace
    # per batch size, not one per corpus size)
    emb_small = T.embed_all(state.params, tiny_cfg, tiny_dataset,
                            node_type=M.USER, ids=np.arange(7), batch=32)
    np.testing.assert_allclose(emb_small, emb[:7], rtol=1e-6)


# ---------------------------------------------------------------------------
# dedup / id-only forward equivalence (the PR-4 hot-path rework)
# ---------------------------------------------------------------------------

def _forward_tasks(cfg, state, batch, features=None):
    tasks, _ = T._forward_losses(state.params, cfg,
                                 jax.tree.map(jnp.asarray, batch),
                                 state.pool, state.rq_state,
                                 jax.random.key(99), NULL_CTX, True,
                                 features)
    return {k: float(v) for k, v in tasks.items()}


def test_dedup_forward_matches_legacy_forward(tiny_cfg, tiny_dataset):
    """Unique-node forward == per-endpoint PR-3 forward on the same
    edge draws (expand_batch re-materializes the legacy view)."""
    state, _, _ = T.init_state(jax.random.key(0), tiny_cfg, pool_size=128)
    per_type = {"uu": 16, "ui": 16, "ii": 16}
    dedup = tiny_dataset.sample_batch(7, 0, per_type, format="dedup")
    legacy = tiny_dataset.expand_batch(dedup)
    td = _forward_tasks(tiny_cfg, state, dedup)
    tl = _forward_tasks(tiny_cfg, state, legacy)
    assert set(td) == set(tl)
    for k in td:
        np.testing.assert_allclose(td[k], tl[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)


def test_id_only_forward_matches_feat_forward(tiny_cfg, tiny_dataset):
    state, _, _ = T.init_state(jax.random.key(0), tiny_cfg, pool_size=128)
    per_type = {"uu": 16, "ui": 16, "ii": 16}
    feats = T.make_feature_store(tiny_dataset.user_feat,
                                 tiny_dataset.item_feat)
    bf = tiny_dataset.sample_batch(9, 0, per_type, format="dedup")
    bi = tiny_dataset.sample_batch(9, 0, per_type, format="dedup_ids")
    tf = _forward_tasks(tiny_cfg, state, bf)
    ti = _forward_tasks(tiny_cfg, state, bi, features=feats)
    for k in tf:
        np.testing.assert_allclose(tf[k], ti[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)


def test_id_only_pipeline_trains_identically(tiny_cfg, tiny_dataset):
    """Full jitted steps: feat-mode dedup vs id-only device-gather land
    on the same parameters."""
    per_type = {"uu": 16, "ui": 16, "ii": 16}

    def run(fmt, features=None):
        state, _, opt = T.init_state(jax.random.key(0), tiny_cfg,
                                     pool_size=128)
        step = T.make_train_step(tiny_cfg, opt)
        return _step_n(state, step, tiny_dataset, per_type, 0, 4,
                       format=fmt, features=features)[0]

    feats = T.make_feature_store(tiny_dataset.user_feat,
                                 tiny_dataset.item_feat)
    s_feat = run("dedup")
    s_ids = run("dedup_ids", features=feats)
    for a, b in zip(jax.tree.leaves(s_feat.params),
                    jax.tree.leaves(s_ids.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_id_only_without_store_raises(tiny_cfg, tiny_dataset):
    state, _, opt = T.init_state(jax.random.key(0), tiny_cfg, pool_size=64)
    step = T.make_train_step(tiny_cfg, opt)
    batch = jax.tree.map(jnp.asarray, tiny_dataset.sample_batch(
        0, 0, {"ui": 8}, format="dedup_ids"))
    with pytest.raises(ValueError, match="FeatureStore"):
        step(state, batch, jax.random.key(0))


def test_lprime_negative_reuse_flag(tiny_cfg, tiny_dataset):
    """Reused negatives change only the L' task (raw losses share keys);
    the PR-3 double-draw is restorable for old-run reproducibility."""
    state, _, _ = T.init_state(jax.random.key(0), tiny_cfg, pool_size=128)
    # a filled pool makes the second draw actually differ
    pool = state.pool
    k1, k2 = jax.random.split(jax.random.key(5))
    from repro.core import negatives as N
    pool = N.update_pool(pool, jax.random.normal(k1, (64, tiny_cfg.d_embed)),
                         jax.random.normal(k2, (64, tiny_cfg.d_embed)))
    state = dataclasses.replace(state, pool=pool)
    batch = tiny_dataset.sample_batch(3, 0, {"uu": 16, "ui": 16, "ii": 16})
    cfg_old = dataclasses.replace(tiny_cfg, reuse_lprime_negatives=False)
    t_new = _forward_tasks(tiny_cfg, state, batch)
    t_old = _forward_tasks(cfg_old, state, batch)
    for k in t_new:
        if k.startswith(("margin_", "infonce_")) or k in ("rq_recon",
                                                          "rq_reg"):
            np.testing.assert_allclose(t_new[k], t_old[k], rtol=1e-6,
                                       err_msg=k)
    assert abs(t_new["rq_contrastive"] - t_old["rq_contrastive"]) > 1e-7


def test_fused_kernel_step_matches_reference(tiny_cfg, tiny_dataset):
    """cfg.use_fused_contrastive routes pair losses through the Pallas
    custom-VJP kernel under value_and_grad; parameters after a step must
    match the jnp path."""
    per_type = {"uu": 8, "ui": 8, "ii": 8}

    def run(cfg):
        state, _, opt = T.init_state(jax.random.key(0), cfg, pool_size=64)
        step = T.make_train_step(cfg, opt)
        return _step_n(state, step, tiny_dataset, per_type, 0, 2)[0]

    s_ref = run(tiny_cfg)
    s_ker = run(dataclasses.replace(tiny_cfg, use_fused_contrastive=True))
    for a, b in zip(jax.tree.leaves(s_ref.params),
                    jax.tree.leaves(s_ker.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-5)


def test_donated_step_checkpoint_roundtrip(tmp_path, tiny_cfg,
                                           tiny_dataset):
    """The donated jitted step + Checkpointer round-trip: save mid-run,
    restore into fresh buffers, resume — identical to an uninterrupted
    run (the donate_argnums=0 migration must not break fault
    tolerance)."""
    from repro.checkpoint.checkpointer import Checkpointer
    per_type = {"uu": 8, "ui": 8, "ii": 8}
    state, _, opt = T.init_state(jax.random.key(0), tiny_cfg, pool_size=64)
    step = T.make_train_step(tiny_cfg, opt)
    s_full, _ = _step_n(state, step, tiny_dataset, per_type, 0, 6)

    state2, _, opt2 = T.init_state(jax.random.key(0), tiny_cfg,
                                   pool_size=64)
    step2 = T.make_train_step(tiny_cfg, opt2)
    s_half, _ = _step_n(state2, step2, tiny_dataset, per_type, 0, 3)
    ck = Checkpointer(str(tmp_path))
    ck.save(int(s_half.step), s_half, metadata={"data_seed": 0})
    like = jax.tree.map(jnp.zeros_like, s_half)
    restored, meta = ck.restore(like)
    assert int(restored.step) == 3
    s_resumed, _ = _step_n(restored, step2, tiny_dataset, per_type, 0, 3,
                           start=3)
    for a, b in zip(jax.tree.leaves(s_full), jax.tree.leaves(s_resumed)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_donated_step_survives_dead_code_reset(tiny_cfg, tiny_dataset):
    """The self-healing pass swaps codebook rows host-side mid-burst;
    the already-compiled donated step must keep running on the new
    state (same pytree structure/dtypes), and optimizer moments must
    ride through the functional swap untouched."""
    state, _, opt = T.init_state(jax.random.key(0), tiny_cfg,
                                 pool_size=128)
    step = T.make_train_step(tiny_cfg, opt)
    per_type = {"uu": 16, "ui": 16, "ii": 16}
    state, _ = _step_n(state, step, tiny_dataset, per_type, 0, 2)
    probe = np.random.default_rng(0).normal(
        size=(64, tiny_cfg.d_embed)).astype(np.float32)
    sizes = tiny_cfg.rq.codebook_sizes
    usage = [np.r_[np.ones(n // 2), np.zeros(n - n // 2)]
             .astype(np.float32) for n in sizes]
    opt_before = [np.asarray(x) for x in jax.tree.leaves(state.opt_state)]
    books_before = [np.asarray(state.params["rq"]["codebooks"][f"layer{l}"])
                    for l in range(len(sizes))]
    state, rep = T.reset_dead_codes(state, probe, tiny_cfg, seed=3,
                                    usage=usage)
    assert sum(rep.values()) == sum(n - n // 2 for n in sizes)
    for a, b in zip(opt_before, jax.tree.leaves(state.opt_state)):
        np.testing.assert_array_equal(a, np.asarray(b))
    for l, n in enumerate(sizes):                  # live rows untouched
        after = np.asarray(state.params["rq"]["codebooks"][f"layer{l}"])
        np.testing.assert_array_equal(books_before[l][: n // 2],
                                      after[: n // 2])
        assert not np.array_equal(books_before[l][n // 2:],
                                  after[n // 2:])
    state, m = _step_n(state, step, tiny_dataset, per_type, 0, 2, start=2)
    assert int(state.step) == 4
    assert np.isfinite(float(m["total"]))
