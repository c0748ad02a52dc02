"""The chip bring-up script's main path at tiny widths on the CPU, its
refusal to run without a TPU, and the train step's independence of the
feature-table size."""
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

TINY = dict(n_users=300, n_items=400, events_per_user=25.0,
            batch_per_type=32, steps=2, new_users=5, new_items=5,
            n_requests=32, request_batch=16, ingest_batch=256, i2i_k=8,
            ppr_nodes=64, ppr_starts=8)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke_cfg():
    from repro.configs.base import RankGraph2Config, RQConfig
    return RankGraph2Config(
        d_user_feat=32, d_item_feat=32, d_embed=16, n_heads=2, d_hidden=32,
        k_imp=8, k_train=4, n_negatives=12, n_pool_neg=4, ppr_walks=8,
        ppr_len=3, rq=RQConfig(codebook_sizes=(8, 4), hist_len=10,
                               reset_every=100))


def test_smoke_main_path_and_kernels_at_tiny_widths(smoke, smoke_cfg):
    sz = smoke.Sizes(**TINY)
    lines = []
    ctx = smoke.run_lifecycle(smoke_cfg, sz, seed=0, log=lines.append)
    rt = ctx["runtime"]
    assert rt.server.version == 2 and rt.version == 2
    assert {"construction", "cycle0", "traffic_v1", "cycle1",
            "traffic_v2"} <= set(ctx["phases"])
    secs = smoke.run_kernels(ctx, sz, seed=0, log=lines.append)
    assert {"kernel_rq_assign", "kernel_fused_contrastive",
            "kernel_queue_gather", "kernel_ppr_walk"} <= set(secs)
    assert any(l.startswith("ppr_walk:") for l in lines)


def test_smoke_check_report_rejects_degraded_cycles(smoke):
    ok = {"train": {"total": 1.0}, "swap": {"to_version": 2.0},
          "degraded": False}
    smoke.check_report(ok, "c")
    for bad in ({**ok, "degraded": True},
                {**ok, "swap": {"skipped": True}},
                {**ok, "train": {"total": float("nan")}}):
        with pytest.raises(smoke.SmokeFailure):
            smoke.check_report(bad, "c")


def test_smoke_refuses_to_run_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_smoke_four_chip_path_on_virtual_devices(smoke_cfg):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('s', {SCRIPT!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "sys.modules['s'] = m\n"
        "spec.loader.exec_module(m)\n"
        "from repro.configs.base import RankGraph2Config, RQConfig\n"
        "cfg = RankGraph2Config(d_embed=16, rq=RQConfig("
        "codebook_sizes=(40, 5)))\n"
        f"m.run_four_chips(cfg, m.Sizes(**{TINY!r}), log=print)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "bitwise equal" in out.stdout


def test_train_step_program_does_not_grow_with_feature_tables(
        tiny_cfg, tiny_dataset):
    """The feature tables are step arguments: lowering the step for a
    corpus ~60x larger gives the same program text, with no table
    embedded as a constant."""
    from repro.core import trainer as T
    state, _, opt = T.init_state(jax.random.key(0), tiny_cfg, pool_size=64)
    step = T.make_train_step(tiny_cfg, opt)
    batch = jax.tree.map(jnp.asarray, tiny_dataset.sample_batch(
        0, 0, {"uu": 8, "ui": 8, "ii": 8}, format="dedup_ids"))
    key = jax.random.key(0)
    rng = np.random.default_rng(0)

    def hlo(nu, ni):
        feats = T.make_feature_store(
            rng.normal(size=(nu, tiny_cfg.d_user_feat)).astype(np.float32),
            rng.normal(size=(ni, tiny_cfg.d_item_feat)).astype(np.float32))
        return step.lower(state, batch, key, feats).as_text()

    small = hlo(tiny_dataset.user_feat.shape[0],
                tiny_dataset.item_feat.shape[0])
    big = hlo(20_000, 30_000)
    # only the two table-shape annotations may differ
    assert abs(len(big) - len(small)) < 200
    assert len(small) < 2_000_000


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_directory(tmp_path, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and a compile lands there;
    without it the cache is the fixed in-checkout ``.jax_cache``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        f"if {env_dir}:   # compile only into the temporary directory\n"
        "    jax.config.update("
        "'jax_persistent_cache_min_compile_time_secs', 0)\n"
        "    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    want = str(tmp_path) if env_dir else os.path.join(REPO, ".jax_cache")
    assert out.stdout.strip().splitlines()[-1] == want
    if env_dir:
        assert any(tmp_path.iterdir()), "no cache entry was written"
