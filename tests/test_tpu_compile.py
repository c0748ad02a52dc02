"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e
that is described, not attached, at the §5.1 widths of the rankgraph2
config.  Nothing runs: each test proves that the chip's compiler accepts
the kernel (tiling, VMEM, lowering) and that the compiled program really
contains it.

The topology is described inside a module fixture, never at import: only
the test worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D, N_NEG, CODEBOOKS = 256, 100, (5000, 50)     # configs/rankgraph2.py
C, Q, I2I_K = 5000 * 50, 256, 16               # serving store geometry


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_rq_assign_compiles_at_production_codebooks(one_chip):
    from repro.kernels.rq_assign.rq_assign import rq_assign
    f32 = jnp.float32
    _compile(lambda x, a, b: rq_assign(x, [a, b], interpret=False),
             one_chip, ((8192, D), f32), ((CODEBOOKS[0], D), f32),
             ((CODEBOOKS[1], D), f32))


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_fused_contrastive_compiles(one_chip, direction, monkeypatch):
    from repro.kernels.fused_contrastive import fused_contrastive as fc
    # the differentiable op asks the default backend (the CPU here)
    # whether to interpret; this compile is for the chip
    monkeypatch.setattr(fc, "should_interpret", lambda: False)

    def fwd(s, t, n):
        return fc.fused_contrastive(s, t, n, interpret=False)

    def bwd(s, t, n):
        def loss(s, t, n):
            m, i = fc.fused_contrastive_diff(0.1, 0.06, s, t, n)
            return jnp.sum(m + i)
        return jax.grad(loss, argnums=(0, 1, 2))(s, t, n)

    B = 2048
    f32 = jnp.float32
    _compile(fwd if direction == "forward" else bwd, one_chip,
             ((B, D), f32), ((B, D), f32), ((B, N_NEG, D), f32))


def test_queue_gather_compiles_at_full_store(one_chip):
    from repro.kernels.queue_gather.queue_gather import queue_gather
    i32 = jnp.int32

    def serve(items, times, cursor, clusters, i2i):
        return queue_gather(items, times, cursor, clusters, i2i,
                            cutoff=0.0, n_recent=8, k=32, interpret=False)

    _compile(serve, one_chip, ((C, Q), i32), ((C, Q), jnp.float32),
             ((C,), i32), ((512,), i32), ((1_000_000, I2I_K), i32))


def test_ppr_walk_compiles_at_resident_size(one_chip):
    from repro.kernels.ppr_walk.ppr_walk import ppr_walk
    n_nodes, d2, walks, walk_len = 8192, 64, 64, 5   # 2 x 32 per type

    def walk(nbrs, cum, starts, u):
        return ppr_walk(nbrs, cum, starts, u, restart=0.15,
                        interpret=False)

    _compile(walk, one_chip, ((n_nodes, d2), jnp.int32),
             ((n_nodes, d2), jnp.float32), ((1024,), jnp.int32),
             ((1024, walks, 2 * walk_len), jnp.float32))
