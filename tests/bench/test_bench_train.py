"""The training cell at tiny sizes on the CPU: the reference agrees with
the program, and the control and each planted fault come out not
correct."""
import pytest

import bench_tiny as B


def _with_step(monkeypatch, wrap):
    from repro.core import trainer
    real = trainer.make_train_step

    def make(*a, **kw):
        return wrap(real(*a, **kw))

    monkeypatch.setattr(trainer, "make_train_step", make)


def test_train_burst_agrees_with_the_reference():
    res = B.run("train-burst", 1.0)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"]


def test_train_control_in_fp8_is_not_correct():
    res = B.run("train-burst", 0.2, control=True)
    assert not res["correct"]


def test_a_step_that_returns_its_state_unchanged_is_caught(monkeypatch):
    def wrap(step):
        def unchanged(state, batch, key, features=None):
            _, metrics = step(state, batch, key, features)
            return state, metrics
        return unchanged

    import jax
    _with_step(monkeypatch, lambda s: wrap(
        jax.jit(s.__wrapped__ if hasattr(s, "__wrapped__") else s)))
    res = B.run("train-burst", 0.2)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_caught(monkeypatch):
    def wrap(step):
        def half(state, batch, key, features=None):
            edges = {et: {k: v[: v.shape[0] // 2] for k, v in e.items()}
                     for et, e in batch["edges"].items()}
            return step(state, dict(batch, edges=edges), key, features)
        return half

    _with_step(monkeypatch, wrap)
    res = B.run("train-burst", 0.2)
    assert not res["correct"]
