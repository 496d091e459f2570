"""Checkpoint/resume: exact learner-state round-trip (params, target
params, optimizer moments, step) and resume-through-the-train-loop."""

import numpy as np
import pytest

from distributed_deep_q_tpu.config import (
    Config, MeshConfig, NetConfig, ReplayConfig, TrainConfig)
from distributed_deep_q_tpu.utils.checkpoint import Checkpointer


def _solver(seed=0):
    from distributed_deep_q_tpu.solver import Solver
    cfg = Config()
    cfg.net = NetConfig(kind="mlp", num_actions=2, hidden=(16,))
    cfg.train = TrainConfig(seed=seed, target_update_period=3)
    cfg.mesh = MeshConfig(backend="cpu", num_fake_devices=2, dp=2)
    return Solver(cfg, obs_dim=4)


def _batch(rng, b=8):
    return {
        "obs": rng.standard_normal((b, 4)).astype(np.float32),
        "action": rng.integers(0, 2, b).astype(np.int32),
        "reward": rng.standard_normal(b).astype(np.float32),
        "next_obs": rng.standard_normal((b, 4)).astype(np.float32),
        "discount": np.full(b, 0.99, np.float32),
        "weight": np.ones(b, np.float32),
    }


def _leaves(tree):
    import jax
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def test_checkpoint_roundtrip_exact(tmp_path):
    s = _solver()
    rng = np.random.default_rng(0)
    for _ in range(5):
        s.train_step(_batch(rng))
    ckpt = Checkpointer(str(tmp_path / "ck"))
    ckpt.save(s.state, extra={"env_steps": 123}, wait=True)

    s2 = _solver(seed=99)  # different init — must be fully overwritten
    restored, extra = ckpt.restore(s2.state)
    assert int(restored.step) == 5
    assert int(extra["env_steps"]) == 123
    for a, b in zip(_leaves(s.state.params), _leaves(restored.params)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_leaves(s.state.target_params),
                    _leaves(restored.target_params)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_leaves(s.state.opt_state), _leaves(restored.opt_state)):
        np.testing.assert_array_equal(a, b)


def test_checkpoint_resume_continues_identically(tmp_path):
    """10 straight steps == 5 steps → save → restore → 5 more steps."""
    rng_a = np.random.default_rng(7)
    a = _solver()
    batches = [_batch(rng_a) for _ in range(10)]
    for b in batches:
        a.train_step(dict(b))

    rng_b = np.random.default_rng(7)
    b1 = _solver()
    for bt in batches[:5]:
        b1.train_step(dict(bt))
    ckpt = Checkpointer(str(tmp_path / "ck"))
    ckpt.save(b1.state, wait=True)

    b2 = _solver(seed=42)
    b2.state, _ = ckpt.restore(b2.state)
    for bt in batches[5:]:
        b2.train_step(dict(bt))

    for x, y in zip(_leaves(a.state.params), _leaves(b2.state.params)):
        np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-7)
    assert int(b2.state.step) == 10


def test_extra_preserves_scalar_kinds(tmp_path):
    """Regression: ``extra`` values must round-trip with their Python kind
    intact — a blanket float() coercion silently turned step counters into
    floats (exact-step arithmetic drifts past 2**53)."""
    s = _solver()
    rng = np.random.default_rng(0)
    s.train_step(_batch(rng))
    ckpt = Checkpointer(str(tmp_path / "ck"))
    ckpt.save(s.state, extra={
        "env_steps": 123,
        "big": 2**53 + 1,            # not representable as float64
        "lr": 6.25e-5,
        "np_int": np.int64(77),
        "np_float": np.float32(0.5),
        "flag": True,
    }, wait=True)
    _, extra = ckpt.restore(s.state)
    assert extra["env_steps"] == 123 and type(extra["env_steps"]) is int
    assert extra["big"] == 2**53 + 1 and type(extra["big"]) is int
    assert extra["lr"] == pytest.approx(6.25e-5)
    assert type(extra["lr"]) is float
    assert extra["np_int"] == 77 and type(extra["np_int"]) is int
    assert extra["np_float"] == pytest.approx(0.5)
    assert type(extra["np_float"]) is float
    assert extra["flag"] is True


def test_keep_retention(tmp_path):
    s = _solver()
    rng = np.random.default_rng(0)
    ckpt = Checkpointer(str(tmp_path / "ck"), keep=2)
    for i in range(4):
        s.train_step(_batch(rng))
        ckpt.save(s.state, wait=True)
    assert ckpt.latest_step() == 4


def test_train_loop_checkpoint_and_resume(tmp_path):
    """The loop-level wiring: run with checkpoint_every, then resume=True
    restarts from the snapshot step."""
    from distributed_deep_q_tpu.train import train_single_process

    cfg = Config()
    cfg.net = NetConfig(kind="mlp", num_actions=2, hidden=(16,))
    cfg.replay = ReplayConfig(capacity=2000, batch_size=16, learn_start=100)
    cfg.train = TrainConfig(
        total_steps=300, train_every=1, target_update_period=50,
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=100)
    cfg.mesh = MeshConfig(backend="cpu", dp=1)
    cfg.env.id = "CartPole-v1"
    s1 = train_single_process(cfg, log_every=100)
    assert s1["solver"].step == 201  # 300 env steps - 100 warmup + final

    cfg2 = cfg.replace()
    cfg2.train = TrainConfig(
        total_steps=100, train_every=1, target_update_period=50,
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=100,
        resume=True)
    s2 = train_single_process(cfg2, log_every=100)
    # resumed from step 201, then trained on top of it
    assert s2["solver"].step == 201 + 1  # 100 env steps - 100 warmup + final
