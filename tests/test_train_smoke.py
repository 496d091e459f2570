"""End-to-end smoke: the minimum slice (SURVEY §7.2 step 1) runs and learns."""

import numpy as np
import pytest

from distributed_deep_q_tpu.config import cartpole_config, Config, NetConfig, EnvConfig
from distributed_deep_q_tpu.train import train_single_process, evaluate


def test_cartpole_smoke_runs_and_improves():
    cfg = cartpole_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.train.total_steps = 3_000
    cfg.replay.learn_start = 300
    out = train_single_process(cfg, log_every=1000)
    assert np.isfinite(out["final_return_avg100"])
    assert out["eval_return"] > 15  # random policy ≈ 9.3 on CartPole


def test_host_batch_loop_on_the_sharded_mesh():
    """The loop of the two CartPole tests around this one, for 19 grad
    steps over the 8-device mesh: they learn, on one device (conftest's
    docstring says why), so this is where the loop places a host batch on
    the shards and its step's `psum` runs."""
    cfg = cartpole_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 8
    cfg.train.total_steps = 320
    cfg.replay.learn_start = 300
    out = train_single_process(cfg, log_every=10)
    assert out["solver"].mesh.size == 8
    assert out["solver"].step == 19
    assert np.isfinite(out["loss"])


def test_fake_atari_pixel_path():
    """Fused device ring (uniform draws: alpha 0) + CNN learner end to end
    on FakeAtari frames, sharded over the 8-device mesh for its 16 grad
    steps."""
    cfg = Config()
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(84, 84), stack=4)
    cfg.env = EnvConfig(id="fake", kind="fake_atari", stack=4)
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 8
    cfg.replay.capacity = 2_000
    cfg.replay.batch_size = 16
    cfg.replay.learn_start = 200
    cfg.replay.prioritized = True
    cfg.replay.priority_alpha = 0.0
    cfg.train.total_steps = 260
    cfg.train.train_every = 4
    out = train_single_process(cfg, log_every=5)
    assert np.isfinite(out["eval_return"])


def test_cartpole_fast_proxy_reaches_150():
    """Fast-suite regression gate for the config-1 recipe (VERDICT r1 #1):
    a 10k-step run of the real preset must clear 150/500 — a config change
    that breaks learning can never ship on the fast suite alone again."""
    cfg = cartpole_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.train.total_steps = 10_000
    out = train_single_process(cfg, log_every=5000)
    assert out["eval_return"] >= 150


@pytest.mark.slow
def test_cartpole_solves():
    """Config-1 parity bar (SURVEY §7.2 step 1): CartPole solved — ≥475/500
    greedy eval over 10 fresh episodes. Cross-seed robustness is validated
    by the sweep logs (seeds 0–3 all ≥475, scripts/diag_cartpole.py)."""
    cfg = cartpole_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    out = train_single_process(cfg, log_every=5000)
    solver = out["solver"]
    assert evaluate(solver, cfg, episodes=10) >= 475
