"""Multi-game fleet support (config 4 "Atari-57 8-game subset",
VERDICT round 2 #6): per-actor game assignment, shared action space
validation, per-game eval metrics.
"""

import numpy as np
import pytest

from distributed_deep_q_tpu.config import (
    Config, EnvConfig, apex_config, env_for_actor)


def test_env_for_actor_round_robin():
    env = EnvConfig(id="a", games=("a", "b", "c"))
    assert [env_for_actor(env, i).id for i in range(7)] == \
        ["a", "b", "c", "a", "b", "c", "a"]
    # single-game passthrough (same object, no copy churn)
    single = EnvConfig(id="only")
    assert env_for_actor(single, 5) is single


def test_apex_preset_is_multigame():
    cfg = apex_config()
    assert len(cfg.env.games) == 8
    assert cfg.env.full_action_space and cfg.net.num_actions == 18
    assert cfg.actors.num_actors == 256


def test_probe_rejects_mismatched_action_spaces(monkeypatch):
    """Fleet bring-up must fail fast when games disagree on action count."""
    from distributed_deep_q_tpu.actors import supervisor

    class TwoActionEnv:
        num_actions, obs_shape, obs_dtype = 2, (4,), np.float32

    class FourActionEnv:
        num_actions, obs_shape, obs_dtype = 4, (4,), np.float32

    def fake_make_env(env_cfg, seed=0):
        return TwoActionEnv() if env_cfg.id == "two" else FourActionEnv()

    monkeypatch.setattr("distributed_deep_q_tpu.actors.game.make_env",
                        fake_make_env)
    cfg = Config()
    cfg.env = EnvConfig(id="two", games=("two", "four"))
    with pytest.raises(ValueError, match="one shared action space"):
        supervisor._probe_envs(cfg)


def test_evaluate_per_game_single_and_multi():
    from distributed_deep_q_tpu.config import NetConfig
    from distributed_deep_q_tpu.solver import Solver
    from distributed_deep_q_tpu.train import evaluate_per_game

    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.env = EnvConfig(id="signal", kind="signal_atari",
                        games=("signal", "signal-h"), frame_shape=(36, 36),
                        stack=4)
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36), compute_dtype="float32")
    cfg.train.eval_episodes = 2
    solver = Solver(cfg)
    out = evaluate_per_game(solver, cfg)
    assert set(out) == {"signal", "signal-h"}
    assert all(np.isfinite(v) for v in out.values())


@pytest.mark.slow
def test_distributed_multigame_end_to_end():
    """2-actor fleet, each actor assigned a DIFFERENT fake game, learner
    trains through the device ring; summary reports per-game eval."""
    from distributed_deep_q_tpu.actors.supervisor import train_distributed
    from distributed_deep_q_tpu.config import pong_config, ReplayConfig

    cfg = pong_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.num_fake_devices = 2
    cfg.env.kind = "signal_atari"
    cfg.env.id = "signal"
    cfg.env.games = ("signal", "signal-h")
    cfg.env.frame_shape = (36, 36)
    cfg.net.frame_shape = (36, 36)
    cfg.net.compute_dtype = "float32"
    cfg.replay = ReplayConfig(capacity=4096, batch_size=16, learn_start=300,
                              n_step=2, prioritized=True, write_chunk=16)
    cfg.train.total_steps = 60
    cfg.train.target_update_period = 10
    cfg.train.eval_episodes = 2
    cfg.actors.num_actors = 2
    cfg.actors.send_batch = 20
    cfg.actors.param_sync_period = 25
    summary = train_distributed(cfg, log_every=20)
    assert summary["solver"].step == 60
    assert np.isfinite(summary["loss"])
    assert set(summary["eval_per_game"]) == {"signal", "signal-h"}
    assert np.isfinite(summary["eval_return"])
