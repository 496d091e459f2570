"""Pixel-path learning evidence (VERDICT round 2 #4 / SURVEY §4 item 2).

FakeAtari's reward is a function of the step counter, so the pixel e2e
tests built on it can only assert liveness. ``SignalAtari``'s reward is a
function of what's on screen — these tests prove the CNN + device-ring
topology actually LEARNS from pixels: greedy return must beat the
random-policy return with a wide margin.
"""

import numpy as np
import pytest

from distributed_deep_q_tpu.actors.game import SignalAtari
from distributed_deep_q_tpu.config import Config, EnvConfig, NetConfig, \
    ReplayConfig, TrainConfig


def _decode_target(frame: np.ndarray, num_actions: int,
                   orientation: str) -> int:
    """Recover the rewarded action from pixels alone."""
    axis = 0 if orientation == "v" else 1
    profile = frame.mean(axis=axis)
    band = len(profile) // num_actions
    return int(np.argmax([profile[i * band:(i + 1) * band].mean()
                          for i in range(num_actions)]))


def test_signal_atari_reward_is_pixel_observable():
    """The frame fully determines the rewarded action, for both 'games'."""
    for orientation in ("v", "h"):
        env = SignalAtari(episode_len=16, num_actions=4,
                          frame_shape=(36, 36), seed=3,
                          orientation=orientation)
        frame = env.reset()
        total = 0.0
        for _ in range(16):
            a = _decode_target(frame, 4, orientation)
            frame, r, done, over = env.step(a)
            total += r
        assert total == 16.0 and done and over


def test_signal_atari_random_policy_baseline():
    """Random actions score ~1/num_actions per step — the floor the
    learning test must clearly beat."""
    env = SignalAtari(episode_len=32, num_actions=4, frame_shape=(36, 36),
                      seed=0)
    rng = np.random.default_rng(0)
    rewards = []
    for _ in range(30):
        env.reset()
        ep = 0.0
        for _ in range(32):
            _, r, *_ = env.step(int(rng.integers(4)))
            ep += r
        rewards.append(ep)
    assert 4.0 < np.mean(rewards) < 13.0  # ~8 expected


def test_signal_games_differ():
    """'signal' and 'signal-h' are visually distinct games (multi-game
    fleets must not collapse them)."""
    from distributed_deep_q_tpu.actors.game import make_env

    v = make_env(EnvConfig(id="signal", kind="signal_atari",
                           frame_shape=(36, 36)), seed=0)
    h = make_env(EnvConfig(id="signal-h", kind="signal_atari",
                           frame_shape=(36, 36)), seed=0)
    assert v.orientation == "v" and h.orientation == "h"
    fv, fh = v.reset(), h.reset()
    # vertical bands: every row is identical, columns vary; horizontal: the
    # transpose property
    assert (fv == fv[0]).all() and fv[0].std() > 0
    assert (fh.T == fh.T[0]).all() and fh.T[0].std() > 0


@pytest.mark.slow
def test_pixel_path_learns_through_device_ring():
    """THE gate for the pixel topology: Nature-CNN learner fed by the
    device-resident HBM ring, uniform draws (the fused sampler at alpha
    0), beats the random policy (≈8/episode) by ≥2× on SignalAtari greedy
    eval."""
    from distributed_deep_q_tpu.train import train_single_process

    cfg = Config()
    cfg.env = EnvConfig(id="signal", kind="signal_atari",
                        frame_shape=(36, 36), stack=4, reward_clip=0.0)
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36), stack=4,
                        compute_dtype="float32")
    cfg.replay = ReplayConfig(capacity=8192, batch_size=32,
                              learn_start=500, n_step=1,
                              prioritized=True, priority_alpha=0.0,
                              write_chunk=64)
    cfg.train = TrainConfig(lr=1e-3, adam_eps=1e-8, gamma=0.99,
                            target_tau=0.01, double_dqn=True,
                            total_steps=4000, train_every=2,
                            eval_episodes=10, seed=0)
    cfg.actors.eps_decay_steps = 2000
    cfg.actors.eps_end = 0.05
    cfg.actors.eval_eps = 0.0
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1

    summary = train_single_process(cfg, log_every=500)
    # random ≈ 8/episode, perfect = 32; demand ≥2× random with margin
    assert summary["eval_return"] >= 16.0, (
        f"pixel path failed to learn: eval_return="
        f"{summary['eval_return']:.1f} (random ≈ 8, perfect = 32)")
