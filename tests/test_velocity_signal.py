"""VelocitySignalAtari: temporal-integration learning evidence (VERDICT r3
next #9).

``SignalAtari`` proves the pixel paths can learn from single-frame
appearance; its reward is readable off one frame, so a policy that ignores
the stack entirely can still win. ``VelocitySignalAtari`` closes that gap:
the rewarded action is the band's VELOCITY, position is redrawn uniformly
(independent of velocity) at every segment start, so a single frame carries
zero reward signal. The fast tests pin the env's information structure
(two frames decode it, one frame cannot); the slow gates prove the
frame-stack CNN paths (device ring, fused device-PER) and the stack=1
recurrent R2D2 path each beat the random policy ≥2× on it.
"""

import numpy as np
import pytest

from distributed_deep_q_tpu.actors.game import VelocitySignalAtari, make_env
from distributed_deep_q_tpu.config import Config, EnvConfig, NetConfig, \
    ReplayConfig, TrainConfig

FRAME = (36, 36)
A = 4


def _band_pos(frame: np.ndarray, env: VelocitySignalAtari) -> int:
    """Recover the band's start offset via circular box correlation."""
    axis = 1 if env.orientation == "v" else 0
    profile = frame.mean(axis=1 - axis).astype(np.float64)
    n, bw = len(profile), env.band_width
    scores = [profile[(np.arange(bw) + p) % n].sum() for p in range(n)]
    return int(np.argmax(scores))


def _decode_velocity(prev: np.ndarray, cur: np.ndarray,
                     env: VelocitySignalAtari) -> int:
    """Two-frame decoder: circular displacement → nearest velocity index."""
    n = env._axis
    d = (_band_pos(cur, env) - _band_pos(prev, env) + n // 2) % n - n // 2
    return int(np.argmin([abs(d - v) for v in env.velocities]))


def test_two_frame_decoder_hits_ceiling():
    """The reward IS motion-observable: a perfect two-frame decoder scores
    near the (1 - 1/segment) ceiling, for both orientations."""
    for orientation in ("v", "h"):
        env = VelocitySignalAtari(episode_len=64, frame_shape=FRAME,
                                  seed=5, orientation=orientation)
        prev = env.reset()
        cur, _, _, _ = env.step(0)  # burn one step so two frames exist
        total, steps = 0.0, 0
        for _ in range(62):
            a = _decode_velocity(prev, cur, env)
            nxt, r, done, _ = env.step(a)
            total += r
            steps += 1
            prev, cur = cur, nxt
        # ceiling ≈ (1 - 1/8); boundary steps (stale displacement) miss
        assert total >= 0.75 * steps, (orientation, total, steps)


def test_single_frame_carries_no_reward_signal():
    """Anti-leak: at segment starts, position is drawn independent of
    velocity — for any position bucket, no velocity index dominates, so no
    single-frame policy can beat random. (Seeded ⇒ deterministic.)"""
    env = VelocitySignalAtari(episode_len=32, frame_shape=FRAME, seed=11)
    counts = np.zeros((6, A), np.int64)  # position bucket × velocity
    for _ in range(600):
        frame = env.reset()  # each reset = one independent segment draw
        bucket = _band_pos(frame, env) * 6 // env._axis
        counts[bucket, env._v_idx] += 1
    for b in range(6):
        n = counts[b].sum()
        assert n >= 50  # uniform positions fill every bucket
        assert counts[b].max() / n < 0.45, (b, counts[b])  # ≈0.25 expected


def test_velocity_random_policy_baseline():
    env = VelocitySignalAtari(episode_len=32, frame_shape=FRAME, seed=0)
    rng = np.random.default_rng(0)
    rewards = []
    for _ in range(30):
        env.reset()
        ep = 0.0
        for _ in range(32):
            _, r, *_ = env.step(int(rng.integers(A)))
            ep += r
        rewards.append(ep)
    assert 4.0 < np.mean(rewards) < 13.0  # ~8 expected


def test_make_env_velocity_ids():
    """'signal-vel' / 'signal-vel-h' select the variant + orientation."""
    v = make_env(EnvConfig(id="signal-vel", kind="signal_atari",
                           frame_shape=FRAME), seed=0)
    h = make_env(EnvConfig(id="signal-vel-h", kind="signal_atari",
                           frame_shape=FRAME), seed=0)
    assert isinstance(v, VelocitySignalAtari) and v.orientation == "v"
    assert isinstance(h, VelocitySignalAtari) and h.orientation == "h"
    fv, fh = v.reset(), h.reset()
    assert (fv == fv[0]).all() and fv[0].std() > 0      # vertical band
    assert (fh.T == fh.T[0]).all() and fh.T[0].std() > 0


def test_episode_constant_variant():
    """'-ep' holds one velocity per episode (only reset redraws); a
    two-frame decoder reading any adjacent pair then wins every later
    step — and single frames still carry nothing (position redraw at
    reset is velocity-independent by the same construction)."""
    env = make_env(EnvConfig(id="signal-vel-ep", kind="signal_atari",
                             frame_shape=FRAME), seed=3)
    assert env.segment > env.episode_len  # never redraws mid-episode
    prev = env.reset()
    cur, _, _, _ = env.step(0)
    a = _decode_velocity(prev, cur, env)
    total = 0.0
    for _ in range(env.episode_len - 1):
        _, r, done, _ = env.step(a)   # one read, constant answer
        total += r
    assert total == float(env.episode_len - 1) and done


def _pixel_cfg(vel_id: str = "signal-vel", total_steps: int = 6000,
               **replay_kw) -> Config:
    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.env = EnvConfig(id=vel_id, kind="signal_atari", frame_shape=FRAME,
                        stack=4, reward_clip=0.0)
    cfg.net = NetConfig(kind="nature_cnn", num_actions=A, frame_shape=FRAME,
                        stack=4, compute_dtype="float32")
    cfg.replay = ReplayConfig(capacity=8192, batch_size=32, learn_start=500,
                              n_step=1, write_chunk=64, **replay_kw)
    cfg.train = TrainConfig(lr=1e-3, adam_eps=1e-8, gamma=0.99,
                            target_tau=0.01, double_dqn=True,
                            total_steps=total_steps, train_every=2,
                            eval_episodes=10, seed=0)
    cfg.actors.eps_decay_steps = total_steps // 2
    cfg.actors.eps_end = 0.05
    cfg.actors.eval_eps = 0.0
    return cfg


@pytest.mark.slow
def test_velocity_learns_through_device_ring():
    """Motion gate #1: the frame-stack CNN over the device-resident HBM
    ring, uniform draws (the fused sampler at alpha 0), must read
    displacement ACROSS stack channels — ≥2× random."""
    from distributed_deep_q_tpu.train import train_single_process

    cfg = _pixel_cfg(prioritized=True, priority_alpha=0.0)
    summary = train_single_process(cfg, log_every=500)
    assert summary["eval_return"] >= 16.0, (
        f"device-ring path failed to learn motion: "
        f"{summary['eval_return']:.1f} (random ≈ 8, ceiling ≈ 29)")


@pytest.mark.slow
def test_velocity_learns_through_fused_device_per():
    """Motion gate #2: same bar on the fused device-PER path."""
    from distributed_deep_q_tpu.train import train_single_process

    cfg = _pixel_cfg(prioritized=True, device_per=True)
    summary = train_single_process(cfg, log_every=500)
    assert summary["eval_return"] >= 16.0, (
        f"fused-PER path failed to learn motion: "
        f"{summary['eval_return']:.1f} (random ≈ 8, ceiling ≈ 29)")


@pytest.mark.slow
def test_velocity_learns_through_r2d2_stack1():
    """Motion gate #3: R2D2 at stack=1 — the ONLY place the previous band
    position can live is the LSTM carry, so this is a true memory gate,
    not channel-difference pattern matching. Episode-constant velocity
    ("-ep": read the motion once, carry the answer) keeps the credit
    assignment tractable — the segment=8 tier stays a stretch goal (the
    same budget plateaus at random there, while the static-band stack=1
    control reaches ~19 in 5k steps)."""
    from distributed_deep_q_tpu.train import train_recurrent

    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.env = EnvConfig(id="signal-vel-ep", kind="signal_atari",
                        frame_shape=FRAME, stack=1, reward_clip=0.0)
    cfg.net = NetConfig(kind="r2d2", num_actions=A, frame_shape=FRAME,
                        stack=1, lstm_size=128, compute_dtype="float32")
    cfg.replay = ReplayConfig(capacity=16384, batch_size=16, learn_start=640,
                              sequence_length=16, burn_in=4)
    cfg.train = TrainConfig(lr=1e-3, adam_eps=1e-8, gamma=0.99,
                            target_tau=0.01, double_dqn=True,
                            total_steps=8000, train_every=2,
                            eval_episodes=10, seed=0)
    cfg.actors.eps_decay_steps = 4000
    cfg.actors.eps_end = 0.05
    cfg.actors.eval_eps = 0.0
    summary = train_recurrent(cfg, log_every=500)
    assert summary["eval_return"] >= 16.0, (
        f"R2D2 stack=1 failed to learn motion from memory: "
        f"{summary['eval_return']:.1f} (random ≈ 8, perfect ≈ 31)")


