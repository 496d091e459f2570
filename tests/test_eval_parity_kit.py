"""ALE eval-parity readiness kit (VERDICT r4 next #9, SURVEY §7.3 item 5).

This image has never had ``ale_py``, so the Pong/Breakout eval-return
half of the north star cannot be produced here. This kit makes it a
ZERO-NEW-CODE exercise the moment an ALE-enabled host runs the suite:

- ``test_preprocessing_golden_checksums`` (always runs): the FULL actor
  preprocessing stack — ≤30 no-op starts, frame-skip 4, 2-frame max,
  luma grayscale, 84×84 area resize, reward sum+clip, life-loss
  done/over split — executes over a deterministic procedural raw-frame
  stream at the real ALE raw resolution (210×160×3) and must reproduce
  the frozen SHA-256 stream in ``tests/fixtures/atari_golden.npz``
  byte-for-byte. Any change to any constant in the stack trips this.
- ``test_real_ale_pipeline``: auto-activates when ``ale_py`` imports —
  drives the REAL ALE through the same class and the standard eval
  entry point. On this image it reports SKIPPED, loudly.

The measurement protocol itself is documented in ``EVAL_PROTOCOL.md``
(repo root): exact CLI commands, ε, no-op starts, episode caps, and the
parity gates (Pong ≥ +19, Breakout ≥ ~300).
"""

from __future__ import annotations

import hashlib
import os
from types import SimpleNamespace

import numpy as np
import pytest

from distributed_deep_q_tpu.actors.game import AtariEnv
from distributed_deep_q_tpu.config import EnvConfig

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "atari_golden.npz")
RAW_HW = (210, 160)  # real ALE raw frame geometry
N_STEPS = 96


def _raw_frame(t: int) -> np.ndarray:
    """Deterministic, structured 210×160×3 frame: moving gradient field +
    a bright 'ball' and two 'paddles' whose positions derive from t — rich
    enough that every stage (max, luma, area-resize) sees non-trivial
    content, cheap enough to regenerate anywhere."""
    h, w = RAW_HW
    yy, xx = np.mgrid[0:h, 0:w]
    base = ((yy * 3 + xx * 5 + t * 7) % 251).astype(np.uint8)
    frame = np.stack([base, (base * 2) % 251, (base * 3) % 251], axis=-1)
    by, bx = (37 * t) % (h - 8), (23 * t) % (w - 8)
    frame[by:by + 8, bx:bx + 8] = 236
    frame[20 + (t % 150):20 + (t % 150) + 16, 8:12] = 200
    frame[40 + (t * 2 % 140):40 + (t * 2 % 140) + 16, w - 12:w - 8] = 180
    return frame


class _ScriptedRaw:
    """Gymnasium-style raw env over the procedural frames: scripted
    rewards (reward-clip/sum must see >1 and <-1 values) and a life-loss
    at raw step 40."""

    def __init__(self):
        self.action_space = SimpleNamespace(n=6)
        self.t = 0

    def reset(self, seed=None):
        self.t = 0
        return _raw_frame(0), {"lives": 3}

    def step(self, action):
        self.t += 1
        r = [0.0, 0.7, 0.9, -1.5, 2.0][self.t % 5]
        lives = 3 if self.t < 40 else 2
        return _raw_frame(self.t), r, False, False, {"lives": lives}


def _run_stack():
    cfg = EnvConfig(id="golden", kind="atari", frame_shape=(84, 84),
                    frame_skip=4, reward_clip=1.0,
                    terminal_on_life_loss=True, noop_max=30)
    env = AtariEnv(cfg, seed=123, env=_ScriptedRaw())
    obs = env.reset()
    hashes = [hashlib.sha256(np.ascontiguousarray(obs).tobytes())
              .hexdigest()]
    rewards, dones, overs = [], [], []
    for i in range(N_STEPS):
        obs, r, done, over = env.step(i % 6)
        hashes.append(hashlib.sha256(
            np.ascontiguousarray(obs).tobytes()).hexdigest())
        rewards.append(r)
        dones.append(done)
        overs.append(over)
        if over:
            obs = env.reset()
            hashes.append(hashlib.sha256(
                np.ascontiguousarray(obs).tobytes()).hexdigest())
    return (np.asarray(hashes), np.asarray(rewards, np.float32),
            np.asarray(dones), np.asarray(overs))


def test_preprocessing_golden_checksums():
    hashes, rewards, dones, overs = _run_stack()
    if not os.path.exists(FIXTURE):  # pragma: no cover - first generation
        os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
        np.savez(FIXTURE, hashes=hashes, rewards=rewards, dones=dones,
                 overs=overs)
        pytest.skip("golden fixture generated — rerun to verify")
    z = np.load(FIXTURE, allow_pickle=False)
    np.testing.assert_array_equal(hashes, z["hashes"].astype(str))
    np.testing.assert_array_equal(rewards, z["rewards"])
    np.testing.assert_array_equal(dones, z["dones"])
    np.testing.assert_array_equal(overs, z["overs"])


def _has_ale() -> bool:
    try:
        import ale_py  # noqa: F401
        import gymnasium  # noqa: F401
        return True
    except ImportError:
        return False


@pytest.mark.skipif(not _has_ale(), reason=(
    "ale_py not installed in this image — this test auto-activates on an "
    "ALE-enabled host and produces the real-Atari pipeline evidence "
    "(EVAL_PROTOCOL.md has the full parity recipe)"))
def test_real_ale_pipeline():
    """Real ALE through the SAME class + standard eval entry point: the
    exact code path the parity numbers come from."""
    from distributed_deep_q_tpu.config import pong_config
    from distributed_deep_q_tpu.solver import Solver
    from distributed_deep_q_tpu.train import evaluate

    cfg = pong_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.env.id = "ALE/Pong-v5"
    cfg.net.compute_dtype = "float32"
    env = AtariEnv(cfg.env, seed=0)
    obs = env.reset()
    assert obs.shape == (84, 84) and obs.dtype == np.uint8
    cfg.net.num_actions = env.num_actions
    solver = Solver(cfg)
    cfg.train.eval_episodes = 1
    ret = evaluate(solver, cfg, episodes=1)
    assert -21.0 <= ret <= 21.0  # a legal Pong return; untrained ≈ -21
