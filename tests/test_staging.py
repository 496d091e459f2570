"""DeviceStager: prefetched batches are device-resident and correctly laid
out; host bookkeeping keys survive untransferred; errors surface."""

import numpy as np
import pytest

from distributed_deep_q_tpu.config import Config
from distributed_deep_q_tpu.replay.replay_memory import ReplayMemory
from distributed_deep_q_tpu.replay.staging import DeviceStager
from distributed_deep_q_tpu.solver import Solver


def _filled_replay(n=512):
    replay = ReplayMemory(1024, (4,), np.float32, seed=0)
    rng = np.random.default_rng(0)
    for _ in range(n):
        replay.add(rng.normal(size=4).astype(np.float32),
                   int(rng.integers(2)), 1.0,
                   rng.normal(size=4).astype(np.float32), 0.99)
    return replay


def test_stager_delivers_device_batches_with_host_keys():
    replay = _filled_replay()
    stager = DeviceStager(lambda: replay.sample(64), depth=2)
    try:
        for _ in range(4):
            batch = stager.get()
            assert isinstance(batch["index"], np.ndarray)  # stayed on host
            assert hasattr(batch["obs"], "devices")        # on device
            assert batch["obs"].shape == (64, 4)
    finally:
        stager.close()


def test_stager_feeds_learner_end_to_end():
    replay = _filled_replay()
    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    solver = Solver(cfg, obs_dim=4)
    stager = DeviceStager(lambda: replay.sample(64),
                          sharding=solver.learner._batch_sharding, depth=2)
    try:
        losses = [float(solver.train_step(stager.get())["loss"])
                  for _ in range(3)]
        assert all(np.isfinite(l) for l in losses)
    finally:
        stager.close()


def test_stager_surfaces_sampler_errors():
    def boom():
        raise ValueError("sampler exploded")

    stager = DeviceStager(boom, depth=1)
    try:
        with pytest.raises(RuntimeError, match="staging thread failed"):
            stager.get(timeout=5.0)
    finally:
        stager.close()
