"""Test harness configuration.

Forces JAX onto the CPU platform with 8 virtual devices so the full
multi-device learner path (shard_map + psum over a `dp` mesh) is exercised
without TPU hardware — the TPU-native analogue of the reference's
`--backend`-switch "dummy backend" testing pattern (SURVEY.md §4 [M]).

Both options are set here, before any backend is initialized (conftest
runs before test modules import jax users), so the suite is on the CPU
whatever platform the environment names.
"""

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


@pytest.fixture(scope="module")
def toy_fused_pair():
    """Builder of a real fused pair at toy size — ``build(chain)`` gives
    the solver and its filled device-PER ring — for the tests that drive
    ``FusedStepStream`` over real programs."""
    def build(chain: int, patch=None):
        import numpy as np

        from distributed_deep_q_tpu.config import (
            Config, NetConfig, ReplayConfig)
        from distributed_deep_q_tpu.replay.device_per import (
            DevicePERFrameReplay)
        from distributed_deep_q_tpu.solver import Solver

        cfg = Config()
        cfg.mesh.backend = "cpu"
        cfg.mesh.dp = 1
        cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                            frame_shape=(36, 36))
        cfg.replay = ReplayConfig(capacity=512, batch_size=16, n_step=2,
                                  prioritized=True, device_per=True,
                                  write_chunk=16, fused_chain=chain)
        if patch is not None:
            patch(cfg)          # e.g. the tree body at a toy batch
        solver = Solver(cfg)
        dev = DevicePERFrameReplay(cfg.replay, solver.mesh, (36, 36),
                                   stack=4, gamma=0.99, seed=0,
                                   write_chunk=16)
        rng = np.random.default_rng(0)
        for i in range(300):
            dev.add(rng.integers(0, 255, (36, 36), dtype=np.uint8),
                    int(rng.integers(4)), float(rng.standard_normal()),
                    done=(i % 9 == 8))
        dev.flush()
        return solver, dev
    return build
