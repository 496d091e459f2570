"""Test harness configuration.

Forces JAX onto the CPU platform with 8 virtual devices — the TPU-native
analogue of the reference's `--backend`-switch "dummy backend" testing
pattern (SURVEY.md §4 [M]). The 8 devices are there for the tests whose
SUBJECT is sharding (shard_map + psum over a `dp` mesh, the sharded
rings): those ask for them by name (`mesh.dp = 8`, or the `dp` they
compare) and run a handful of train steps each. Every other test — one
whose subject is learning, a loop, a wire, a checkpoint or a metric —
says `cfg.mesh.dp = 1` where it makes its `Config` (`dp = 0`, the
default, means all 8). The reason is XLA:CPU, not the TPU: an all-reduce
over N virtual devices blocks N workers of the process's thread pool
until all N have joined, and on a loaded machine some never arrive
("Expected 8 threads to join the rendezvous, but only 6 of them arrived
on time", rendezvous.cc:127; it has read 4, 6 and 7): after 40 s XLA
aborts the process, and xdist then re-queues the dead worker's whole file
into the same abort. A 3 000-step CartPole run is 2 700 such rendezvous;
one device has none.

Both options are set here, before any backend is initialized (conftest
runs before test modules import jax users), so the suite is on the CPU
whatever platform the environment names.
"""

import contextlib
import signal

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Every test's own time limit, in seconds, so that a test that hangs costs
# its own result and not everyone's. The slowest tier-1 test takes 2-3
# minutes on the sandbox inside a whole run (a token family's, 162-167 s:
# CHANGES.md, PR 37); three times that, rounded up to a minute, is 9
# minutes, more than a quarter of the 1 470 s the driver gives the whole
# run — so the limit is the 6 minutes under that quarter.
TEST_TIME_LIMIT_S = 360.0


@contextlib.contextmanager
def time_limit(seconds: float, what: str):
    """Fail ``what`` by name once it has run for ``seconds``.

    The alarm's handler raises in the main thread, so it ends the waits
    Python can see: a socket, a queue, a join, a child process. A main
    thread parked in native code is not interrupted until it returns —
    inside an XLA:CPU collective the limit is XLA's own 40 s termination
    timeout, which kills the process and not the test.
    """
    def overdue(signum, frame):
        pytest.fail(f"{what} outlived its time limit of {seconds:g} s "
                    "(tests/conftest.py)")

    handler = signal.signal(signal.SIGALRM, overdue)
    outer = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *outer)
        signal.signal(signal.SIGALRM, handler)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    if item.get_closest_marker("slow"):
        # the constant is sized from tier-1; a learning gate of the slow
        # tier runs for many minutes by design
        return (yield)
    with time_limit(TEST_TIME_LIMIT_S, item.nodeid):
        return (yield)


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
