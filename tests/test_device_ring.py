"""Device-resident replay tests.

The load-bearing property: a batch composed on device from the HBM ring
(gather + validity masking + transpose inside the jitted step) is BYTE-EXACT
equal to the host ``FrameStackReplay.gather`` path for the same transition
stream and indices — on a 1-device mesh and sharded over 8 devices.
"""

import numpy as np
import pytest

from distributed_deep_q_tpu.config import Config, NetConfig, ReplayConfig, TrainConfig
from distributed_deep_q_tpu.replay.device_ring import DeviceFrameReplay, compose_stacks
from distributed_deep_q_tpu.replay.replay_memory import FrameStackReplay


def _mesh(n):
    from distributed_deep_q_tpu.config import MeshConfig
    from distributed_deep_q_tpu.parallel.mesh import make_mesh
    return make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=n))


def _play_stream(replay, host, n_steps, seed=0, episode_len=13,
                 frame_shape=(8, 8)):
    """Feed the same deterministic transition stream to both buffers."""
    rng = np.random.default_rng(seed)
    t = 0
    for i in range(n_steps):
        frame = rng.integers(0, 255, frame_shape, dtype=np.uint8)
        a = int(rng.integers(0, 4))
        r = float(rng.standard_normal())
        t += 1
        done = t % episode_len == 0
        replay.add(frame, a, r, done, boundary=done)
        if host is not None:
            host.add(frame, a, r, done, boundary=done)
        if done:
            t = 0


def test_device_batch_matches_host_gather_dp1():
    mesh = _mesh(1)
    cfg = ReplayConfig(capacity=512, batch_size=32, n_step=3)
    dev = DeviceFrameReplay(cfg, mesh, (8, 8), stack=4, gamma=0.99, seed=0)
    # host shadow of the stream: with dp=1 every episode goes to shard 0
    host = FrameStackReplay(512, (8, 8), 4, 3, 0.99, seed=0)
    _play_stream(dev, host, 400)
    dev.flush()

    batch = dev.sample(32)
    batch.pop("_sampled_at")

    # the device composition must be byte-identical to the host replay's
    # gather for the same indices
    import functools

    import jax
    idx = batch["index"].astype(np.int64)
    ref = host.gather(idx)
    compose = functools.partial(compose_stacks, frame_shape=(8, 8))
    obs_dev = np.asarray(jax.jit(compose)(
        dev.ring, batch["oidx"], batch["valid"]))
    nobs_dev = np.asarray(jax.jit(compose)(
        dev.ring, batch["noidx"], batch["nvalid"]))
    np.testing.assert_array_equal(obs_dev, ref["obs"])
    np.testing.assert_array_equal(nobs_dev, ref["next_obs"])
    for k in ("action", "reward", "discount"):
        np.testing.assert_array_equal(batch[k], ref[k])


def test_device_batch_shard_locality_dp8():
    """The REAL sharded path: compose through shard_map exactly as the
    learner does, and check each device's rows against pixels from its OWN
    ring shard and metadata from its OWN shard buffer — catches shard
    mis-ordering or layout drift that a global-gather comparison cannot."""
    import functools

    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    dp, per = 8, 4
    mesh = _mesh(dp)
    cfg = ReplayConfig(capacity=512 * dp, batch_size=dp * per, n_step=2)
    dev = DeviceFrameReplay(cfg, mesh, (8, 8), stack=4, gamma=0.99, seed=0)
    _play_stream(dev, None, 2000, episode_len=9)  # many episodes → all shards
    dev.flush()

    batch = dev.sample(dp * per)
    batch.pop("_sampled_at")

    sharded = jax.jit(shard_map(
        functools.partial(compose_stacks, frame_shape=(8, 8)), mesh=mesh,
        in_specs=(P("dp"), P("dp"), P("dp")), out_specs=P("dp"),
        check_vma=False))
    obs_dev = np.asarray(sharded(dev.ring, batch["oidx"], batch["valid"]))

    ring = np.asarray(dev.ring).reshape(-1, 8, 8)
    cap_l = dev.cap_local
    for s in range(dp):
        rows = slice(s * per, (s + 1) * per)
        local_ring = ring[s * cap_l:(s + 1) * cap_l]
        expect = np.moveaxis(
            local_ring[batch["oidx"][rows]]
            * batch["valid"][rows][..., None, None], 1, -1)
        np.testing.assert_array_equal(obs_dev[rows], expect)
        # metadata rows come from shard s's own slot buffers
        gidx = batch["index"][rows].astype(np.int64)
        assert ((s * cap_l <= gidx) & (gidx < (s + 1) * cap_l)).all()
        slots, local = dev._slot_of_global(gidx)
        for r in range(len(gidx)):
            assert int(slots[r]) % dp == s
            assert batch["action"][rows][r] == \
                dev.slots[int(slots[r])].action[int(local[r])]


def test_ring_contents_match_stream_dp1():
    mesh = _mesh(1)
    cfg = ReplayConfig(capacity=64, batch_size=8)
    dev = DeviceFrameReplay(cfg, mesh, (4, 4), stack=2, seed=0)
    frames = []
    for i in range(40):
        f = np.full((4, 4), i, np.uint8)
        frames.append(f)
        dev.add(f, 0, 0.0, done=(i % 10 == 9))
    dev.flush()
    ring = np.asarray(dev.ring).reshape(-1, 4, 4)
    for i, f in enumerate(frames):
        np.testing.assert_array_equal(ring[i], f)


def test_ring_wraparound_overwrites():
    mesh = _mesh(1)
    cfg = ReplayConfig(capacity=16, batch_size=4)
    dev = DeviceFrameReplay(cfg, mesh, (4, 4), stack=2, seed=0)
    for i in range(24):  # 1.5 × capacity
        dev.add(np.full((4, 4), i % 256, np.uint8), 0, 0.0,
                done=(i % 6 == 5))
    dev.flush()
    ring = np.asarray(dev.ring).reshape(-1, 4, 4)
    # slots 0..7 hold frames 16..23; slots 8..15 still hold 8..15
    for slot in range(8):
        np.testing.assert_array_equal(ring[slot], np.full((4, 4), 16 + slot))
    for slot in range(8, 16):
        np.testing.assert_array_equal(ring[slot], np.full((4, 4), slot))


def test_sharded_episode_routing():
    mesh = _mesh(4)
    cfg = ReplayConfig(capacity=256, batch_size=8)
    dev = DeviceFrameReplay(cfg, mesh, (4, 4), stack=2, seed=0)
    _play_stream(dev, None, 200, episode_len=7, frame_shape=(4, 4))
    # episodes round-robin across 4 shards: all shards received data
    filled = [0] * 4
    for g in range(dev.num_slots):
        filled[g % 4] += len(dev.slots[g])
    assert all(f > 0 for f in filled)
    assert len(dev) == 200


def test_ready_waits_for_all_shards():
    """Regression: aggregate fill can pass learn_start while some shards are
    still empty (episodes route whole to shards); ready() must gate until
    every shard can sample, or the first grad step crashes."""
    mesh = _mesh(4)
    cfg = ReplayConfig(capacity=2048, batch_size=8)
    dev = DeviceFrameReplay(cfg, mesh, (4, 4), stack=4, seed=0)
    # one long first episode: 300 steps, no boundary → all in shard 0
    for i in range(300):
        dev.add(np.zeros((4, 4), np.uint8), 0, 0.0, done=False)
    assert len(dev) == 300
    assert not dev.ready(200)  # would crash sample() without the gate
    # finish episode; play 3 more short episodes to reach the other shards
    dev.add(np.zeros((4, 4), np.uint8), 0, 0.0, done=True)
    for _ in range(3):
        for i in range(20):
            dev.add(np.zeros((4, 4), np.uint8), 0, 0.0, done=(i == 19))
    assert dev.ready(200)
    dev.sample(8)  # must not raise


def test_per_over_device_ring():
    mesh = _mesh(2)
    cfg = ReplayConfig(capacity=256, batch_size=16, prioritized=True,
                       priority_alpha=1.0)
    dev = DeviceFrameReplay(cfg, mesh, (4, 4), stack=2, seed=0)
    _play_stream(dev, None, 200, episode_len=11, frame_shape=(4, 4))
    batch = dev.sample(16)
    sampled_at = batch.pop("_sampled_at")
    assert len(sampled_at) == dev.num_slots
    assert batch["weight"].max() == pytest.approx(1.0)
    # priorities route back to the owning slot tree
    dev.update_priorities(batch["index"], np.full(16, 50.0),
                          sampled_at=sampled_at)
    seen = np.zeros(dev.num_slots, bool)
    for g in batch["index"].astype(np.int64):
        slot, local = dev._slot_of_global(np.asarray([g]))
        p = dev.trees[int(slot[0])].get(local)[0]
        assert p == pytest.approx(50.0 + cfg.priority_eps, rel=1e-6)
        seen[int(slot[0])] = True
    assert seen.all()


def test_multi_stream_subrings_no_interleave():
    """More streams than shards: each stream writes its own sub-ring, so
    concurrent actor chunks never interleave within a metadata ring."""
    mesh = _mesh(2)
    cfg = ReplayConfig(capacity=512, batch_size=8)
    dev = DeviceFrameReplay(cfg, mesh, (4, 4), stack=2, seed=0,
                            num_streams=4)
    assert dev.num_slots == 4 and dev.subs_per_shard == 2
    # interleave chunks from 4 streams, each stream's frames tagged by value
    for rnd in range(6):
        for stream in range(4):
            n = 10
            dev.add_batch({
                "frame": np.full((n, 4, 4), 10 * stream + rnd, np.uint8),
                "action": np.full(n, stream, np.int32),
                "reward": np.zeros(n, np.float32),
                "done": np.asarray([i == n - 1 for i in range(n)]),
            }, stream=stream)
    dev.flush()
    ring = np.asarray(dev.ring).reshape(-1, 4, 4)
    # every slot's metadata holds exactly one stream's actions, and its ring
    # region holds only that stream's frame tags
    for g in range(4):
        meta = dev.slots[g]
        n = len(meta)
        assert n == 60  # single writer, contiguous
        streams = np.unique(meta.action[:n])
        assert len(streams) == 1
        shard, base = dev._slot_base(g)
        region = ring[shard * dev.cap_local + base:
                      shard * dev.cap_local + base + n]
        assert set(np.unique(region)) <= {10 * streams[0] + r
                                          for r in range(6)}


def test_single_stream_reaches_all_shards():
    """Fewer streams than shards: one stream cycles its slots per episode,
    so warm-up fills every shard instead of deadlocking ready()."""
    mesh = _mesh(4)
    cfg = ReplayConfig(capacity=1024, batch_size=8)
    dev = DeviceFrameReplay(cfg, mesh, (4, 4), stack=2, seed=0,
                            num_streams=1)
    for ep in range(8):
        for t in range(30):
            dev.add(np.zeros((4, 4), np.uint8), 0, 0.0, done=(t == 29))
    assert dev.ready(100)
    dev.sample(8)  # draws 2 per shard without raising


@pytest.mark.parametrize("prioritized", [False, True],
                         ids=["uniform", "per"])
def test_train_loop_with_device_ring_fake_atari(prioritized):
    """End-to-end: single-process train loop on FakeAtari with the device
    ring (uniform or PER) over two shards, 26 grad steps, runs and
    produces finite losses."""
    from distributed_deep_q_tpu.config import pong_config
    from distributed_deep_q_tpu.train import train_single_process

    cfg = pong_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 2
    cfg.env.id = "fake"
    cfg.env.kind = "fake_atari"
    cfg.env.frame_shape = (36, 36)
    cfg.net.frame_shape = (36, 36)
    cfg.net.compute_dtype = "float32"
    cfg.replay = ReplayConfig(
        capacity=2048, batch_size=16, learn_start=200, n_step=2,
        prioritized=prioritized, write_chunk=16)
    cfg.train.total_steps = 400
    cfg.train.train_every = 8
    cfg.train.target_update_period = 10
    summary = train_single_process(cfg, log_every=10)
    assert np.isfinite(summary["loss"])
    assert summary["solver"].step == pytest.approx(25, abs=1)
