"""Device ring host-side tests (replay/device_ring.py).

What the base class is: geometry, stream -> slot routing, staging and the
chunked flush into its HBM ring — the rows the ring holds are BYTE-EXACT
the stream's, on a 1-device mesh and sharded. The sample path of a pixel
device run is the fused ring's (tests/test_device_per.py holds its
composition to the host ``FrameStackReplay.gather``, dp=8 included); the
loop cases here run it end to end and see the unprioritized run refused.
"""

import numpy as np
import pytest

from distributed_deep_q_tpu.config import ReplayConfig
from distributed_deep_q_tpu.replay.device_ring import DeviceFrameReplay


def _mesh(n):
    from distributed_deep_q_tpu.config import MeshConfig
    from distributed_deep_q_tpu.parallel.mesh import make_mesh
    return make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=n))


def _play_stream(replay, n_steps, seed=0, episode_len=13,
                 frame_shape=(8, 8)):
    """Feed a deterministic transition stream to the ring."""
    rng = np.random.default_rng(seed)
    t = 0
    for i in range(n_steps):
        frame = rng.integers(0, 255, frame_shape, dtype=np.uint8)
        a = int(rng.integers(0, 4))
        r = float(rng.standard_normal())
        t += 1
        done = t % episode_len == 0
        replay.add(frame, a, r, done, boundary=done)
        if done:
            t = 0


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
    _play_stream(dev, 200, episode_len=7, frame_shape=(4, 4))
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
    assert not dev.ready(200)  # a fused draw would find shards empty
    # finish episode; play 3 more short episodes to reach the other shards
    dev.add(np.zeros((4, 4), np.uint8), 0, 0.0, done=True)
    for _ in range(3):
        for i in range(20):
            dev.add(np.zeros((4, 4), np.uint8), 0, 0.0, done=(i == 19))
    assert dev.ready(200)


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


def _pixel_loop_cfg(**replay):
    from distributed_deep_q_tpu.config import pong_config

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
        write_chunk=16, **replay)
    cfg.train.total_steps = 400
    cfg.train.train_every = 8
    cfg.train.target_update_period = 10
    return cfg


@pytest.mark.parametrize("alpha", [0.0, 0.6], ids=["uniform", "per"])
def test_train_loop_with_device_ring_fake_atari(alpha):
    """End-to-end: single-process train loop on FakeAtari with the device
    ring (uniform = the fused sampler at alpha 0, or PER) over two shards,
    26 grad steps, runs and produces finite losses."""
    from distributed_deep_q_tpu.train import train_single_process

    # ``replay.device_per`` stays at its default, false: it does not choose
    # the transition ring
    cfg = _pixel_loop_cfg(prioritized=True, priority_alpha=alpha)
    summary = train_single_process(cfg, log_every=10)
    assert np.isfinite(summary["loss"])
    assert summary["solver"].step == pytest.approx(25, abs=1)
    assert summary["solver"].learner._device_per_steps, "no fused step ran"


def _choose_ring(cfg):
    from distributed_deep_q_tpu.parallel.mesh import make_mesh
    from distributed_deep_q_tpu.replay.device_per import pixel_device_ring

    pixel_device_ring(cfg.replay, make_mesh(cfg.mesh), (36, 36), 4, 0.99,
                      seed=0)


def _single_process(cfg):
    from distributed_deep_q_tpu.train import train_single_process

    train_single_process(cfg)


def _distributed(cfg):
    from distributed_deep_q_tpu.actors.supervisor import train_distributed

    train_distributed(cfg)


@pytest.mark.parametrize("run", [_choose_ring, _single_process, _distributed],
                         ids=["chooser", "single_process", "distributed"])
def test_unprioritized_pixel_device_run_is_refused(run):
    """A pixel run with ``device_resident=true`` has one ring to build, the
    fused one, whose sampler draws by priority: ``prioritized=false`` is
    refused where the ring is chosen, before either loop starts anything,
    and the message names both ways out. The config is not rewritten."""
    cfg = _pixel_loop_cfg(prioritized=False)
    with pytest.raises(ValueError) as e:
        run(cfg)
    assert "replay.prioritized=true replay.priority_alpha=0" in str(e.value)
    assert "replay.device_resident=false" in str(e.value)
    assert cfg.replay.prioritized is False
