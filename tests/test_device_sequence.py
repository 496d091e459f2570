"""Device-resident sequence replay tests (replay/device_sequence.py).

Equivalence bar: pixels composed on device from the unstacked frame
streams must match the host ``SequenceReplay``'s stored stacked
observations byte-for-byte — including episode-start stack padding and
zero tail padding — on the same emission stream; the recurrent ring step
must train end-to-end through it.
"""

import numpy as np
import pytest

import jax

from distributed_deep_q_tpu.config import (
    Config, EnvConfig, MeshConfig, NetConfig, ReplayConfig, TrainConfig)
from distributed_deep_q_tpu.parallel.mesh import make_mesh
from distributed_deep_q_tpu.replay.device_sequence import (
    DeviceSequenceReplay, compose_sequence_rows, stream_from_stacked_obs)
from distributed_deep_q_tpu.replay.sequence import (
    SequenceBuilder, SequenceReplay)


def _pixel_stream(n_steps, seq_len=8, burn_in=4, stack=3, hw=(6, 6),
                  episode_len=11, seed=0):
    """Emit sequences from a synthetic pixel episode stream through the
    REAL SequenceBuilder + FrameStacker (exact actor-side semantics)."""
    from distributed_deep_q_tpu.actors.game import FrameStacker

    rng = np.random.default_rng(seed)
    obs_shape = hw + (stack,)
    builder = SequenceBuilder(seq_len, burn_in, obs_shape, np.uint8,
                              lstm_size=4)
    stacker = FrameStacker(hw, stack)
    seqs = []
    obs = stacker.reset(rng.integers(0, 255, hw, dtype=np.uint8))
    t_in_ep = 0
    for t in range(n_steps):
        carry = (np.full(4, t, np.float32), np.full(4, -t, np.float32))
        t_in_ep += 1
        done = t_in_ep >= episode_len
        frame = rng.integers(0, 255, hw, dtype=np.uint8)
        next_obs = stacker.push(frame)
        seqs.extend(builder.on_step(obs, t % 4, float(t % 7) - 3.0, done,
                                    carry, next_obs))
        obs = next_obs
        if done:
            t_in_ep = 0
            builder.reset()
            obs = stacker.reset(rng.integers(0, 255, hw, dtype=np.uint8))
    return seqs


def test_stream_roundtrip_reconstructs_stacked_obs():
    """stream_from_stacked_obs → compose_sequence_rows is the identity on
    host-stored observations (per sequence, off-mesh math)."""
    import jax.numpy as jnp

    seq_len, burn_in, stack = 8, 4, 3
    seqs = _pixel_stream(60, seq_len, burn_in, stack)
    assert len(seqs) >= 8
    # include an episode-start window (stack padding) and a short tail
    for s in seqs:
        n_valid = int(s["mask"].sum())
        stream = stream_from_stacked_obs(s["obs"], n_valid, stack)
        W = (stack - 1) + (seq_len + 1)
        assert stream.shape == (W, 36)
        rows = compose_sequence_rows(
            jnp.asarray(stream), jnp.asarray([0], jnp.int32),
            jnp.asarray([n_valid], jnp.int32), seq_len, stack)
        got = np.moveaxis(
            np.asarray(rows)[0].reshape(seq_len + 1, stack, 6, 6), 1, -1)
        np.testing.assert_array_equal(got, s["obs"])


def test_device_sequence_sample_matches_host_store():
    """Same emission stream into DeviceSequenceReplay and SequenceReplay:
    device-composed pixel batches equal the host store's rows byte-exactly
    (metadata equality included)."""
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    seq_len, burn_in, stack = 8, 4, 3
    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=2))
    dev = DeviceSequenceReplay(64, seq_len, (6, 6, stack), mesh,
                               lstm_size=4, seed=0, write_chunk=2)
    host = SequenceReplay(64, seq_len, (6, 6, stack), np.uint8,
                          lstm_size=4, seed=0)
    seqs = _pixel_stream(150, seq_len, burn_in, stack)
    host_slot_of = {}  # device global slot -> host slot
    for s in seqs:
        g = dev.add_sequence(s)
        host_slot_of[g] = host.add_sequence(s)
    dev.flush()

    batch = dev.sample(16)
    hidx = np.asarray([host_slot_of[g] for g in batch["index"]])
    for k in ("action", "reward", "discount", "mask", "init_c", "init_h"):
        np.testing.assert_array_equal(batch[k], getattr(host, k)[hidx],
                                      err_msg=k)

    # compose pixels through the PRODUCTION path: per-sequence window DMA
    # (interpret on the CPU mesh) + static-slice stacking
    from distributed_deep_q_tpu.ops.ring_gather import gather_windows
    from distributed_deep_q_tpu.replay.device_sequence import (
        compose_sequence_block)

    S = P("dp")
    per = 16 // dev.num_shards
    W, rowb, rowp = dev.W, dev.rowb, dev.rowp

    def fn(ring, sl, msk):
        win = gather_windows(sl * W, ring, n=per, w=W, rowb=rowb,
                             interpret=True)
        return compose_sequence_block(win.reshape(per, W, rowp), msk,
                                      seq_len, stack, dev._row_len)

    rows = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(S, S, S), out_specs=S,
        check_vma=False))(
        dev.ring, jnp.asarray(batch["seq_local"]),
        jnp.asarray(batch["mask"]))
    got = np.moveaxis(
        np.asarray(rows).reshape(16, seq_len + 1, stack, 6, 6), 2, -1)
    np.testing.assert_array_equal(got, host.obs[hidx])


def test_device_sequence_storage_is_stack_times_smaller():
    seq_len, stack = 80, 4
    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=2))
    dev = DeviceSequenceReplay(16, seq_len, (84, 84, stack), mesh,
                               lstm_size=8)
    host_rows_per_seq = (seq_len + 1) * stack       # stacked store
    dev_rows_per_seq = dev.W                        # unstacked stream
    assert dev_rows_per_seq == (stack - 1) + (seq_len + 1)
    assert host_rows_per_seq / dev_rows_per_seq > 3.8


def test_recurrent_ring_step_end_to_end():
    """Full R2D2 loop with the device sequence ring on the CPU mesh:
    finite losses, priorities updated, step count advances."""
    from distributed_deep_q_tpu.train import train_recurrent

    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 2
    cfg.env = EnvConfig(id="signal", kind="signal_atari",
                        frame_shape=(36, 36), stack=4, reward_clip=0.0)
    cfg.net = NetConfig(kind="r2d2", num_actions=4, frame_shape=(36, 36),
                        stack=4, lstm_size=16, compute_dtype="float32")
    cfg.replay = ReplayConfig(capacity=4096, batch_size=8, learn_start=256,
                              sequence_length=16, burn_in=4,
                              prioritized=True, device_resident=True)
    cfg.train = TrainConfig(lr=1e-3, total_steps=500, train_every=16,
                            target_update_period=10, seed=0,
                            eval_episodes=1)
    summary = train_recurrent(cfg, log_every=10)
    assert np.isfinite(summary["loss"])
    assert summary["solver"].step >= 10


def test_recurrent_fused_chained_end_to_end():
    """The round-5 fused sequence path (device_per=true): sampling,
    metadata, pixels, and per-sequence priorities all on device, chain
    grad steps per dispatch — finite losses, exact step total, priorities
    moved off the fresh seed."""
    from distributed_deep_q_tpu.train import train_recurrent

    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 2
    cfg.env = EnvConfig(id="signal", kind="signal_atari",
                        frame_shape=(36, 36), stack=4, reward_clip=0.0)
    cfg.net = NetConfig(kind="r2d2", num_actions=4, frame_shape=(36, 36),
                        stack=4, lstm_size=16, compute_dtype="float32")
    cfg.replay = ReplayConfig(capacity=4096, batch_size=8, learn_start=256,
                              sequence_length=16, burn_in=4,
                              prioritized=True, device_resident=True,
                              device_per=True, fused_chain=3)
    cfg.train = TrainConfig(lr=1e-3, total_steps=500, train_every=16,
                            target_update_period=10, seed=0,
                            eval_episodes=1)
    summary = train_recurrent(cfg, log_every=10)
    assert np.isfinite(summary["loss"])
    # exact step total: the FusedStepStream tail clamp must neither skip
    # nor overrun (learn starts once ready; every 16th env step trains)
    assert 10 <= summary["solver"].step <= 500 // 16 + 1
    replay = summary["replay"]
    prio = np.asarray(replay.dmeta["prio"])
    seeded = prio[prio > 0]
    assert len(seeded) > 0, "no sequence priorities were seeded"
    assert (~np.isclose(seeded, float(np.asarray(replay.dmaxp))
                        ** replay.alpha)).any(), (
        "fused sequence steps never moved a priority off the fresh seed")


@pytest.mark.slow
def test_distributed_recurrent_device_ring_end_to_end():
    """Distributed R2D2 over RPC with the device sequence ring: recurrent
    actors stream stacked sequences; the server stores unstacked streams
    in HBM; the learner trains from the ring under the replay lock."""
    from distributed_deep_q_tpu.actors.supervisor import train_distributed
    from distributed_deep_q_tpu.config import r2d2_config

    cfg = r2d2_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 2
    cfg.env = EnvConfig(id="fake", kind="fake_atari", frame_shape=(36, 36),
                        stack=4, reward_clip=1.0)
    cfg.net.frame_shape = (36, 36)
    cfg.net.lstm_size = 16
    cfg.net.compute_dtype = "float32"
    cfg.net.num_actions = 4
    cfg.replay = ReplayConfig(capacity=8192, batch_size=8, learn_start=512,
                              sequence_length=16, burn_in=4,
                              prioritized=True, device_resident=True)
    cfg.train.total_steps = 30
    cfg.train.target_update_period = 10
    cfg.train.eval_episodes = 1
    cfg.actors.num_actors = 2
    cfg.actors.send_batch = 24
    cfg.actors.param_sync_period = 20
    summary = train_distributed(cfg, log_every=10)
    assert summary["solver"].step == 30
    assert np.isfinite(summary["loss"])
