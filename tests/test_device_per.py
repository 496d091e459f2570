"""Device-resident PER tests (replay/device_per.py).

Equivalence bars: the device twins (validity mask, stack/n-step
composition) must match the host ``FrameStackReplay`` implementations
byte-for-byte on the same transition stream; inverse-CDF sampling must be
proportional to priorities; the fused step must run and learn end-to-end.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_deep_q_tpu.config import (
    Config, EnvConfig, MeshConfig, NetConfig, ReplayConfig, TrainConfig)
from distributed_deep_q_tpu.parallel.mesh import make_mesh
from distributed_deep_q_tpu.replay.device_per import (
    DevicePERFrameReplay, build_cdf, draw_from_cdf, stack_rows_to_obs,
    valid_mask)
from distributed_deep_q_tpu.replay.replay_memory import FrameStackReplay


def _stream(replay, n_steps, episode_len=13, seed=0, frame_shape=(8, 8),
            shadow=None):
    rng = np.random.default_rng(seed)
    t = 0
    for i in range(n_steps):
        frame = rng.integers(0, 255, frame_shape, dtype=np.uint8)
        a, r = int(rng.integers(0, 4)), float(rng.standard_normal())
        t += 1
        done = t % episode_len == 0
        # sprinkle truncation-only boundaries to exercise the trunc mask
        trunc = (not done) and (t % 29 == 0)
        replay.add(frame, a, r, done, boundary=done or trunc)
        if shadow is not None:
            shadow.add(frame, a, r, done, boundary=done or trunc)
        if done or trunc:
            t = 0


@pytest.mark.parametrize("n_fill", [60, 300])  # partial fill and wrapped
def test_valid_mask_matches_host_invalid(n_fill):
    cap, stack, n_step = 128, 4, 3
    host = FrameStackReplay(cap, (8, 8), stack, n_step, 0.99, seed=0)
    _stream(host, n_fill)
    idx = np.arange(min(len(host), cap))
    host_bad = host._invalid(idx)
    dev_valid = np.asarray(valid_mask(
        jnp.asarray(host.done, jnp.uint8), jnp.asarray(host.boundary,
                                                       jnp.uint8),
        jnp.asarray([host._cursor], jnp.int32),
        jnp.asarray([len(host)], jnp.int32), cap, stack, n_step))
    np.testing.assert_array_equal(dev_valid[idx], ~host_bad)


def test_compose_matches_host_gather():
    """Device composition == host FrameStackReplay.gather, byte-exact on
    pixels, tight on n-step float math — via the PRODUCTION primitives:
    ``build_meta_pack`` row-lanes for meta/validity and the Pallas window
    DMA (``ops/ring_gather.py``) for pixels."""
    from distributed_deep_q_tpu.ops.ring_gather import gather_windows
    from distributed_deep_q_tpu.replay.device_per import build_meta_pack

    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=1))
    cfg = ReplayConfig(capacity=256, batch_size=32, n_step=3,
                       prioritized=True, device_per=True, write_chunk=16)
    stack, n_step = 4, 3
    dev = DevicePERFrameReplay(cfg, mesh, (8, 8), stack=stack, gamma=0.99,
                               seed=0, write_chunk=16)
    host = FrameStackReplay(256, (8, 8), stack, n_step, 0.99, seed=0)
    _stream(dev, 200, shadow=host)
    dev.flush()

    ok = ~host._invalid(np.arange(len(host)))
    idx = np.flatnonzero(ok)[:32]
    ref = host.gather(idx)

    # meta + validity bit-planes off the per-row pack (dp=1: sub == 0,
    # real coords == slot-local coords)
    d = dev.dstate
    pack = np.asarray(build_meta_pack(
        d.action, d.reward, d.done, d.boundary, dev.slot_cap, stack,
        n_step, 0.99))
    mp = pack[idx]
    mp2 = pack[(idx + n_step) % dev.slot_cap]
    np.testing.assert_array_equal(mp[:, 0].astype(np.int32), ref["action"])
    np.testing.assert_allclose(mp[:, 1], ref["reward"], atol=1e-5)
    np.testing.assert_array_equal(mp[:, 2], ref["discount"])

    # pixels: one contiguous ghost-row window per sample, via the DMA
    # kernel (interpret mode on the CPU mesh), validity-masked
    window = stack + n_step
    ws = (idx - (stack - 1)) % dev.slot_cap
    win = np.asarray(gather_windows(
        jnp.asarray(ws, jnp.int32), d.frames, n=len(idx), w=window,
        rowb=dev.rowb, interpret=True)).view(np.uint8)
    win = win.reshape(len(idx), window, dev.rowb)[:, :, :64]
    ovalid = mp[:, 3:3 + stack].astype(np.uint8)
    nvalid = mp2[:, 3:3 + stack].astype(np.uint8)
    obs = win[:, :stack] * ovalid[..., None]
    nobs = win[:, n_step:n_step + stack] * nvalid[..., None]
    np.testing.assert_array_equal(
        np.asarray(stack_rows_to_obs(jnp.asarray(obs), (8, 8))),
        ref["obs"])
    np.testing.assert_array_equal(
        np.asarray(stack_rows_to_obs(jnp.asarray(nobs), (8, 8))),
        ref["next_obs"])


def test_fused_draw_is_shard_local_dp8():
    """The REAL sharded path at dp=8: the learner's own sample program
    (packed draw + window DMA under ``shard_map``), each device's rows
    held against a host shadow of ITS OWN slot — metadata and n-step math
    from that slot's rows, pixels from that shard's block of the ring,
    wrapped sub-rings and ghost rows included. Catches shard mis-ordering
    or layout drift that a dp=1 comparison cannot."""
    from distributed_deep_q_tpu.solver import Solver

    dp, per, stack, n_step, chain = 8, 4, 4, 2, 2
    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = dp
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36))
    cfg.replay = ReplayConfig(capacity=dp * 128, batch_size=dp * per,
                              n_step=n_step, prioritized=True,
                              write_chunk=16)
    solver = Solver(cfg)
    dev = DevicePERFrameReplay(cfg.replay, solver.mesh, (36, 36),
                               stack=stack, gamma=0.99, seed=0,
                               write_chunk=16)
    assert dev.subs_per_shard == 1      # one stream: slot g IS shard g
    shadows = [FrameStackReplay(dev.slot_cap, (36, 36), stack, n_step, 0.99,
                                seed=0) for _ in range(dp)]
    rng = np.random.default_rng(0)
    for i in range(1300):       # episodes round-robin the shards; they wrap
        frame = rng.integers(0, 255, (36, 36), dtype=np.uint8)
        a, r, done = int(rng.integers(4)), float(rng.standard_normal()), \
            i % 9 == 8
        shard, local = divmod(dev.add(frame, a, r, done), dev.cap_local)
        assert shadows[shard].add(frame, a, r, done) == local
    dev.flush()
    assert all(len(m) == dev.slot_cap for m in shadows)

    sample, _ = solver.learner.device_per_programs(
        solver.device_per_spec(dev), chain)
    cursors, sizes = dev.device_inputs()
    keys = np.random.default_rng(5).integers(0, 2**32, (dp, chain, 2),
                                             np.uint32)
    d = dev.dstate
    metas, win, idx = sample(keys, d.frames, d.action, d.reward, d.done,
                             d.boundary, d.prio, cursors, sizes,
                             np.full(chain, 0.4, np.float32))
    metas = {k: np.asarray(v) for k, v in metas.items()}
    idx = np.asarray(idx)                           # [chain, B] shard-local
    win = np.asarray(win).reshape(chain, dp * per, stack + n_step, dev.rowp)
    win = win.view(np.uint8)[..., :36 * 36]         # packed words -> pixels
    assert (idx < dev.cap_local).all()
    for c in range(chain):
        for s in range(dp):
            rows = slice(s * per, (s + 1) * per)
            ref = shadows[s].gather(idx[c, rows])
            np.testing.assert_array_equal(metas["action"][c, rows],
                                          ref["action"])
            np.testing.assert_allclose(metas["reward"][c, rows],
                                       ref["reward"], atol=1e-5)
            np.testing.assert_allclose(metas["discount"][c, rows],
                                       ref["discount"], rtol=1e-6)
            w = win[c, rows]
            obs = w[:, :stack] * metas["ovalid"][c, rows][..., None]
            nobs = w[:, n_step:] * metas["nvalid"][c, rows][..., None]
            np.testing.assert_array_equal(
                np.asarray(stack_rows_to_obs(jnp.asarray(obs), (36, 36))),
                ref["obs"])
            np.testing.assert_array_equal(
                np.asarray(stack_rows_to_obs(jnp.asarray(nobs), (36, 36))),
                ref["next_obs"])


def test_packed_draw_matches_reference_draw():
    """The production packed sampler (``fused_sample_draw_packed``: meta
    from ``build_meta_pack`` row lanes) must agree with the reference
    gather-based sampler (``fused_sample_draw_many``) on identical state,
    keys, and βs — meta, IS weights, validity planes, and scatter
    indices. This is the invariant that lets the two implementations
    coexist without drifting."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from distributed_deep_q_tpu.replay.device_per import (
        build_meta_pack, fused_sample_draw_many, fused_sample_draw_packed,
        fused_sample_prep)

    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=2))
    cfg = ReplayConfig(capacity=512, batch_size=32, n_step=3,
                       prioritized=True, device_per=True, write_chunk=16)
    stack, n_step, gamma = 4, 3, 0.99
    dev = DevicePERFrameReplay(cfg, mesh, (8, 8), stack=stack, gamma=gamma,
                               seed=0, write_chunk=16, num_streams=2)
    rng = np.random.default_rng(3)
    for c in range(40):
        n = 8
        done = np.zeros(n, bool)
        done[-1] = c % 3 == 2
        dev.add_batch({
            "frame": rng.integers(0, 255, (n, 8, 8), dtype=np.uint8),
            "action": rng.integers(0, 4, n).astype(np.int32),
            "reward": rng.standard_normal(n).astype(np.float32),
            "done": done}, stream=c % 2)
    dev.flush()

    chain, per = 3, 16
    keys = rng.integers(0, 2**32, (2, chain, 2), dtype=np.uint32)
    betas = np.linspace(0.4, 0.6, chain).astype(np.float32)
    cursors, sizes = dev.device_inputs()
    L, Lp = dev.slot_cap, dev.slot_pad

    def both(keys, action, reward, done, boundary, prio, cur, siz, betas):
        rows = {"action": action, "reward": reward, "done": done,
                "boundary": boundary, "prio": prio}
        pm, cdf, mass, n_glob = fused_sample_prep(
            rows, cur, siz, L, stack, n_step)
        pack = build_meta_pack(action, reward, done, boundary, L, stack,
                               n_step, gamma)
        mp, ws, idx_p = fused_sample_draw_packed(
            keys[0], pack, pm, cdf, mass, n_glob, per, L, Lp, stack,
            n_step, betas, 2)
        mr, oflat, ovalid, nflat, nvalid, idx_r = fused_sample_draw_many(
            keys[0], rows, pm, cdf, mass, n_glob, per, L, stack, n_step,
            gamma, betas, 2)
        return (mp, ws, idx_p), (mr, ovalid, nvalid, idx_r)

    S = P("dp")
    SK = P(None, "dp")
    SK3 = P(None, "dp", None)
    d = dev.dstate
    (mp, ws, idx_p), (mr, ovalid, nvalid, idx_r) = shard_map(
        both, mesh=mesh,
        in_specs=(S, S, S, S, S, S, S, S, P()),
        out_specs=(({"action": SK, "reward": SK, "discount": SK,
                     "weight": SK, "ovalid": SK3, "nvalid": SK3}, SK, SK),
                   ({"action": SK, "reward": SK, "discount": SK,
                     "weight": SK}, SK3, SK3, SK)),
        check_vma=False)(
        keys, d.action, d.reward, d.done, d.boundary, d.prio,
        np.asarray(cursors), np.asarray(sizes), betas)

    np.testing.assert_array_equal(np.asarray(idx_p), np.asarray(idx_r))
    np.testing.assert_array_equal(np.asarray(mp["action"]),
                                  np.asarray(mr["action"]))
    np.testing.assert_allclose(np.asarray(mp["reward"]),
                               np.asarray(mr["reward"]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(mp["discount"]),
                                  np.asarray(mr["discount"]))
    np.testing.assert_allclose(np.asarray(mp["weight"]),
                               np.asarray(mr["weight"]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(mp["ovalid"]),
                                  np.asarray(ovalid).astype(np.uint8))
    np.testing.assert_array_equal(np.asarray(mp["nvalid"]),
                                  np.asarray(nvalid).astype(np.uint8))
    # window starts point where the reference's oldest obs row lives
    # (padded coords): ws == sub*slot_pad + oldest-local
    idx = np.asarray(idx_r)
    live = idx < dev.cap_local
    sub, local = idx // L, idx % L
    want_ws = sub * Lp + (local - (stack - 1)) % L
    np.testing.assert_array_equal(np.asarray(ws)[live], want_ws[live])


def test_draw_from_cdf_proportional():
    p = jnp.asarray([0.0, 1.0, 3.0, 0.0, 6.0], jnp.float32)
    cdf, mass = build_cdf(p)
    idx, prob = draw_from_cdf(jax.random.PRNGKey(0), cdf, p, mass, 20_000)
    counts = np.bincount(np.asarray(idx), minlength=5) / 20_000
    np.testing.assert_allclose(counts, [0, 0.1, 0.3, 0, 0.6], atol=0.02)
    assert float(mass) == 10.0
    # reported probabilities match the draw distribution
    np.testing.assert_allclose(np.asarray(prob),
                               np.asarray(p)[np.asarray(idx)] / 10.0)


def test_fresh_rows_get_max_priority():
    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=2))
    cfg = ReplayConfig(capacity=128, batch_size=8, n_step=1,
                       prioritized=True, device_per=True, priority_alpha=0.6,
                       write_chunk=8)
    dev = DevicePERFrameReplay(cfg, mesh, (4, 4), stack=2, seed=0,
                               write_chunk=8)
    _stream(dev, 50, frame_shape=(4, 4))
    dev.flush()
    prio = np.asarray(dev.dstate.prio)
    np.testing.assert_allclose(prio[prio > 0], 1.0)  # maxp=1 ⇒ 1^α
    assert (prio > 0).sum() == 50


def test_fused_step_end_to_end_smoke():
    """The full fused pipeline on the 8-device CPU mesh: train on
    SignalAtari with device_per, finite losses, priorities updated by the
    step itself (no host write-back path in the loop)."""
    from distributed_deep_q_tpu.train import train_single_process

    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 2
    cfg.env = EnvConfig(id="signal", kind="signal_atari",
                        frame_shape=(36, 36), stack=4, reward_clip=0.0)
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36), compute_dtype="float32")
    cfg.replay = ReplayConfig(capacity=2048, batch_size=16, learn_start=200,
                              n_step=2, prioritized=True, device_per=True,
                              write_chunk=16)
    cfg.train = TrainConfig(lr=1e-3, total_steps=400, train_every=8,
                            target_update_period=10, seed=0)
    summary = train_single_process(cfg, log_every=10)
    assert np.isfinite(summary["loss"])
    assert summary["solver"].step == pytest.approx(25, abs=1)


def test_fused_step_updates_priorities():
    """The fused step's scatter must move sampled rows' priorities off the
    fresh-row max-priority seed (and track the running max)."""
    from distributed_deep_q_tpu.solver import Solver

    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 2
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36))
    cfg.replay = ReplayConfig(capacity=512, batch_size=16, n_step=2,
                              prioritized=True, device_per=True,
                              write_chunk=16)
    solver = Solver(cfg)
    dev = DevicePERFrameReplay(cfg.replay, solver.mesh, (36, 36), stack=4,
                               gamma=0.99, seed=0, write_chunk=16)
    rng = np.random.default_rng(0)
    for i in range(300):
        dev.add(rng.integers(0, 255, (36, 36), dtype=np.uint8),
                int(rng.integers(4)), float(rng.standard_normal()),
                done=(i % 9 == 8))
    dev.flush()
    seed_prio = np.asarray(dev.dstate.prio)
    seeded = seed_prio[seed_prio > 0]
    assert np.allclose(seeded, seeded[0])  # all rows at the fresh seed
    for _ in range(4):
        solver.train_step_device_per(dev)
    jax.block_until_ready(solver.state.params)
    after = np.asarray(dev.dstate.prio)
    changed = (after > 0) & ~np.isclose(after, seeded[0])
    assert changed.sum() > 0, "no priority moved off the fresh-row seed"


@pytest.mark.slow
def test_device_per_pixel_path_learns():
    """Learning gate, fused-PER edition: same bar as the host-path pixel
    learning test — ≥2× the random policy on SignalAtari."""
    from distributed_deep_q_tpu.train import train_single_process

    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.env = EnvConfig(id="signal", kind="signal_atari",
                        frame_shape=(36, 36), stack=4, reward_clip=0.0)
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36), stack=4,
                        compute_dtype="float32")
    cfg.replay = ReplayConfig(capacity=8192, batch_size=32,
                              learn_start=500, n_step=1, prioritized=True,
                              device_per=True, write_chunk=64)
    cfg.train = TrainConfig(lr=1e-3, adam_eps=1e-8, gamma=0.99,
                            target_tau=0.01, double_dqn=True,
                            total_steps=4000, train_every=2,
                            eval_episodes=10, seed=0)
    cfg.actors.eps_decay_steps = 2000
    cfg.actors.eps_end = 0.05
    cfg.actors.eval_eps = 0.0
    summary = train_single_process(cfg, log_every=500)
    assert summary["eval_return"] >= 16.0, (
        f"device-PER pixel path failed to learn: "
        f"{summary['eval_return']:.1f} (random ≈ 8, perfect = 32)")


def test_reset_stream_seals_device_boundary():
    """Actor-restart seal must land in the DEVICE boundary ring (the fused
    sampler reads it there); a host-only seal would let windows straddle
    the dead writer's seam."""
    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=2))
    cfg = ReplayConfig(capacity=128, batch_size=8, n_step=1,
                       prioritized=True, device_per=True, write_chunk=8)
    dev = DevicePERFrameReplay(cfg, mesh, (4, 4), stack=2, seed=0,
                               write_chunk=8, num_streams=2)
    for i in range(20):  # mid-episode: no boundary yet
        dev.add_batch({"frame": np.zeros((1, 4, 4), np.uint8),
                       "action": np.zeros(1, np.int32),
                       "reward": np.zeros(1, np.float32),
                       "done": np.zeros(1, bool),
                       "boundary": np.zeros(1, bool)}, stream=0)
    # NOTE deliberately NO flush here: rows staged pre-seal must not
    # clobber the seal when a later flush drains them
    before = np.asarray(dev.dstate.boundary).sum()
    dev.reset_stream(0)
    dev.flush()  # no-op; must NOT erase the device seal
    after = np.asarray(dev.dstate.boundary)
    assert after.sum() == before + 1
    # the sealed row is the stream's last written row, on device
    slot = dev._slot_cycle[0][dev._stream_pos[0] % 1]
    m = dev.slots[slot]
    shard, base = dev._slot_base(slot)
    gidx = shard * dev.cap_local + base + (m._cursor - 1) % dev.slot_cap
    assert after[gidx] == 1
    assert m.boundary[(m._cursor - 1) % dev.slot_cap]  # host seal too


@pytest.mark.slow
def test_distributed_fused_per_end_to_end():
    """RPC actors streaming pixels into the fused device-PER replay while
    the learner runs the zero-readback step — the distributed flagship
    topology (config 3/4 with device_per)."""
    from distributed_deep_q_tpu.actors.supervisor import train_distributed
    from distributed_deep_q_tpu.config import pong_config

    cfg = pong_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 2
    cfg.env.id = "signal"
    cfg.env.kind = "signal_atari"
    cfg.env.frame_shape = (36, 36)
    cfg.net.frame_shape = (36, 36)
    cfg.net.compute_dtype = "float32"
    cfg.replay = ReplayConfig(capacity=4096, batch_size=16, learn_start=300,
                              n_step=2, prioritized=True, device_per=True,
                              write_chunk=16)
    cfg.train.total_steps = 60
    cfg.train.target_update_period = 10
    cfg.train.eval_episodes = 2
    cfg.actors.num_actors = 3   # 3 streams > 2 shards → sub-rings in play
    cfg.actors.send_batch = 20
    cfg.actors.param_sync_period = 25
    summary = train_distributed(cfg, log_every=20)
    assert summary["solver"].step == 60
    assert np.isfinite(summary["loss"])
    assert summary["env_steps"] >= 300


def _filled_dev_replay(solver, cfg, alpha_seed=0, n=300):
    dev = DevicePERFrameReplay(cfg.replay, solver.mesh, (36, 36), stack=4,
                               gamma=0.99, seed=alpha_seed, write_chunk=16)
    rng = np.random.default_rng(alpha_seed)
    for i in range(n):
        dev.add(rng.integers(0, 255, (36, 36), dtype=np.uint8),
                int(rng.integers(4)), float(rng.standard_normal()),
                done=(i % 9 == 8))
    dev.flush()
    return dev


@pytest.mark.parametrize("mu_dtype", ["float32", "bfloat16"])
def test_chained_fused_steps_match_sequential_alpha0(mu_dtype):
    """α=0 makes sampling independent of priorities, so a chain=3 chunk
    must reproduce THREE sequential single-step dispatches bit-for-bit
    (same keys/βs) — optimizer state, params, and priorities included.
    Both run the plane body (8 rows a shard), so the sequential side goes
    trees -> planes -> trees three times where the chunk goes once: the
    conversions at the chunk's boundary (``plane_to_param_trees``,
    ``plane_to_tree``) have to be exact, a bfloat16 first moment too."""
    from distributed_deep_q_tpu.solver import Solver

    def build():
        cfg = Config()
        cfg.mesh.backend = "cpu"
        cfg.mesh.dp = 2
        cfg.train.adam_mu_dtype = mu_dtype
        cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                            frame_shape=(36, 36))
        cfg.replay = ReplayConfig(capacity=512, batch_size=16, n_step=2,
                                  prioritized=True, priority_alpha=0.0,
                                  device_per=True, write_chunk=16,
                                  fused_chain=3)
        solver = Solver(cfg)
        return solver, _filled_dev_replay(solver, cfg)

    sa, da = build()
    sb, db = build()
    # pin identical key sequences: both solvers start at step 0 with the
    # same seed, so Philox counters line up; sequential issues 1+1+1,
    # chained issues 3 — same counter range, same keys
    for _ in range(3):
        sa.train_step_device_per(da)
    sb.train_steps_device_per(db, chain=3)
    jax.block_until_ready(sa.state.params)
    jax.block_until_ready(sb.state.params)
    for xa, xb in zip(jax.tree.leaves(sa.state), jax.tree.leaves(sb.state)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    np.testing.assert_array_equal(np.asarray(da.dstate.prio),
                                  np.asarray(db.dstate.prio))


def test_chained_fused_steps_alpha_positive_learns_and_scatters():
    """With real PER (α>0) a chained chunk must keep the step total: all
    chain steps apply (step counter advances by chain), priorities move
    off the fresh-row seed, and losses are finite."""
    from distributed_deep_q_tpu.solver import Solver

    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 2
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36))
    cfg.replay = ReplayConfig(capacity=512, batch_size=16, n_step=2,
                              prioritized=True, priority_alpha=0.6,
                              device_per=True, write_chunk=16)
    solver = Solver(cfg)
    dev = _filled_dev_replay(solver, cfg)
    seed_val = np.asarray(dev.dstate.prio).max()
    m = solver.train_steps_device_per(dev, chain=4)
    jax.block_until_ready(solver.state.params)
    assert solver.step == 4
    assert np.all(np.isfinite(np.asarray(m["loss"]))) and \
        np.asarray(m["loss"]).shape == (4,)
    after = np.asarray(dev.dstate.prio)
    assert ((after > 0) & ~np.isclose(after, seed_val)).sum() > 0
    # β annealed once per chained step, host-path ordering (advance first)
    assert dev._samples == 4


def test_fused_sample_zero_mass_shard_yields_zero_weights():
    """A shard with zero masked priority mass must contribute zero-weight
    rows and drop its priority scatter (OOB index) instead of composing
    garbage with extreme IS weights."""
    from distributed_deep_q_tpu.replay.device_per import (
        fused_sample_draw_many, fused_sample_prep)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=2))
    cap_local, slot_cap = 64, 64
    rows = {
        "action": jnp.zeros(2 * cap_local, jnp.int32),
        "reward": jnp.zeros(2 * cap_local, jnp.float32),
        "done": jnp.zeros(2 * cap_local, jnp.uint8),
        "boundary": jnp.zeros(2 * cap_local, jnp.uint8),
        # shard 0 has mass, shard 1 is all-zero (e.g. sealed away)
        "prio": jnp.concatenate([jnp.ones(cap_local, jnp.float32),
                                 jnp.zeros(cap_local, jnp.float32)]),
    }
    cursors = jnp.asarray([30, 0], jnp.int32)
    sizes = jnp.asarray([60, 0], jnp.int32)

    def fn(action, reward, done, boundary, prio, cur, siz):
        shard_rows = {"action": action, "reward": reward, "done": done,
                      "boundary": boundary, "prio": prio}
        pm, cdf, mass, n_glob = fused_sample_prep(
            shard_rows, cur, siz, slot_cap, 2, 1)
        batch, *_, idx = fused_sample_draw_many(
            jnp.asarray([[0, 1]], jnp.uint32), shard_rows, pm, cdf, mass,
            n_glob, 8, slot_cap, 2, 1, 0.99, jnp.full(1, 0.4, jnp.float32),
            2)
        return batch["weight"][0], idx[0]

    S = P("dp")
    w, idx = shard_map(
        fn, mesh=mesh, in_specs=(S,) * 7, out_specs=(S, S),
        check_vma=False)(
        rows["action"], rows["reward"], rows["done"],
        rows["boundary"], rows["prio"], cursors, sizes)
    w, idx = np.asarray(w), np.asarray(idx)
    assert np.all(np.isfinite(w))
    assert np.all(w[8:] == 0.0), "empty shard's weights must be zero"
    assert np.all(idx[8:] == cap_local), "empty shard's scatter must be OOB"
    # live shard normalizes against its OWN max (==1.0 here, uniform p):
    # the dead shard's floored probabilities must not enter the w_max pmax
    np.testing.assert_allclose(w[:8], 1.0, atol=1e-6)


def test_fused_key_sequence_continues_across_resume():
    """ADVICE r3: a resumed solver must NOT replay the sampling key
    sequence from the start — keys derive from the train-step counter."""
    from distributed_deep_q_tpu.solver import Solver

    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 2
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36))
    cfg.replay = ReplayConfig(capacity=512, batch_size=16, n_step=2,
                              prioritized=True, device_per=True,
                              write_chunk=16)
    a = Solver(cfg)
    k1 = a._next_sample_keys(2, 2)
    k2 = a._next_sample_keys(2, 2)
    assert not np.array_equal(k1, k2)
    # fresh solver "resumed" at step 2 (counter base from state.step)
    b = Solver(cfg)
    b.state = b.state.replace(step=jnp.asarray(2, jnp.int32))
    kb = b._next_sample_keys(2, 2)
    np.testing.assert_array_equal(kb, k2)
    assert not np.array_equal(kb, k1)


def test_alpha_zero_fused_sampler_is_uniform():
    """α=0 (the pong preset's fused-uniform mode): constant priorities ⇒
    exactly-uniform draws and IS weights exactly 1."""
    from distributed_deep_q_tpu.solver import Solver

    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 2
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36))
    cfg.replay = ReplayConfig(capacity=512, batch_size=16, n_step=2,
                              prioritized=True, priority_alpha=0.0,
                              device_per=True, write_chunk=16)
    solver = Solver(cfg)
    dev = DevicePERFrameReplay(cfg.replay, solver.mesh, (36, 36), stack=4,
                               gamma=0.99, seed=0, write_chunk=16)
    rng = np.random.default_rng(0)
    for i in range(300):
        dev.add(rng.integers(0, 255, (36, 36), dtype=np.uint8),
                int(rng.integers(4)), float(rng.standard_normal()),
                done=(i % 9 == 8))
    dev.flush()
    for _ in range(3):
        solver.train_step_device_per(dev)
    jax.block_until_ready(solver.state.params)
    # priorities stay flat after TD scatters (x^0 == 1) → still uniform
    prio = np.asarray(dev.dstate.prio)
    np.testing.assert_allclose(prio[prio > 0], 1.0)
    # pull one sample batch through the compiled program: weights == 1
    cache_key = list(solver.learner._device_per_steps)[0]
    sample, _ = solver.learner._device_per_steps[cache_key]
    chain = cache_key[1]
    cursors, sizes = dev.device_inputs()
    keys = np.random.default_rng(5).integers(0, 2**32, (2, chain, 2),
                                             np.uint32)
    rows = dev.dstate
    metas, _win, idx = sample(keys, rows.frames, rows.action, rows.reward,
                              rows.done, rows.boundary, rows.prio, cursors,
                              sizes, np.full(chain, 0.4, np.float32))
    w = np.asarray(metas["weight"][0])  # first chunk row
    # per shard the draw is exactly uniform → constant weight; across
    # shards the stratified-IS math compensates unequal sampleable mass
    # (each shard contributes B/D draws regardless), so weights sit within
    # a few percent of 1 and converge there as fills equalize
    per_shard = w.reshape(2, -1)
    for row in per_shard:
        np.testing.assert_allclose(row, row[0], atol=1e-6)
    np.testing.assert_allclose(w, 1.0, atol=0.05)
