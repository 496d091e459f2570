"""Anakin mode (ISSUE 11): the mode-not-a-fork pins.

Two claims carry the fully-jitted act+learn loop:

1. It is the SAME system. Driving ``act_tick`` from the host one env at
   a time, feeding the rows through the public ``add_batch(stream=gid)``
   write path, and training with the distributed fused chain
   (``train_steps_device_per``) must produce the SAME ring contents and
   the SAME parameters as the single fused superstep — bitwise. This
   pins the env→slot identity (gid = sub·D + shard), the device cursor
   math against ``_apply_write``'s staging, the frozen-θ-per-superstep
   acting schedule, and the plane-carry train body, all at once.

2. It trains. A short signal_atari run must move ε-greedy reward above
   chance with finite losses, and ``sync_solver`` must hand a usable
   state back to the solver.

Scale notes: 16 envs on the 8-device test mesh → 2 sub-rings per shard,
so the non-trivial plane-position↔stream mapping is exercised (not the
identity); 3 supersteps × 8 ticks against slot_cap 16 wraps every
sub-ring and overwrites its oldest rows, covering ghost-row rewrites.
"""

import functools

import numpy as np
import pytest

import jax

from distributed_deep_q_tpu.config import (
    ActorConfig, Config, EnvConfig, MeshConfig, NetConfig, ReplayConfig,
    TrainConfig)


def _anakin_config(n_envs=16, ticks=8, capacity=256):
    return Config(
        env=EnvConfig(id="signal", kind="signal_atari",
                      frame_shape=(10, 10), stack=2),
        net=NetConfig(kind="mlp", num_actions=4, hidden=(32, 32),
                      frame_shape=(10, 10), stack=2),
        replay=ReplayConfig(capacity=capacity, batch_size=16,
                            fused_chain=2, n_step=1, learn_start=0,
                            device_resident=True, write_chunk=32),
        train=TrainConfig(optimizer="adam", seed=3, stack_forwards="on"),
        actors=ActorConfig(anakin_envs=n_envs, anakin_ticks=ticks),
        mesh=MeshConfig(backend="cpu", num_fake_devices=8),
    )


def test_anakin_matches_host_fused_loop():
    """Same seeds → same ring, same θ: one Anakin superstep vs host-driven
    act_tick + add_batch + train_steps_device_per, three rounds."""
    from distributed_deep_q_tpu.actors.supervisor import actor_epsilon
    from distributed_deep_q_tpu.parallel.anakin import AnakinRunner, act_tick
    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay
    from distributed_deep_q_tpu.solver import Solver

    cfg = _anakin_config()
    n, ticks, supersteps = cfg.actors.anakin_envs, cfg.actors.anakin_ticks, 3
    h, w = cfg.env.frame_shape
    stack = cfg.env.stack

    runner = AnakinRunner(cfg)
    assert runner.replay.slot_cap == 16  # wrap coverage depends on this
    for _ in range(supersteps):
        runner.superstep()
    runner.sync_solver()

    # -- host twin: same config, fresh solver/replay, public write path --
    solver = Solver(cfg, obs_dim=h * w * stack)
    replay = DevicePERFrameReplay(
        cfg.replay, solver.mesh, (h, w), stack, cfg.train.gamma,
        seed=cfg.train.seed, write_chunk=cfg.replay.write_chunk,
        num_streams=n)
    reset_fn, step_fn = runner._reset_fn, runner._step_fn
    tick = jax.jit(functools.partial(
        act_tick, solver.apply_fn, step_fn, (h, w)))
    base = jax.random.PRNGKey(cfg.train.seed)
    row_len = h * w
    envs = {}
    for g in range(n):  # one host acting state per global stream id
        st, frame = jax.jit(jax.vmap(reset_fn))(
            jax.random.fold_in(base, 1000 * (g + 1))[None])
        buf = np.zeros((1, stack, row_len), np.uint8)
        buf[0, -1] = np.asarray(frame).reshape(-1)
        envs[g] = {
            "st": st, "buf": jax.numpy.asarray(buf),
            "akeys": jax.random.fold_in(base, 7777 * (g + 1))[None],
            "eps": jax.numpy.asarray(
                [actor_epsilon(g, n, cfg.actors.eps_base,
                               cfg.actors.eps_alpha)], jax.numpy.float32),
        }
    for _ in range(supersteps):
        params = solver.state.params  # frozen θ for this superstep's acting
        rows = {g: {k: [] for k in ("frame", "action", "reward", "done")}
                for g in range(n)}
        for _t in range(ticks):
            for g, e in envs.items():
                e["st"], e["buf"], e["akeys"], rec = tick(
                    params, e["eps"], e["st"], e["buf"], e["akeys"])
                for k in rows[g]:
                    rows[g][k].append(np.asarray(rec[k])[0])
        for g in range(n):
            done = np.asarray(rows[g]["done"], bool)
            replay.add_batch({
                "frame": np.asarray(rows[g]["frame"], np.uint8),
                "action": np.asarray(rows[g]["action"], np.int64),
                "reward": np.asarray(rows[g]["reward"], np.float32),
                "done": done, "boundary": done}, stream=g)
        solver.train_steps_device_per(replay, runner.chain)

    ds_a, ds_h = runner.dstate, replay.dstate
    # frames compare per REAL row — the per-shard scratch row (index
    # cap_local_pad) is the designated dump for out-of-window ghost lanes,
    # whose duplicate-target writes resolve by kernel order; its content
    # is garbage by contract on BOTH paths and never read back
    rp = runner.replay
    shape = (rp.num_shards, rp.shard_rows, rp.rowb // 4)
    np.testing.assert_array_equal(
        np.asarray(ds_a.frames).reshape(shape)[:, :rp.cap_local_pad],
        np.asarray(ds_h.frames).reshape(shape)[:, :rp.cap_local_pad],
        err_msg="frame plane (real + ghost rows) diverged from host loop")
    for field in ("action", "reward", "done", "boundary", "prio"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ds_a, field)),
            np.asarray(getattr(ds_h, field)),
            err_msg=f"ring field {field!r} diverged from the host loop")
    np.testing.assert_array_equal(np.asarray(ds_a.maxp),
                                  np.asarray(ds_h.maxp))
    assert int(runner.solver.state.step) == int(solver.state.step) \
        == supersteps * runner.chain
    for pa, ph in zip(jax.tree.leaves(runner.solver.state.params),
                      jax.tree.leaves(solver.state.params)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(ph))
    for pa, ph in zip(jax.tree.leaves(runner.solver.state.target_params),
                      jax.tree.leaves(solver.state.target_params)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(ph))


def test_anakin_trains_signal_end_to_end():
    """The learning smoke: reward above chance on signal_atari, finite
    losses, and a solver state the rest of the system can use."""
    from distributed_deep_q_tpu.parallel.anakin import AnakinRunner

    cfg = _anakin_config(capacity=2048)
    cfg.mesh.dp = 1
    cfg.train.lr = 3e-3
    runner = AnakinRunner(cfg)
    for _ in range(39):
        jax.block_until_ready(runner.superstep())
    metrics = runner.run(1)
    assert all(np.isfinite(v).all() for v in metrics.values())
    assert metrics["loss"].shape == (runner.chain,)
    # signal_atari pays 1 for reading the current frame: chance is 1/4;
    # late-run ε-greedy acting should comfortably beat it
    act_r = float(np.asarray(runner.last_act_reward))
    assert act_r > 0.30, f"acting reward {act_r:.3f} stuck at chance"
    assert runner.env_steps == 40 * 8 * 16
    assert runner.grad_steps == 40 * runner.chain
    st = runner.solver.state
    assert int(st.step) == runner.grad_steps
    q = runner.solver.q_values(np.zeros((2, 10, 10, 2), np.uint8))
    assert np.asarray(q).shape == (2, 4) and np.isfinite(q).all()


def test_anakin_rejects_unsupported_shapes():
    """The mode is explicit and guarded: non-dividing env counts and
    non-JAX envs fail loudly at construction, not at dispatch."""
    from distributed_deep_q_tpu.parallel.anakin import AnakinRunner

    cfg = _anakin_config(n_envs=12)  # 12 % 8 != 0
    with pytest.raises(AssertionError, match="divide"):
        AnakinRunner(cfg)
    cfg = _anakin_config()
    cfg.env = EnvConfig(id="fake", kind="fake_atari",
                        frame_shape=(10, 10), stack=2)
    with pytest.raises(ValueError, match="no JAX port"):
        AnakinRunner(cfg)
