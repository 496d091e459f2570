"""Shard-local sampling worker — spawned by tests/test_multihost.py.

One process of an N-process multi-controller learner running ONLY the
fused SAMPLE program (ISSUE 10: per-host local PER sampling). The global
ring content is made identical across process layouts by construction:
with ``num_streams = slots / nproc`` per host, the replay's stream→slot
cycles are 1:1 (host p's stream s owns exactly global slot
``p * streams + s``), so feeding stream s from an rng seeded by its
GLOBAL slot id writes the same bytes into the same slots whether one
process owns all of them or two processes own half each.

With identical ring state, identical replicated betas, and the
host-generated per-shard key schedule (a pure function of the train
seed), every shard's prioritized draw must be BITWISE identical across
layouts — the pin that sampling is shard-local: each shard's draw reads
nothing outside its own rows, so re-partitioning shards over hosts
cannot perturb it. Each process dumps its LOCAL blocks of the sampled
indices / weights / metadata and of the pixel ring; the test reassembles
them in shard order and compares against the single-process reference.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

DEVICES = 8
BATCH = 32
CHAIN = 2
FRAME = (36, 36)     # Nature conv stack minimum (kernels 8/4/3, strides 4/2/1)


def _local_blocks(arr, axis: int) -> np.ndarray:
    """This process's addressable blocks of a sharded array, concatenated
    in global (index) order along the sharded axis."""
    shards = sorted(arr.addressable_shards,
                    key=lambda s: s.index[axis].start or 0)
    return np.concatenate([np.asarray(s.data) for s in shards], axis=axis)


def main() -> None:
    pid, nproc = int(sys.argv[1]), int(sys.argv[2])
    port, out = sys.argv[3], sys.argv[4]

    from distributed_deep_q_tpu.config import (
        Config, MeshConfig, NetConfig, ReplayConfig)
    from distributed_deep_q_tpu.parallel.multihost import initialize_multihost

    mesh_cfg = MeshConfig(backend="cpu", num_fake_devices=DEVICES,
                          dp=DEVICES, coordinator=f"127.0.0.1:{port}",
                          num_processes=nproc, process_id=pid)
    if nproc == 1:
        import jax
        jax.config.update("jax_platforms", "cpu")
        from distributed_deep_q_tpu.parallel.mesh import set_cpu_device_count
        set_cpu_device_count(DEVICES, exact=True)
    initialize_multihost(mesh_cfg)

    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay
    from distributed_deep_q_tpu.solver import Solver, next_fused_keys

    streams = DEVICES // nproc  # 1:1 stream↔slot in every layout
    cfg = Config()
    cfg.mesh = mesh_cfg
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4, frame_shape=FRAME)
    cfg.replay = ReplayConfig(capacity=512, batch_size=BATCH, n_step=2,
                              prioritized=True, device_per=True,
                              write_chunk=16)
    solver = Solver(cfg)
    replay = DevicePERFrameReplay(cfg.replay, solver.mesh, FRAME, stack=4,
                                  gamma=0.99, seed=0, write_chunk=16,
                                  num_streams=streams)
    assert replay.num_slots == DEVICES
    for s in range(streams):
        assert replay._slot_cycle[s] == [pid * streams + s], \
            (replay._slot_cycle, pid, streams)

    # slot-keyed feeding: stream s's bytes depend only on its GLOBAL slot
    rows = 40
    for s in range(streams):
        rng = np.random.default_rng(2000 + pid * streams + s)
        replay.add_batch({
            "frame": rng.integers(0, 255, (rows,) + FRAME, dtype=np.uint8),
            "action": rng.integers(0, 4, rows).astype(np.int32),
            "reward": rng.standard_normal(rows).astype(np.float32),
            "done": (np.arange(rows) % 7 == 6),
        }, stream=s)
    replay.flush()  # lockstep collective when nproc > 1

    # the sample program alone, exactly the Solver's dispatch plumbing
    # (Solver.train_steps_device_per) minus the train half
    learner = solver.learner
    spec = (replay.slot_cap, replay.slot_pad, replay.rowb, replay._row_len,
            replay.stack, replay.n_step, replay.gamma,
            tuple(replay.frame_shape), BATCH // replay.num_shards,
            float(cfg.replay.priority_alpha), float(cfg.replay.priority_eps),
            replay.num_shards, replay._interpret)
    if (spec, CHAIN) not in learner._device_per_steps:
        learner._device_per_steps[(spec, CHAIN)] = \
            learner._build_device_per_step(spec, CHAIN)
    sample, _ = learner._device_per_steps[(spec, CHAIN)]

    cursors, sizes = replay.device_inputs()
    betas = replay.next_betas(CHAIN)
    keys = next_fused_keys(solver, replay.num_shards, CHAIN)
    if replay._pc > 1:
        keys = replay.to_global(
            np.ascontiguousarray(keys[replay.local_shards]))
        cursors = replay.to_global(np.asarray(cursors))
        sizes = replay.to_global(np.asarray(sizes))
        betas = replay.to_replicated(np.asarray(betas, np.float32))
    else:
        cursors, sizes = np.asarray(cursors), np.asarray(sizes)
        betas = np.asarray(betas, np.float32)
    rows_d = replay.dstate
    metas, win, idx = sample(keys, rows_d.frames, rows_d.action,
                             rows_d.reward, rows_d.done, rows_d.boundary,
                             rows_d.prio, cursors, sizes, betas)

    # local blocks only: ring sharded on dim 0, sampled planes on dim 1
    np.savez(
        out,
        frames=_local_blocks(rows_d.frames, 0),
        prio=_local_blocks(rows_d.prio, 0),
        idx=_local_blocks(idx, 1),
        weight=_local_blocks(metas["weight"], 1),
        action=_local_blocks(metas["action"], 1),
        reward=_local_blocks(metas["reward"], 1),
    )


if __name__ == "__main__":
    main()
