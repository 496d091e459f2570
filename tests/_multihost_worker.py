"""Multi-host learner worker — spawned by tests/test_multihost.py.

One process of an N-process multi-controller learner (SURVEY.md §5.8
"jax.distributed.initialize + global-mesh pjit"). Every process runs this
same program (multi-controller SPMD): connect, build the global mesh, run
``steps`` deterministic train steps feeding only this process's local batch
rows, then process 0 dumps the final (replicated) params to ``out``.

Run with nproc=1 to produce the single-process reference trajectory — same
seeds, same global batches — which the test compares against the 2-process
run for identical final parameters.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def synthetic_batch(rng: np.random.Generator, b: int, obs_dim: int,
                    num_actions: int) -> dict[str, np.ndarray]:
    return {
        "obs": rng.standard_normal((b, obs_dim)).astype(np.float32),
        "action": rng.integers(0, num_actions, b).astype(np.int32),
        "reward": rng.standard_normal(b).astype(np.float32),
        "next_obs": rng.standard_normal((b, obs_dim)).astype(np.float32),
        "discount": np.full(b, 0.99, np.float32),
        "weight": np.ones(b, np.float32),
    }


def main() -> None:
    pid, nproc = int(sys.argv[1]), int(sys.argv[2])
    port, out, steps = sys.argv[3], sys.argv[4], int(sys.argv[5])

    from distributed_deep_q_tpu.config import (
        MeshConfig, NetConfig, TrainConfig)
    from distributed_deep_q_tpu.parallel.multihost import (
        initialize_multihost, local_rows)

    mesh_cfg = MeshConfig(backend="cpu", num_fake_devices=8,
                          coordinator=f"127.0.0.1:{port}",
                          num_processes=nproc, process_id=pid)
    if nproc == 1:
        # single-process reference run: initialize_multihost is a no-op, so
        # pin the CPU platform + 8 virtual devices the conftest way
        import jax
        jax.config.update("jax_platforms", "cpu")
        from distributed_deep_q_tpu.parallel.mesh import set_cpu_device_count
        set_cpu_device_count(8, exact=True)
    # must precede any backend init — this is the whole API contract
    initialize_multihost(mesh_cfg)

    import jax

    from distributed_deep_q_tpu.models.qnet import build_qnet, init_params
    from distributed_deep_q_tpu.parallel.learner import Learner
    from distributed_deep_q_tpu.parallel.mesh import make_mesh

    assert jax.device_count() == 8, jax.device_count()
    assert jax.process_count() == nproc, jax.process_count()

    mesh = make_mesh(mesh_cfg)
    net_cfg = NetConfig(kind="mlp", num_actions=3, hidden=(32, 32),
                        dueling=True)
    train_cfg = TrainConfig(lr=1e-3, double_dqn=True, target_update_period=3)
    module = build_qnet(net_cfg)
    params = init_params(module, net_cfg, seed=0, obs_dim=6)
    learner = Learner(lambda p, o: module.apply({"params": p}, o),
                      train_cfg, mesh)
    state = learner.init_state(params)

    b_global = 16
    b_local = b_global // nproc
    rng = np.random.default_rng(0)  # same stream in every process
    for _ in range(steps):
        batch = synthetic_batch(rng, b_global, obs_dim=6, num_actions=3)
        local = {k: v[pid * b_local:(pid + 1) * b_local]
                 for k, v in batch.items()}
        state, metrics, td_abs = learner.train_step(state, local)
        # every process must see its own row count back (PER write-back path)
        assert local_rows(td_abs).shape == (b_local,)

    jax.block_until_ready(state.params)
    if pid == 0:
        flat = {f"w{i}": np.asarray(x) for i, x in
                enumerate(jax.tree_util.tree_leaves(state.params))}
        flat["loss"] = np.float32(metrics["loss"])
        np.savez(out, **flat)


if __name__ == "__main__":
    main()
