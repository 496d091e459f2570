"""The adapter to the system under test: everything the harness takes from
``distributed_deep_q_tpu`` is imported here or in a driver, never in the
yardstick (reference, counts, trace reduction, readers)."""

from __future__ import annotations

import dataclasses


def program_seed(seed: int) -> int:
    """The driver's seeds pass 2**31; the program's seed fields feed 32-bit
    PRNG keys and per-actor offsets, so it gets the seed folded below."""
    return int(seed) % (2 ** 31 - 1)


def make_cfg(conf: dict, seed: int, backend: str, extra: list[str] = ()):
    """The program's Config for a configuration file: its preset, the
    file's overrides, then the traffic's, then the seed."""
    from distributed_deep_q_tpu.config import PRESETS, apply_overrides

    cfg = PRESETS[conf["preset"]]()
    cfg.mesh.backend = backend
    apply_overrides(cfg, [*conf["overrides"], *extra,
                          f"train.seed={program_seed(seed)}"])
    cfg.net.num_actions = conf["hparams"]["num_actions"]
    return cfg


def make_solver(cfg):
    import numpy as np

    from distributed_deep_q_tpu.solver import Solver

    return Solver(cfg, obs_dim=int(np.prod(cfg.env.frame_shape)))


def make_replay(cfg, solver, beta_steps: int | None = None):
    """The ring as ``train_distributed`` builds it for this Config."""
    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay

    rcfg = cfg.replay if beta_steps is None else dataclasses.replace(
        cfg.replay, priority_beta_steps=beta_steps)
    return DevicePERFrameReplay(
        rcfg, solver.mesh, tuple(cfg.env.frame_shape), cfg.env.stack,
        cfg.train.gamma, seed=cfg.train.seed,
        write_chunk=cfg.replay.write_chunk,
        num_streams=cfg.actors.num_actors
        * max(int(cfg.actors.vector_envs), 1))


def place_compile_cache() -> str:
    from distributed_deep_q_tpu.utils.compile_cache import (
        place_compile_cache as place)

    return place()


def program_flops_per_step(solver, replay, chain: int):
    """The program's own census of its train program (XLA cost analysis),
    printed beside the benchmark's analytic count; never used for a metric."""
    from distributed_deep_q_tpu.profiling import fused_train_flops

    try:
        return fused_train_flops(solver, replay, chain)
    except Exception as e:  # noqa: BLE001 — reporting only
        return f"unavailable: {type(e).__name__}: {e}"
