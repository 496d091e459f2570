"""Multi-host learner support (SURVEY.md §5.8, §2.2 "Multi-host DP" [M]).

The reference scales workers across nodes with Spark (actor + gradient
parallelism, parameter-server plane [M][P]); the TPU-native equivalent is
**multi-controller JAX**: every learner process runs the same program,
``jax.distributed.initialize`` connects them into one runtime, the device
mesh spans all processes, and the existing ``shard_map`` + ``lax.pmean``
train step works unchanged — XLA routes the gradient allreduce over ICI
within a slice and DCN across hosts (config 5's "v4-32 multi-host" path).
No gradient code changes between 1 host and N hosts; that is the point.

What does change is *data placement*: in multi-controller mode a process
can only hand JAX the rows that live on its own devices. The helpers here
are that seam:

- ``initialize_multihost(cfg)`` — one-call bring-up from ``MeshConfig``.
  On the ``cpu`` test backend it pins the platform, splits
  ``num_fake_devices`` virtual devices evenly across processes, and selects
  the gloo cross-process collective implementation (the reference's
  ``local[N]``-style Spark test mode, rebuilt — SURVEY §4).
- ``global_batch(sharding, batch)`` — assemble the global sharded batch
  from each process's local rows (``jax.make_array_from_process_local_data``).
- ``put_replicated(tree, sharding)`` — replicate host values across every
  process's devices (TrainState init / weight installs).
- ``local_rows(arr)`` — read back this process's rows of a batch-sharded
  result (per-sample |TD| for PER write-back into the local replay shard).

Process topology for config 5: each learner process hosts its own replay
shard fed by its own slice of the actor fleet (per-host replay shards,
SURVEY §7.3 item 6 — sampling is dedup-free because shards never overlap);
the per-process sample feeds ``global_batch``; metrics out-specs are
replicated so every process can read them without extra collectives.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np

from distributed_deep_q_tpu.config import MeshConfig


def initialize_multihost(cfg: MeshConfig) -> None:
    """Connect this process to the multi-controller runtime (idempotent).

    Must run before any JAX backend initialization in the process. With
    ``num_processes <= 1`` this is a no-op, so single-host entry points can
    call it unconditionally.
    """
    if cfg.num_processes <= 1:
        return
    # NOTE: do not probe jax.process_count() here — it initializes the
    # backend, which forbids the device-count config updates below.
    # is_initialized() is the init-free "already connected?" signal.
    if jax.distributed.is_initialized():
        return  # already connected
    if cfg.backend == "cpu":
        if cfg.num_fake_devices % cfg.num_processes:
            raise ValueError(
                f"num_fake_devices={cfg.num_fake_devices} must divide evenly "
                f"across num_processes={cfg.num_processes}")
        # same pre-init pattern as parallel.mesh._cpu_devices: backend=cpu
        # wins over the environment's platform, then size this process's
        # local slice
        jax.config.update("jax_platforms", "cpu")
        from distributed_deep_q_tpu.parallel.mesh import set_cpu_device_count
        set_cpu_device_count(cfg.num_fake_devices // cfg.num_processes,
                             exact=True)
        # cross-process collectives on the CPU backend go through gloo
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs: dict[str, Any] = {}
    if cfg.coordinator:
        kwargs = dict(coordinator_address=cfg.coordinator,
                      num_processes=cfg.num_processes,
                      process_id=cfg.process_id)
    # on TPU pods initialize() auto-detects everything from the metadata
    # server when no coordinator is given
    jax.distributed.initialize(**kwargs)


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def put_replicated(tree: Any, sharding) -> Any:
    """Place a host pytree onto every device of a (possibly multi-host)
    mesh. ``sharding`` is either ONE sharding applied to every leaf (the
    replicated TrainState path) or a matching pytree of per-leaf
    shardings (partition-rule placement, ``parallel.mesh.tree_shardings``
    — ISSUE 10's model-axis hook). Single-process: plain ``device_put``.
    Multi-process: every process holds the full value, so the
    process-local data IS the global array."""
    if not is_multiprocess():
        return jax.device_put(tree, sharding)

    def put(x, s):
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(
            s, x, global_shape=x.shape)

    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(lambda x: put(x, sharding), tree)
    return jax.tree.map(put, tree, sharding)


def global_batch(sharding, batch: dict[str, Any]) -> dict[str, Any]:
    """Assemble the global batch from this process's local rows.

    Each process passes its own ``global_B / process_count`` rows (its
    replay shard's sample); the returned dict holds global jax.Arrays
    sharded over the batch axis, ready for the sharded train step.
    Single-process mode passes the batch through untouched (jit shards
    host arrays itself).
    """
    if not is_multiprocess():
        return batch
    n = jax.process_count()

    def put(x):
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(
            sharding, x, global_shape=(x.shape[0] * n,) + x.shape[1:])

    return {k: put(v) for k, v in batch.items()}


def all_processes_ready(local_ready: bool) -> bool:
    """AND-reduce a host-side readiness flag across processes.

    Used to open the learn gate simultaneously on every process (each host
    fills its own replay shard at its own pace; the sharded train step is a
    collective, so no process may enter it early). This is itself a
    collective — every process must call it at the same loop point.
    Single-process: identity.
    """
    if not is_multiprocess():
        return bool(local_ready)
    from jax.experimental import multihost_utils
    flags = multihost_utils.process_allgather(np.asarray([bool(local_ready)]))
    return bool(np.all(flags))


def global_max_int(value: int) -> int:
    """MAX-reduce a host-side integer across processes. Collective —
    every process must call it at the same loop point. Used by the
    multi-host fused replay to agree on a uniform flush-round count
    before the lockstep flush dispatches (each host's staged backlog
    differs; the flush program is a global-array computation every
    process must enter the same number of times). Single-process:
    identity."""
    if not is_multiprocess():
        return int(value)
    from jax.experimental import multihost_utils
    vals = multihost_utils.process_allgather(np.asarray([int(value)]))
    return int(np.max(vals))


def local_rows(arr: jax.Array) -> np.ndarray:
    """This process's rows of a batch-axis-sharded result, in shard order
    (e.g. per-sample |TD| destined for the local replay shard's PER
    write-back). Works in single-process mode too (returns all rows)."""
    shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start or 0)
    return np.concatenate([np.asarray(s.data) for s in shards], axis=0)
