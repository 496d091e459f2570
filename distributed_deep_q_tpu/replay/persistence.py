"""Optional replay persistence — npz dump/load of every replay tier.

SURVEY.md §5.4: the reference family optionally persisted the replay buffer
(HDF5-backed variant [R]); the rebuild's default stays warm-refill (matching
reference behavior), and this module supplies the opt-in persistence behind
``ReplayConfig.persist_path``. One ``.npz`` file carries the complete
sampling state of a buffer — ring contents, cursors, priority trees, the
β-anneal counter, and the numpy RNG states — so a restored buffer's next
``sample()`` is byte-identical to what the saved one would have drawn
(tests/test_persistence.py proves exactly that).

Device-resident tiers (``DeviceFrameReplay`` / ``DevicePERFrameReplay``)
download their HBM rings once at save (``np.asarray`` on the sharded array
assembles the global view) and re-upload with the mesh sharding at load —
persistence is a cold-path operation; nothing here touches the train step.

Format: flat npz keys. Scalars ride as 0-d arrays; RNG states as JSON
strings. ``meta_kind`` + geometry keys guard against loading a file into a
mismatched buffer.
"""

from __future__ import annotations

import json
import os

import numpy as np

from distributed_deep_q_tpu.utils.durability import atomic_write, savez_bytes

SCHEMA = 1


# -- rng state (json round-trip keeps npz dtype-clean) -----------------------


def _rng_dump(rng: np.random.Generator) -> str:
    return json.dumps(rng.bit_generator.state)


def _rng_load(rng: np.random.Generator, s: str) -> None:
    rng.bit_generator.state = json.loads(s)


def _str(v) -> str:
    """npz round-trips str as 0-d ``<U`` arrays."""
    return str(np.asarray(v)[()]) if not isinstance(v, str) else v


# -- per-tier (de)serializers -------------------------------------------------


def _frame_stack_state(m, prefix: str) -> dict:
    d = {
        f"{prefix}action": m.action, f"{prefix}reward": m.reward,
        f"{prefix}done": m.done, f"{prefix}boundary": m.boundary,
        f"{prefix}cursor": m._cursor, f"{prefix}size": m._size,
        f"{prefix}steps_added": m._steps_added,
        f"{prefix}rng": _rng_dump(m._rng),
    }
    if m.frames is not None:
        d[f"{prefix}frames"] = m.frames
    return d


def _frame_stack_restore(m, z, prefix: str) -> None:
    assert int(z[f"{prefix}size"]) <= m.capacity, "capacity shrank under file"
    m.action[:] = z[f"{prefix}action"]
    m.reward[:] = z[f"{prefix}reward"]
    m.done[:] = z[f"{prefix}done"]
    m.boundary[:] = z[f"{prefix}boundary"]
    m._cursor = int(z[f"{prefix}cursor"])
    m._size = int(z[f"{prefix}size"])
    m._steps_added = int(z[f"{prefix}steps_added"])
    _rng_load(m._rng, _str(z[f"{prefix}rng"]))
    if m.frames is not None:
        m.frames[:] = z[f"{prefix}frames"]


_SEQ_META = ("action", "reward", "discount", "mask", "init_c", "init_h")


def _owned(d: dict) -> dict:
    """Snapshot isolation for a captured state dict: copy host-resident
    array views so the caller can serialize off-lock while the replay
    keeps mutating. ``dev_*`` keys are fresh HBM downloads (np.asarray
    of device arrays) and already owned."""
    return {k: np.array(v) if isinstance(v, np.ndarray)
            and not k.startswith("dev_") else v
            for k, v in d.items()}


def save_replay(replay, path: str) -> None:
    """Dump ``replay``'s complete sampling state to ``path`` atomically
    (tmp + fsync + rename — ``np.savez`` straight to the final path
    leaves a torn file on crash). Mirrors np.savez's historical naming:
    ``.npz`` is appended when ``path`` lacks it."""
    path = os.fspath(path)
    if not path.endswith(".npz"):
        path += ".npz"
    atomic_write(path, savez_bytes(**replay_state(replay)))


def replay_state(replay) -> dict:
    """Capture ``replay``'s complete sampling state as a flat dict (the
    npz key space of ``save_replay``). Every array is owned by the
    result — callers holding ``replay_lock`` can capture briefly and
    serialize/fsync after releasing it."""
    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay
    from distributed_deep_q_tpu.replay.device_ring import DeviceFrameReplay
    from distributed_deep_q_tpu.replay.device_sequence import (
        DeviceSequenceReplay)
    from distributed_deep_q_tpu.replay.prioritized import PrioritizedReplay
    from distributed_deep_q_tpu.replay.replay_memory import (
        FrameStackReplay, ReplayMemory)
    from distributed_deep_q_tpu.replay.sequence import SequenceReplay

    d: dict = {"meta_schema": SCHEMA}

    if isinstance(replay, SequenceReplay):
        d["meta_kind"] = "sequence"
        d["meta_capacity"] = replay.capacity
        d["meta_seq_len"] = replay.seq_len
        for k in _SEQ_META + ("obs",):
            d[k] = getattr(replay, k)
        d["cursor"] = replay._cursor
        d["size"] = replay._size
        d["seqs_added"] = replay._seqs_added
        d["samples"] = replay._samples
        d["max_priority"] = replay.max_priority
        d["rng"] = _rng_dump(replay._rng)
        if replay.prioritized:
            d["tree"] = replay.tree.tree
        return _owned(d)

    if isinstance(replay, DeviceSequenceReplay):
        replay.flush()  # staged sequences must be in the state we dump
        d["meta_kind"] = "device_sequence"
        d["meta_capacity"] = replay.capacity
        d["meta_seq_len"] = replay.seq_len
        d["meta_W"] = replay.W
        for k in _SEQ_META + ("n_valid",):
            d[k] = getattr(replay, k)
        d["cursor"] = replay._cursor
        d["sizes"] = replay._sizes
        d["added"] = replay._added
        d["next_shard"] = replay._next_shard
        d["seqs_added"] = replay._seqs_added
        d["samples"] = replay._samples
        d["max_priority"] = replay.max_priority
        d["rng"] = _rng_dump(replay._rng)
        if replay.prioritized:
            for i, t in enumerate(replay.trees):
                d[f"tree{i}"] = t.tree
        d["dev_ring"] = np.asarray(replay.ring)
        for k, v in replay.dmeta.items():
            d[f"dev_{k}"] = np.asarray(v)
        d["dev_maxp"] = np.asarray(replay.dmaxp)
        return _owned(d)

    if isinstance(replay, PrioritizedReplay):
        d["meta_kind"] = "prioritized"
        d["tree"] = replay.tree.tree
        d["max_priority"] = replay.max_priority
        d["samples"] = replay._samples
        d["per_rng"] = _rng_dump(replay._rng)
        base, inner = replay.base, "base_"
    else:
        base, inner = replay, ""

    if isinstance(replay, DeviceFrameReplay):  # incl. DevicePERFrameReplay
        replay.flush()  # staged rows must be in the device state we dump
        d["meta_kind"] = ("device_per" if isinstance(replay,
                                                     DevicePERFrameReplay)
                         else d.get("meta_kind", "device_ring"))
        d["meta_capacity"] = replay.capacity
        d["meta_num_slots"] = replay.num_slots
        d["meta_num_streams"] = replay.num_streams
        d["stream_pos"] = np.asarray(replay._stream_pos, np.int64)
        d["max_priority"] = replay.max_priority
        d["samples"] = replay._samples
        d["ring_rng"] = _rng_dump(replay._rng)
        for i, m in enumerate(replay.slots):
            d.update(_frame_stack_state(m, f"slot{i}_"))
        if isinstance(replay, DevicePERFrameReplay):
            for k in ("frames", "action", "reward", "done", "boundary",
                      "prio", "maxp"):
                d[f"dev_{k}"] = np.asarray(getattr(replay.dstate, k))
        else:
            d["dev_frames"] = np.asarray(replay.ring)
    elif isinstance(base, FrameStackReplay):
        d.setdefault("meta_kind", "frame_stack")
        d["meta_capacity"] = base.capacity
        d.update(_frame_stack_state(base, inner))
    elif isinstance(base, ReplayMemory):
        d.setdefault("meta_kind", "memory")
        d["meta_capacity"] = base.capacity
        d.update({
            f"{inner}obs": base.obs, f"{inner}next_obs": base.next_obs,
            f"{inner}action": base.action, f"{inner}reward": base.reward,
            f"{inner}discount": base.discount,
            f"{inner}cursor": base._cursor, f"{inner}size": base._size,
            f"{inner}steps_added": base._steps_added,
            f"{inner}rng": _rng_dump(base._rng),
        })
    else:
        raise TypeError(f"no persistence for {type(replay).__name__}")
    return _owned(d)


def load_replay(replay, path: str) -> None:
    """Restore state saved by ``save_replay`` into a freshly constructed,
    geometry-matched ``replay`` (same class, capacity, slot layout)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_deep_q_tpu.parallel.mesh import AXIS_DP
    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay
    from distributed_deep_q_tpu.replay.device_ring import DeviceFrameReplay
    from distributed_deep_q_tpu.replay.device_sequence import (
        DeviceSequenceReplay)
    from distributed_deep_q_tpu.replay.prioritized import PrioritizedReplay
    from distributed_deep_q_tpu.replay.replay_memory import (
        FrameStackReplay, ReplayMemory)
    from distributed_deep_q_tpu.replay.sequence import SequenceReplay

    z = np.load(path, allow_pickle=False)
    kind = _str(z["meta_kind"])

    if isinstance(replay, SequenceReplay):
        assert kind == "sequence", f"file holds {kind!r}"
        assert int(z["meta_capacity"]) == replay.capacity and \
            int(z["meta_seq_len"]) == replay.seq_len, "geometry mismatch"
        assert ("tree" in z) == replay.prioritized, (
            "prioritized-ness mismatch: file was saved with prioritized="
            f"{'tree' in z}, buffer is prioritized={replay.prioritized}")
        assert z["obs"].shape == replay.obs.shape and \
            z["obs"].dtype == replay.obs.dtype, "obs store mismatch"
        for k in _SEQ_META + ("obs",):
            getattr(replay, k)[:] = z[k]
        replay._cursor = int(z["cursor"])
        replay._size = int(z["size"])
        replay._seqs_added = int(z["seqs_added"])
        replay._samples = int(z["samples"])
        replay.max_priority = float(z["max_priority"])
        _rng_load(replay._rng, _str(z["rng"]))
        if replay.prioritized:
            t = replay.tree
            t.set(np.arange(t.size), z["tree"][t.size: 2 * t.size])
        return

    if isinstance(replay, DeviceSequenceReplay):
        assert kind == "device_sequence", f"file holds {kind!r}"
        assert int(z["meta_capacity"]) == replay.capacity and \
            int(z["meta_seq_len"]) == replay.seq_len and \
            int(z["meta_W"]) == replay.W, "geometry mismatch"
        assert ("tree0" in z) == replay.prioritized, (
            "prioritized-ness mismatch: file was saved with prioritized="
            f"{'tree0' in z}, buffer is prioritized={replay.prioritized}")
        assert z["dev_ring"].shape == replay.ring.shape and \
            z["dev_ring"].dtype == replay.ring.dtype, (
            "pixel-plane layout mismatch (saved by an incompatible "
            "version)")
        for k in _SEQ_META + ("n_valid",):
            getattr(replay, k)[:] = z[k]
        replay._cursor[:] = z["cursor"]
        replay._sizes[:] = z["sizes"]
        replay._added[:] = z["added"]
        replay._next_shard = int(z["next_shard"])
        replay._seqs_added = int(z["seqs_added"])
        replay._samples = int(z["samples"])
        replay.max_priority = float(z["max_priority"])
        _rng_load(replay._rng, _str(z["rng"]))
        if replay.prioritized:
            for i, t in enumerate(replay.trees):
                t.set(np.arange(t.size), z[f"tree{i}"][t.size: 2 * t.size])
        sharded = NamedSharding(replay.mesh, P(AXIS_DP))
        replay.ring = jax.device_put(z["dev_ring"], sharded)
        replay.dmeta = {k: jax.device_put(z[f"dev_{k}"], sharded)
                        for k in replay.dmeta}
        replay.dmaxp = jax.device_put(z["dev_maxp"],
                                      NamedSharding(replay.mesh, P()))
        return

    if isinstance(replay, PrioritizedReplay):
        assert kind == "prioritized", f"file holds {kind!r}"
        replay.tree.set(np.arange(replay.tree.size),
                        z["tree"][replay.tree.size:
                                  replay.tree.size + replay.tree.size])
        replay.max_priority = float(z["max_priority"])
        replay._samples = int(z["samples"])
        _rng_load(replay._rng, _str(z["per_rng"]))
        base, inner = replay.base, "base_"
    else:
        base, inner = replay, ""

    if isinstance(replay, DeviceFrameReplay):
        expect = ("device_per" if isinstance(replay, DevicePERFrameReplay)
                  else "device_ring")
        assert kind == expect, f"file holds {kind!r}, buffer is {expect!r}"
        assert int(z["meta_capacity"]) == replay.capacity and \
            int(z["meta_num_slots"]) == replay.num_slots, \
            "ring geometry mismatch (capacity / slot layout)"
        replay._stream_pos = [int(v) for v in z["stream_pos"]]
        replay.max_priority = float(z["max_priority"])
        replay._samples = int(z["samples"])
        _rng_load(replay._rng, _str(z["ring_rng"]))
        for i, m in enumerate(replay.slots):
            _frame_stack_restore(m, z, f"slot{i}_")
        sharded = NamedSharding(replay.mesh, P(AXIS_DP))
        if isinstance(replay, DevicePERFrameReplay):
            # frame-plane format guard: the round-5 ring is flat padded
            # int32 (ghost rows, DMA layout) — a file from the old 2-D
            # uint8 layout has matching capacity/slots but would fail
            # deep inside shard_map on the next dispatch
            want = replay.dstate.frames
            got = z["dev_frames"]
            assert got.shape == want.shape and got.dtype == want.dtype, (
                f"frame-plane layout mismatch: file has {got.dtype}"
                f"{got.shape}, buffer expects {want.dtype}{want.shape} "
                "(saved by an incompatible version)")
            replicated = NamedSharding(replay.mesh, P())
            replay.dstate = replay.dstate.replace(**{
                k: jax.device_put(z[f"dev_{k}"],
                                  replicated if k == "maxp" else sharded)
                for k in ("frames", "action", "reward", "done", "boundary",
                          "prio", "maxp")})
            replay._di_cache = None
        else:
            # (an older file of this kind may carry per-slot ``tree{i}``
            # keys: they load ignored)
            replay.ring = jax.device_put(z["dev_frames"], sharded)
    elif isinstance(base, FrameStackReplay):
        assert int(z["meta_capacity"]) == base.capacity, "capacity mismatch"
        _frame_stack_restore(base, z, inner)
    elif isinstance(base, ReplayMemory):
        assert int(z["meta_capacity"]) == base.capacity, "capacity mismatch"
        base.obs[:] = z[f"{inner}obs"]
        base.next_obs[:] = z[f"{inner}next_obs"]
        base.action[:] = z[f"{inner}action"]
        base.reward[:] = z[f"{inner}reward"]
        base.discount[:] = z[f"{inner}discount"]
        base._cursor = int(z[f"{inner}cursor"])
        base._size = int(z[f"{inner}size"])
        base._steps_added = int(z[f"{inner}steps_added"])
        _rng_load(base._rng, _str(z[f"{inner}rng"]))
    else:
        raise TypeError(f"no persistence for {type(replay).__name__}")
