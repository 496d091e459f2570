"""The host side of a device-resident pixel ring — frames live in HBM.

The TPU-first redesign of the replay data path (SURVEY.md §7.3 item 1: "this
is where the 50× target is won or lost"). The reference streams full pixel
minibatches host→device every step (Caffe blob loads, SURVEY §3.1); a
pmap-fed rebuild doing the same ships ~29 MB/step at batch 512 — measured
at ~160 ms over this container's TPU link vs a 0.2 ms train step. Instead:

- **Frames enter HBM once, at actor rate.** A ring of frame rows (frames
  flattened row-wise: TPU tiles the two minor dims of an array, so a
  ``[cap, 84, 84]`` uint8 ring would pad each frame to 96×128, 1.74× the
  bytes) lives on the learner mesh, sharded over the ``dp`` axis (each
  device owns a contiguous shard — Ape-X-style per-learner replay
  shards). Writers append in fixed-size chunks through a donated
  ``shard_map`` scatter.
- **Sampling is not here.** This module routes streams to slots, stages
  rows and flushes them; the one sample path of a pixel device run is the
  fused step over ``replay/device_per.py``'s ring, which inherits all of
  this and draws ``batch/D`` rows a shard on the device, in mesh order
  (``PartitionSpec('dp')`` row blocks: each device gathers only from its
  local shard, no cross-device collective in the data path).

Layout — shards and stream slots:

    device shard s owns ring rows [s·cap_local, (s+1)·cap_local)
    each shard is split into ``subs_per_shard`` SLOTS of ``slot_cap`` rows
    slot g (global id) lives on shard g % D at sub-ring g // D

Frame stacking relies on temporal adjacency, so every slot has exactly ONE
writer stream at a time. Stream i owns slots {g : g % num_streams == i} and
cycles through them at episode boundaries; with fewer streams than shards a
single stream still reaches every shard (episode round-robin), and with more
streams than shards each shard hosts several sub-rings instead of
interleaving writers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_deep_q_tpu import tracing
from distributed_deep_q_tpu.config import ReplayConfig
from distributed_deep_q_tpu.parallel.mesh import AXIS_DP
from distributed_deep_q_tpu.replay.prioritized import beta_at
from distributed_deep_q_tpu.replay.replay_memory import FrameStackReplay


class DeviceFrameReplay:
    """The host side of a device ring: geometry, stream→slot routing,
    per-slot metadata, staging (columnar or legacy), prepared rounds, the
    drain and the chunked flush into a uint8 HBM frame ring. It has no
    sample path of its own: ``DevicePERFrameReplay`` (replay/device_per.py)
    inherits this bookkeeping and is sampled inside the fused learner step.
    """

    prioritized: bool

    def __init__(
        self,
        cfg: ReplayConfig,
        mesh: Mesh,
        frame_shape: tuple[int, int] = (84, 84),
        stack: int = 4,
        gamma: float = 0.99,
        seed: int = 0,
        write_chunk: int = 64,
        num_streams: int = 1,
    ):
        self.mesh = mesh
        d = self.num_shards = mesh.shape[AXIS_DP]
        self.num_streams = max(int(num_streams), 1)
        # multi-controller topology (SURVEY §7.3 item 6): this process
        # owns only the shards whose devices it hosts; its streams route
        # to slots on those shards, its staging covers only them, and
        # flush planes assemble per-process local rows into the global
        # sharded arrays. Geometry (subs/slot_cap) must be identical on
        # every process, so it derives from the GLOBAL stream count.
        self._pc = jax.process_count()
        self._pid = jax.process_index()
        self.local_shards = [s for s, dev in enumerate(mesh.devices.flat)
                             if dev.process_index == self._pid]
        total_streams = self.num_streams * self._pc
        self.subs_per_shard = -(-max(total_streams, d) // d)  # ceil
        g = self.num_slots = self.subs_per_shard * d
        self.slot_cap = int(cfg.capacity) // g
        assert self.slot_cap > 0 and cfg.batch_size % d == 0, (
            f"capacity {cfg.capacity} must split over {g} stream slots and "
            f"batch {cfg.batch_size} over {d} shards")
        # one flush chunk must never wrap a sub-ring: a wrap would scatter
        # duplicate in-shard offsets in one .at[].set (unspecified winner →
        # stale pixels under fresh metadata), so clamp the chunk size
        write_chunk = min(int(write_chunk), self.slot_cap)
        self.cap_local = self.slot_cap * self.subs_per_shard
        self.capacity = self.cap_local * d
        self.stack = int(stack)
        self.frame_shape = tuple(frame_shape)
        self.write_chunk = int(write_chunk)
        self.prioritized = bool(cfg.prioritized)
        self._cfg = cfg

        # per-slot metadata rings (single writer each → adjacency holds)
        self.slots = [
            FrameStackReplay(self.slot_cap, frame_shape, stack, cfg.n_step,
                             gamma, seed=seed + i, store_frames=False)
            for i in range(g)]
        # keys of the ring's snapshot (replay/persistence.py) that nothing
        # else reads: they go with the snapshot's next schema (ROADMAP D3b)
        self._rng = np.random.default_rng(seed)
        self.max_priority = 1.0
        # the β anneal's position: fused steps taken (``next_betas``)
        self._samples = 0

        # stream → its slot cycle over this process's LOCAL slots (stream
        # i owns every num_streams-th local slot; single-process this is
        # exactly the old {g : g % num_streams == i} assignment since
        # local slots are all slots in order)
        local_set = set(self.local_shards)
        local_slots = [s for s in range(g) if s % d in local_set]
        self._slot_cycle = [
            [s for j, s in enumerate(local_slots) if j % self.num_streams == i]
            for i in range(self.num_streams)]
        self._stream_pos = [0] * self.num_streams
        # multi-host: flushes must be LOCKSTEP collectives (the scatter
        # runs on global arrays), so ingest defers them to the chunk
        # boundary where every process flushes an agreed round count
        self.defer_flush = self._pc > 1

        self._row_len = int(np.prod(self.frame_shape))
        self._alloc_ring()

        # host staging: _stage_columns describes the staged columns'
        # (tail shape, dtype); subclasses (device_per) extend it with
        # metadata columns. Two interchangeable backends (ISSUE 8):
        # - columnar (default): per-shard preallocated column buffers,
        #   one memcpy per column per staged segment (replay/columnar.py)
        # - legacy: per-shard FIFO of (in-shard offsets [n], *columns)
        #   array tuples — the bit-identical reference the columnar
        #   path is pinned against (tests/test_columnar_ingest.py)
        self._stage_columns: list[tuple[tuple[int, ...], type]] = [
            ((self._row_len,), np.uint8)]
        self._columnar = bool(getattr(cfg, "staging_columnar", True))
        self._staging_depth = int(getattr(cfg, "staging_depth", 4096))
        self._stages: list | None = None  # built lazily: subclasses widen
        self._pending: list[list[tuple]] = [[] for _ in range(d)]
        self._pending_rows = [0] * d
        # pre-assembled flush planes (ISSUE 10 shard-aware drain): FIFO
        # of (idx, cols, rows) built host-side by prepare_rounds; the
        # next flush() dispatches these BEFORE assembling fresh rounds,
        # so write order is staged order regardless of who assembled
        self._prepared: list[tuple[np.ndarray, list, int]] = []
        self._prepared_rows = 0
        self._drain = None  # optional IngestDrain (start_drain)
        self._drain_enabled = bool(getattr(cfg, "ingest_drain", True))

    def _alloc_ring(self) -> None:
        """Allocate the HBM frame plane + its scatter-writer. Overridden by
        ``DevicePERFrameReplay`` (flat padded ring + Pallas row-DMA).

        Frames are flattened to [H·W] rows (TPU (32,128) tiling of the
        minor dims — module docstring). Allocated directly with
        the dp sharding (no host copy); the donated scatter lets each
        device write its chunk into its own ring shard, padding lanes
        carry idx == cap_local and are dropped."""
        ring_sharding = NamedSharding(self.mesh, P(AXIS_DP))
        shape = (self.capacity, self._row_len)
        self.ring = jax.jit(
            lambda: jnp.zeros(shape, jnp.uint8),
            out_shardings=ring_sharding)()

        def write(ring_local, idx, frames):
            return ring_local.at[idx].set(frames, mode="drop")

        self._write = jax.jit(
            shard_map(write, mesh=self.mesh,
                      in_specs=(P(AXIS_DP), P(AXIS_DP), P(AXIS_DP)),
                      out_specs=P(AXIS_DP)),
            donate_argnums=0)

    # -- layout helpers -----------------------------------------------------

    def _slot_base(self, slot: int) -> tuple[int, int]:
        """(shard, in-shard base offset) of a slot's sub-ring."""
        return slot % self.num_shards, (slot // self.num_shards) * self.slot_cap

    def _global_index(self, slot: int, local: np.ndarray) -> np.ndarray:
        shard, base = self._slot_base(slot)
        return shard * self.cap_local + base + local

    # -- bookkeeping --------------------------------------------------------

    def __len__(self) -> int:
        return sum(len(m) for m in self.slots)

    def pending_rows(self) -> int:
        """Rows staged or pre-assembled but not yet flushed to HBM.
        Public because the solver's flush gate keys off it — callers
        must not reach into ``_pending_rows``."""
        return sum(self._pending_rows) + self._prepared_rows

    def _staged_rows(self) -> int:
        """Rows still in staging (NOT counting pre-assembled planes) —
        the shard-aware drain's backlog signal: once a row is in a
        prepared plane there is no host work left, only the lockstep
        dispatch."""
        return sum(self._pending_rows)

    @property
    def steps_added(self) -> int:
        return sum(m.steps_added for m in self.slots)

    def _sampleable(self, slot: int) -> int:
        """Sampleable transition mass of a slot (0 until it can sample)."""
        m = self.slots[slot]
        window = m.stack + m.n_step + 1
        if len(m) <= window or m.valid_fraction() <= 0:
            return 0
        return len(m) - window

    def ready(self, learn_start: int) -> bool:
        """True when sampling can proceed: aggregate fill reached AND every
        shard has at least one slot with sampleable transitions (the
        fused draw takes batch/D from *each* shard — SURVEY §7.3 item 6)."""
        if len(self) < learn_start:
            return False
        # multi-host: a process can only see (and fill) its local shards;
        # the cross-host AND happens at the caller (all_processes_ready)
        per_shard = {s: 0 for s in self.local_shards}
        for g in range(self.num_slots):
            if g % self.num_shards in per_shard:
                per_shard[g % self.num_shards] += self._sampleable(g)
        return all(mass > 0 for mass in per_shard.values())

    @property
    def beta(self) -> float:
        return beta_at(self._samples, self._cfg.priority_beta0,
                       self._cfg.priority_beta_steps)

    # -- write path ---------------------------------------------------------

    def _stage_rows(self, shard: int, idx: np.ndarray, cols: tuple) -> None:
        """Append one staged segment (in-shard offsets + payload columns)
        to the shard's staging backend. Columnar: one memcpy per column
        into the preallocated stage (``staged_append``); legacy: FIFO of
        array tuples. Callers hold the replay lock."""
        if self._columnar:
            if self._stages is None:
                self._stages = [None] * self.num_shards
            st = self._stages[shard]
            if st is None:
                from distributed_deep_q_tpu.replay.columnar import ColumnStage
                st = self._stages[shard] = ColumnStage(
                    [((), np.int32)] + list(self._stage_columns),
                    depth=self._staging_depth,
                    use_native=self._cfg.use_native)
            with tracing.span("staged_append"):
                st.append(idx, *cols)
        else:
            self._pending[shard].append((idx,) + tuple(cols))
        self._pending_rows[shard] += len(idx)

    def _stage(self, slot: int, local: np.ndarray, frames: np.ndarray) -> None:
        """Queue (slot-local rows, flat frames) for the HBM scatter."""
        shard, base = self._slot_base(slot)
        self._stage_rows(shard, (base + local).astype(np.int32), (frames,))

    def add(self, frame, action, reward, done, boundary=None) -> int:
        """Single-stream add (in-process training loop)."""
        cycle = self._slot_cycle[0]
        slot = cycle[self._stream_pos[0] % len(cycle)]
        i = self.slots[slot].add(None, action, reward, done, boundary=boundary)
        self._stage(slot, np.asarray([i]),
                    np.asarray(frame, np.uint8).reshape(1, -1))
        if done if boundary is None else boundary:
            # episode finished → move this stream to its next slot, so one
            # stream eventually reaches every shard it owns
            self._stream_pos[0] += 1
        self._flush_or_notify()
        return int(self._global_index(slot, np.asarray(i)))

    def add_batch(self, batch, stream: int = 0) -> np.ndarray:
        """Contiguous chunk from one actor stream (RPC path). The chunk may
        contain episode boundaries; rows route to the stream's current slot,
        which advances at each boundary — so the chunk splits into
        boundary-delimited segments, each inserted with ONE vectorized
        metadata add + ONE priority-tree set + ONE staged frame block
        (per-row Python here was the measured config-4 ingest ceiling)."""
        assert 0 <= stream < self.num_streams, \
            f"stream {stream} outside configured num_streams={self.num_streams}"
        n = len(batch["action"])
        done = np.asarray(batch["done"], bool)
        boundary = np.asarray(batch.get("boundary", batch["done"]), bool)
        frames = np.ascontiguousarray(
            np.asarray(batch["frame"], np.uint8).reshape(n, -1))
        action = np.asarray(batch["action"])
        reward = np.asarray(batch["reward"])
        out = np.empty(n, np.int64)
        cuts = np.flatnonzero(boundary) + 1  # segment ends (exclusive)
        if len(cuts) == 0 or cuts[-1] != n:
            cuts = np.append(cuts, n)
        s0 = 0
        for s1 in cuts:
            cycle = self._slot_cycle[stream]
            slot = cycle[self._stream_pos[stream] % len(cycle)]
            m = self.slots[slot]
            # cap one metadata insert at slot_cap rows so a single call can
            # never wrap its own sub-ring (duplicate offsets in one scatter)
            for p0 in range(s0, s1, self.slot_cap):
                p1 = min(p0 + self.slot_cap, s1)
                li = m.add_batch({
                    "action": action[p0:p1], "reward": reward[p0:p1],
                    "done": done[p0:p1], "boundary": boundary[p0:p1]})
                self._stage(slot, li, frames[p0:p1])
                out[p0:p1] = self._global_index(slot, li)
            if boundary[s1 - 1]:
                self._stream_pos[stream] += 1
            s0 = s1
        self._flush_or_notify()
        return out

    def _flush_or_notify(self) -> None:
        """Chunk-boundary flush gate. With an ``IngestDrain`` attached
        the writer only nudges the drain thread (the work happens there,
        off this thread's lock hold); otherwise the legacy inline flush
        runs here. Multi-host the flush itself is deferred to the
        lockstep chunk boundary, but the drain still gets the nudge —
        its work there is host-only plane assembly (prepare_rounds)."""
        if max(self._pending_rows) < self.write_chunk:
            return
        if self._drain is not None:
            self._drain.notify()
        elif not self.defer_flush:
            self.flush()

    def start_drain(self, lock):
        """Attach a background staging→device drain thread sharing
        ``lock`` (the caller's replay lock — mutual exclusion with
        writers and the sampler is unchanged). Returns the drain, or
        None when disabled by config.

        Multi-host meshes get a SHARD-AWARE drain (ISSUE 10): flushes
        there are lockstep collectives every process must enter at the
        same loop point, which a free-running thread cannot do — so the
        drain's work becomes ``prepare_rounds`` (host-only assembly of
        padded flush planes, zero collectives) keyed off the STAGED
        backlog, and the agreed-round flush at the chunk boundary only
        pops planes and dispatches. Same zero-copy columnar path as
        single-host, minus nothing."""
        if self._drain is not None:
            return self._drain
        if not self._drain_enabled:
            return None
        from distributed_deep_q_tpu.replay.columnar import IngestDrain
        if self.defer_flush:
            self._drain = IngestDrain(self, lock, self.write_chunk,
                                      work=self.prepare_rounds,
                                      backlog=self._staged_rows)
        else:
            self._drain = IngestDrain(self, lock, self.write_chunk)
        return self._drain

    def stop_drain(self) -> None:
        drain, self._drain = self._drain, None
        if drain is not None:
            drain.close()

    def reset_stream(self, stream: int) -> None:
        """Seal the stream's current slot at a writer identity change
        (actor restart reusing the stream id — SURVEY §5.3 recovery path):
        the slot's last written row gets a truncation boundary so no sampled
        stack or n-step window can straddle the dead actor's half-episode
        and the replacement's first episode."""
        if not (0 <= stream < self.num_streams):
            return
        cycle = self._slot_cycle[stream]
        slot = cycle[self._stream_pos[stream] % len(cycle)]
        self.slots[slot].seal_stream()

    def _flush_rounds_needed(self) -> int:
        backlog = -(-max((self._pending_rows[s] for s in self.local_shards),
                         default=0) // self.write_chunk)
        return len(self._prepared) + backlog

    def _assemble_round(self) -> tuple[np.ndarray, list, int]:
        """Build ONE padded write round from staging: ``write_chunk``
        lanes per LOCAL shard, shards with fewer pending rows padded
        with out-of-bounds indices the scatter drops. Pure host work (no
        device dispatch, no collective) — callable from the drain thread
        under the replay lock. Returns (idx, cols, rows_taken)."""
        k = self.write_chunk
        shards = self.local_shards
        dl = len(shards)
        idx = np.full((dl, k), self.cap_local, np.int32)  # OOB = drop
        cols = [np.zeros((dl, k) + tail, dt)
                for tail, dt in self._stage_columns]
        rows = 0
        for li, s in enumerate(shards):
            if self._columnar:
                st = (self._stages[s]
                      if self._stages is not None else None)
                if st is not None:
                    taken = st.take(k, [idx] + cols, li)
                    self._pending_rows[s] -= taken
                    rows += taken
                continue
            fill = 0
            while self._pending[s] and fill < k:
                entry = self._pending[s][0]
                i_arr = entry[0]
                take = min(len(i_arr), k - fill)
                idx[li, fill:fill + take] = i_arr[:take]
                for col, arr in zip(cols, entry[1:]):
                    col[li, fill:fill + take] = arr[:take]
                fill += take
                self._pending_rows[s] -= take
                rows += take
                if take == len(i_arr):
                    self._pending[s].pop(0)
                else:  # split the chunk, preserving FIFO write order
                    self._pending[s][0] = tuple(
                        a[take:] for a in entry)
        return idx, cols, rows

    def prepare_rounds(self, max_rounds: int | None = None) -> int:
        """Assemble staged rows into padded flush planes WITHOUT
        dispatching them — the shard-aware drain's work unit (ISSUE 10).
        Host-only, so it is safe from a free-running thread even on
        multi-host meshes where the dispatch itself is a lockstep
        collective; the planes go out FIFO-first at the next ``flush()``
        (the fused chunk boundary), so HBM write order is exactly staged
        order. Returns the number of rows moved into planes."""
        rounds = -(-max((self._pending_rows[s] for s in self.local_shards),
                        default=0) // self.write_chunk)
        if max_rounds is not None:
            rounds = min(rounds, int(max_rounds))
        total = 0
        for _ in range(rounds):
            plane = self._assemble_round()
            self._prepared.append(plane)
            self._prepared_rows += plane[2]
            total += plane[2]
        return total

    def flush(self) -> None:
        """Push all staged frames to HBM in fixed-shape chunks.

        Pre-assembled planes (``prepare_rounds``) dispatch first, then
        fresh rounds assemble from staging. Multi-host: the scatter is a
        global-array computation — a collective every process must enter
        the same number of times — so the round count is MAX-agreed
        across processes first (``global_max_int``) and short hosts
        dispatch all-padding chunks. Every process must therefore call
        ``flush()`` at the same loop point (the fused chunk boundary
        does; ingest defers via ``defer_flush``).
        """
        rounds = self._flush_rounds_needed()
        if self._pc > 1:
            from distributed_deep_q_tpu.parallel.multihost import (
                global_max_int)
            rounds = global_max_int(rounds)
        for _ in range(rounds):
            if self._prepared:
                idx, cols, rows = self._prepared.pop(0)
                self._prepared_rows -= rows
            else:
                idx, cols, _ = self._assemble_round()
            self._apply_write(idx, cols)

    def _apply_write(self, idx: np.ndarray, cols: list) -> None:
        """Dispatch one padded write chunk ([local_shards, k] planes) to
        the device ring. Subclasses with extra staged columns (device_per)
        override this to feed their wider scatter program."""
        d, k = self.num_shards, self.write_chunk
        assert len(self.local_shards) == d, (
            "DeviceFrameReplay's own uint8 ring is single-controller; "
            "multi-host pixel runs use the fused DevicePERFrameReplay")
        self.ring = self._write(
            self.ring, idx.reshape(d * k),
            cols[0].reshape((d * k,) + self._stage_columns[0][0]))
