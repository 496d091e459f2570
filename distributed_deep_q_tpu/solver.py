"""``Solver`` — the backend-dispatching train-step owner (SURVEY.md §2 [M]).

Reference surface kept verbatim: a ``Solver`` constructed with a
``--backend`` switch that owns the DQN loss/targets and the per-minibatch
``train_step``, plus weight IO (``update`` / ``get_weights``) for the
distribution layer. What changed underneath (north star [M]): the backend is
now a JAX device mesh + compile strategy — ``tpu`` compiles the step for the
accelerator, ``cpu`` runs the identical program on N virtual host devices —
and gradient exchange is an in-step ``lax.pmean`` over ICI instead of a
parameter-server round trip.
"""

from __future__ import annotations

import collections
import contextlib
from collections.abc import Iterator, Mapping
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from distributed_deep_q_tpu import profiling, tracing
from distributed_deep_q_tpu.config import Config
from distributed_deep_q_tpu.models.qnet import build_qnet, init_params
from distributed_deep_q_tpu.parallel.learner import Learner, TrainState
from distributed_deep_q_tpu.parallel.mesh import make_mesh


def sample_key_schedule(seed: int, start_step: int, num_shards: int,
                        chain: int) -> np.ndarray:
    """Device-sampling keys ``[D, chain, 2]`` for grad steps
    ``start_step .. start_step+chain``: key (i, s) is a pure function of
    (seed, global step index, shard), so a chain=k chunk draws
    byte-identical keys to k single-step dispatches, a resumed run
    continues the sequence instead of replaying it, and two replay
    geometries never correlate. One vectorized splitmix64 pass (the r4
    code built a Philox ``Generator`` per step in a Python loop)."""
    steps = start_step + np.arange(chain, dtype=np.uint64)
    lane = (steps[None, :] * np.uint64(num_shards)
            + np.arange(num_shards, dtype=np.uint64)[:, None])
    with np.errstate(over="ignore"):
        x = lane + np.uint64(seed) * np.uint64(0x9E3779B97F4A7C15)
        x = (x + np.uint64(0x9E3779B97F4A7C15))
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    out = np.empty((num_shards, chain, 2), np.uint32)
    out[..., 0] = (x >> np.uint64(32)).astype(np.uint32)
    out[..., 1] = (x & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return out


def next_fused_keys(owner, num_shards: int, chain: int) -> np.ndarray:
    """``sample_key_schedule`` with the owner's anchoring bookkeeping —
    THE single copy of the fused paths' key-state logic, shared by
    ``Solver`` and ``SequenceSolver``. Anchors at the train step the
    fused path FIRST ran from, read once — never per step
    (``int(state.step)`` is a D2H sync) — so a resumed run continues the
    key sequence instead of replaying it."""
    if owner._fused_key_base is None:
        owner._fused_key_base = int(jax.device_get(owner.state.step))
        owner._fused_steps_issued = 0
    out = sample_key_schedule(
        owner.config.train.seed,
        owner._fused_key_base + owner._fused_steps_issued,
        num_shards, chain)
    owner._fused_steps_issued += chain
    return out


def _strip_host_keys(batch: dict[str, Any]) -> dict[str, Any]:
    """Drop host-only bookkeeping (slot indices, sample snapshots) before a
    batch crosses into the jitted step."""
    return {k: v for k, v in batch.items()
            if k not in ("index", "_sampled_at")}


class Solver:
    """Facade over (module, mesh, learner, state).

    API parity with the reference Solver [M]:
      - ``train_step(batch) -> metrics``  (fwd+bwd+optimize, one XLA program)
      - ``update(weights)`` / ``get_weights()``  (numpy weight IO for RPC)
      - ``q_values(obs)``  (the actor-side forward path)
    """

    def __init__(self, config: Config, obs_dim: int = 4,
                 backend: str | None = None):
        if config.net.kind == "r2d2":
            raise NotImplementedError(
                "r2d2 uses the sequence learner "
                "(parallel/sequence_learner.py + SequenceSolver)")
        self.config = config
        if backend is not None:
            # don't mutate the caller's config tree
            import dataclasses
            config = dataclasses.replace(
                config, mesh=dataclasses.replace(config.mesh, backend=backend))
            self.config = config
        self.backend = config.mesh.backend
        self.mesh = make_mesh(config.mesh)
        self.module = build_qnet(config.net)
        self.apply_fn = lambda p, o: self.module.apply({"params": p}, o)
        self.learner = Learner(self.apply_fn, config.train, self.mesh)
        params = init_params(self.module, config.net, config.train.seed, obs_dim)
        self.state: TrainState = self.learner.init_state(params)
        self._treedef = jax.tree_util.tree_structure(params)
        self._qv = jax.jit(self.apply_fn)
        # fused device-PER bookkeeping (see train_steps_device_per)
        self._dp_spec: tuple | None = None
        self._dp_spec_replay = None
        self._fused_key_base: int | None = None
        self._fused_steps_issued = 0

    # -- training ----------------------------------------------------------

    @property
    def step(self) -> int:
        return int(self.state.step)

    def train_step(self, batch: dict[str, np.ndarray]) -> dict[str, Any]:
        """One gradient step on a host batch.

        Returns metrics as *device* scalars plus per-sample ``td_abs`` (PER
        priorities) and the sampled ``index``. Nothing here blocks on the
        step — callers convert with ``float()``/``np.asarray`` only when
        they log / write priorities back, keeping dispatch pipelined.
        """
        self.state, metrics, td_abs = self.learner.train_step(
            self.state, _strip_host_keys(batch))
        out: dict[str, Any] = dict(metrics)
        out["td_abs"] = td_abs
        if "index" in batch:
            out["index"] = batch["index"]
        return out

    def train_step_device_per(self, replay) -> dict[str, Any]:
        """One FUSED prioritized step on a ``DevicePERFrameReplay``:
        sampling, composition, the gradient step, and the priority update
        are one XLA program; the host ships ~bytes of cursors and reads
        back nothing (replay/device_per.py). Metrics come back as device
        scalars."""
        m = self.train_steps_device_per(replay, chain=1)
        # the learning-dynamics plane is per-DISPATCH (no chain axis) —
        # it must not be sliced like the per-step metric rows
        plane = m.pop("learn_plane", None)
        out = {k: v[0] for k, v in m.items()}
        if plane is not None:
            out["learn_plane"] = plane
        return out

    def train_steps_device_per(self, replay,
                               chain: int | None = None) -> dict[str, Any]:
        """``chain`` fused prioritized steps in ONE two-program dispatch
        (lax.scan inside — see ``Learner._build_device_per_step``). Host
        cost per chunk: a flush check, (cached) cursor/size arrays, one
        Philox key draw, two dispatches — amortized over ``chain`` grad
        steps; this is what closes the matched-batch north star's ~400 µs
        of per-step host overhead. Returns metrics stacked ``[chain]``
        (device arrays; ``FusedStepStream`` hands them out as per-step
        views — index or convert one only where its value is read: each
        ``v[i]`` launches two tiny device programs)."""
        chain = chain or max(int(self.config.replay.fused_chain), 1)
        # the span takes the gate with the flush, so every chunk has one:
        # a chunk that found nothing staged (the drain thread got there
        # first) reads as microseconds, not as a span that is missing
        with tracing.span("learner_flush"):
            if replay.pending_rows() or replay.defer_flush:
                # device rows must cover everything the host bookkeeping
                # (cursors/sizes below) claims is written. Multi-host the
                # flush is a lockstep collective with an agreed round
                # count, so EVERY process calls it here even with an
                # empty backlog.
                replay.flush()
        with tracing.span("learner_feed"):
            cursors, sizes = replay.device_inputs()
            betas = replay.next_betas(chain)
            spec = self.device_per_spec(replay)
            keys = self._next_sample_keys(replay.num_shards, chain)
            if replay._pc > 1:
                # multi-controller: ship each plane as this process's
                # local block of the global P('dp') array (keys are
                # computed identically everywhere — slice the local
                # shard rows)
                keys = replay.to_global(
                    np.ascontiguousarray(keys[replay.local_shards]))
                cursors = replay.to_global(np.asarray(cursors))
                sizes = replay.to_global(np.asarray(sizes))
                betas = replay.to_replicated(
                    np.asarray(betas, np.float32))
        out = self.learner.train_steps_device_per(
            self.state, replay.dstate, cursors, sizes, betas, keys, spec)
        # taking the new handles drops the donated ones. Beside busy RPC
        # threads the learner loses the interpreter here for one switch
        # interval (5 ms) a chunk, still under the dispatch lock (PERF.md
        # §5) — so the stretch has a span of its own
        with tracing.span("learner_adopt"):
            self.state, prio, maxp, metrics = out
            replay.dstate = replay.dstate.replace(prio=prio, maxp=maxp)
            return dict(metrics)

    def device_per_spec(self, replay) -> tuple:
        """Static geometry of the fused step for ``replay`` (the key of
        ``Learner.device_per_programs``), cached per replay object."""
        if self._dp_spec is None or self._dp_spec_replay is not replay:
            self._dp_spec = (
                replay.slot_cap, replay.slot_pad, replay.rowb,
                replay._row_len, replay.stack, replay.n_step,
                replay.gamma, tuple(replay.frame_shape),
                self.config.replay.batch_size // replay.num_shards,
                float(self.config.replay.priority_alpha),
                float(self.config.replay.priority_eps),
                replay.num_shards, replay._interpret)
            self._dp_spec_replay = replay
        return self._dp_spec

    def _next_sample_keys(self, num_shards: int, chain: int) -> np.ndarray:
        return next_fused_keys(self, num_shards, chain)

    def fused_executables(self, replay, chain: int) -> dict[str, Any]:
        """The executables of the fused step's two programs (the ones the
        loop ran, where it has run: nothing executes)."""
        return profiling.fused_executables(self, replay, chain)

    def fused_gauges(self) -> dict[str, int]:
        """Static gauges of the fused step for a train loop's log rows:
        ``train/unpack_planes`` 1 = its train program unpacks the pixel
        windows by byte planes, 0 = by a bitcast to uint8
        (``Learner.unpack_planes``); nothing before a fused step was
        built."""
        planes = self.learner.unpack_planes
        return {} if planes is None else {"train/unpack_planes": planes}

    # -- inference (actor path) -------------------------------------------

    def q_values(self, obs: np.ndarray) -> np.ndarray:
        if obs.ndim == 1 or (self.config.net.kind != "mlp" and obs.ndim == 3):
            obs = obs[None]
        return np.asarray(self._qv(self.state.params, obs))

    def act(self, obs: np.ndarray, epsilon: float,
            rng: np.random.Generator) -> int:
        """ε-greedy action — the reference actor policy (SURVEY §3.3 [M])."""
        if rng.random() < epsilon:
            return int(rng.integers(self.config.net.num_actions))
        return int(np.argmax(self.q_values(obs)[0]))

    # -- weight IO (reference parity: QNet/PS serialization surface) -------

    def get_weights(self) -> list[np.ndarray]:
        return [np.asarray(x)
                for x in jax.tree_util.tree_leaves(self.state.params)]

    def update(self, weights: list[np.ndarray]) -> None:
        """Install new parameters (reference ``Solver.update`` [M])."""
        params = jax.tree_util.tree_unflatten(self._treedef, list(weights))
        params = jax.device_put(params, self.learner._replicated)
        self.state = self.state.replace(params=params)

    set_weights = update


class _StepRow(Mapping):
    """One grad step's metrics: a read-only view of row ``i`` of a chunk's
    stacked ``[chain]`` device arrays. Making one launches nothing;
    ``row[k]`` is ``chunk[k][i]`` — ONE slice of ONE array (two tiny
    device programs, ~0.35 ms of dispatch on the chip), paid by the
    caller that reads the value, when it reads it. Everything else a
    dict of the chunk's keys answers (``keys``, iteration, ``len``,
    ``in``, ``items``, ``dict(row)``) comes from ``Mapping``."""

    __slots__ = ("_chunk", "_i")

    def __init__(self, chunk: dict[str, Any], i: int):
        self._chunk = chunk
        self._i = i

    def __getitem__(self, key: str) -> Any:
        return self._chunk[key][self._i]

    def __iter__(self) -> Iterator[str]:
        return iter(self._chunk)

    def __len__(self) -> int:
        return len(self._chunk)

    def __contains__(self, key: object) -> bool:
        return key in self._chunk       # Mapping's default would slice


class FusedStepStream:
    """Per-grad-step metrics from chained fused-PER dispatches.

    Both train loops consume the fused path one GRAD STEP at a time (their
    bookkeeping — priority write-back cadence, checkpoints, logging — is
    per-step), while the device runs ``chain`` scanned steps per dispatch.
    This owns the bridge in ONE place: dispatch a chunk of
    ``min(chain, steps_left)`` steps whenever the previous chunk is
    exhausted (the tail clamp keeps the optimizer-step total exact), then
    hand out the chunk's stacked metrics row by row. The row index is
    easy to get subtly wrong in hand-maintained copies — an off-by-one
    would attribute metrics to the neighboring grad step.

    A row is a VIEW (``_StepRow``): handing it out launches no device
    program, and reading ``row[k]`` slices that one key then — the loops
    read ``loss`` / ``q_mean`` once a log row, so the eleven-key token
    family no longer pays 22 tiny programs a step for values nobody reads.

    ``dispatch_lock`` (optional lock, e.g. the ReplayFeed
    server's ``replay_lock``) is held across the dispatch only — the
    donated device state must not be swapped mid-dispatch, but writers get
    the window while the chunk executes on device. ``timer`` is the train
    loop's ``StepTimer`` (dispatch phase attribution).
    """

    # How far the host may run ahead of the chip, in chunks: before chunk
    # k+1 is dispatched, chunk k-1 has finished. Nothing in
    # ``train_steps_device_per`` waits for the device, so without a bound
    # the host queues chunks until the runtime's own launch queue stops it
    # — minutes of work in flight past a deadline in the token family,
    # and in ``train_distributed`` a dispatch that blocks while holding
    # ``replay_lock``. Two, not one: with one chunk executing or queued
    # while the host prepares the next, the device never waits for the
    # host as long as a chunk's device time exceeds the host's dispatch
    # time; a bound of one serialises the two.
    RUN_AHEAD_CHUNKS = 2

    def __init__(self, solver: Solver, replay, chain: int,
                 dispatch_lock=None, timer=None):
        self._solver = solver
        self._replay = replay
        self.chain = max(int(chain), 1)
        self._lock = dispatch_lock
        self._timer = timer
        self._chunk: dict[str, Any] | None = None
        self._len = 0
        self._pending = 0
        # one metric array of each chunk still allowed in flight, oldest
        # first: what ``_wait_for_room`` blocks on
        self._in_flight: collections.deque = collections.deque()
        # learning-dynamics planes (cfg.train.learn_metrics): one device
        # array per dispatched chunk, popped out of the chunk so a row
        # never carries the odd-shaped leaf;
        # drained by the train loop at log cadence (drain_planes)
        self._planes: list[Any] = []
        profiling.register_programs(self, FusedStepStream._executables)

    def _executables(self) -> dict[str, Any]:
        """What a ``TraceWindow`` writes the scope tables of."""
        return self._solver.fused_executables(self._replay,
                                              self._len or self.chain)

    def drain_planes(self) -> list[Any]:
        """Hand back (and clear) the accumulated learning-dynamics
        planes — still device arrays; the caller converts when folding
        (``LearnAccumulator.ingest``), at log cadence, never per step."""
        out, self._planes = self._planes, []
        return out

    def _wait_for_room(self) -> None:
        """Hold the next dispatch until at most ``RUN_AHEAD_CHUNKS - 1``
        chunks are unfinished. Called with no lock held and outside the
        ``dispatch`` phase: the wait releases the interpreter, and RPC
        writers keep ``replay_lock`` while the learner sleeps here."""
        with tracing.span("learner_wait"):
            if len(self._in_flight) >= self.RUN_AHEAD_CHUNKS:
                jax.block_until_ready(self._in_flight.popleft())

    def next(self, steps_left: int) -> Mapping[str, Any]:
        """Metrics for one grad step (a ``_StepRow``: nothing is sliced
        until a key is read); dispatches a fresh chunk as needed.

        ``steps_left`` counts THIS step: the final partial chunk compiles
        one extra (smaller) program pair — pick totals divisible by
        ``fused_chain`` to avoid it.
        """
        if self._pending == 0:
            assert int(steps_left) >= 1, (
                f"steps_left={steps_left}: dispatching with a non-positive "
                "budget would silently run an extra optimizer step")
            self._len = min(self.chain, int(steps_left))
            self._wait_for_room()
            # the learner's wait for the lock is its own span
            # (lock_wait), outside the dispatch phase as before
            lock = (tracing.locked(self._lock) if self._lock is not None
                    else contextlib.nullcontext())
            phase = (self._timer.phase("dispatch") if self._timer
                     else contextlib.nullcontext())
            with tracing.span("learner_chunk"):
                with lock, phase:
                    self._chunk = self._solver.train_steps_device_per(
                        self._replay, chain=self._len)
                plane = self._chunk.pop("learn_plane", None)
                if plane is not None:
                    self._planes.append(plane)
            self._in_flight.append(next(iter(self._chunk.values())))
            self._pending = self._len
        with tracing.span("learner_slice"):
            row = _StepRow(self._chunk, self._len - self._pending)
        self._pending -= 1
        return row
