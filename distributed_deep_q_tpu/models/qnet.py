"""Q-network model zoo (Flax) + the ``QNet`` wrapper.

Replaces the reference's Caffe net wrapper layer (SURVEY.md §1 L1 [M]): the
Caffe ``.prototxt`` topologies become Flax modules, and ``QNet`` keeps the
reference wrapper's surface — ``forward``, weight get/set as numpy — while
backward/optimize live in the jitted train step (``parallel/learner.py``).

Topologies (SURVEY.md §2 "Q-net definition" [P]):
- ``MlpQNet``     — 2-layer MLP for vector envs (CartPole smoke, config 1).
- ``NatureCnnQNet`` — Nature-DQN CNN: 84×84×stack → conv(32,8,4) →
  conv(64,4,2) → conv(64,3,1) → FC512 → FC|A|; optional dueling heads.
- ``R2d2QNet``    — recurrent Q-net: CNN/MLP torso → LSTM(512) → (dueling)
  head, applied over [B, T, ...] sequences (config 5).

TPU notes: conv/FC run in ``compute_dtype`` (bfloat16 on TPU keeps the MXU
in its native precision); parameters stay float32. uint8 pixel input is
normalized in-module so actors ship bytes, not floats, over RPC.
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_deep_q_tpu.config import NetConfig

Carry = Any  # LSTM carry pytree


def _to_compute(x: jax.Array, dtype: jnp.dtype) -> jax.Array:
    """Cast input to compute dtype; normalize uint8 pixels to [0, 1]."""
    if x.dtype == jnp.uint8:
        return x.astype(dtype) / np.asarray(255.0, dtype)
    return x.astype(dtype)


class _Head(nn.Module):
    """Final Q head: plain FC|A| or dueling value/advantage streams."""

    num_actions: int
    dueling: bool
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h: jax.Array) -> jax.Array:
        with jax.named_scope("ddq.fc"):
            if not self.dueling:
                q = nn.Dense(self.num_actions, dtype=self.dtype, name="q")(h)
            else:
                v = nn.Dense(1, dtype=self.dtype, name="value")(h)
                a = nn.Dense(self.num_actions, dtype=self.dtype,
                             name="advantage")(h)
                q = v + a - jnp.mean(a, axis=-1, keepdims=True)
            return q.astype(jnp.float32)  # Q-values / losses always fp32


class MlpQNet(nn.Module):
    """2-layer (by default) MLP Q-network — config 1 (CartPole smoke) [M]."""

    num_actions: int
    hidden: Sequence[int] = (64, 64)
    dueling: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, obs: jax.Array) -> jax.Array:
        h = MlpTorso(tuple(self.hidden), self.dtype, name="torso")(obs)
        return _Head(self.num_actions, self.dueling, self.dtype)(h)


class _NatureTorso(nn.Module):
    """The Nature-DQN conv stack (shared by CNN and R2D2 nets)."""

    dtype: jnp.dtype

    @nn.compact
    def __call__(self, frames: jax.Array) -> jax.Array:
        # frames: [B, H, W, stack] uint8 (or float)
        # ``ddq.*``: the names a trace's device time is given to
        # (profiling.scope_table); they touch no parameter path
        with jax.named_scope("ddq.conv_in"):
            h = _to_compute(frames, self.dtype)     # conv 1's input scaling
            h = nn.relu(nn.Conv(32, (8, 8), strides=(4, 4), padding="VALID",
                                dtype=self.dtype, name="conv1")(h))
        with jax.named_scope("ddq.conv_mid"):
            h = nn.relu(nn.Conv(64, (4, 4), strides=(2, 2), padding="VALID",
                                dtype=self.dtype, name="conv2")(h))
            h = nn.relu(nn.Conv(64, (3, 3), strides=(1, 1), padding="VALID",
                                dtype=self.dtype, name="conv3")(h))
        with jax.named_scope("ddq.fc"):
            h = h.reshape(h.shape[0], -1)
            h = nn.relu(nn.Dense(512, dtype=self.dtype, name="fc4")(h))
        return h


class NatureCnnQNet(nn.Module):
    """Nature-DQN CNN Q-network — configs 2–4 [M][P]."""

    num_actions: int
    dueling: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, frames: jax.Array) -> jax.Array:
        h = _NatureTorso(self.dtype, name="torso")(frames)
        return _Head(self.num_actions, self.dueling, self.dtype)(h)


class R2d2QNet(nn.Module):
    """Recurrent (LSTM) Q-network over sequences — config 5 (stretch) [M].

    ``__call__`` consumes ``obs`` of shape [B, T, ...] plus an LSTM carry and
    returns (q [B, T, A], final carry). Burn-in is handled by the learner
    (``ops/losses.py`` / sequence train step) by running a stop-gradient
    prefix; the module itself is shape-static and scan-compiled for XLA.
    """

    num_actions: int
    lstm_size: int = 512
    torso: str = "nature_cnn"  # nature_cnn | mlp
    hidden: Sequence[int] = (64, 64)
    dueling: bool = True
    dtype: jnp.dtype = jnp.float32

    def initial_state(self, batch_size: int) -> Carry:
        # OptimizedLSTMCell carry is (c, h); zeros, no params needed — kept
        # free of module binding so actors/learner can build carries cheaply.
        z = jnp.zeros((batch_size, self.lstm_size), jnp.float32)
        return (z, z)

    @nn.compact
    def __call__(self, obs: jax.Array, carry: Carry) -> tuple[jax.Array, Carry]:
        b, t = obs.shape[0], obs.shape[1]
        flat = obs.reshape((b * t,) + obs.shape[2:])
        if self.torso == "nature_cnn":
            feats = _NatureTorso(self.dtype, name="torso")(flat)
        else:
            feats = MlpTorso(self.hidden, self.dtype, name="torso")(flat)
        feats = feats.reshape(b, t, -1).astype(jnp.float32)

        # nn.RNN = flax-lifted lax.scan over time — compiler-friendly static
        # loop (XLA sees one fused scan body, no Python unrolling).
        rnn = nn.RNN(nn.OptimizedLSTMCell(self.lstm_size), name="lstm")
        carry, hs = rnn(feats, initial_carry=carry, return_carry=True)
        q = _Head(self.num_actions, self.dueling, self.dtype, name="head")(
            hs.reshape(b * t, -1)).reshape(b, t, self.num_actions)
        return q, carry


class MlpTorso(nn.Module):
    hidden: Sequence[int]
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, obs: jax.Array) -> jax.Array:
        h = _to_compute(obs.reshape(obs.shape[0], -1), self.dtype)
        for i, width in enumerate(self.hidden):
            h = nn.relu(nn.Dense(width, dtype=self.dtype, name=f"fc{i}")(h))
        return h


# ---------------------------------------------------------------------------
# Stacked-weight applications (op-count surgery)
# ---------------------------------------------------------------------------
#
# A DQN step needs up to three torso forwards per minibatch — θ on s, θ on
# s' (Double-DQN action selection), θ⁻ on s' — and the compiled step is
# op-count-bound at small batch (~4.5 µs fixed cost per scheduled op on
# the measured chip, PERF.md §3). Stacking θ and θ⁻ on a leading axis and
# ``vmap``-ing the module apply collapses the three conv/dense chains into
# ONE: jax's conv batching rule lowers a batched-kernel convolution to a
# single grouped convolution, and batched Dense layers become one batched
# ``dot_general``, so the scheduled conv count is that of a single
# forward. Numerics are unchanged — each group/batch slice computes
# exactly the per-net program (equivalence held by tests/test_op_surgery.py).


def stack_pytrees(a: Any, b: Any) -> Any:
    """Leaf-wise ``jnp.stack([a, b])`` of two same-structure pytrees."""
    return jax.tree.map(lambda x, y: jnp.stack([x, y]), a, b)


def stacked_q_forwards(
    apply_fn, params: Any, target_params: Any,
    obs: jax.Array, next_obs: jax.Array, double: bool,
) -> tuple[jax.Array, jax.Array | None, jax.Array]:
    """The train step's Q-forwards as ONE stacked application.

    Returns ``(q, q_next_online, q_next_target)`` — ``q_next_online`` is
    ``None`` when ``double`` is off, and carries ``stop_gradient`` (action
    selection must not backprop into the online net) when on.

    Double-DQN feeds both nets the same ``concat([s, s'])`` batch (the
    θ⁻-on-s quarter is computed and discarded — at the small batches where
    this path is selected the step is op-count-bound, not flop-bound, so
    one wasted forward quarter buys a halved schedule); vanilla DQN stacks
    ``[s, s']`` against ``[θ, θ⁻]`` with no wasted work at all.
    """
    return stacked_q_apply(apply_fn, stack_pytrees(params, target_params),
                           obs, next_obs, double)


def stacked_q_apply(
    apply_fn, stacked: Any,
    obs: jax.Array, next_obs: jax.Array, double: bool,
) -> tuple[jax.Array, jax.Array | None, jax.Array]:
    """``stacked_q_forwards`` against an ALREADY-stacked ``[2, ...]``-leaf
    tree — the entry point for callers that hold θ/θ⁻ pre-stacked (the
    chained device-PER program's flat parameter plane, where each stacked
    leaf is a contiguous plane slice and re-stacking would cost a concat
    per leaf per grad step)."""
    if double:
        b = obs.shape[0]
        both = jnp.concatenate([obs, next_obs], axis=0)
        qq = jax.vmap(apply_fn, in_axes=(0, None))(stacked, both)
        q = qq[0, :b]
        q_next_online = jax.lax.stop_gradient(qq[0, b:])
        q_next_target = qq[1, b:]
        return q, q_next_online, q_next_target
    qq = jax.vmap(apply_fn)(stacked, jnp.stack([obs, next_obs]))
    return qq[0], None, qq[1]


def r2d2_torso_module(module: "R2d2QNet") -> nn.Module:
    """The (unbound) torso submodule an ``R2d2QNet`` builds internally —
    applying it standalone against the ``params["torso"]`` subtree is
    exactly the in-module application (same scope, same leaves)."""
    if module.torso == "nature_cnn":
        return _NatureTorso(module.dtype)
    return MlpTorso(tuple(module.hidden), module.dtype)


def r2d2_features(module: "R2d2QNet", torso_params: Any,
                  obs: jax.Array) -> jax.Array:
    """Conv/MLP torso over a [B, T, ...] sequence block as ONE flattened
    [B·T] batch → [B, T, F] float32 features. This is the hoisted half of
    ``R2d2QNet.__call__``: the torso has no recurrence, so it never needs
    to run inside the time scan — one large MXU-friendly batch replaces
    per-window applications, and the conv count is independent of T."""
    b, t = obs.shape[0], obs.shape[1]
    flat = obs.reshape((b * t,) + obs.shape[2:])
    feats = r2d2_torso_module(module).apply({"params": torso_params}, flat)
    return feats.reshape(b, t, -1).astype(jnp.float32)


def stacked_r2d2_features(module: "R2d2QNet", params: Any,
                          target_params: Any, obs: jax.Array) -> jax.Array:
    """θ and θ⁻ torso features for the SAME [B, T, ...] block in one
    stacked-weight application → [2, B, T, F] (0 = online, 1 = target)."""
    stacked = stack_pytrees(params["torso"], target_params["torso"])
    return jax.vmap(lambda p: r2d2_features(module, p, obs))(stacked)


def r2d2_param_split(params: Any) -> tuple[Any, Any, Any]:
    """Split an ``R2d2QNet`` param tree into (torso, lstm_cell, head)
    subtrees. The LSTM cell's scope name is flax-version-dependent (the
    ``nn.RNN`` wrapper is scope-transparent here, so the cell lands at the
    top level under its class-derived name), so it is located as the one
    key that is neither ``torso`` nor ``head``."""
    (lstm_key,) = [k for k in params if k not in ("torso", "head")]
    return params["torso"], params[lstm_key], params["head"]


def _lstm_scan(module: "R2d2QNet", lstm_params: Any, feats: jax.Array,
               carry: Carry, with_outputs: bool) -> tuple[Carry, Any]:
    """``lax.scan`` of the bare LSTM cell over [B, T, F] features — the
    per-step math is exactly the cell ``R2d2QNet`` scans, applied against
    the same param leaves, so values match the in-module RNN bitwise."""
    cell = nn.OptimizedLSTMCell(module.lstm_size)

    def step(c, x):
        c2, y = cell.apply({"params": lstm_params}, c, x)
        return c2, (y if with_outputs else None)

    carry, hs = jax.lax.scan(step, carry, jnp.swapaxes(feats, 0, 1))
    return carry, (jnp.swapaxes(hs, 0, 1) if with_outputs else None)


def r2d2_burn_carry(module: "R2d2QNet", lstm_params: Any,
                    feats: jax.Array, carry: Carry) -> Carry:
    """LSTM-only burn-in: advance the carry over [B, T, F] features. The
    head contributes nothing to the carry, so burn-in never computes Q."""
    carry, _ = _lstm_scan(module, lstm_params, feats, carry,
                          with_outputs=False)
    return carry


def r2d2_recur(module: "R2d2QNet", lstm_params: Any, head_params: Any,
               feats: jax.Array, carry: Carry,
               ) -> tuple[jax.Array, Carry]:
    """LSTM + head over [B, T, F] features → (q [B, T, A], carry) — the
    recurrent half of ``R2d2QNet.__call__``, fed precomputed features so
    only the LSTM lives inside the time scan."""
    b, t = feats.shape[0], feats.shape[1]
    carry, hs = _lstm_scan(module, lstm_params, feats, carry,
                           with_outputs=True)
    q = _Head(module.num_actions, module.dueling, module.dtype).apply(
        {"params": head_params}, hs.reshape(b * t, -1))
    return q.reshape(b, t, module.num_actions), carry


# ---------------------------------------------------------------------------
# Factory + parameter helpers
# ---------------------------------------------------------------------------


def build_qnet(cfg: NetConfig) -> nn.Module:
    dtype = jnp.dtype(cfg.compute_dtype)
    if cfg.kind == "mlp":
        return MlpQNet(cfg.num_actions, tuple(cfg.hidden), cfg.dueling, dtype)
    if cfg.kind == "nature_cnn":
        return NatureCnnQNet(cfg.num_actions, cfg.dueling, dtype)
    if cfg.kind == "r2d2":
        if cfg.torso not in ("nature_cnn", "mlp"):
            raise ValueError(f"unknown r2d2 torso: {cfg.torso!r}")
        return R2d2QNet(cfg.num_actions, cfg.lstm_size, cfg.torso,
                        tuple(cfg.hidden), cfg.dueling, dtype)
    raise ValueError(f"unknown net kind: {cfg.kind!r}")


def example_obs(cfg: NetConfig, batch_size: int = 1,
                obs_dim: int = 4) -> np.ndarray:
    """A zero observation batch with the right shape/dtype for ``cfg``.

    MLP nets (and r2d2 with the mlp torso) take flat [B, obs_dim] vectors;
    conv torsos take [B, H, W, stack] uint8 frames.
    """
    if cfg.kind == "mlp" or (cfg.kind == "r2d2" and cfg.torso == "mlp"):
        return np.zeros((batch_size, obs_dim), np.float32)
    h, w = cfg.frame_shape
    return np.zeros((batch_size, h, w, cfg.stack), np.uint8)


def init_params(module: nn.Module, cfg: NetConfig, seed: int = 0,
                obs_dim: int = 4) -> Any:
    rng = jax.random.PRNGKey(seed)
    obs = example_obs(cfg, 1, obs_dim)
    if cfg.kind == "r2d2":
        obs = obs[:, None]  # [B, T=1, ...]
        carry = R2d2QNet(cfg.num_actions, cfg.lstm_size).initial_state(1)
        return module.init(rng, obs, carry)["params"]
    return module.init(rng, obs)["params"]


class QNet:
    """Reference-parity net wrapper (SURVEY.md §1 L1, §2 "QNet" [M]).

    The reference ``QNet`` binds a Caffe net: minibatch → blobs, forward /
    backward, weight/grad IO as numpy. Here forward is a jitted Flax apply;
    backward lives inside the learner's train step (jax.value_and_grad), and
    the numpy weight IO surface (``get_weights`` / ``set_weights``) is what
    actors and the RPC layer use to ship θ.
    """

    def __init__(self, cfg: NetConfig, seed: int = 0, obs_dim: int = 4):
        self.cfg = cfg
        self.module = build_qnet(cfg)
        self.params = init_params(self.module, cfg, seed, obs_dim)
        self._treedef = jax.tree_util.tree_structure(self.params)
        if cfg.kind == "r2d2":
            self._fwd = jax.jit(
                lambda p, o, c: self.module.apply({"params": p}, o, c))
        else:
            self._fwd = jax.jit(
                lambda p, o: self.module.apply({"params": p}, o))

    # -- forward -----------------------------------------------------------
    def forward(self, obs: np.ndarray, carry: Carry | None = None):
        """Q-values for a batch of observations (adds batch dim if absent)."""
        if self.cfg.kind == "r2d2":
            # r2d2 callers pass explicit [B, T, ...] plus a carry.
            if carry is None:
                carry = self.initial_state(obs.shape[0])
            return self._fwd(self.params, obs, carry)
        squeeze = False
        expected = 2 if self.cfg.kind == "mlp" else 4
        if obs.ndim == expected - 1:
            obs, squeeze = obs[None], True
        q = self._fwd(self.params, obs)
        return q[0] if squeeze else q

    def argmax_action(self, obs: np.ndarray) -> int:
        return int(np.argmax(np.asarray(self.forward(obs))))

    def initial_state(self, batch_size: int) -> Carry:
        assert self.cfg.kind == "r2d2"
        return R2d2QNet(self.cfg.num_actions, self.cfg.lstm_size).initial_state(
            batch_size)

    # -- weight IO (numpy; RPC serialization surface) ----------------------
    def get_weights(self) -> list[np.ndarray]:
        return [np.asarray(x) for x in jax.tree_util.tree_leaves(self.params)]

    def set_weights(self, flat: list[np.ndarray]) -> None:
        self.params = jax.tree_util.tree_unflatten(self._treedef, list(flat))

    def num_params(self) -> int:
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(self.params))
