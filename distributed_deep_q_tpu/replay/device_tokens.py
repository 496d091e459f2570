"""Device-resident token-window replay — the ring of the token-window
Q-network (``net.kind = "tokenq"``), beside ``device_sequence.py``.

A slot is one WINDOW of a token rollout: ``T + 1`` int32 tokens (the
state at step t is the prefix ``tok[0..t]``, the action the token
``tok[t+1]``), and per step a float32 reward and one flag byte (bit 0:
the episode ended at this step, bit 1: the step is real, not padding) —
about 9 bytes a step, where the frame rings hold a frame. Tokens,
rewards, flags and the per-window priority row all live in HBM, so the
fused sequence step (``SequenceLearner`` token path) draws windows by
priority, gathers them and writes priorities back without the host: per
chunk the host ships sizes, βs and keys.

Sharding follows the other rings: slots are block-partitioned over the
``dp`` mesh axis, writes go round-robin across shards (each shard a ring
of ``caps_local`` windows plus one scratch slot that absorbs the padding
lanes of a short flush), and the sampler draws ``B/D`` windows a shard.
Single-process only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_deep_q_tpu.parallel.mesh import AXIS_DP
from distributed_deep_q_tpu.replay.prioritized import beta_at

FLAG_DONE = 1
FLAG_VALID = 2


def pack_flags(done: np.ndarray, valid: np.ndarray) -> np.ndarray:
    return (np.asarray(done, bool) * FLAG_DONE
            + np.asarray(valid, bool) * FLAG_VALID).astype(np.uint8)


def unpack_flags(flags: jax.Array, gamma: float):
    """flag bytes → (discount γ·(1-done), validity mask), float32."""
    done = (flags & FLAG_DONE) > 0
    valid = (flags & FLAG_VALID) > 0
    return (jnp.where(done, 0.0, gamma).astype(jnp.float32),
            valid.astype(jnp.float32))


class DeviceTokenReplay:
    """``capacity`` windows of ``seq_len`` steps (+1 token) in HBM, with
    proportional per-window priorities."""

    prioritized = True
    window_kind = "tokens"      # which fused step the sequence learner builds

    def __init__(self, capacity: int, seq_len: int, mesh: Mesh,
                 gamma: float, *, alpha: float = 0.6, beta0: float = 0.4,
                 beta_steps: int = 1_000_000, eps: float = 1e-6,
                 write_chunk: int = 64):
        if jax.process_count() > 1:
            raise NotImplementedError(
                "DeviceTokenReplay is single-process (one host's chips)")
        d = self.num_shards = mesh.shape[AXIS_DP]
        self.mesh = mesh
        self._pc = 1
        self.local_shards = list(range(d))
        self.defer_flush = False
        self.seq_len = t = int(seq_len)
        self.gamma = float(gamma)
        self.caps_local = max(int(capacity) // d, 1)
        self.capacity = self.caps_local * d
        self.slots_local = self.caps_local + 1           # + scratch
        self.alpha, self.beta0 = float(alpha), float(beta0)
        self.beta_steps, self.eps = int(beta_steps), float(eps)
        self.write_chunk = max(int(write_chunk), 1)
        self._cursor = np.zeros(d, np.int64)
        self._sizes = np.zeros(d, np.int64)
        self._next_shard = 0
        self._windows_added = 0
        self._samples = 0
        self._pending: list[list[tuple]] = [[] for _ in range(d)]

        sharded = NamedSharding(mesh, P(AXIS_DP))
        replicated = NamedSharding(mesh, P())
        n = d * self.slots_local

        def init():
            return ({"tokens": jnp.zeros((n, t + 1), jnp.int32),
                     "reward": jnp.zeros((n, t), jnp.float32),
                     "flags": jnp.zeros((n, t), jnp.uint8)},
                    {"prio": jnp.zeros((d * self.caps_local,),
                                       jnp.float32)})

        self.ring, self.dmeta = jax.jit(
            init, out_shardings=(
                {"tokens": sharded, "reward": sharded, "flags": sharded},
                {"prio": sharded}))()
        self.dmaxp = jax.device_put(jnp.ones((), jnp.float32), replicated)

        def write(ring, meta, maxp, idx, tok, rew, flg):
            ring = {"tokens": ring["tokens"].at[idx].set(tok),
                    "reward": ring["reward"].at[idx].set(rew),
                    "flags": ring["flags"].at[idx].set(flg)}
            # a new window enters at the running max priority; scratch
            # lanes (idx == caps_local) fall off the priority row
            prio = meta["prio"].at[idx].set(maxp ** self.alpha, mode="drop")
            return ring, {"prio": prio}

        s = P(AXIS_DP)
        ring_spec = {"tokens": s, "reward": s, "flags": s}
        self._write = jax.jit(jax.named_scope("ddq.write")(shard_map(
            write, mesh=mesh,
            in_specs=(ring_spec, {"prio": s}, P(), s, s, s, s),
            out_specs=(ring_spec, {"prio": s}), check_vma=False)),
            donate_argnums=(0, 1))

    def __len__(self) -> int:
        return int(self._sizes.sum())

    @property
    def steps_added(self) -> int:
        return self._windows_added

    def pending_rows(self) -> int:
        return sum(len(p) for p in self._pending)

    def ready(self, learn_start: int) -> bool:
        return (len(self) + self.pending_rows() >= max(learn_start, 1)
                and bool(((self._sizes > 0) | [len(p) > 0 for p in
                                               self._pending]).all()))

    @property
    def beta(self) -> float:
        return beta_at(self._samples, self.beta0, self.beta_steps)

    def next_betas(self, n: int) -> np.ndarray:
        out = np.empty(n, np.float32)
        for i in range(n):
            self._samples += 1
            out[i] = self.beta
        return out

    def device_inputs(self) -> np.ndarray:
        """Filled-slot counts per shard [d] int32 for the fused sampler."""
        return self._sizes.astype(np.int32)

    # -- write --------------------------------------------------------------

    def add_windows(self, tokens: np.ndarray, reward: np.ndarray,
                    done: np.ndarray, valid: np.ndarray) -> None:
        """Stage ``n`` windows: ``tokens`` [n, T+1] int32, ``reward`` /
        ``done`` / ``valid`` [n, T]. They reach HBM at the next
        ``flush()`` (the fused dispatch calls it)."""
        tokens = np.asarray(tokens, np.int32)
        reward = np.asarray(reward, np.float32)
        flags = pack_flags(done, valid)
        if tokens.shape[1:] != (self.seq_len + 1,) or \
                reward.shape != (len(tokens), self.seq_len):
            raise ValueError(f"window shapes {tokens.shape} {reward.shape} "
                             f"do not fit seq_len {self.seq_len}")
        for i in range(len(tokens)):
            s = self._next_shard
            self._next_shard = (s + 1) % self.num_shards
            local = int(self._cursor[s])
            self._cursor[s] = (local + 1) % self.caps_local
            self._pending[s].append((local, tokens[i], reward[i], flags[i]))
            self._windows_added += 1

    def add_window(self, tokens, reward, done, valid) -> None:
        self.add_windows(np.asarray(tokens)[None], np.asarray(reward)[None],
                         np.asarray(done)[None], np.asarray(valid)[None])

    def flush(self) -> None:
        """Staged windows → HBM, ``write_chunk`` a shard a program."""
        k, t, d = self.write_chunk, self.seq_len, self.num_shards
        rounds = -(-max(len(p) for p in self._pending) // k)
        for _ in range(rounds):
            idx = np.full((d, k), self.caps_local, np.int32)    # scratch
            tok = np.zeros((d, k, t + 1), np.int32)
            rew = np.zeros((d, k, t), np.float32)
            flg = np.zeros((d, k, t), np.uint8)
            for s in range(d):
                take, self._pending[s] = (self._pending[s][:k],
                                          self._pending[s][k:])
                for c, (local, a, r, f) in enumerate(take):
                    idx[s, c], tok[s, c], rew[s, c], flg[s, c] = local, a, r, f
                self._sizes[s] = min(self._sizes[s] + len(take),
                                     self.caps_local)
            self.ring, self.dmeta = self._write(
                self.ring, self.dmeta, self.dmaxp, idx.reshape(-1),
                tok.reshape(d * k, t + 1), rew.reshape(d * k, t),
                flg.reshape(d * k, t))
