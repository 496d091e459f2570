"""DQN loss construction — the compute core the reference's ``Solver`` owns.

The reference Solver "builds Bellman targets (r + γ·max_a' Q_target(s',a')),
computes loss, runs fwd/bwd, extracts grads" (SURVEY.md §2 "Solver" [M][R]).
Here targets/loss are pure jax functions, differentiated by
``jax.value_and_grad`` inside the jitted train step, so forward+backward+
optimizer compile into a single XLA program (no per-minibatch Python↔C++
boundary like the reference's pycaffe hot loop, SURVEY §3.1).

All functions are shape-static and elementwise-fusable; XLA folds them into
the matmul epilogues on TPU. The optional Pallas fused variant lives in
``ops/pallas_kernels.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def huber(x: jax.Array, delta: float = 1.0) -> jax.Array:
    """Huber loss elementwise: quadratic within ±delta, linear outside."""
    abs_x = jnp.abs(x)
    quad = jnp.minimum(abs_x, delta)
    return 0.5 * quad * quad + delta * (abs_x - quad)


def bellman_targets(
    reward: jax.Array,           # [B] float32 (n-step summed on host)
    discount: jax.Array,         # [B] float32: γ^n · (1 - done)
    q_next_target: jax.Array,    # [B, A] target-net Q(s')
    q_next_online: jax.Array | None = None,  # [B, A] online Q(s') for DDQN
    double: bool = False,
) -> jax.Array:
    """r + γⁿ·(1-done)·Q⁻(s', a*) with a* from online net when ``double``.

    Double-DQN (van Hasselt 2016) decouples action selection (online net)
    from evaluation (target net); vanilla DQN maxes the target net directly.
    """
    if double:
        assert q_next_online is not None
        a_star = jnp.argmax(q_next_online, axis=-1)
        q_sel = jnp.take_along_axis(
            q_next_target, a_star[:, None], axis=-1)[:, 0]
    else:
        q_sel = jnp.max(q_next_target, axis=-1)
    return reward + discount * q_sel


def dqn_loss(
    q: jax.Array,         # [B, A] online Q(s)
    actions: jax.Array,   # [B] int32
    targets: jax.Array,   # [B] float32 (stop-gradient applied here)
    weights: jax.Array,   # [B] importance weights (ones when uniform)
    delta: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Weighted Huber TD loss. Returns (scalar loss, |TD| for PER updates)."""
    q_sa = jnp.take_along_axis(q, actions[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    td = q_sa - jax.lax.stop_gradient(targets)
    loss = jnp.mean(weights * huber(td, delta))
    return loss, jnp.abs(jax.lax.stop_gradient(td))


def value_rescale(x: jax.Array, eps: float = 1e-3) -> jax.Array:
    """R2D2 invertible value rescaling h(x) = sign(x)(√(|x|+1)−1) + εx
    (Kapturowski et al. 2019, from Pohlen et al. 2018) — lets the recurrent
    learner train on unclipped rewards with bounded targets."""
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def value_rescale_inv(x: jax.Array, eps: float = 1e-3) -> jax.Array:
    """Analytic inverse of ``value_rescale``."""
    return jnp.sign(x) * (
        jnp.square((jnp.sqrt(1.0 + 4.0 * eps * (jnp.abs(x) + 1.0 + eps))
                    - 1.0) / (2.0 * eps)) - 1.0)


def sequence_bellman_targets(
    reward: jax.Array,          # [B, T]
    discount: jax.Array,        # [B, T]: γ·(1-done) per step
    q_next_target: jax.Array,   # [B, T, A] target net Q(s_{t+1})
    q_next_online: jax.Array | None = None,  # [B, T, A] for Double-DQN
    double: bool = True,
    rescale: bool = True,
) -> jax.Array:
    """Per-step targets h(r + γ·h⁻¹(Q⁻(s', a*))) over a sequence window."""
    if double:
        assert q_next_online is not None
        a_star = jnp.argmax(q_next_online, axis=-1)
    else:
        a_star = jnp.argmax(q_next_target, axis=-1)
    q_sel = jnp.take_along_axis(q_next_target, a_star[..., None],
                                axis=-1)[..., 0]
    if rescale:
        return value_rescale(reward + discount * value_rescale_inv(q_sel))
    return reward + discount * q_sel


def span_returns(
    reward: jax.Array,      # [B, T] per step
    discount: jax.Array,    # [B, T]: γ·(1-done) per step
    mask: jax.Array,        # [B, T] 1.0 on valid steps
    start: jax.Array,       # [B, G] int32: a span's first step
    length: jax.Array,      # [B, G] int32: its steps, 1..max_len
    max_len: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Uncorrected n-step returns over spans whose length is DATA (from
    one decision of a block-diffusion window to the next): for span
    ``[start, start + length)`` → (``R = Σ_k (Π_{i<k} discount[start + i])
    reward[start + k]``, ``Γ = Π_i discount[start + i]``, valid = ``mask``
    is 1 on every step of the span and the span ends inside the window),
    each [B, G]. Exact: a loop over the ``max_len`` offsets a span can
    have, so an episode end inside a span cuts the sum there (its
    discount is 0) and the bootstrap with it."""
    t = reward.shape[1]
    ret = jnp.zeros(start.shape, jnp.float32)
    gamma = jnp.ones(start.shape, jnp.float32)
    valid = start + length <= t
    for k in range(max_len):
        inside = k < length
        at = jnp.minimum(start + k, t - 1)
        take = lambda x: jnp.take_along_axis(x, at, axis=1)  # noqa: E731
        ret = ret + jnp.where(inside, gamma * take(reward), 0.0)
        gamma = gamma * jnp.where(inside, take(discount), 1.0)
        valid = valid & (~inside | (take(mask) > 0))
    return ret, gamma, valid.astype(jnp.float32)


def sequence_dqn_loss(
    q: jax.Array,         # [B, T, A] online Q over the training window
    actions: jax.Array,   # [B, T] int32
    targets: jax.Array,   # [B, T] float32
    mask: jax.Array,      # [B, T] 1.0 on valid steps, 0.0 past episode end
    weights: jax.Array,   # [B] per-sequence importance weights
    delta: float = 1.0,
    eta: float = 0.9,
) -> tuple[jax.Array, jax.Array]:
    """R2D2 sequence TD loss with validity masking.

    Returns (scalar loss, per-sequence priority) where priority follows the
    R2D2 mixed max/mean rule: η·max_t|TD| + (1-η)·mean_t|TD| (Kapturowski
    et al. 2019), computed over valid steps only.
    """
    q_sa = jnp.take_along_axis(q, actions[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    td = (q_sa - jax.lax.stop_gradient(targets)) * mask
    per_t = huber(td, delta) * mask
    denom = jnp.maximum(jnp.sum(mask, axis=1), 1.0)
    per_seq = jnp.sum(per_t, axis=1) / denom
    loss = jnp.mean(weights * per_seq)

    abs_td = jnp.abs(jax.lax.stop_gradient(td))
    max_td = jnp.max(abs_td, axis=1)
    mean_td = jnp.sum(abs_td, axis=1) / denom
    priority = eta * max_td + (1.0 - eta) * mean_td
    return loss, priority
