"""Pallas TPU kernels — the fused masked-Huber TD loss.

The reference's loss math lives inside Caffe's C++/CUDA layers (SURVEY.md §1
L0 [P][R]); the rebuilt compute path normally leans on XLA to fuse
``ops/losses.py`` into the matmul epilogues. This module is the
hand-scheduled alternative for the loss tail: ONE VMEM-resident kernel that
fuses the action gather (one-hot contraction), TD residual, Huber, and the
importance-weighted mean — plus a matching hand-written backward kernel so
the whole loss is a single fused region in both directions
(``jax.custom_vjp``).

Enabled with ``TrainConfig.use_pallas_loss``; the learner falls back to the
jnp path otherwise (both are tested for equivalence in
``tests/test_pallas.py``). ``interpret`` comes from the caller, decided
once per mesh by ``parallel.mesh.pallas_interpret`` — the same rule the
ring DMA kernels follow — so the kernel is Mosaic-compiled on a TPU mesh
and interpreted on the CPU test mesh.

Shapes are the per-device view inside ``shard_map``: ``q`` is [B, A] with B
the per-device batch. Everything fits in VMEM by construction (B ≤ a few
hundred, A ≤ 18), so there is no grid — one program, full blocks, which is
exactly the right schedule for a loss tail this small.

MEASUREMENT: not measured on today's code. Whether the kernel beats the
XLA-fused jnp path on the chip is a question for the benchmark; it ships
default OFF (``use_pallas_loss=False``) as the tested hand-written-kernel
path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _huber_pieces(td: jax.Array, delta: float):
    abs_td = jnp.abs(td)
    quad = jnp.minimum(abs_td, delta)
    return abs_td, 0.5 * quad * quad + delta * (abs_td - quad)


def _fwd_kernel(q_ref, a_ref, t_ref, w_ref, loss_ref, td_ref, *, delta: float):
    """loss = mean_b w_b · huber(q[b, a_b] − t_b); td_ref = |TD| per sample."""
    q = q_ref[:]                                            # [B, A]
    col = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1)   # [B, A]
    onehot = (col == a_ref[:]).astype(q.dtype)              # a_ref: [B, 1]
    q_sa = jnp.sum(q * onehot, axis=1, keepdims=True)       # [B, 1]
    td = q_sa - t_ref[:]
    abs_td, hub = _huber_pieces(td, delta)
    loss_ref[0, 0] = jnp.mean(w_ref[:] * hub)
    td_ref[:] = abs_td


def _bwd_kernel(q_ref, a_ref, t_ref, w_ref, g_ref, dq_ref, *, delta: float):
    """dL/dq[b, a] = g · w_b · huber'(TD_b) / B at a = a_b, else 0.

    huber'(x) = clip(x, −delta, +delta) — recomputing TD here is cheaper
    than round-tripping it through HBM (free recompute vs. bandwidth).
    """
    q = q_ref[:]
    col = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1)
    onehot = (col == a_ref[:]).astype(q.dtype)
    q_sa = jnp.sum(q * onehot, axis=1, keepdims=True)
    td = q_sa - t_ref[:]
    dhub = jnp.clip(td, -delta, delta)
    coeff = g_ref[0, 0] * w_ref[:] * dhub / q.shape[0]      # [B, 1]
    dq_ref[:] = onehot * coeff


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_dqn_loss(q, actions, targets, weights, delta: float = 1.0,
                   interpret: bool = False):
    """Fused masked-Huber TD loss (Pallas). Same contract as
    ``ops.losses.dqn_loss``: returns (scalar loss, |TD| [B]).

    ``targets``/``weights`` are treated as constants (no gradient), matching
    the stop-gradient semantics of the jnp path.
    """
    loss, td_abs = _call_fwd(q, actions, targets, weights, delta, interpret)
    return loss, td_abs


def _call_fwd(q, actions, targets, weights, delta, interpret):
    b, _ = q.shape
    a2 = actions.astype(jnp.int32).reshape(b, 1)
    t2 = targets.astype(q.dtype).reshape(b, 1)
    w2 = weights.astype(q.dtype).reshape(b, 1)
    loss, td = pl.pallas_call(
        functools.partial(_fwd_kernel, delta=float(delta)),
        out_shape=(
            jax.ShapeDtypeStruct((1, 1), q.dtype),
            jax.ShapeDtypeStruct((b, 1), q.dtype),
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 4,
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )(q, a2, t2, w2)
    return loss[0, 0], td[:, 0]


def _fwd_rule(q, actions, targets, weights, delta, interpret):
    out = _call_fwd(q, actions, targets, weights, delta, interpret)
    return out, (q, actions, targets, weights)


def _bwd_rule(delta, interpret, residuals, cotangents):
    q, actions, targets, weights = residuals
    g_loss, _ = cotangents  # td_abs output carries no gradient (|TD| is
    #                         stop-gradient by contract, like the jnp path)
    b, _ = q.shape
    a2 = actions.astype(jnp.int32).reshape(b, 1)
    t2 = targets.astype(q.dtype).reshape(b, 1)
    w2 = weights.astype(q.dtype).reshape(b, 1)
    g2 = jnp.asarray(g_loss, q.dtype).reshape(1, 1)
    dq = pl.pallas_call(
        functools.partial(_bwd_kernel, delta=float(delta)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 4
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(q, a2, t2, w2, g2)
    # int actions take a float0 cotangent; targets/weights are constants
    da = np.zeros(actions.shape, jax.dtypes.float0)
    return dq, da, jnp.zeros_like(targets), jnp.zeros_like(weights)


fused_dqn_loss.defvjp(_fwd_rule, _bwd_rule)
