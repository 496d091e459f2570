"""The rotate-half rotary embedding of q or k and the cast to the compute
dtype as ONE pass over ``[B, H, T, D]``, each way.

``y = (x · cos + swap(x) · sin).astype(dtype)``: ``swap`` exchanges columns
i and i + r/2 inside the first r columns of a head, and everything else
lives in the two tables ``[T, D]`` float32 (``tables``): the sign
(``sin`` holds −sin on the first r/2 columns and +sin on the next r/2),
the columns past r that do not turn (cos 1, sin 0), a kind's factor on
both (YaRN's ``attention_factor``), and each row's position. The
arithmetic a column is the plain form's (``models/tokenq.rotary_by_table``
then ``.astype``: two float32 products, a sum, one round), so the two
agree to the last bit.

A rotation is linear and orthogonal: its transpose is the rotation by the
negated angle, ``dx = g · cos − swap(g) · sin`` — the SAME kernel with the
sine's sign turned, reading the compute-dtype cotangent once and writing
float32 once (``jax.custom_vjp``; the residuals are the two tables). Left
to XLA the half-split is two lane slices and a concatenate, which autodiff
turns into pads and slices and the TPU compiler into float32 halves
standing in HBM on padded lanes (PERF.md §6, PR 46); ``jnp.roll`` is cut
back into the same slices. ``pltpu.roll`` turns the lanes inside VMEM.

The kernel wants a head that fills the lanes (``fills_lanes``: D a
multiple of 128); the caller keeps the plain form for any other.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# a grid step turns HEAD_BLOCK heads x ROW_BLOCK rows: 2 MB of float32 in,
# 1 MB of bfloat16 out, the tables' 2 x 256 KB resident while the heads of
# one row block go by (the head axis is the grid's innermost)
ROW_BLOCK = 512
HEAD_BLOCK = 8


def fills_lanes(d: int) -> bool:
    """Whether a head of ``d`` columns takes the kernel: one head's columns
    are the lanes of a block, so ``d`` is a multiple of 128."""
    return d % LANES == 0


def tables(inv, factor: float, positions: jax.Array, d: int):
    """(cos, sin) ``[T, d]`` float32 of rows at ``positions`` [T] float32
    for the inverse frequencies ``inv`` [r / 2]: column i < r turns by
    ``position · inv[i mod r/2]``, cos and sin times ``factor``, the sine
    negated on the first r/2 columns; columns from r on hold cos 1 and
    sin 0. Every column is computed where it stands (no concatenate or
    pad of a ``[T, d]`` array)."""
    half = inv.shape[0]
    col = np.arange(d)
    turns = col < 2 * half
    signed = np.where(col < half, -factor, factor).astype(np.float32)
    ang = positions[:, None] * jnp.where(turns, inv[col % half], 0.0)
    return (jnp.where(turns, jnp.cos(ang) * factor, 1.0),
            jnp.where(turns, jnp.sin(ang) * signed, 0.0))


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, r: int, sign: float):
    heads, rows, d = x_ref.shape
    x = x_ref[...].astype(jnp.float32).reshape(heads * rows, d)
    if r == d:      # i <-> i + d/2: one turn of the lanes by half a head
        swapped = pltpu.roll(x, d // 2, 1)
    else:           # past r the sine is 0: what stands there is not read
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        swapped = jnp.where(col < r // 2, pltpu.roll(x, d - r // 2, 1),
                            pltpu.roll(x, r // 2, 1))
    x, swapped = (a.reshape(heads, rows, d) for a in (x, swapped))
    sin = sin_ref[...] if sign > 0 else -sin_ref[...]
    o_ref[...] = (x * cos_ref[...] + swapped * sin).astype(o_ref.dtype)


def _pass(x, cos, sin, r, sign, dtype, interpret):
    b, h, t, d = x.shape
    heads = max(n for n in range(1, HEAD_BLOCK + 1) if h % n == 0)
    rows = min(ROW_BLOCK, -(-t // 16) * 16)
    rows_of = pl.BlockSpec((None, heads, rows, d),
                           lambda b, i, n: (b, n, i, 0))
    table = pl.BlockSpec((rows, d), lambda b, i, n: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, r=r, sign=sign),
        grid=(b, pl.cdiv(t, rows), h // heads),
        in_specs=[rows_of, table, table], out_specs=rows_of,
        out_shape=jax.ShapeDtypeStruct(x.shape, dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="rotary_turn")(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def turn(x: jax.Array, cos: jax.Array, sin: jax.Array, r: int, dtype,
         interpret: bool) -> jax.Array:
    """``x`` [B, H, T, D] float32 (``fills_lanes(D)``) turned by
    ``tables``' (cos, sin) [T, D] over its first ``r`` columns and rounded
    to ``dtype``. No gradient reaches the tables."""
    return _pass(x, cos, sin, r, 1.0, dtype, interpret)


def _turn_fwd(x, cos, sin, r, dtype, interpret):
    return _pass(x, cos, sin, r, 1.0, dtype, interpret), (cos, sin)


def _turn_bwd(r, dtype, interpret, res, g):
    cos, sin = res
    return _pass(g, cos, sin, r, -1.0, jnp.float32, interpret), None, None


turn.defvjp(_turn_fwd, _turn_bwd)
