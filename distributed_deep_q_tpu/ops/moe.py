"""A mixture-of-experts layer that holds a SHARE of its experts.

The router is as wide as the model says (all ``E`` experts); this process
is told it holds ``held`` of them from ``offset`` on — one member of an
expert-parallel group. It routes over all ``E``, keeps the top ``k`` and
adds ``p_e · f_e(x)`` only for chosen experts it holds. How the router
scores (a softmax, SmallThinker's; a sigmoid whose selection adds a
per-expert bias, LFM2's) and which gate the experts carry (ReGLU,
SmallThinker's; SwiGLU, LFM2's) come from the configuration. What the absent
experts would add is left out (their owners add it, after an exchange this
process does not stand in for).

Dropless: the token-slots routed to held experts are SORTED by expert into
one buffer and multiplied in ONE grouped product per projection
(``jax.experimental.pallas.ops.tpu.megablox``: a Pallas grouped matmul
that visits only the row tiles its group sizes cover, with its own
backward). There is no per-expert capacity — a skewed router only moves
the group boundaries. The buffer's row count is static: the worst case
(``buffer_rows``: every token with ``min(k, held)`` slots here), so
``overflow``, which counts held slots beyond the buffer, reads 0. The
caller (``models/tokenq.layer``) runs one SEQUENCE of the batch at a time,
so the worst case is a sequence's: a router that trains on its share alone
drifts within a few steps (PERF.md §6, PR 27: a buffer of twice the
expected slots overflowed on the chip).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def route(u: jax.Array, w_router: jax.Array, top_k: int, *,
          softmax: bool = True, bias: jax.Array | None = None,
          scale: float = 1.0):
    """Scores over ALL experts in float32, ``top_k`` kept, renormalised
    to sum 1 and multiplied by ``scale`` (``routed_scaling_factor``: 1.0
    for SmallThinker, LFM2 and Keye-VL-2.0: a product with 1.0 is
    exact and the compiler drops it, so their gates stay the renormalised
    scores bit for bit). ``softmax`` (SmallThinker's
    setting): softmax scores, the largest kept. Otherwise (LFM2's):
    sigmoid scores ``s``; the experts with the largest ``s + bias`` are
    CHOSEN and weighted by ``s`` without the bias (no gradient reaches
    ``bias``: indices carry none), divided by ``sum + 1e-6``. ``u`` [N, h]
    float32 → (expert ids [N, k] int32, weights [N, k] float32)."""
    z = jnp.dot(u, w_router, precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
    if softmax:
        top_p, top_i = lax.top_k(jax.nn.softmax(z, axis=-1), top_k)
        total = jnp.sum(top_p, -1, keepdims=True)
    else:
        s = jax.nn.sigmoid(z)
        _, top_i = lax.top_k(s if bias is None else s + bias, top_k)
        top_p = jnp.take_along_axis(s, top_i, axis=-1)
        total = jnp.sum(top_p, -1, keepdims=True) + 1e-6
    return top_i.astype(jnp.int32), top_p / total * scale


def buffer_rows(tokens: int, top_k: int, held: int, tile: int) -> int:
    """Static rows of the held-slot buffer: the worst case, every token
    with ``min(top_k, held)`` slots here, rounded up to ``tile``."""
    return max(-(-tokens * min(top_k, held) // tile), 1) * tile


def _fit(dim: int, want: int = 512) -> int:
    """Largest multiple of 128 <= ``want`` that divides ``dim`` (the
    grouped matmul's k/n tile), else ``dim`` whole."""
    for t in range(min(want, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def _gmm(lhs, rhs, sizes, tile, interpret):
    from jax.experimental.pallas.ops.tpu import megablox

    tiling = (tile, _fit(rhs.shape[1]), _fit(rhs.shape[2]))
    return megablox.gmm(lhs, rhs, sizes, jnp.float32, tiling, None, None,
                        False, interpret)


def held_experts_ffn(x: jax.Array, idx: jax.Array, p: jax.Array,
                     w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                     *, offset: int, rows: int, tile: int,
                     compute_dtype=jnp.bfloat16, interpret: bool = False,
                     act=jax.nn.relu):
    """Σ over the chosen experts HELD here of ``p_e · f_e(x)``, ``f_e(x) =
    (act(x W_gate,e) * (x W_up,e)) W_down,e``: ReGLU with ``act`` relu,
    SwiGLU with silu.

    ``x`` [N, h] float32, ``idx``/``p`` [N, k] from ``route``, weights
    ``[held, h, f]`` / ``[held, f, h]``. Returns ``(y [N, h] float32,
    counters)`` with counters ``load`` [held] (token-slots per held
    expert), ``slots_held``, ``slots`` (all token-slots) and ``overflow``
    (held slots beyond ``rows``: 0 at ``buffer_rows``' worst case)."""
    n, k = idx.shape
    held = w_gate.shape[0]
    f = w_gate.shape[2]
    local = idx - offset
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    load = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)
    slots_held = jnp.sum(load)
    # held slots first, grouped by expert, in token order inside a group
    order = jnp.argsort(key, stable=True)
    order = jnp.pad(order, (0, max(rows - n * k, 0)))[:rows]   # tile round-up
    tok = order // k
    valid = (jnp.arange(rows) < slots_held)[:, None]
    ends = jnp.minimum(jnp.cumsum(load), rows)
    sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)

    xs = jnp.where(valid, x[tok], 0.0).astype(compute_dtype)
    w_gu = jnp.concatenate([w_gate, w_up], axis=-1).astype(compute_dtype)
    gu = _gmm(xs, w_gu, sizes, tile, interpret)            # [rows, 2f]
    hid = (act(gu[:, :f]) * gu[:, f:]).astype(compute_dtype)
    out = _gmm(hid, w_down.astype(compute_dtype), sizes, tile, interpret)
    # rows past the last group were never visited by the kernels
    out = jnp.where(valid, out, 0.0) * jnp.where(
        valid, p.reshape(-1)[order][:, None], 0.0)
    y = jnp.zeros_like(x).at[tok].add(out)
    counters = {"load": load, "slots_held": slots_held,
                "slots": jnp.asarray(n * k, jnp.int32),
                "overflow": jnp.maximum(slots_held - rows, 0)}
    return y, counters
