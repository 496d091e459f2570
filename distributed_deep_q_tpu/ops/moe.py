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

Dropless: the token-slots routed to held experts are SORTED by expert
(held first, grouped by expert, token order inside a group) and multiplied
in grouped products (``jax.experimental.pallas.ops.tpu.megablox``: a Pallas
grouped matmul that visits only the row tiles its group sizes cover, with
its own backward). There is no per-expert capacity — a skewed router only
moves the group boundaries. The row count of that sorted order is static:
the worst case (``buffer_rows``: every token with ``min(k, held)`` slots
here), so ``overflow``, which counts held slots beyond it, reads 0 under
any routing: a router that trains on its share alone drifts within a few
steps (PERF.md §6, PR 27: a buffer of twice the expected slots overflowed
on the chip). The worst case is only the BOUND, though. The layer walks
the sorted order in blocks of ``block_rows`` rows and runs a block —
gather, gate+up product, activation, down product, weighting, scatter-add
— only when it holds a slot: a loop of ``ceil(slots_held / block_rows)``
turns, forward and backward (``jax.custom_vjp``: the backward recomputes a
block on its own and adds its cotangents up in float32), so the work
follows the slots the held experts really got (``rows_run``) and no array
of the worst case's rows ever stands. The caller
(``models/tokenq.feed_forward``) hands over the whole batch's token-slots
at once: a held expert's rows are contiguous across the sequences.
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
    """The grouped matmul's k/n tile for a dimension ``dim``: the largest
    multiple of 128 <= ``want`` that divides it; where none does, the one
    that rounds it up least (the largest of those: 1 856 = 14.5 x 128
    runs in 5 tiles of 384 = 1 920 — the kernel masks the last tile's
    overhang at the call, 3.4 % of its columns, counted nowhere as work,
    and the held weights keep their published shape); under 128 (a toy's
    width) ``dim`` whole."""
    tiles = range(min(want, dim) // 128 * 128, 0, -128)
    if not tiles:
        return dim
    return min(tiles, key=lambda t: (-(-dim // t) * t, -t))


def _gmm(lhs, rhs, sizes, tile, interpret, transposed=False):
    """``lhs`` [rows, k] against ``rhs`` [groups, k, n] (``transposed``:
    [groups, n, k]), the rows of group g by group g's matrix."""
    from jax.experimental.pallas.ops.tpu import megablox

    k, n = rhs.shape[1:][::-1] if transposed else rhs.shape[1:]
    return megablox.gmm(lhs, rhs, sizes, jnp.float32,
                        (tile, _fit(k), _fit(n)), None, None, transposed,
                        interpret)


def block_rows(rows: int, tile: int) -> int:
    """Rows of one block of the walk over the held-slot buffer: 16 m-tiles
    (4 096 rows at the published m-tile of 256; half a block is run for
    nothing on average, and every block pays a pass over the held
    weights' cotangents), the whole buffer where that is smaller."""
    return min(rows, 16 * tile)


def held_experts_ffn(x: jax.Array, idx: jax.Array, p: jax.Array,
                     w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                     *, offset: int, rows: int, tile: int,
                     compute_dtype=jnp.bfloat16, interpret: bool = False,
                     act=jax.nn.relu):
    """Σ over the chosen experts HELD here of ``p_e · f_e(x)``, ``f_e(x) =
    (act(x W_gate,e) * (x W_up,e)) W_down,e``: ReGLU with ``act`` relu,
    SwiGLU with silu; with ``w_gate`` ``None`` the two-matrix form
    ``act(x W_up,e) W_down,e`` (one product less a block; the sort, the
    walk and the counters are the same).

    ``x`` [..., T, h] float32 over N tokens in all (``[B, T, h]`` as the
    model holds it; inside, its rows lie ``T`` rounded up to the float32
    tile's 8 apart, so that flattening the batch and taking it apart
    again copy nothing), ``idx``/``p`` [N, k] from ``route``, weights
    ``[held, h, f]`` / ``[held, f, h]``. Returns ``(y`` as ``x``,
    ``counters)`` with counters ``load`` [held] (token-slots per held
    expert), ``slots_held``, ``slots`` (all token-slots), ``overflow``
    (held slots beyond ``rows``: 0 at ``buffer_rows``' worst case) and
    ``rows_run`` (rows of the blocks the walk ran: ``slots_held`` rounded
    up to ``block_rows``; the module docstring has the walk)."""
    n, k = idx.shape
    *outer, t, h = x.shape
    stride = -(-t // 8) * 8         # [..., stride, h] -> [-1, h]: no copy
    held, _, f = w_up.shape
    gated = w_gate is not None
    # a width the 128 lanes do not divide: the chip keeps ``[held, h, f]``
    # with ``h`` innermost (no lane is padding), so the up product reads
    # its weights as ``[held, f, h]`` — as they lie — and nothing is laid
    # out again on the way into and out of the chained steps (at 1 856
    # that was 12 copies of 160 MB each way, 1.9 GB held)
    up_t = f > 128 and f % 128 != 0
    block = block_rows(rows, tile)
    padded = -(-rows // block) * block
    local = idx - offset
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    load = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)
    slots_held = jnp.sum(load)
    # held slots first, grouped by expert, in token order inside a group
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, max(padded - n * k, 0)))[:padded]
    ends = jnp.minimum(jnp.cumsum(load), rows)      # the groups' last rows

    def blocks_held(ends):
        return (ends[-1] + block - 1) // block

    def rows_of(i, order, ends):
        """Block ``i``: its token-slots, its share of every group, and
        which of its rows hold a slot."""
        slot = lax.dynamic_slice(order, (i * block,), (block,))
        edges = jnp.clip(ends - i * block, 0, block)
        valid = (jnp.arange(block) < edges[-1])[:, None]
        tok = slot // k
        return (slot, tok // t * stride + tok % t,
                jnp.diff(edges, prepend=0).astype(jnp.int32), valid)

    def one_block(xs, ps, w_gu, w_d, sizes, valid):
        xs = jnp.where(valid, xs, 0.0).astype(compute_dtype)
        gu = _gmm(xs, w_gu, sizes, tile, interpret, up_t)  # [block, 2f | f]
        hid = (act(gu[:, :f]) * gu[:, f:] if gated else act(gu)).astype(
            compute_dtype)
        out = _gmm(hid, w_d, sizes, tile, interpret)
        # rows past the last group were never visited by the kernels
        return jnp.where(valid, out, 0.0) * jnp.where(valid, ps[:, None], 0.0)

    def forward(x, ps, w_gate, w_up, w_down, order, ends):
        w_gu = (jnp.concatenate([w_gate, w_up], axis=-1) if gated
                else w_up).astype(compute_dtype)
        w_gu = w_gu.swapaxes(1, 2) if up_t else w_gu
        w_d = w_down.astype(compute_dtype)

        def body(i, y):
            slot, row, sizes, valid = rows_of(i, order, ends)
            return y.at[row].add(
                one_block(x[row], ps[slot], w_gu, w_d, sizes, valid))

        y = lax.fori_loop(0, blocks_held(ends), body, jnp.zeros_like(x))
        return y, (x, ps, w_gu, w_d, order, ends)

    def backward(res, dy):
        x, ps, w_gu, w_d, order, ends = res

        def body(i, carry):
            dx, dps, dw_gu, dw_d = carry
            slot, row, sizes, valid = rows_of(i, order, ends)
            _, vjp = jax.vjp(
                lambda *a: one_block(*a, sizes, valid),
                x[row], ps[slot], w_gu, w_d)
            g_x, g_p, g_gu, g_d = vjp(dy[row])
            return (dx.at[row].add(g_x), dps.at[slot].add(g_p),
                    dw_gu + g_gu.astype(jnp.float32),
                    dw_d + g_d.astype(jnp.float32))

        dx, dps, dw_gu, dw_d = lax.fori_loop(0, blocks_held(ends), body, (
            jnp.zeros_like(x), jnp.zeros_like(ps),
            jnp.zeros(w_gu.shape, jnp.float32),
            jnp.zeros(w_d.shape, jnp.float32)))
        dw_gu = dw_gu.swapaxes(1, 2) if up_t else dw_gu
        return (dx, dps,
                dw_gu[..., :f].astype(w_gate.dtype) if gated else None,
                dw_gu[..., -f:].astype(w_up.dtype),
                dw_d.astype(w_down.dtype), None, None)

    walk = jax.custom_vjp(lambda *a: forward(*a)[0])
    walk.defvjp(forward, backward)
    flat = jnp.pad(x, [(0, 0)] * len(outer) + [(0, stride - t), (0, 0)])
    y = walk(flat.reshape(-1, h), p.reshape(-1), w_gate, w_up, w_down, order,
             ends).reshape(*outer, stride, h)[..., :t, :]
    counters = {"load": load, "slots_held": slots_held,
                "slots": jnp.asarray(n * k, jnp.int32),
                "overflow": jnp.maximum(slots_held - rows, 0),
                "rows_run": blocks_held(ends) * block}
    return y, counters
