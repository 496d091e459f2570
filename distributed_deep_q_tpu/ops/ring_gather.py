"""Pallas row-DMA kernels for the 1-D linear frame ring (round 5).

Why these exist — measured XLA:TPU gather pathology on the fused PER path
(scripts/sample_ablate.py, 1M-frame ring, chain=32 × batch 512):

- a row gather from a tiled ``uint8 [cap, 7056]`` ring reads whole
  (32, 128) tiles per requested row — ~32× the wanted bytes; the two
  obs/next-obs gathers measured ~44 ms/chunk (~20 GB/s useful).
- a slice-gather of multi-row windows compiles to a lane-padded
  ``[N, W, row]`` temp (16× expansion → 13.8 GB → compile OOM), and
  Mosaic rejects sublane-unaligned HBM slices for DMA.

The fix is layout, not lowering: store the ring as ONE flat **int32**
array (pixel bytes packed 4-per-element, little-endian — round-trips
``np.uint8.view(int32)`` ↔ ``lax.bitcast_convert_type``, verified on TPU
and CPU; byte ``k`` of a word is ``(word >> 8k) & 0xff``, which is how the
fused train program reads them: ``replay/device_per.window_to_obs``)
whose rows are padded to a multiple of the 1024-element 1-D
tile, so every window's element range ``[idx·rowp, idx·rowp + w·rowp)``
is provably tile-aligned and a plain async DMA copies exactly the wanted
bytes. int32 rather than uint8 because Mosaic's scalar index arithmetic
is 32-bit: at the 1M-frame × 8192 B flagship shape BYTE offsets pass
2³¹ and a u8-element ring overflows into wild DMAs (measured
FAILED_PRECONDITION faults; an int32 ring's ELEMENT offsets stay < 2³¹
— asserted at construction). Measured: 16384 8-row windows from the 1M
ring in **3.7 ms (290 GB/s useful)** vs 44 ms for the tiled-gather pair
it replaces; correctness verified against high ring addresses.

Two kernels, both pipelined over ``NBUF`` DMA semaphores (the sweep
measured 1.2 µs/DMA at depth 8 — completion-latency-bound — down to
~0.2 µs at depth 64):

- ``gather_windows`` — HBM→HBM copy of ``n`` windows of ``w`` rows each
  (the fused sampler's obs+next-obs plane: one window covers both).
- ``scatter_rows``   — HBM→HBM copy of staged rows into the ring at
  arbitrary row indices (the flush path), ring aliased in place.

Rows never wrap inside a window: the ring carries ``w-1`` ghost rows per
sub-ring that mirror rows ``0..w-2`` (written twice by the flush), so
window starts are always contiguous (see replay/device_per.py).

Reference scope: the reference streams full pixel minibatches host→device
per step (SURVEY §3.1); this plane replaces that with device-resident
rows + on-device window composition, so only indices cross the host
boundary.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# 1-D int32 arrays tile at 1024 elements (4096 B) on TPU (Mosaic requires
# dynamic slice starts/sizes provably divisible by the tile) — all row
# strides here must be multiples of this.
I32_TILE = 1024
LANES = 128     # minor dim of the (8, 128) tile every >= 2-D int32 array gets
NBUF = 64  # outstanding DMAs (depth sweep: 8→1.2 µs/DMA, 64→~0.2 µs)


def padded_row_bytes(row_len: int) -> int:
    """Smallest tile-aligned row stride (BYTES) holding ``row_len`` pixel
    bytes; always a multiple of 4·I32_TILE."""
    return -(-row_len // (4 * I32_TILE)) * (4 * I32_TILE)


def _pipelined(n: int, dma):
    """Issue ``dma(k, slot)`` for k in [0, n), ``NBUF`` outstanding."""

    def body(sems):
        for k in range(min(NBUF, n)):
            dma(k, sems.at[k]).start()

        def loop(k, _):
            dma(k, sems.at[k % NBUF]).wait()

            @pl.when(k + NBUF < n)
            def _():
                dma(k + NBUF, sems.at[k % NBUF]).start()

            return 0

        lax.fori_loop(0, n, loop, 0)

    pl.run_scoped(body, pltpu.SemaphoreType.DMA((min(NBUF, n),)))


def _gather_kernel(n, wsz, rowb, idx_ref, ring_ref, out_ref):
    _pipelined(n, lambda k, sem: pltpu.make_async_copy(
        ring_ref.at[pl.ds(idx_ref[k] * rowb, wsz)],
        out_ref.at[pl.ds(k * wsz, wsz)], sem))


def _scatter_kernel(n, rowb, sidx_ref, didx_ref, staged_ref, ring_in_ref,
                    ring_out_ref):
    _pipelined(n, lambda k, sem: pltpu.make_async_copy(
        staged_ref.at[pl.ds(sidx_ref[k] * rowb, rowb)],
        ring_out_ref.at[pl.ds(didx_ref[k] * rowb, rowb)], sem))


def gather_windows(idx: jax.Array, ring: jax.Array, *, n: int, w: int,
                   rowb: int, interpret: bool = False) -> jax.Array:
    """Copy ``n`` contiguous ``w``-row windows out of the flat ring.

    ``idx`` [n] int32 — window-start ROW indices (callers guarantee
    ``idx + w`` stays inside the ring via ghost rows); ``ring`` [S] int32
    (packed pixel bytes); ``rowb`` row stride in BYTES. Returns
    [n · w · rowb/4] int32, FLAT: the consumer gives it its dims. A view
    that puts the ``w`` rows next to the row's words (``[n, w, rowp]``) is
    a physical copy on the chip; ``tile_rows`` is the view that is free,
    and the one the fused sample program hands to the train program. The
    consumer then takes the bytes out of the words — the fused train
    program by shift and mask into byte planes where the frame width is a
    multiple of 4 and the batch fills the lanes, else by a bitcast to
    uint8: ``replay/device_per.window_to_obs``.
    """
    rowp = rowb // 4
    wsz = w * rowp
    kernel = functools.partial(_gather_kernel, n, wsz, rowp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n * wsz,), jnp.int32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(idx.astype(jnp.int32), ring)


def tile_rows(flat: jax.Array, *lead: int) -> jax.Array:
    """``gather_windows``' flat output as ``[*lead, rowp // 128, 128]``
    (``lead`` e.g. ``(chain, batch, w)``, ``rowp`` what is left over): the
    view in which the windows cross a program boundary, and a bitcast.

    The kernel writes a 1-D int32 array, tiled ``T(1024)``; anything with
    two dims or more is tiled ``T(8, 128)`` over its LAST TWO. One 1-D
    tile and one (8, 128) tile are the same 4 096 bytes in the same order
    (``I32_TILE == 8 * LANES``), and ``padded_row_bytes`` makes a row a
    whole number of them, so with a row's words as the last two dims —
    ``(rowp // 128, 128)``, the first a multiple of 8 — no byte moves.
    ``[*lead, rowp]`` instead leaves ``(w, rowp)`` in the tiled dims, and a
    window of 7 (or 5) rows is no multiple of 8 sublanes: the reshape pads
    every window to 8 rows, a copy of the whole chunk, and the compiler
    then copies it once more into a result layout without the padding
    (1.53 of the b512 chunk's 10.39 ms, PERF.md §6, PR 35).
    ``flat_rows`` undoes it."""
    rowp, rest = divmod(flat.size, math.prod(lead))
    assert rest == 0 and rowp % I32_TILE == 0, (
        f"{flat.size} words are not {lead} rows of whole 1-D tiles")
    return flat.reshape(*lead, rowp // LANES, LANES)


def flat_rows(tiled: jax.Array) -> jax.Array:
    """``tile_rows``' view back to ``[*lead, rowp]``: a row's words in
    one dim again. Inside the train program this costs nothing of its own:
    the one relayout a step that feeds the unpack reads either form."""
    return tiled.reshape(tiled.shape[:-2] + (-1,))


def scatter_rows(src_idx: jax.Array, dst_idx: jax.Array, staged: jax.Array,
                 ring: jax.Array, *, n: int, rowb: int,
                 interpret: bool = False) -> jax.Array:
    """Write ``n`` rows ``staged[src_idx[k]] → ring[dst_idx[k]]`` (row
    units; ``staged``/``ring`` flat int32, ``rowb`` in BYTES; the ring is
    aliased in place via input_output_aliases).

    ``src_idx`` decouples lane from source row so ghost rows re-send the
    same staged bytes to their mirror target without duplicating them
    host-side. There is no out-of-bounds drop — padding lanes must point
    at the ring's scratch row (the caller maps them), where racing
    same-destination DMAs are harmless. Distinct REAL targets within one
    call are the caller's invariant (one flush chunk never wraps a
    sub-ring; ghost copies target distinct rows by construction).
    """
    kernel = functools.partial(_scatter_kernel, n, rowb // 4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(ring.shape, jnp.int32),
        grid_spec=grid_spec,
        input_output_aliases={3: 0},  # indexes include the scalar operands
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(src_idx.astype(jnp.int32), dst_idx.astype(jnp.int32), staged, ring)
