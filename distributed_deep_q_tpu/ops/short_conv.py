"""The gated short convolution of LFM2's ``conv`` layers, without its two
projections: an input gate, a depthwise causal convolution of ``L`` taps
along the sequence, an output gate.

``[B, C, z] = split3(u · W_in)`` arrives as one ``[..., T, 3h]`` array;
``g = B * z``; ``c_t = Σ_{j<L} w[:, j] · g_{t-(L-1)+j}`` with ``g_s = 0``
for ``s < 0`` (no bias); the result is ``C * c``, which the caller
multiplies by ``W_out``. Memory-bound work between two compute-bound
products: ``L`` shifted multiply-adds in float32, left to XLA to fuse,
with autodiff's backward (the same taps run the other way). No Pallas
kernel: on the chip XLA's fusions of this body and of its backward run at
60 % of the HBM roofline over the bytes the operator must move
(``short_conv_mix_roofline``; PERF.md §6, PR 31), over the half below
which one would be worth writing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def short_conv_mix(bcz: jax.Array, w_conv: jax.Array) -> jax.Array:
    """``bcz`` [..., T, 3h] float32 (input gate, output gate, value),
    ``w_conv`` [h, L] → ``C * conv(B * z)`` [..., T, h]. Position t sees
    positions t-L+1..t only; the first L-1 see zeros before the window."""
    h, taps = w_conv.shape
    t = bcz.shape[-2]
    b, c, z = bcz[..., :h], bcz[..., h:2 * h], bcz[..., 2 * h:]
    g = b * z
    lead = [(0, 0)] * (g.ndim - 2)
    conv = g * w_conv[:, taps - 1]
    for j in range(taps - 1):
        back = taps - 1 - j
        shifted = jnp.pad(g, lead + [(back, 0), (0, 0)])[..., :t, :]
        conv = conv + shifted * w_conv[:, j]
    return c * conv
