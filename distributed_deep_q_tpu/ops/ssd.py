"""The selective state-space scan of a Mamba-2 mixer in CHUNKED form, and
the causal depthwise convolution in front of it — without the mixer's two
projections and its gated norm (``models/tokenq.mamba_mixer``).

A head j carries a state ``h [P, N]`` (``P`` channels, ``N`` =
``ssm_state_size``) along the sequence, from zero at the window's start:

    h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t B_tᵀ        y_t = h_t C_t + D x_t

with ``Δ_t`` > 0 and ``A`` < 0 a head, ``x_t [P]`` a head, ``B_t``, ``C_t
[N]`` a GROUP of heads (head j reads group ``j // (H / G)``). The
recurrence is linear in ``h``, so it is run a chunk of ``chunk`` positions
at a time as matrix products (Dao & Gu 2024, "state space duality"). With
``a_i = Σ_{k<=i} Δ_k A`` the cumulative log-decay inside a chunk:

1. ``CB[i, j] = C_i · B_j`` a group (all pairs of a chunk);
2. inside the chunk ``y_i += Σ_{j<=i} CB[i, j] exp(a_i - a_j) Δ_j x_j``;
3. the chunk's own contribution to the state at its end,
   ``S = Σ_j exp(a_last - a_j) Δ_j x_j B_jᵀ``;
4. across chunks the carried state ``h ← exp(a_last) h + S`` (a
   ``lax.scan`` over the chunks, float32), and from the state a chunk
   starts with ``y_i += exp(a_i) C_i · h``.

Both functions take what came before their rows (the convolution's last
rows, the state) and hand on what comes after, so a caller may run a
window a SEGMENT at a time (``models/tokenq.mamba_mixer`` does, for
memory: a segment's intermediates stand alone).

The four products take ``dtype`` operands with float32 accumulation; Δ,
the log-decays, the carried state and the ``D`` skip are float32. A window
whose length no chunk divides is padded with rows of Δ = 0, which pass the
state on unchanged and add nothing to it. Plain ``jax.numpy`` left to XLA,
with autodiff's backward; the weights of step 2 (one ``[chunk, chunk]``
block a head a chunk) are built again in the backward pass instead of
kept. No Pallas kernel: ``ssm_scan_roofline`` (PERF.md §3) says what one
would be worth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array, tail: jax.Array):
    """Depthwise causal convolution along the sequence with a bias, then
    SiLU: ``x`` [B, T, C] float32, ``w`` [C, L], ``b`` [C], ``tail`` [B,
    L - 1, C] the rows before ``x`` (zeros before the window) →
    (``silu(Σ_{j<L} w[:, j] · x_{t-(L-1)+j} + b)``, the last ``L - 1`` rows
    as the next segment's ``tail``)."""
    taps = w.shape[1]
    t = x.shape[1]
    rows = jnp.concatenate([tail, x], axis=1)
    conv = sum(rows[:, j:j + t] * w[:, j] for j in range(taps)) + b
    return jax.nn.silu(conv), rows[:, t:]


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _pair_weights(a: jax.Array, dt: jax.Array, cb: jax.Array, dtype):
    """Step 2's weights ``CB[i, j] · exp(a_i - a_j) · Δ_j`` for ``j <= i``,
    0 above the diagonal: ``a``, ``dt`` [B, c, G, R, Q] (R heads a group),
    ``cb`` [B, c, G, Q, Q] → [B, c, G, R, Q, Q] in ``dtype``.
    Rematerialised: only its inputs outlive the forward pass."""
    q = a.shape[-1]
    seg = a[..., :, None] - a[..., None, :]
    lower = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf)) * dt[..., None, :]
    return (decay * cb[:, :, :, None]).astype(dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
             cm: jax.Array, d: jax.Array, state: jax.Array, *, chunk: int,
             dtype=jnp.bfloat16):
    """``x`` [B, T, H, P], ``dt`` [B, T, H] (Δ > 0), ``a`` [H] (A < 0),
    ``bm`` / ``cm`` [B, T, G, N], ``d`` [H], ``state`` [B, G, H / G, P, N]
    (the state before row 0: zeros at a window's start), all float32 →
    (``y`` [B, T, H, P] float32, the state after row T - 1); the module
    docstring has the four steps. The ``R = H / G`` heads of a group stand
    side by side in steps 3 and 4: one product a group, ``R · P`` wide."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    r = h // g
    q = min(chunk, t)
    c = -(-t // q)
    if c * q != t:      # rows of Δ = 0: the state passes unchanged
        pad = [(0, 0), (0, c * q - t)]
        x, bm, cm = (jnp.pad(v, pad + [(0, 0)] * 2) for v in (x, bm, cm))
        dt = jnp.pad(dt, pad + [(0, 0)])
    xc = x.reshape(b, c, q, g, r, p)
    bl = bm.reshape(b, c, q, g, n).astype(dtype)
    cl = cm.reshape(b, c, q, g, n).astype(dtype)
    dtc = dt.reshape(b, c, q, g, r).transpose(0, 1, 3, 4, 2)  # [B,c,G,R,Q]
    acs = jnp.cumsum(dtc * a.reshape(g, r, 1), axis=-1)       # a_i
    low = {"preferred_element_type": jnp.float32}

    def rows(v):        # [B, c, G, R, Q] -> [B, c, Q, G, R, 1]
        return v.transpose(0, 1, 4, 2, 3)[..., None]
    # 1. all pairs of a chunk, a group
    cb = jnp.einsum("bcign,bcjgn->bcgij", cl, bl, **low)
    # 2. inside the chunk
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp",
                   _pair_weights(acs, dtc, cb, dtype), xc.astype(dtype),
                   **low)
    # 3. what the chunk adds to the state at its end, a head [P, N]
    to_end = jnp.exp(acs[..., -1:] - acs) * dtc
    add = jnp.einsum("bcjgrp,bcjgn->bcgrpn",
                     (xc * rows(to_end)).astype(dtype), bl, **low)
    # 4. the carried state, float32: the state each chunk STARTS with
    keep = jnp.exp(acs[..., -1])                              # [B, c, G, R]

    def carry(state, chunk_in):
        k, s = chunk_in
        return state * k[..., None, None] + s, state

    state, start = lax.scan(
        carry, state, (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(add, 1, 0)))
    off = jnp.einsum("bcign,bcgrpn->bcigrp", cl,
                     jnp.moveaxis(start, 0, 1).astype(dtype), **low)
    y = y + off * rows(jnp.exp(acs)) + xc * d.reshape(g, r, 1)
    return y.reshape(b, c * q, h, p)[:, :t], state
