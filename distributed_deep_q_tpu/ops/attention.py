"""Blockwise causal attention with an optional sliding window — one kernel
for both kinds of layer the token-window Q-network mixes (full causal, and
a window of ``window`` keys), forward and backward, at whatever head count
and block the calling LAYER has (a kernel is built and kept for each
``(padded length, group, window, blocks)`` it is asked for).

The kernel is JAX's own TPU splash attention
(``jax.experimental.pallas.ops.tpu.splash_attention``): a Pallas flash
kernel driven by a block-sparse description of the mask, so key blocks
wholly outside the causal triangle or the window are SKIPPED (never
loaded, never masked), and blocks the mask cuts are masked inside the
kernel. Scores are never materialised: at 8 192 tokens a head's would be
268 MB in float32. A block far wider than the window wastes the kernel's
time on pairs outside the band (at window 512 a block of 1 024 computes
four times the pairs the mask lets through, a block of 512 twice): the
caller gives such layers a block of their own. Its backward is one more
kernel (dq fused into dkv) under the same mask, or two where the caller
asks (``fused_bwd``).

Grouped-query layout: the ``mqa`` kernel serves one key/value head and
the ``Hq // Hkv`` query heads that share it; batch and key/value heads
are vmapped (extra grid dimensions of the same kernel). The sequence is
padded to a multiple of the block (padded keys lie in every real query's
future, so the causal mask hides them; padded queries are cut off).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.lru_cache(maxsize=None)
def _kernel(t_pad: int, group: int, window: int, block: int,
            compute_block: int, interpret: bool, fused_bwd: bool = True):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    if window:
        # query t sees keys s with t - window < s <= t
        one = sm.LocalMask((t_pad, t_pad), (window - 1, 0), 0)
    else:
        one = sm.CausalMask((t_pad, t_pad))
    # one fused backward kernel (dq inside dkv): on a v5e chip it read
    # 64.8 against 78.6 ms for the full layer and 52.6 against 59.7 for
    # the window layer (batch 4, 8 193 tokens; PERF.md §6, PR 27). It
    # writes one partial dq a KV BLOCK (``[t_pad / block, heads, t_pad,
    # D]``, summed afterwards) whatever the mask: at 16 896 tokens, 64
    # heads and a block of 512 that is 33 x 264 MB, so a caller with many
    # small blocks asks for the two separate kernels (PERF.md §6, PR 40)
    own_dq = {} if fused_bwd else dict(block_q_dq=block, block_kv_dq=block)
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=compute_block,
        block_q_dkv=block, block_kv_dkv=block,
        block_kv_dkv_compute=compute_block, use_fused_bwd_kernel=fused_bwd,
        **own_dq)
    # built once, outside whatever trace asks first (its mask tables are
    # constants of every program that uses it)
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa_single_device(
            sm.MultiHeadMask([one] * group), block_sizes=sizes,
            interpret=interpret)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     window: int = 0, block: int = 128,
                     compute_block: int = 0, fused_bwd: bool = True,
                     interpret: bool = False) -> jax.Array:
    """softmax(q kᵀ · D^-½ + mask) v over ``[B, H, T, D]`` queries,
    ``[B, Hkv, T, D]`` keys and ``[B, Hkv, T, Dv]`` values (``H`` a
    multiple of ``Hkv``; ``v`` may be narrower or wider than ``q`` / ``k``:
    latent attention scores over 192 and mixes values of 128; the scale
    is the SCORE width's); causal, and with ``window`` > 0 limited to the
    last ``window`` keys. Returns ``[B, H, T, Dv]`` in ``q``'s dtype.
    ``block`` (a multiple of 128) is the kernel's q and kv block and
    ``compute_block`` (a divisor of it, 0 = the block) the kv columns one
    inner step multiplies; ``T`` need not be a multiple of the block.
    ``fused_bwd``: the backward as ONE kernel (dq inside dkv, with a
    partial dq a kv block) or as two."""
    b, h, t, d = q.shape
    dv = v.shape[-1]
    hkv = k.shape[1]
    group = h // hkv
    t_pad = -(-t // block) * block
    q = q * jnp.asarray(d ** -0.5, q.dtype)
    if t_pad != t:
        pad = ((0, 0), (0, 0), (0, t_pad - t), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    kern = _kernel(t_pad, group, int(window), int(block),
                   int(compute_block) or int(block), bool(interpret),
                   bool(fused_bwd))
    per_kv_head = jax.vmap(kern)          # [Hkv, group, T, D], [Hkv, T, D]
    out = jax.vmap(per_kv_head)(q.reshape(b, hkv, group, t_pad, d), k, v)
    return out.reshape(b, h, t_pad, dv)[:, :, :t]
