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
padded to a multiple of the block and padded queries are cut off. Under
the causal masks padded keys lie in every real query's future, so the
mask hides them; under a mask that is NOT causal that is no argument, and
the mask itself has to say that a padded row is a key to no real row
(``BlockDiffusionMask`` does, row by row).

``block_diffusion_attention`` is the same kernel under the three-part mask
of generation by diffusion over blocks: the window stands in the sequence
twice, a clean copy and a partly masked ("noised") copy, and a noised row
sees the clean rows of earlier blocks and the noised rows of its own. Most
of that mask is empty (clean rows see no noised key; noised rows see no
other noised block): the kernel's block tables skip those blocks like the
causal mask's upper triangle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _kernel(t_pad: int, group: int, window: int, block: int,
            compute_block: int, interpret: bool, fused_bwd: bool = True):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)

    if window:
        # query t sees keys s with t - window < s <= t
        one = sm.LocalMask((t_pad, t_pad), (window - 1, 0), 0)
    else:
        one = sm.CausalMask((t_pad, t_pad))
    return _splash(one, group, block, compute_block, interpret, fused_bwd)


def _splash(one, group: int, block: int, compute_block: int,
            interpret: bool, fused_bwd: bool):
    """The splash kernel of ``group`` query heads under the mask ``one``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

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


# ---- generation by diffusion over blocks: the three-part mask --------------

BD_CLEAN = 1 << 20      # the bit that marks a clean query's code


def bd_rows(t: int, block_length: int, copies: int = 2):
    """The packed rows of ONE window of ``t`` steps (+1 token) → (copy [N],
    position [N], block [N]), numpy int32. The clean copy holds positions
    0..t (``copies`` = 1: it alone, the acting path's layout); the noised
    copy follows with positions 1..G·B, G = ceil(t / B) whole blocks (the
    positions past ``t`` of a last block that ``t`` does not fill hold the
    mask token: what lies there has not been generated). Position 0 is a
    block of its own (-1); position p >= 1 lies in block (p - 1) // B."""
    bl = int(block_length)
    noised = np.arange(1, -(-t // bl) * bl + 1) if copies == 2 else \
        np.arange(0)
    pos = np.concatenate([np.arange(t + 1), noised]).astype(np.int32)
    copy = np.concatenate([np.zeros(t + 1), np.ones(len(noised))]).astype(
        np.int32)
    return copy, pos, ((pos + bl - 1) // bl - 1).astype(np.int32)


def _bd_clean_keys(t: int, block_length: int, copies: int):
    """(copy [N], the number of CLEAN keys each packed row sees [N]): a
    clean row the clean rows up to its block's end, a noised row those
    before its block. They are the keys ``k < f``."""
    copy, _, blk = bd_rows(t, block_length, copies)
    return copy, np.where(copy == 0,
                          np.minimum((blk + 1) * block_length, t) + 1,
                          blk * block_length + 1).astype(np.int64)


def bd_allowed_per_row(t: int, block_length: int, copies: int = 2):
    """Keys each packed row may attend, [N] int64, from the four rules: a
    clean row the clean rows of its own and earlier blocks; a noised row
    the clean rows of strictly earlier blocks and the noised rows of its
    own block."""
    copy, f = _bd_clean_keys(t, block_length, copies)
    return f + np.where(copy == 0, 0, block_length)


def _bd_codes(t: int, block_length: int, copies: int, n_pad: int):
    """One int32 a query row, all the kernel's mask function gets of it
    (``BlockDiffusionMask``): ``f``, the number of clean keys the row sees
    (``_bd_clean_keys``), plus ``BD_CLEAN`` on a clean row. A noised row's
    own block is the keys ``t + f .. t + f + B - 1``, so its code is ``f``
    alone. A padded row i gets ``i - t``: it sees itself (and padded rows
    after it, and clean keys — any key will do: its output is cut off and
    no gradient reaches it, but its softmax is never empty)."""
    copy, f = _bd_clean_keys(t, block_length, copies)
    return np.concatenate([f + np.where(copy == 0, BD_CLEAN, 0),
                           np.arange(len(copy), n_pad) - t]).astype(np.int32)


@functools.lru_cache(maxsize=None)
def _bd_mask_class():
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)

    class BlockDiffusionMask(sm._ComputableMask):
        """The three-part block mask over the packed rows, computed inside
        the kernel from a query's code (``_bd_codes``) and the key's index:
        clean keys ``k < f``, and on a noised row the ``block_length``
        keys from ``t + f``. A padded key (index past the packed rows) is
        seen by padded queries only: ``f <= t + 1`` keeps clean ranges
        inside the clean copy, and a noised block ends inside the noised
        copy, which holds whole blocks.

        ``_ComputableMask`` is PRIVATE to the splash kernel's package (the
        one way it offers to hand the kernel a mask FUNCTION and a code a
        query row instead of a dense ``[N, N]`` array, 1 GiB here): what
        this class relies on is its ``q_sequence`` / ``mask_function``
        pair and ``__getitem__`` on two slices, as jax 0.9 has them; a
        release that moves them fails here at import or in the mask tests
        (``tests/test_tokenq_block_diffusion.py``), not silently."""

        def __init__(self, n_pad: int, t: int, block_length: int,
                     copies: int):
            self.key = (n_pad, t, block_length, copies)

            def mask_function(q_code, kv_ids):
                clean = kv_ids < (q_code & (BD_CLEAN - 1))
                d = kv_ids - q_code         # negative on a clean row
                return clean | ((d >= t) & (d < t + block_length))

            super().__init__((n_pad, n_pad), mask_function)
            self.q_sequence = _bd_codes(t, block_length, copies, n_pad)

        def __getitem__(self, idx) -> np.ndarray:
            """The dense mask of ``[rows, keys]`` (two slices). The kernel
            builds its block tables by asking for every block in turn
            (1 024 blocks of 1 024 x 1 024 at the cell's size, three
            tables): a block with no allowed pair, or none forbidden, is
            answered from the rows' key RANGES (``[0, f)`` and the own
            block) without evaluating its pairs — a constant of the
            block's shape."""
            n_pad, t, bl, _ = self.key
            (q0, q1, _), (k0, k1, _) = (s.indices(n_pad) for s in idx)
            code = self.q_sequence[q0:q1].astype(np.int64)
            noised = code < BD_CLEAN
            f = code & (BD_CLEAN - 1)
            own = np.where(noised, t + f, 0)
            seen = np.clip(np.minimum(f, k1) - k0, 0, None) + np.where(
                noised, np.clip(np.minimum(own + bl, k1)
                                - np.maximum(own, k0), 0, None), 0)
            shape = (q1 - q0, k1 - k0)
            if seen.sum() in (0, shape[0] * shape[1]):
                return np.broadcast_to(np.bool_(seen[0] > 0), shape)
            return super().__getitem__(idx)

        def __eq__(self, other):
            return isinstance(other, type(self)) and self.key == other.key

        def __hash__(self):
            return hash((type(self).__name__, self.key))

    return BlockDiffusionMask


def bd_mask(n_pad: int, t: int, block_length: int, copies: int = 2):
    """The mask object the kernel is built from (its ``[rows, keys]`` slice
    is the dense mask of those packed rows: the tests hold it to the four
    rules)."""
    return _bd_mask_class()(n_pad, t, block_length, copies)


@functools.lru_cache(maxsize=None)
def _bd_kernel(n_pad: int, t: int, block_length: int, copies: int,
               group: int, block: int, compute_block: int, interpret: bool,
               fused_bwd: bool):
    return _splash(bd_mask(n_pad, t, block_length, copies), group, block,
                   compute_block, interpret, fused_bwd)


def _bd_kernel_for(n: int, t: int, block_length: int, group: int,
                   block: int, compute_block: int, fused_bwd: bool,
                   interpret: bool):
    """(the kernel of ``n`` packed rows — one copy of the window or two —,
    the rows padded to its block)."""
    noised = -(-t // block_length) * block_length
    if n not in (t + 1, t + 1 + noised):
        raise ValueError(
            f"{n} rows are neither one nor two copies of a window of {t} "
            f"steps in blocks of {block_length}")
    n_pad = -(-n // block) * block
    return _bd_kernel(n_pad, int(t), int(block_length), 1 + (n > t + 1),
                      group, int(block), int(compute_block) or int(block),
                      bool(interpret), bool(fused_bwd)), n_pad


def bd_blocks_run_share(n: int, t: int, block_length: int, group: int, *,
                        block: int = 128, compute_block: int = 0,
                        fused_bwd: bool = True,
                        interpret: bool = False) -> float:
    """Blocks the forward kernel RUNS over all ``(n_pad / block)²`` blocks
    of the packed rows, from the kernel's own block table (what skipping
    achieves; the mask lets about a quarter of the pairs through)."""
    kern, n_pad = _bd_kernel_for(n, t, block_length, group, block,
                                 compute_block, fused_bwd, interpret)
    table = np.asarray(kern.fwd_mask_info.block_mask)
    return float(np.count_nonzero(table)) / (table.shape[0]
                                             * (n_pad // block) ** 2)


def block_diffusion_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                              t: int, block_length: int, block: int = 128,
                              compute_block: int = 0, fused_bwd: bool = True,
                              interpret: bool = False) -> jax.Array:
    """softmax(q kᵀ · D^-½ + mask) v over the PACKED rows of a window of
    ``t`` steps in blocks of ``block_length`` (``bd_rows``): ``[B, H, N,
    D]`` queries, ``[B, Hkv, N, D]`` keys and values, N = ``t + 1`` (the
    clean copy alone: block-causal, the acting path) or ``t + 1 + G·B``
    (clean then noised). A clean row sees the clean rows of its own and
    earlier blocks; a noised row the clean rows of strictly earlier blocks
    and the noised rows of its own block. Blocks of the kernel that hold no
    allowed pair are never loaded. The rows are padded to the kernel's
    block; a padded row is a key to no real row."""
    b, h, n, d = q.shape
    hkv = k.shape[1]
    group = h // hkv
    kern, n_pad = _bd_kernel_for(n, t, block_length, group, block,
                                 compute_block, fused_bwd, interpret)
    q = q * jnp.asarray(d ** -0.5, q.dtype)
    if n_pad != n:
        pad = ((0, 0), (0, 0), (0, n_pad - n), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    with jax.named_scope("ddq.attn_bd_core"):       # the kernel calls alone
        out = jax.vmap(jax.vmap(kern))(
            q.reshape(b, hkv, group, n_pad, d), k, v)
    return out.reshape(b, h, n_pad, v.shape[-1])[:, :, :n]
