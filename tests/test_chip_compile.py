"""The main path's Pallas kernels, compiled by the TPU's own compiler for a
DESCRIBED v5e chip (no chip attached) at the real geometries.

Interpret-mode tests cannot see what Mosaic refuses (unaligned slices,
VMEM limits, 32-bit index overflow); these can, at no chip time. All of
them live in this ONE file: the worker that runs it loads libtpu once and
keeps it. The topology is described inside a fixture — never at import —
so every xdist worker collects the same tests. A compile that passes is
not a chip run.
"""

import collections
import functools
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_deep_q_tpu.ops.pallas_kernels import fused_dqn_loss
from distributed_deep_q_tpu.ops.ring_gather import (
    gather_windows, padded_row_bytes, scatter_rows)
from distributed_deep_q_tpu.profiling import scope_table

ROWB = padded_row_bytes(84 * 84)        # 8192 B per 84x84 frame row
# breakout/apex preset: 1M frames, 4 sub-rings, window = stack 4 + n_step 3
BREAKOUT_RING_ROWS = 4 * (250_000 + 6) + 1
BATCH = 512
# r2d2 preset: one "row" is a whole sequence — (stack-1) + (seq_len+1)
# frames — and a shard holds its share of capacity // seq_len sequences
# + 1 scratch. The per-shard plane of the preset's 12 500 sequences on ONE
# chip (12 501 x 172 032 int32) passes Mosaic's 2^31 element range, and
# DeviceSequenceReplay refuses to build it; the dp=4 share is what fits.
R2D2_W = 3 + 81
R2D2_SEQ_BYTES = R2D2_W * ROWB
R2D2_RING_SEQS = (1_000_000 // 80) // 4 + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip, with the persistent compile cache
    off around the module's compiles: an entry written for a described
    device cannot be read back without the chip, and the retry warns."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *avals) -> str:
    return jax.jit(fn).lower(*avals).compile().as_text()


@pytest.mark.parametrize("n,w,rowb,ring_rows", [
    (8 * BATCH, 7, ROWB, BREAKOUT_RING_ROWS),     # fused_chain=8 chunk
    (32 * BATCH, 7, ROWB, BREAKOUT_RING_ROWS),    # chain=32 chunk
    (64, R2D2_W, ROWB, R2D2_RING_SEQS * R2D2_W),        # r2d2 per-step
    (8 * 64, R2D2_W, ROWB, R2D2_RING_SEQS * R2D2_W),    # r2d2 chained
], ids=["breakout-chain8", "breakout-chain32", "r2d2-step", "r2d2-chain8"])
def test_gather_windows_compiles_for_v5e(one_chip, n, w, rowb, ring_rows):
    assert ring_rows * (rowb // 4) < 2**31    # Mosaic's 32-bit indexing
    idx = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    ring = jax.ShapeDtypeStruct((ring_rows * (rowb // 4),), jnp.int32,
                                sharding=one_chip)
    text = _compiled_text(
        functools.partial(gather_windows, n=n, w=w, rowb=rowb), idx, ring)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,staged_rows,rowb,ring_rows", [
    (2 * 64, 64, ROWB, BREAKOUT_RING_ROWS),       # write_chunk=64 + ghosts
    (2 * 1024, 1024, ROWB, BREAKOUT_RING_ROWS),   # largest chunk in use
    (4, 4, R2D2_SEQ_BYTES, R2D2_RING_SEQS),       # r2d2: 4 sequences/flush
], ids=["breakout-chunk64", "breakout-chunk1024", "r2d2-chunk4"])
def test_scatter_rows_compiles_for_v5e(one_chip, n, staged_rows, rowb,
                                       ring_rows):
    rowp = rowb // 4
    assert ring_rows * rowp < 2**31
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    text = _compiled_text(
        functools.partial(scatter_rows, n=n, rowb=rowb),
        i32((n,)), i32((n,)), i32((staged_rows * rowp,)),
        i32((ring_rows * rowp,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch,actions", [(512, 4), (512, 18), (32, 6)],
                         ids=["breakout-b512", "apex-b512-a18", "b32"])
def test_fused_huber_fwd_and_grad_compile_for_v5e(one_chip, batch, actions):
    def loss_and_grad(q, a, t, w):
        return jax.value_and_grad(
            lambda qq: fused_dqn_loss(qq, a, t, w, 1.0, False)[0])(q)

    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    text = _compiled_text(
        loss_and_grad, f32((batch, actions)),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip),
        f32((batch,)), f32((batch,)))
    # forward kernel + hand-written backward kernel
    assert text.count("tpu_custom_call") >= 2


# -- the token-window Q-network's kernels at the published widths -----------
# SmallThinker-21BA3B: 28 query / 4 key-value heads of 128, window 4 096 on
# windows of 8 193 tokens (padded to the 1 024 block); experts of width 768
# over hidden 2 560, 8 held: the sizes, the buffer and the tiles are read
# from the preset (config.smallthinker_tokenq_config), not restated here.
# LFM2-24B-A2B (config.lfm2_tokenq_config): 32 / 8 heads of 64 — half the
# 128 lanes — full attention; SwiGLU experts of width 1 536 over hidden
# 2 048, top 4.
# Moonlight-16B-A3B (config.moonlight_tokenq_config): 16 latent heads whose
# scores run over 128 + 64 = 192 — one and a half lane tiles — and whose
# values are 128 wide, on windows of 8 192 tokens (whole blocks); SwiGLU
# experts of width 1 408 over hidden 2 048, top 6.

@pytest.mark.parametrize(
    "preset,window,sizes",      # sizes: T + 1, head size, the preset's window
    [("smallthinker_tokenq", 0, (8193, 128, 4096)),
     ("smallthinker_tokenq", 4096, (8193, 128, 4096)),
     ("lfm2_tokenq", 0, (8193, 64, 0))],
    ids=["full", "window4096", "lfm2-head64"])
def test_window_attention_compiles_for_v5e(one_chip, preset, window, sizes):
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.ops.attention import causal_attention

    cfg = PRESETS[preset]()
    tq, t = cfg.net.tokenq, cfg.replay.sequence_length + 1
    windowed = any(tq.sliding_window_layout[:tq.num_hidden_layers])
    assert (t, tq.head_dim,
            tq.sliding_window_size if windowed else 0) == sizes
    q = jax.ShapeDtypeStruct(
        (1, tq.num_attention_heads, t, tq.head_dim), jnp.bfloat16,
        sharding=one_chip)
    kv = jax.ShapeDtypeStruct(
        (1, tq.num_key_value_heads, t, tq.head_dim), jnp.bfloat16,
        sharding=one_chip)

    def fwd_bwd(q, k, v):
        f = lambda *a: jnp.sum(causal_attention(  # noqa: E731
            *a, window=window, block=tq.attn_block,
            compute_block=tq.attn_compute_block).astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_bwd, q, kv, kv)
    for kernel in ("splash_mqa_fwd", "splash_mqa_dkv"):    # dq is fused in
        assert kernel in text
    assert "tpu_custom_call" in text


def test_latent_attention_core_compiles_for_v5e(one_chip):
    """The latent mixer's kernel call at the Moonlight preset's sizes:
    scores 192 wide over values of 128, 16 key/value heads (group 1), the
    preset's blocks, forward and the fused backward. Mosaic takes the
    192-wide contraction as it is: nothing is padded to 256."""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.ops.attention import causal_attention

    cfg = PRESETS["moonlight_tokenq"]()
    tq, t = cfg.net.tokenq, cfg.replay.sequence_length + 1
    d_qk = tq.qk_nope_head_dim + tq.qk_rope_head_dim
    assert (t, tq.num_attention_heads, d_qk, tq.v_head_dim,
            t % tq.attn_block) == (8192, 16, 192, 128, 0)
    S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                          sharding=one_chip)
    qk = S((1, tq.num_attention_heads, t, d_qk))
    v = S((1, tq.num_attention_heads, t, tq.v_head_dim))

    def fwd_bwd(q, k, v):
        f = lambda *a: jnp.sum(causal_attention(  # noqa: E731
            *a, block=tq.attn_block,
            compute_block=tq.attn_compute_block).astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_bwd, qk, qk, v)
    for kernel in ("splash_mqa_fwd", "splash_mqa_dkv"):    # dq is fused in
        assert kernel in text
    assert f"bf16[1,16,{t},{tq.v_head_dim}]" in text


@pytest.mark.parametrize(
    "preset,buffer_rows", [("smallthinker_tokenq", 196864),  # 4 x 8 193 x 6
                           ("lfm2_tokenq", 65792),           # 2 x 8 193 x 4
                           ("moonlight_tokenq", 98304)],     # 2 x 8 192 x 6
    ids=["smallthinker-reglu", "lfm2-swiglu", "moonlight-swiglu"])
def test_held_experts_grouped_matmul_compiles_for_v5e(one_chip, preset,
                                                      buffer_rows):
    """The expert layer as ``models/tokenq.feed_forward`` calls it: the
    whole batch's token-slots, the preset's worst-case bound (tile-rounded)
    walked in blocks of 16 m-tiles, bfloat16, with its model's gate. The
    bound is never a buffer: no array of that many rows stands, float32
    or bfloat16, forward or backward."""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.models.tokenq import ACTS
    from distributed_deep_q_tpu.ops import moe

    cfg = PRESETS[preset]()
    tq = cfg.net.tokenq
    b, t = cfg.replay.batch_size, cfg.replay.sequence_length + 1
    n = b * t
    k, held = tq.moe_num_active_primary_experts, tq.experts_held
    h, f = tq.hidden_size, tq.moe_ffn_hidden_size
    rows = moe.buffer_rows(n, k, held, tq.moe_tile)
    assert (rows, tq.moe_tile) == (buffer_rows, 256)
    assert moe.block_rows(rows, tq.moe_tile) == 4096
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)

    def fwd_bwd(x, idx, p, wg, wu, wd):
        f_ = lambda x, wg, wu, wd: jnp.sum(jnp.sin(  # noqa: E731
            moe.held_experts_ffn(
                x, idx, p, wg, wu, wd, offset=tq.expert_offset, rows=rows,
                tile=tq.moe_tile, compute_dtype=jnp.dtype(
                    cfg.net.compute_dtype),
                act=ACTS[tq.hidden_act])[0]))
        return jax.grad(f_, argnums=(0, 1, 2, 3))(x, wg, wu, wd)

    text = _compiled_text(
        fwd_bwd, S((b, t, h), jnp.float32), S((n, k), jnp.int32),
        S((n, k), jnp.float32), S((held, h, f), jnp.float32),
        S((held, h, f), jnp.float32), S((held, f, h), jnp.float32))
    # a block's two forward products, again in the backward's recomputed
    # block, and there two input-side products and two weight-side ones
    assert text.count('custom_call_target="tpu_custom_call"') >= 5
    # the pin that the whole buffer never stands: f32[49408,2560] (a
    # sequence's) and its twins are gone, and none batch-wide took their
    # place (the sort's keys and the load's count are integers a slot)
    wide = re.findall(rf"\b(?:f32|bf16)\[(?:{rows}|{n * k}),\d+\]", text)
    assert not wide, sorted(set(wide))
    assert f"f32[4096,{h}]" in text


# NVIDIA-Nemotron-3-Nano-30B-A3B (config.nemotron_tokenq_config): Mamba-2
# mixers of 64 heads of 64 with a state of 128 (8 groups, chunks of 128,
# segments of 2 048 rows), ONE attention layer of 32 query heads over 2
# key/value heads (a group of 16), two-matrix relu² experts of width 1 856
# = 14.5 x 128 over hidden 2 688, on windows of 8 192 tokens.

def test_attention_in_groups_of_16_compiles_for_v5e(one_chip):
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.ops.attention import causal_attention

    cfg = PRESETS["nemotron_tokenq"]()
    tq, t = cfg.net.tokenq, cfg.replay.sequence_length + 1
    assert (t, tq.num_attention_heads, tq.num_key_value_heads, tq.head_dim,
            t % tq.attn_block, any(tq.rope_layout[:7])) == (
        8192, 32, 2, 128, 0, False)
    S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                          sharding=one_chip)
    q = S((1, tq.num_attention_heads, t, tq.head_dim))
    kv = S((1, tq.num_key_value_heads, t, tq.head_dim))

    def fwd_bwd(q, k, v):
        f = lambda *a: jnp.sum(causal_attention(  # noqa: E731
            *a, block=tq.attn_block,
            compute_block=tq.attn_compute_block).astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_bwd, q, kv, kv)
    for kernel in ("splash_mqa_fwd", "splash_mqa_dkv"):    # dq is fused in
        assert kernel in text


def test_two_matrix_experts_off_the_lanes_compile_for_v5e(one_chip):
    """The expert layer without a gate matrix at a width no multiple of
    128 divides: the grouped matmul takes tiles of 384 (the last one
    overhangs and the kernel masks it), the up product reads its weights
    as ``[held, f, h]`` — the layout the chip keeps ``[held, h, f]`` in
    when ``f`` is off the lanes —, nothing is padded to 1 920 and no
    tile is 1 856 wide."""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.models.tokenq import ACTS
    from distributed_deep_q_tpu.ops import moe

    cfg = PRESETS["nemotron_tokenq"]()
    tq = cfg.net.tokenq
    b, t = cfg.replay.batch_size, cfg.replay.sequence_length + 1
    n, k, held = b * t, tq.moe_num_active_primary_experts, tq.experts_held
    h, f = tq.hidden_size, tq.moe_ffn_hidden_size
    rows = moe.buffer_rows(n, k, held, tq.moe_tile)
    assert (h, f, rows, tq.ffn_gated, tq.hidden_act) == (
        2688, 1856, 98304, False, "relu2")
    assert (moe._fit(f), moe._fit(h)) == (384, 384)
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)

    def fwd_bwd(x, idx, p, wu, wd):
        f_ = lambda x, wu, wd: jnp.sum(jnp.sin(  # noqa: E731
            moe.held_experts_ffn(
                x, idx, p, None, wu, wd, offset=0, rows=rows,
                tile=tq.moe_tile, compute_dtype=jnp.bfloat16,
                act=ACTS[tq.hidden_act])[0]))
        return jax.grad(f_, argnums=(0, 1, 2))(x, wu, wd)

    text = _compiled_text(
        fwd_bwd, S((b, t, h), jnp.float32), S((n, k), jnp.int32),
        S((n, k), jnp.float32), S((held, h, f), jnp.float32),
        S((held, f, h), jnp.float32))
    assert text.count('custom_call_target="tpu_custom_call"') >= 5
    assert "1920" not in re.sub(r"metadata=\{[^}]*\}", "", text)
    assert f"bf16[{held},{f},{h}]" in text      # the up matrices, as held
    assert not re.findall(rf"\b(?:f32|bf16)\[(?:{rows}|{n * k}),\d+\]",
                          text)


def test_a_state_space_layer_compiles_for_v5e(one_chip):
    """A Mamba-2 layer whole (``models/tokenq.mamba_mixer``: norm, the two
    projections, convolution, the chunked scan, the gated group norm) at
    the preset's sizes, forward and backward: a segment of 2 048 rows at a
    time, so what stands beside the layer's input, output and gradients is
    a segment's intermediates, under 2.5 GB where the whole window's were
    4.9 GB."""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.models import tokenq

    cfg = PRESETS["nemotron_tokenq"]()
    tq = cfg.net.tokenq
    b, t = cfg.replay.batch_size, cfg.replay.sequence_length + 1
    assert (tq.mamba_num_heads, tq.mamba_head_dim, tq.ssm_state_size,
            tq.n_groups, tq.conv_kernel, tq.chunk_size, tq.ssm_segment,
            t % tq.ssm_segment) == (64, 64, 128, 8, 4, 128, 2048, 0)
    S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                          sharding=one_chip)
    p = jax.tree.map(S, tokenq.param_shapes(cfg.net)["layer_00"],
                     is_leaf=lambda x: isinstance(x, tuple))

    def fwd_bwd(x, p):
        return jax.grad(lambda x, p: jnp.sum(jnp.square(
            tokenq.mamba_mixer(x, p, cfg.net)[0])), argnums=(0, 1))(x, p)

    compiled = jax.jit(fwd_bwd).lower(S((b, t, tq.hidden_size)), p).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 2 ** 30
    assert "tpu_custom_call" not in compiled.as_text()  # plain XLA, no kernel


def _instructions(text: str) -> str:
    """The optimised module's computations alone: no module header, no
    ``metadata={...}``, none of the source tables after the computations
    (they carry the Python lines of whoever traced it)."""
    keep, table = [], False
    for ln in re.sub(r", metadata=\{[^}]*\}", "", text).splitlines():
        if ln.startswith(("FileNames", "FunctionNames", "FileLocations",
                          "StackFrames")):
            table = True
        elif ln and not ln[0].isdigit() and not ln[0].isspace():
            table = False
        if not table and not ln.startswith("HloModule"):
            keep.append(ln)
    return "\n".join(keep)


@pytest.mark.parametrize("preset", ["smallthinker_tokenq", "lfm2_tokenq"])
def test_route_scale_one_is_the_unscaled_router_for_v5e(one_chip, preset):
    """``ops/moe.route`` multiplies the renormalised gates by ``scale``
    with no branch on 1.0: the chip's compiler drops a product with 1.0,
    so forward and backward of the router at a sibling's settings (one
    sequence of the preset, its router's width and top k) optimise to the
    instructions the router had BEFORE it had a scale."""
    from jax import lax

    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.ops import moe

    cfg = PRESETS[preset]()
    tq, n = cfg.net.tokenq, cfg.replay.sequence_length + 1
    k, e, h = (tq.moe_num_active_primary_experts,
               tq.moe_num_primary_experts, tq.hidden_size)
    soft = tq.moe_primary_router_apply_softmax

    def unscaled(u, w, bias):
        z = jnp.dot(u, w, precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
        if soft:
            top_p, top_i = lax.top_k(jax.nn.softmax(z, -1), k)
            total = jnp.sum(top_p, -1, keepdims=True)
        else:
            s = jax.nn.sigmoid(z)
            _, top_i = lax.top_k(s + bias, k)
            top_p = jnp.take_along_axis(s, top_i, axis=-1)
            total = jnp.sum(top_p, -1, keepdims=True) + 1e-6
        return top_i.astype(jnp.int32), top_p / total

    def scaled(u, w, bias, scale=1.0):
        return moe.route(u, w, k, softmax=soft,
                         bias=None if soft else bias, scale=scale)

    def program(route):
        def fwd_bwd(u, w, bias, g):
            def loss(u, w):
                idx, p = route(u, w, bias)
                return jnp.sum(p * g), idx
            return jax.value_and_grad(loss, argnums=(0, 1),
                                      has_aux=True)(u, w)
        S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                              sharding=one_chip)
        return _instructions(_compiled_text(
            fwd_bwd, S((n, h)), S((h, e)), S((e,)), S((n, k))))

    before = program(unscaled)
    assert program(scaled) == before
    # and the comparison sees a product that stays
    assert program(functools.partial(scaled, scale=2.446)) != before


def test_short_conv_mix_compiles_for_v5e(one_chip):
    """LFM2's gates and 3-tap convolution, forward and backward, on one
    sequence of the cell's batch at the published width: plain XLA
    fusions, no custom call."""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.models.tokenq import CONV_TAPS
    from distributed_deep_q_tpu.ops.short_conv import short_conv_mix

    cfg = PRESETS["lfm2_tokenq"]()
    tq, t = cfg.net.tokenq, cfg.replay.sequence_length + 1
    S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                          sharding=one_chip)

    def fwd_bwd(bcz, w):
        return jax.grad(lambda *a: jnp.sum(short_conv_mix(*a)),
                        argnums=(0, 1))(bcz, w)

    text = _compiled_text(
        fwd_bwd, S((cfg.replay.batch_size, t, 3 * tq.hidden_size)),
        S((tq.hidden_size, CONV_TAPS)))
    assert "fusion" in text and "tpu_custom_call" not in text


@pytest.mark.parametrize("with_loss", [False, True],
                         ids=["core", "core_and_indexer_loss"])
def test_sparse_attention_compiles_for_v5e(one_chip, with_loss):
    """The learned sparse attention at the Keye preset's widths (32 / 4
    heads of 128, an indexer of 16 heads of 64, top 2 048, blocks of 512)
    on a window of 4 096 + 1 tokens padded to whole blocks: the selection,
    the three flash kernels that read it as bits (forward, dq, dk + dv)
    and with ``with_loss`` the kernel that adds up the heads'
    probabilities for the indexer's loss, forward and backward. The
    indexer's float32 ``highest`` products, one body a loop: the
    selection's one, and the loss's THREE — a chunk's scores once and the
    two products of their pull-back (a fourth is the loss scoring the
    block a second time, which ``select``'s normaliser took away)."""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.ops import sparse_attention as sa

    tq = PRESETS["keye_tokenq"]().net.tokenq
    block, t = tq.indexer_q_chunk, 4096 + 1
    tm = -(-t // block) * block
    hq, hkv, d = (tq.num_attention_heads, tq.num_key_value_heads,
                  tq.head_dim)
    hi, di = tq.indexer_num_heads, tq.indexer_head_dim

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, q_i, w_i, k_i):
        o, c = sa.sparse_attention(
            q, k, v, q_i, w_i, k_i, topk=tq.indexer_topk, block=block,
            t_real=t, with_loss=with_loss)
        return jnp.sum(o.astype(jnp.float32) ** 2) + c["index_loss"]

    text = _compiled_text(
        jax.grad(loss, argnums=tuple(range(6))),
        S((1, hq, tm, d), jnp.bfloat16), S((1, hkv, tm, d), jnp.bfloat16),
        S((1, hkv, tm, d), jnp.bfloat16), S((1, tm, hi, di), jnp.float32),
        S((1, tm, hi), jnp.float32), S((1, tm, di), jnp.float32))
    for kernel in ("topk_threshold", "sparse_core_fwd", "sparse_core_dq",
                   "sparse_core_dkv"):
        assert kernel in text
    assert ("sparse_head_probs" in text) == with_loss
    products = re.findall(
        r"convolution\(.*operand_precision=\{highest,highest\}", text)
    assert len(products) == (1 + 3 if with_loss else 1)


def test_the_selection_holds_its_keys_in_vmem_for_v5e(one_chip):
    """``select`` at the Keye cell's own window (T 16 384 + 1 padded to
    16 896, chunks of 1 536, top 2 048, query blocks of 512): the search's
    kernel takes a block's keys ``u`` [512, 16 896] as a VMEM operand and
    a tile of rows of ALL eleven chunks as scratch — what the 4 608-wide
    window of the test above never asks of the chip's compiler — and XLA
    holds ``u`` in VMEM (``S(1)``) from the loop that scores it to the
    kernel and the keep loop: no copy of it reaches HBM. (A kernel that
    asked for the core's 100 MiB would leave XLA no room for it.)"""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.ops import sparse_attention as sa

    tq = PRESETS["keye_tokenq"]().net.tokenq
    block, t_real = tq.indexer_q_chunk, 16384 + 1
    t = -(-t_real // block) * block
    hi, di = tq.indexer_num_heads, tq.indexer_head_dim

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = _compiled_text(
        lambda *a: sa.select(*a, topk=tq.indexer_topk, block=block,
                             t_real=t_real, interpret=False)[:2],
        S(t, hi, di), S(t, hi), S(t, di))
    assert "topk_threshold" in text
    keys = re.findall(rf"u32\[{block},{t}\]\{{1,0:T[^}}]*\}}", text)
    assert keys and all("S(1)" in layout for layout in keys), set(keys)


# -- the fused CNN programs at the b512 cell's sizes ------------------------

def _cnn_learner(topo, cfg, n_step):
    """``cfg``'s learner on one described chip and the fused programs'
    spec for it (84x84 frames, 1M rows in 4 slots)."""
    import numpy as np
    from jax.sharding import Mesh

    from distributed_deep_q_tpu.models.qnet import build_qnet
    from distributed_deep_q_tpu.parallel.learner import Learner

    rep = cfg.replay
    stack = cfg.net.stack
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dp", "model"))
    module = build_qnet(cfg.net)
    learner = Learner(lambda p, o: module.apply({"params": p}, o),
                      cfg.train, mesh)
    spec = (250_000, 250_000 + stack + n_step - 1, ROWB, 84 * 84, stack,
            n_step, cfg.train.gamma, (84, 84), rep.batch_size,
            rep.priority_alpha, rep.priority_eps, 1, False)
    return cfg, module, learner, mesh, spec


def _b512_learner(topo, window=None):
    """The breakout preset's learner (batch 512, bf16, chain 8);
    ``window`` moves ``n_step`` off the preset's 3 (window 7) to give
    another."""
    from distributed_deep_q_tpu.config import PRESETS

    cfg = PRESETS["breakout"]()
    return _cnn_learner(topo, cfg, cfg.replay.n_step if window is None
                        else window - cfg.net.stack)


def _b32_learner(topo):
    """The ``dqn_b32`` cell's learner: the pong preset with the signal
    env's 4 actions at batch 32 (``benchmark/configs/dqn_b32.json``),
    n-step 1 (window 5), chain 8 — 32 rows a shard, so the plane body."""
    import dataclasses

    from distributed_deep_q_tpu.config import PRESETS

    cfg = PRESETS["pong"]()
    cfg.net = dataclasses.replace(cfg.net, num_actions=4)
    cfg.replay = dataclasses.replace(cfg.replay, batch_size=32)
    return _cnn_learner(topo, cfg, cfg.replay.n_step)


def _sharded_aval(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    def S(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes)))
    return S


def _instructions(text: str) -> list:
    """``(name, result shape with its layout, opcode)`` of every
    instruction of a compiled program's text, fused ones included."""
    return [(m[1], m[2], m[3]) for m in re.finditer(
        r"^\s*(?:ROOT )?(%[\w.-]+) = (\S+) ([\w-]+)\(", text, re.M)]


def _elements(shape: str) -> int:
    """Element count of an HLO array shape like ``s32[8,512,7]{2,1,0}``;
    0 for a tuple or a scalar."""
    m = re.match(r"\w+\[([\d,]+)\]", shape)
    return math.prod(int(d) for d in m[1].split(",")) if m else 0


@pytest.mark.parametrize("window", [7, 5], ids=["window7", "window5"])
def test_b512_sample_program_hands_the_windows_over_as_a_bitcast_for_v5e(
        topo, one_chip, window):
    """The sample program at the b512 cell's sizes (1M metadata rows in 4
    slots, the ring of ``test_gather_windows``' breakout case, chain 8):
    the chunk's windows — 8 x 512 x window padded rows of 2 048 words —
    are written ONCE, by the Mosaic DMA, and leave the program as a
    bitcast of that. ``[chain, batch, window, rowp]`` put 7 rows in the
    sublanes of an (8, 128) tile: a ``reshape`` into the padded layout and
    a ``copy`` out of it, each the whole chunk (PERF.md §6, PR 35).
    25-110 s a case here: the draw over 1M rows is what compiles long."""
    cfg, _, learner, mesh, spec = _b512_learner(topo, window)
    chain, batch = cfg.replay.fused_chain, cfg.replay.batch_size
    slots, slot_cap, slot_pad, rowp = 4, spec[0], spec[1], ROWB // 4
    sample, _ = learner._build_device_per_step(spec, chain)
    S = _sharded_aval(mesh)
    meta = lambda dtype: S((slots * slot_cap,), dtype, "dp")  # noqa: E731
    text = sample.lower(
        S((1, chain, 2), jnp.uint32, "dp"),
        S(((slots * slot_pad + 1) * rowp,), jnp.int32, "dp"),
        meta(jnp.int32), meta(jnp.float32), meta(jnp.uint8),
        meta(jnp.uint8), meta(jnp.float32),
        S((slots,), jnp.int32, "dp"), S((slots,), jnp.int32, "dp"),
        S((chain,), jnp.float32)).compile().as_text()

    words = chain * batch * window * rowp
    assert words == (58_720_256 if window == 7 else 41_943_040)
    tiled = f"s32[{chain},{batch},{window},{rowp // 128},128]"
    # every instruction whose RESULT is chunk-wide: its name and opcode
    wide = [(name, op) for name, shape, op in _instructions(text)
            if words == _elements(shape)]
    ops = sorted(op for _, op in wide)
    assert ops == ["bitcast", "custom-call"], wide
    kernel = next(n for n, op in wide if op == "custom-call")
    assert kernel.startswith("%sample_fn")
    # the program's scope table of this text (ISSUE 36): the three sample
    # scopes partition what ran under ``ddq.sample``, the compiler's own
    # passes (the prefix sums' ``reduce-window``s, relayout copies) took a
    # neighbour's scope, and the kernel stays outside every scope — XLA
    # would name it after one, and ``gather_windows_roofline`` finds it as
    # ``%sample_fn.N``
    table = scope_table(text)
    assert {st[-1] for st in table["scopes"].values()} == {
        "ddq.sample_prep", "ddq.meta_pack", "ddq.draw"}
    assert kernel.lstrip("%") not in table["scopes"]
    made = [i for i in table["inherited"] if i.startswith("reduce-window")]
    assert made and all(
        table["scopes"][i][-1] == "ddq.sample_prep" for i in made)
    assert re.search(
        re.escape(tiled) + r"\S* bitcast\(%sample_fn", text), wide


def _train_program_text(cfg, module, learner, mesh, spec) -> str:
    """The fused TRAIN program of ``_cnn_learner``'s pair, compiled for
    the described chip and fed the windows in the view the sample program
    hands over."""
    from distributed_deep_q_tpu.models.qnet import init_params
    from distributed_deep_q_tpu.parallel.learner import TrainState

    rep = cfg.replay
    stack, chain, batch = cfg.net.stack, rep.fused_chain, rep.batch_size
    window, rowp = stack + spec[5], ROWB // 4     # spec[5]: n_step
    _, train = learner._build_device_per_step(spec, chain)
    S = _sharded_aval(mesh)

    params = jax.eval_shape(lambda: init_params(module, cfg.net, 0, 4))
    state = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda p: TrainState(
            params=p, target_params=p, opt_state=learner.opt.init(p),
            step=jnp.zeros((), jnp.int32)), params))
    row = lambda dtype: S((chain, batch), dtype, None, "dp")  # noqa: E731
    mask = S((chain, batch, stack), jnp.uint8, None, "dp", None)
    metas = {"action": row(jnp.int32), "reward": row(jnp.float32),
             "discount": row(jnp.float32), "weight": row(jnp.float32),
             "ovalid": mask, "nvalid": mask}
    return train.lower(
        state, metas,
        S((chain, batch, window, rowp // 128, 128), jnp.int32,
          None, "dp", None, None, None),
        row(jnp.int32), S((1_000_000,), jnp.float32, "dp"),
        S((), jnp.float32)).compile().as_text()


def test_b512_train_program_unpacks_by_planes_for_v5e(topo, one_chip):
    """The breakout preset's whole train program (batch 512, 84x84, bf16,
    window 7, chain 8), fed the windows in the view the sample program
    hands over: the pixel unpack sits under ``ddq.unpack``, and no
    instruction of the program makes the window four words wide — the
    ``u32[512,7,2048,4]`` broadcast, and its ``u8[512,7,8192]`` consumer,
    by which the chip's compiler lowers a ``bitcast_convert_type`` to
    uint8 (PERF.md §6, PR 32) — in the row's flat spelling or in its
    ``(16, 128)`` one. ~25 s."""
    cfg, module, learner, mesh, spec = _b512_learner(topo)
    rep = cfg.replay
    batch, window, rowp = rep.batch_size, cfg.net.stack + rep.n_step, \
        ROWB // 4
    text = _train_program_text(cfg, module, learner, mesh, spec)
    assert learner.unpack_planes == 1
    assert "ddq.unpack" in text
    innermost = {st[-1] for st in scope_table(text)["scopes"].values()}
    assert innermost == {
        "ddq.train", "ddq.unpack", "ddq.conv_in", "ddq.conv_mid", "ddq.fc",
        "ddq.loss", "ddq.optimizer", "ddq.priority_writeback"}
    for words, bytes_ in ((f"{rowp}", f"{4 * rowp}"),
                          (f"{rowp // 128},128", f"{rowp // 128},512")):
        assert f"[{batch},{window},{words},4]" not in text
        assert f"u8[{batch},{window},{bytes_}]" not in text


def _off_the_lanes(text: str, at_least: int) -> list:
    """Every instruction of a compiled program whose result holds
    ``at_least`` elements or more with fewer than 128 of them along its
    minor-most dimension: laid out in (8, 128) tiles, an array that size
    is padded up to 32x. A 1-D plane is never one."""
    found = []
    for name, shape, op in _instructions(text):
        m = re.match(r"\w+\[([\d,]+)\](?:\{(\d+))?", shape)
        if not m:
            continue
        dims = [int(d) for d in m[1].split(",")]
        minor = dims[int(m[2])] if m[2] else dims[-1]
        if math.prod(dims) >= at_least and minor < 128:
            found.append((name, shape, op))
    return found


def test_b32_plane_conversions_move_leaf_sized_blocks_for_v5e(one_chip):
    """``plane_to_param_trees`` + ``plane_to_tree`` (twice: Adam's two
    moments) over the ``dqn_b32`` cell's ten leaves: what the chip's
    compiler makes of the cut-then-reshape. It used to commute the head
    kernel's ``[512, 4]`` reshape with its slice and lay the WHOLE plane
    out 4 wide — ``f32[843090,4]`` and two ``f32[421545,4]``, (8, 128)
    tiles padded 32x, 0.319 of the 0.808 ms step (PERF.md §6, PR 41) —
    which XLA:CPU never does, so only this compile can see it come back.
    Now whatever is larger than the largest leaf is a 1-D plane (the
    arguments and the compiler's prefetches of them) and the head leaf is
    reshaped from its own 2 048 elements. ~5 s (the parent's form took
    50 s: the compiler laboured over those reshapes too)."""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.models.qnet import build_qnet, init_params
    from distributed_deep_q_tpu.parallel.learner import (
        plane_meta, plane_to_param_trees, plane_to_tree)

    net = PRESETS["pong"]().net
    net.num_actions = 4
    params = jax.eval_shape(lambda: init_params(build_qnet(net), net, 0))
    meta = plane_meta(params)
    assert meta.n == 1_686_180 and (512, 4) in meta.shapes

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tmpl = jax.tree.map(lambda x: S(x.shape, x.dtype), params)
    mu = jax.tree.map(lambda x: S(x.shape, jnp.bfloat16), params)

    def unpack(pt, m, v, params, target, mu):
        return (plane_to_param_trees(meta, pt, params, target),
                plane_to_tree(meta, m, mu), plane_to_tree(meta, v, params))

    text = _compiled_text(
        unpack, S((2 * meta.n,), jnp.float32), S((meta.n,), jnp.bfloat16),
        S((meta.n,), jnp.float32), tmpl, tmpl, mu)
    wide = [(name, shape) for name, shape, _ in _instructions(text)
            if _elements(shape) > max(meta.sizes)]
    assert wide and all(
        re.match(r"\w+\[\d+\]", shape) for _, shape in wide), wide
    assert re.search(r"f32\[512,4\]\S* reshape\(", text)
    assert "ddq.plane_unpack" in text


def test_b32_train_program_keeps_its_planes_flat_for_v5e(topo, one_chip):
    """The ``dqn_b32`` cell's whole train program (the plane body: batch
    32, window 5, chain 8): no instruction lays a plane's worth of
    elements out under 128 lanes wide — where the planes are a scan's
    output and not an argument, the barrier of ``_plane_blocks`` holds
    too — and the three relayout scopes are still there to be read
    (``plane_relayout_ms_per_step``). ~20 s; with the plane-sized
    reshapes in it this program took 270 s to compile here."""
    from distributed_deep_q_tpu.models.qnet import init_params
    from distributed_deep_q_tpu.parallel.learner import plane_meta

    cfg, module, learner, mesh, spec = _b32_learner(topo)
    text = _train_program_text(cfg, module, learner, mesh, spec)
    assert learner.unpack_planes == 0
    assert "plane_train_fn" in text
    innermost = {st[-1] for st in scope_table(text)["scopes"].values()}
    assert innermost >= {"ddq.plane_pack", "ddq.plane_unpack",
                         "ddq.grad_plane", "ddq.optimizer"}
    n = plane_meta(
        jax.eval_shape(lambda: init_params(module, cfg.net, 0))).n
    assert n == 1_686_180
    # the one array that size which IS under the lanes: conv 1's stacked
    # observations, batch-minor with 32 rows of 128 (``ddq.conv_in``,
    # 0.060 ms a step: the model's matter, not the planes')
    obs = f"[2,{cfg.replay.batch_size},84,84,{cfg.net.stack}]"
    assert [i for i in _off_the_lanes(text, n) if obs not in i[1]] == []
    assert "[843090,4]" not in text and "[421545,4]" not in text


# -- Laguna-XS.2's two kinds of attention layer (config.laguna_tokenq_config):
# 48 heads under the causal mask and 64 under a band of 512 keys over the
# same 8 key/value heads, on windows of 16 385 tokens.

@pytest.mark.parametrize("windowed", [False, True], ids=["full", "sliding"])
def test_laguna_attention_kinds_compile_for_v5e(one_chip, windowed):
    """Each kind at its own head count, block and backward. The fused
    backward keeps one partial dq a KV BLOCK whatever the mask: under the
    band at a block of 512 that is 33 x 277 MB = 9.1 GB of temporaries for
    ONE layer and under the causal mask at 1 024 17 x 214 MB = 3.6 GB,
    which is why both kinds ask for the two separate kernels here: with
    them the whole train program stands at 12.8 GB beside a ring of 1.2
    (PERF.md §6, PR 40)."""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.models import tokenq
    from distributed_deep_q_tpu.ops.attention import causal_attention

    cfg = PRESETS["laguna_tokenq"]()
    tq, t = cfg.net.tokenq, cfg.replay.sequence_length + 1
    kind = next(k for k in tokenq.layer_plan(tq) if k["windowed"] == windowed)
    block, compute, window = (
        (tq.sliding_attn_block, 0, tq.sliding_window_size) if windowed
        else (tq.attn_block, tq.attn_compute_block, 0))
    fused = tq.attn_fused_bwd
    assert (t, kind["heads"], window, block, fused) == (
        (16385, 64, 512, 512, False) if windowed
        else (16385, 48, 0, 1024, False))
    S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                          sharding=one_chip)
    q = S((1, kind["heads"], t, tq.head_dim))
    kv = S((1, tq.num_key_value_heads, t, tq.head_dim))

    def fwd_bwd(fused_bwd):
        def run(q, k, v):
            f = lambda *a: jnp.sum(causal_attention(  # noqa: E731
                *a, window=window, block=block, compute_block=compute,
                fused_bwd=fused_bwd).astype(jnp.float32))
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        return jax.jit(run).lower(q, kv, kv).compile()

    two = fwd_bwd(fused)
    for kernel in ("splash_mqa_fwd", "splash_mqa_dkv", "splash_mqa_dq"):
        assert kernel in two.as_text()
    blocks = -(-t // block)
    dq = 2 * kind["heads"] * blocks * block * tq.head_dim      # bfloat16
    one = fwd_bwd(True).memory_analysis().temp_size_in_bytes
    assert one > blocks * dq                # a partial dq a kv block
    assert two.memory_analysis().temp_size_in_bytes < one / 3


# The fused rotary pass (``ops/rotary.turn``) at the geometries that run
# it: Laguna's two kinds on T 16 385 (not a multiple of 8: the last row
# block holds one row) — 48 heads with r 64 of 128 (the lane select, YaRN's
# factor), 64 heads with r 128 — over 8 key/value heads, and SDAR's packed
# window of 32 769 rows at their own position ids.

ROTARY_GEOMETRIES = {
    "laguna_full": ("laguna_tokenq", False, (48, 8, 16385, 64)),
    "laguna_sliding": ("laguna_tokenq", True, (64, 8, 16385, 128)),
    "sdar_packed": ("sdar_tokenq", False, (32, 4, 32769, 128)),
}


def _rotary_geometry(geometry):
    """(the preset's NetConfig, the layer's index and plan entry, the
    rows of a window as the mixer sees them, bd_steps, (inv, factor),
    the rows' position ids)."""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.models import tokenq
    from distributed_deep_q_tpu.ops.attention import bd_rows

    preset, windowed, want = ROTARY_GEOMETRIES[geometry]
    cfg = PRESETS[preset]()
    tq, steps = cfg.net.tokenq, cfg.replay.sequence_length
    i, kind = next((i, k) for i, k in enumerate(tokenq.layer_plan(tq))
                   if k["windowed"] == windowed and k["rope"])
    positions = bd_rows(steps, tq.block_length)[1] if tq.block_length \
        else None
    rows = steps + 1 if positions is None else len(positions)
    table = tokenq.rotary_table(kind["rope_params"], tq.head_dim) \
        if kind["rope_params"] else (tokenq.rope_inv(tq.rope_theta,
                                                     tq.head_dim), 1.0)
    assert (kind["heads"], tq.num_key_value_heads, rows,
            2 * table[0].shape[0]) == want
    assert cfg.replay.batch_size == 1 and tq.head_dim == 128
    return cfg.net, i, kind, rows, steps * bool(tq.block_length), table, \
        positions


@pytest.mark.parametrize("geometry", ROTARY_GEOMETRIES)
def test_rotary_pass_compiles_for_v5e(one_chip, geometry):
    """Forward and backward, q and k from one pair of tables: four calls
    of the ONE kernel, and Mosaic takes each."""
    from distributed_deep_q_tpu.models import tokenq

    net, _, kind, rows, _, table, positions = _rotary_geometry(geometry)
    S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                          sharding=one_chip)
    q = S((1, kind["heads"], rows, 128))
    k = S((1, net.tokenq.num_key_value_heads, rows, 128))

    def fwd_bwd(q, k, wq, wk):
        def loss(q, k):
            tq, tk = tokenq.rotary_cast(q, k, *table, positions,
                                        jnp.bfloat16, False)
            return jnp.sum(tq.astype(jnp.float32) * wq) + jnp.sum(
                tk.astype(jnp.float32) * wk)
        return jax.value_and_grad(loss, argnums=(0, 1))(q, k)

    text = _compiled_text(fwd_bwd, q, k, q, k)
    calls = [(shape, op) for name, shape, op in _instructions(text)
             if "rotary_turn" in name]
    assert len(calls) == 4 and {op for _, op in calls} == {"custom-call"}
    assert sorted(shape.split("[")[0] for shape, _ in calls) == [
        "bf16", "bf16", "f32", "f32"]


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "sliding"])
def test_laguna_mixer_turns_q_and_k_on_the_lanes_for_v5e(one_chip,
                                                         windowed):
    """ONE Laguna layer's mixer as the train program runs it (rematerialised
    under ``jax.grad``), compiled: what stands under ``ddq.rotary`` is the
    six kernel calls (q and k; forward, recomputed forward, backward) and
    the tables — no half of a head laid out on padded lanes, no
    ``concatenate`` or ``pad`` that rebuilds a ``[.., T, 128]`` float32
    array from slices. The plain form held 256 (full) / 194 (sliding)
    instructions under the scope, float32 ``[1, 48, 16385, 32]`` and
    ``[1, 64, 16385, 64]`` pairs among them (ISSUE 46)."""
    from distributed_deep_q_tpu.models import tokenq

    net, i, kind, rows, _, _, _ = _rotary_geometry(
        "laguna_sliding" if windowed else "laguna_full")
    S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                          sharding=one_chip)
    p = jax.tree.map(S, tokenq.param_shapes(net)[tokenq.layer_name(i)],
                     is_leaf=lambda x: isinstance(x, tuple))

    def fwd_bwd(x, p):
        def loss(x, p):
            y, _, _ = jax.checkpoint(lambda x, p: tokenq.mixer(
                x, p, net, windowed, True, False, heads=kind["heads"],
                rope_params=kind["rope_params"]))(x, p)
            return jnp.sum(jnp.square(y))
        return jax.grad(loss, argnums=(0, 1))(x, p)

    text = _compiled_text(fwd_bwd, S((1, rows, net.tokenq.hidden_size)), p)
    scopes = scope_table(text)["scopes"]
    under = [(name, shape, op) for name, shape, op in _instructions(text)
             if "ddq.rotary" in scopes.get(name.lstrip("%"), ())]
    kernels = [shape for name, shape, op in under
               if op == "custom-call" and "rotary_turn" in name]
    assert len(kernels) == 6 and len(under) < 128
    big = rows * 8 * 32         # a quarter of ONE key head's columns
    assert not _off_the_lanes(
        "\n".join(f"{n} = {s} {o}(" for n, s, o in under), big)
    rebuilt = [(name, shape, op) for name, shape, op in under
               if op in ("concatenate", "pad")
               and re.match(rf"f32\[(\d+,)*{rows},128\]", shape)]
    assert not rebuilt, rebuilt


def test_the_sibling_presets_state_none_of_lagunas_mechanisms():
    """What this family added is DATA whose defaults are what the four
    presets ran before: the head count of the configuration on every
    layer, one ``rope_theta`` over the whole head (no table, so no
    ``ddq.rotary`` scope), no gate leaf, the one attention block and the
    fused backward."""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.models import tokenq

    for preset in ("tokenq", "smallthinker_tokenq", "lfm2_tokenq",
                   "keye_tokenq", "moonlight_tokenq"):
        cfg = PRESETS[preset]()
        tq = cfg.net.tokenq
        assert all(k["heads"] == tq.num_attention_heads
                   and k["rope_params"] is None
                   for k in tokenq.layer_plan(tq)), preset
        assert (tq.gating, tq.sliding_attn_block,
                tq.attn_fused_bwd) == (False, 0, True)
        shapes = tokenq.param_shapes(cfg.net)
        assert not any("w_g" in v for v in shapes.values()
                       if isinstance(v, dict)), preset


# SDAR-30B-A3B-Chat (config.sdar_tokenq_config): generation by diffusion
# over blocks of 4 — a window of 16 385 tokens packed to 32 769 rows (clean
# copy, then the partly masked one) under the three-part block mask, 32
# heads over 4 key/value heads of 128.

def test_block_mask_attention_compiles_for_v5e(one_chip):
    """The splash kernel under ``BlockDiffusionMask`` (a mask computed in
    the kernel from one code a query row) at the cell's own shapes, blocks
    and two-kernel backward; the block table leaves out the empty
    quadrants."""
    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.ops import attention

    cfg = PRESETS["sdar_tokenq"]()
    tq, t = cfg.net.tokenq, cfg.replay.sequence_length
    n = len(attention.bd_rows(t, tq.block_length)[0])
    assert (n, tq.block_length, tq.num_attention_heads,
            tq.num_key_value_heads, tq.head_dim, tq.attn_fused_bwd) == (
        32_769, 4, 32, 4, 128, False)
    S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                          sharding=one_chip)
    q = S((1, tq.num_attention_heads, n, tq.head_dim))
    kv = S((1, tq.num_key_value_heads, n, tq.head_dim))
    kw = dict(t=t, block_length=tq.block_length, block=tq.attn_block,
              compute_block=tq.attn_compute_block,
              fused_bwd=tq.attn_fused_bwd)

    def run(q, k, v):
        f = lambda *a: jnp.sum(attention.block_diffusion_attention(  # noqa: E731
            *a, **kw).astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(run).lower(q, kv, kv).compile()
    for kernel in ("splash_mqa_fwd", "splash_mqa_dkv", "splash_mqa_dq"):
        assert kernel in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30
    share = attention.bd_blocks_run_share(
        n, t, tq.block_length,
        tq.num_attention_heads // tq.num_key_value_heads,
        **{k: v for k, v in kw.items() if k not in ("t", "block_length")})
    pairs = attention.bd_allowed_per_row(t, tq.block_length).sum() / n ** 2
    assert 0.2499 < pairs < 0.2502 and pairs < share < 0.4, (pairs, share)


def test_the_sibling_presets_state_none_of_sdars_mechanisms():
    """Blocks and the mask token are DATA whose defaults are what the
    siblings ran: no block, no mask token, so no ``reveal`` in their
    batch, the causal kernels, positions = the row index."""
    from distributed_deep_q_tpu.config import PRESETS

    for preset in ("tokenq", "smallthinker_tokenq", "lfm2_tokenq",
                   "keye_tokenq", "moonlight_tokenq", "laguna_tokenq"):
        tq = PRESETS[preset]().net.tokenq
        assert tq.block_length == 0, preset


# -- the token families' whole train programs, lowered for one chip ----------

def _lowered_token_train_program(topo, preset: str):
    """``SequenceLearner``'s fused token TRAIN program of a preset at its
    own sizes, lowered (not compiled) for one described chip."""
    import numpy as np
    from jax.sharding import Mesh

    from distributed_deep_q_tpu.config import PRESETS
    from distributed_deep_q_tpu.models import tokenq
    from distributed_deep_q_tpu.parallel.learner import TrainState
    from distributed_deep_q_tpu.parallel.sequence_learner import (
        SequenceLearner)

    cfg = PRESETS[preset]()
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dp", "model"))
    S = _sharded_aval(mesh)
    learner = SequenceLearner(None, cfg.train, cfg.replay, mesh,
                              net_cfg=cfg.net)
    rep = cfg.replay
    chain, b, t = rep.fused_chain, rep.batch_size, rep.sequence_length
    caps = rep.capacity // t
    _, train = learner._build_token_fused_steps(
        (caps, t, b, rep.priority_alpha, rep.priority_eps, 1,
         cfg.train.gamma), chain)
    params = jax.tree.map(
        lambda s: S(s, jnp.float32), tokenq.param_shapes(cfg.net),
        is_leaf=lambda x: isinstance(x, tuple))
    opt = jax.tree.map(lambda a: S(a.shape, a.dtype),
                       jax.eval_shape(learner.opt.init, params))
    state = TrainState(params=params, target_params=params, opt_state=opt,
                       step=S((), jnp.int32))
    steps = {k: S((chain, b, t), jnp.float32, None, "dp", None)
             for k in ("reward", "discount", "mask")}
    batch = {"tokens": S((chain, b, t + 1), jnp.int32, None, "dp", None),
             "weight": S((chain, b), jnp.float32, None, "dp"), **steps}
    if cfg.net.tokenq.block_length:     # the sample program's draw
        batch["reveal"] = S(
            (chain, b, -(-t // cfg.net.tokenq.block_length)), jnp.int32,
            None, "dp", None)
    return train.lower(state, batch, S((chain, b), jnp.int32, None, "dp"),
                       S((caps,), jnp.float32, "dp"), S((), jnp.float32))


# What each sibling's lowered train program hands the chip's compiler, in
# a form a diff can read (``tests/fixtures/sibling_train_programs.json``):
# how often each operation stands in it, and every Mosaic kernel by name
# with its operand and result types (a kernel's serialised body carries
# the Python line numbers of whoever called it and is not read). Read on
# the parent of PR 40 and on its change: equal; PR 44 added the fifth
# sibling (read on its parent) and left the four as they were; PR 45 wrote
# the Keye entry anew (eight ``topk_threshold`` kernels where the search's
# counting loops were: 96 -> 68 ``stablehlo.while``) and the four others
# came out of the same writing as they were, to the byte; PR 46 wrote
# Keye's, Laguna's and SmallThinker's anew (``rotary_turn`` kernels where
# the rotate-half slices, negations and concatenates were: Laguna 18 -> 58
# custom calls, 163 -> 61 ``stablehlo.concatenate``, 30 -> 15 cosines — q
# and k share a table) and LFM2's (a head of 64 keeps the plain form; q
# and k now share the inverse frequencies: 9 -> 6 ``stablehlo.power``,
# and twelve products by the factor 1.0 the compiler drops); Moonlight's
# came out as it was; PR 48 added the sixth sibling (Nemotron: a state-space
# mixer, layers of one part alone, two-matrix experts) and the five others
# came out of the same writing as they were, to the byte. Written anew by
# ``PYTHONPATH=. python tests/test_chip_compile.py``.
SIBLING_PRESETS = ("keye_tokenq", "laguna_tokenq", "lfm2_tokenq",
                   "moonlight_tokenq", "nemotron_tokenq",
                   "smallthinker_tokenq")
SIBLING_PROGRAMS = os.path.join(os.path.dirname(__file__), "fixtures",
                                "sibling_train_programs.json")


def _program_summary(text: str) -> dict:
    """{"ops": {operation: count}, "kernels": {"name (operands) ->
    results": count}} of a lowered program's text."""
    ops = collections.Counter(re.findall(
        r"\b((?:stablehlo|chlo|func|sdy)\.[a-z_0-9]+)\b", text))
    kernels = collections.Counter(
        f"{name} {types}" for name, types in re.findall(
            r'@tpu_custom_call\(.*kernel_name = "([^"]*)".* : (\(.*)$',
            text, re.M))
    assert sum(kernels.values()) == text.count("@tpu_custom_call(")
    return {"ops": dict(sorted(ops.items())),
            "kernels": dict(sorted(kernels.items()))}


def _moved(was: dict, now: dict) -> dict:
    """What differs between two summaries: {part: {entry: (was, now)}}."""
    return {part: moved for part in ("ops", "kernels") if (moved := {
        k: (was[part].get(k, 0), now[part].get(k, 0))
        for k in sorted({*was[part], *now[part]})
        if was[part].get(k, 0) != now[part].get(k, 0)})}


@pytest.mark.parametrize("preset", SIBLING_PRESETS)
def test_a_sibling_train_program_is_the_one_it_was_for_v5e(
        topo, one_chip, preset):
    """A mechanism added to the shared backbone for ONE family is data
    whose default is what the others ran: their whole train programs lower
    to the operations and kernels they had. A change that MEANS to move
    one (shared code made faster) writes the file anew and says so; the
    failure names each operation and kernel that moved, with both counts."""
    with open(SIBLING_PROGRAMS) as fh:
        was = json.load(fh)[preset]
    now = _program_summary(
        _lowered_token_train_program(topo, preset).as_text())
    assert not _moved(was, now), (
        f"{preset}'s train program moved (was, now): {_moved(was, now)}")


if __name__ == "__main__":      # write the fixture anew
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    described = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
    with open(SIBLING_PROGRAMS, "w") as fh:
        json.dump({p: _program_summary(_lowered_token_train_program(
            described, p).as_text()) for p in SIBLING_PRESETS}, fh,
            indent=1, sort_keys=True)
        fh.write("\n")
