"""Token-window Q-network: a decoder-only transformer whose head row ``a``
is Q(token prefix, next token ``a``) — ``net.kind = "tokenq"``.

The state at position t is the prefix ``tok[0..t]``, the action is the
next token, and ONE causal forward over a window gives Q at every
position. Every size comes from ``config.TokenQConfig`` (the published
``config.json`` keys of the architecture being run) and ``net.num_actions``
(the vocabulary rows held); nothing is hard-coded here.

Layer l, input x ``[B, T, h]`` (float32 residual stream):

- ``u = rmsnorm_1(x)``; the ROUTER reads ``u`` — the layer's normed
  input, BEFORE attention: softmax over all experts, top k renormalised
  (``ops/moe.route``);
- grouped-query attention over ``u``, causal; ``sliding_window_layout[l]``
  = 1 limits it to the last ``sliding_window_size`` keys and
  ``rope_layout[l]`` = 1 rotates q/k (rotate-half convention, theta
  ``rope_theta``); a 0/0 layer is full attention with no positional
  encoding (``ops/attention.causal_attention`` serves both);
  ``x' = x + attn · W_o``;
- ``v = rmsnorm_2(x')``; ``y = x' + Σ_{e in top k, held here} p_e ·
  ReGLU_e(v)`` (``ops/moe.held_experts_ffn``: this process's share of an
  expert-parallel layer; no shared expert).

Then the final RMSNorm; the untied head ``[h, V]`` is applied by the
learner, blockwise over tokens, together with the TD loss
(``parallel/sequence_learner.py``). Matmuls run in ``net.compute_dtype``
with float32 accumulation; norms, router, rotary and the residual stream
are float32. Each layer is rematerialised in the backward pass.

Parameters are a plain nested dict; a leaf's name is its path
(``layer_02/w_gate``), which is what weight IO uses.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from distributed_deep_q_tpu.config import NetConfig, TokenQConfig
from distributed_deep_q_tpu.ops import moe
from distributed_deep_q_tpu.ops.attention import causal_attention

INIT_STD = 0.02


def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


def layer_kinds(tq: TokenQConfig) -> list[tuple[bool, bool]]:
    """Per layer ``(windowed, rotary)`` from the two layouts."""
    n = tq.num_hidden_layers
    if len(tq.sliding_window_layout) < n or len(tq.rope_layout) < n:
        raise ValueError(
            f"sliding_window_layout/rope_layout must cover "
            f"{n} layers: {tq.sliding_window_layout} {tq.rope_layout}")
    return [(bool(tq.sliding_window_layout[i]), bool(tq.rope_layout[i]))
            for i in range(n)]


def param_shapes(cfg: NetConfig) -> dict[str, Any]:
    tq, v = cfg.tokenq, cfg.num_actions
    h, d = tq.hidden_size, tq.head_dim
    hq, hkv = tq.num_attention_heads, tq.num_key_value_heads
    e, f = tq.experts_held, tq.moe_ffn_hidden_size
    if not 0 <= tq.expert_offset <= tq.moe_num_primary_experts - e:
        raise ValueError(
            f"experts [{tq.expert_offset}, {tq.expert_offset + e}) are not "
            f"among {tq.moe_num_primary_experts}")
    layer = {
        "norm_1": (h,), "norm_2": (h,),
        "w_router": (h, tq.moe_num_primary_experts),
        "w_q": (h, hq * d), "w_k": (h, hkv * d), "w_v": (h, hkv * d),
        "w_o": (hq * d, h),
        "w_gate": (e, h, f), "w_up": (e, h, f), "w_down": (e, f, h),
    }
    shapes: dict[str, Any] = {"embed": (v, h), "final_norm": (h,),
                              "head": (h, v)}
    for i in range(tq.num_hidden_layers):
        shapes[layer_name(i)] = dict(layer)
    return shapes


def init_params(cfg: NetConfig, seed: int) -> dict[str, Any]:
    """Normal(0, 0.02) matrices, unit norms, float32."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    vals = [jnp.ones(s, jnp.float32) if len(s) == 1 else
            INIT_STD * jax.random.normal(k, s, jnp.float32)
            for k, s in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def named_leaves(params: dict[str, Any]) -> dict[str, jax.Array]:
    """``{"layer_00/w_q": leaf, ...}``: the names weight IO goes by."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {"/".join(str(k.key) for k in path): leaf for path, leaf in flat}


def from_named(params: dict[str, Any], named: dict[str, Any]):
    """A tree shaped like ``params`` with every leaf taken from ``named``
    by its path; a missing or extra name is an error."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    names = ["/".join(str(k.key) for k in path) for path, _ in flat]
    if set(names) != set(named):
        raise KeyError(f"leaf names differ: {set(names) ^ set(named)}")
    return jax.tree_util.tree_unflatten(treedef, [named[n] for n in names])


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary embedding over ``[B, H, T, D]`` at positions
    0..T-1, float32."""
    d, t = x.shape[-1], x.shape[-2]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _mm(a: jax.Array, w: jax.Array, dtype) -> jax.Array:
    return jnp.dot(a.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def layer(x: jax.Array, p: dict[str, jax.Array], cfg: NetConfig,
          windowed: bool, rope: bool, interpret: bool):
    """One block; ``x`` [B, T, h] float32 → (x, the expert layer's
    counters)."""
    tq = cfg.tokenq
    dtype = jnp.dtype(cfg.compute_dtype)
    b, t, h = x.shape
    hq, hkv, d = (tq.num_attention_heads, tq.num_key_value_heads,
                  tq.head_dim)
    u = rmsnorm(x, p["norm_1"], tq.rms_norm_eps)
    with jax.named_scope("ddq.router"):
        idx, prob = moe.route(u.reshape(b * t, h), p["w_router"],
                              tq.moe_num_active_primary_experts)
    with jax.named_scope("ddq.attn_window" if windowed else "ddq.attn_full"):
        def heads(w, n):
            return _mm(u, w, dtype).reshape(b, t, n, d).transpose(0, 2, 1, 3)
        q, k, v = heads(p["w_q"], hq), heads(p["w_k"], hkv), heads(
            p["w_v"], hkv)
        if rope:
            q, k = rotary(q, tq.rope_theta), rotary(k, tq.rope_theta)
        a = causal_attention(
            q.astype(dtype), k.astype(dtype), v.astype(dtype),
            window=tq.sliding_window_size if windowed else 0,
            block=tq.attn_block, compute_block=tq.attn_compute_block,
            interpret=interpret)
        a = a.transpose(0, 2, 1, 3).reshape(b, t, hq * d)
        x = x + _mm(a, p["w_o"], dtype)
    with jax.named_scope("ddq.experts"):
        v2 = rmsnorm(x, p["norm_2"], tq.rms_norm_eps)
        k = tq.moe_num_active_primary_experts
        # a SEQUENCE at a time: the held-slot buffer is sized for one
        # sequence's worst case (every token with min(k, held) slots
        # here), so nothing can overflow, at a quarter of the batch's
        # worst case in memory (batch 4); each is recomputed in backward
        rows = moe.buffer_rows(t, k, tq.experts_held, tq.moe_tile)

        @jax.checkpoint
        def one_sequence(xs):
            v_s, idx_s, prob_s = xs
            return moe.held_experts_ffn(
                v_s, idx_s, prob_s, p["w_gate"], p["w_up"], p["w_down"],
                offset=tq.expert_offset, rows=rows, tile=tq.moe_tile,
                compute_dtype=dtype, interpret=interpret)

        y, counters = jax.lax.map(
            one_sequence, (v2, idx.reshape(b, t, k), prob.reshape(b, t, k)))
        counters = jax.tree.map(lambda c: jnp.sum(c, axis=0), counters)
    return x + y, counters


def backbone(params: dict[str, Any], tokens: jax.Array, cfg: NetConfig,
             interpret: bool = False):
    """``tokens`` [B, T] int32 → (final-normed hidden [B, T, h] float32,
    expert counters stacked over layers). Only the attention kernel
    pads the window (to its block); every other product runs on T."""
    tq = cfg.tokenq
    x = params["embed"][tokens]
    counters = []
    for i, (windowed, rope) in enumerate(layer_kinds(tq)):
        fn = jax.checkpoint(
            lambda x, p, w=windowed, r=rope: layer(x, p, cfg, w, r,
                                                   interpret))
        x, c = fn(x, params[layer_name(i)])
        counters.append(c)
    x = rmsnorm(x, params["final_norm"], tq.rms_norm_eps)
    return x, jax.tree.map(lambda *a: jnp.stack(a), *counters)


def q_at(params: dict[str, Any], tokens: jax.Array, pos: jax.Array,
         cfg: NetConfig, interpret: bool = False) -> jax.Array:
    """Q(prefix, ·) at position ``pos`` of each row: ``[B, V]`` — the
    acting path (no cache: the whole window is run; what lies after
    ``pos`` cannot reach it through causal attention)."""
    hid, _ = backbone(params, tokens, cfg, interpret)
    return _mm(hid[:, pos], params["head"], jnp.dtype(cfg.compute_dtype))
