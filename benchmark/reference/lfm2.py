"""Plain reference of the LFM2 block as a token-window Q-network (family
``lfm2``; LiquidAI LFM2-24B-A2B, ``model_type`` lfm2_moe): its forward
pass, the Double-DQN sequence loss, gradients, clip, one Adam + target
step, the PER weights and the priority write-back — ``jax.numpy`` float32
under ``jax.default_matmul_precision("highest")``, no kernel, no cache, no
batching trick. It imports nothing of the program and nothing of a
family's ``check.py``. What ``reference/tokenq.py`` offers unchanged is
imported from there (the seeded windows, the PER arithmetic, the float8
product of the control, RMSNorm, the rotary embedding, blockwise
attention, the blockwise head, the loss's pieces); what LFM2 changes is
written here (the leaves, the layer, the router, the experts, and the
loss and step that call them).

Layer l on one sequence, input x ``[T, h]`` (h = ``hidden_size``, RMSNorm
eps ``norm_eps`` with a learned gain, no biases):

- ``u = rmsnorm_1(x)``.
- ``layer_types[l] == "conv"``: ``[B, C, z] = split3(u W_in)``,
  ``g = B * z``, ``c_t = sum_{j<L} w_conv[:, j] * g_{t-(L-1)+j}`` with
  ``g_s = 0`` for ``s < 0`` (depthwise, causal, ``L = conv_L_cache``, no
  bias), ``m = (C * c) W_out``.
- ``layer_types[l] == "full_attention"``: ``num_attention_heads`` query
  and ``num_key_value_heads`` key/value heads of ``head_dim``; an RMSNorm
  over each head of q and of k (gains ``[head_dim]``); rotary embedding on
  q and k (theta ``rope_theta``, rotate-half, positions 0..T in the
  window); causal ``softmax(q k^T head_dim^-1/2) v``; ``m = attn W_o``.
- ``x' = x + m``; ``w = rmsnorm_2(x')``.
- ``l < num_dense_layers``: ``f = (silu(w W_gate) * (w W_up)) W_down`` of
  width ``intermediate_size``.
- else ``s = sigmoid(w W_r)`` over all ``router_experts``; the
  ``num_experts_per_tok`` experts with the largest ``s + expert_bias`` are
  chosen; their weights are ``s`` WITHOUT the bias, divided by their sum
  + 1e-6 (``norm_topk_prob``) and multiplied by ``routed_scaling_factor``;
  ``f = sum over the chosen experts HELD here of p_e (silu(w W_gate,e) *
  (w W_up,e)) W_down,e`` of width ``moe_intermediate_size``. No shared
  expert.
- ``y = x' + f``. After the last layer the final RMSNorm, then ``Q =
  hidden W_out`` over the ``vocab_size`` rows held (untied).

Departures from the published description, each also under ``assumed`` in
the configuration file:

1. The share: experts ``[expert_offset, expert_offset + experts_held)`` of
   each expert layer and a slice of the vocabulary are held; the router is
   as wide as published; what the absent experts would add is left out
   (``experts_held`` = all of them gives the whole layer: the share test
   in ``tests/`` adds the parts up).
2. ``expert_bias`` is a seeded constant N(0, 0.01^2): no gradient reaches
   it (it only moves ``top_k``'s indices) and the source's load-balancing
   update of it is no part of ``config.json``.
3. The untied Q head (the source's language-model head may be tied to its
   embedding; a Q head is not an embedding).
4. Memory only, no arithmetic changed: attention a block of queries at a
   time, the head and the dense feed-forward a block of tokens at a time,
   each expert in turn over all tokens.

``quant="fp8"`` is the CONTROL: every matrix product the configuration
states in bfloat16 (the projections of both mixers, attention's two
products, the dense and expert products, the head) takes its operands
through float8_e4m3 and its cotangents through float8_e5m2. Router, norms,
gates and convolution, loss and Adam stay float32 on both sides.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.tokenq import (  # noqa: F401 — the family's surface
    ADAM_B1, ADAM_B2, EXACT_LIMITS, GEN_BLOCK, INIT_STD, attention,
    betas_for, huber, init_state, is_weights, mm, q_select, rmsnorm, rotary,
    seeded_windows, value_rescale, value_rescale_inv, windows_at,
    written_priority)

BIAS_STD = 0.01             # the seeded expert bias
DENSE_BLOCK = 2048          # tokens per block of the dense feed-forward
ROUTE_EPS = 1e-6


# ---- seeded weights ----------------------------------------------------

def is_conv(hp: dict, i: int) -> bool:
    return hp["layer_types"][i] == "conv"


def is_dense(hp: dict, i: int) -> bool:
    return i < hp["num_dense_layers"]


def leaf_shapes(hp: dict) -> dict[str, tuple]:
    """The parameters by name (the program's per-path leaf names)."""
    h, d, v = hp["hidden_size"], hp["head_dim"], hp["vocab_size"]
    hq, hkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    e, f = hp["experts_held"], hp["moe_intermediate_size"]
    fi = hp["intermediate_size"]
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v)}
    for i in range(hp["num_hidden_layers"]):
        pre = f"layer_{i:02d}/"
        out.update({pre + "norm_1": (h,), pre + "norm_2": (h,)})
        if is_conv(hp, i):
            out.update({pre + "w_in": (h, 3 * h),
                        pre + "w_conv": (h, hp["conv_L_cache"]),
                        pre + "w_out": (h, h)})
        else:
            out.update({pre + "w_q": (h, hq * d), pre + "w_k": (h, hkv * d),
                        pre + "w_v": (h, hkv * d), pre + "w_o": (hq * d, h),
                        pre + "q_norm": (d,), pre + "k_norm": (d,)})
        if is_dense(hp, i):
            out.update({pre + "w_gate": (h, fi), pre + "w_up": (h, fi),
                        pre + "w_down": (fi, h)})
        else:
            out.update({pre + "w_router": (h, hp["router_experts"]),
                        pre + "expert_bias": (hp["router_experts"],),
                        pre + "w_gate": (e, h, f), pre + "w_up": (e, h, f),
                        pre + "w_down": (e, f, h)})
    return out


def init_weights(seed: int, hp: dict) -> dict[str, np.ndarray]:
    """Seeded float32 weights by name: matrices (the convolution's taps
    among them) N(0, 0.02²), norm gains 1 + N(0, 0.1²), the expert bias
    N(0, 0.01²). One generator a leaf, so any leaf can be made alone."""
    out = {}
    for i, (name, shape) in enumerate(sorted(leaf_shapes(hp).items())):
        rng = np.random.default_rng([int(seed), 7, i])
        x = rng.standard_normal(shape, np.float32)
        if name.endswith("/expert_bias"):
            x = BIAS_STD * x
        elif len(shape) == 1:
            x = 1.0 + 0.1 * x
        else:
            x = INIT_STD * x
        out[name] = x.astype(np.float32)
    return out


# ---- the forward pass --------------------------------------------------

def short_conv(u, w, pre: str, hp: dict, quant):
    """The gated short convolution of one sequence, ``u`` [T, h] →
    [T, h]: the window of ``L`` positions ending at t, read from a copy
    of ``g`` with ``L - 1`` zeros in front."""
    t, taps = u.shape[0], hp["conv_L_cache"]
    gate_in, gate_out, z = jnp.split(mm(u, w[pre + "w_in"], quant), 3, -1)
    g = jnp.pad(gate_in * z, ((taps - 1, 0), (0, 0)))
    taps_w = w[pre + "w_conv"]
    c = sum(taps_w[:, j] * g[j:j + t] for j in range(taps))
    return mm(gate_out * c, w[pre + "w_out"], quant)


def qk_norm_attention(u, w, pre: str, hp: dict, quant):
    t = u.shape[0]
    hq, hkv, d = (hp["num_attention_heads"], hp["num_key_value_heads"],
                  hp["head_dim"])

    def heads(name, n):
        return mm(u, w[pre + name], quant).reshape(t, n, d).transpose(1, 0, 2)
    q, k, v = heads("w_q", hq), heads("w_k", hkv), heads("w_v", hkv)
    q = rmsnorm(q, w[pre + "q_norm"], hp["norm_eps"])
    k = rmsnorm(k, w[pre + "k_norm"], hp["norm_eps"])
    q, k = rotary(q, hp["rope_theta"]), rotary(k, hp["rope_theta"])
    a = attention(q, k, v, 0, quant)
    return mm(a.transpose(1, 0, 2).reshape(t, hq * d), w[pre + "w_o"],
              quant)


def route(x, w_router, bias, hp: dict):
    """Sigmoid scores over all experts; chosen by score + bias, weighted
    by the score alone. Returns dense weights ``[T, E]`` (zero off the
    chosen experts) and the chosen mask."""
    s = jax.nn.sigmoid(x @ w_router)
    _, top_i = jax.lax.top_k(s + bias, hp["num_experts_per_tok"])
    p = jnp.take_along_axis(s, top_i, -1)
    if hp["norm_topk_prob"]:
        p = p / (jnp.sum(p, -1, keepdims=True) + ROUTE_EPS)
    p = p * hp["routed_scaling_factor"]
    rows = jnp.arange(s.shape[0])[:, None]
    return (jnp.zeros_like(s).at[rows, top_i].set(p),
            jnp.zeros(s.shape, bool).at[rows, top_i].set(True))


def swiglu(x, wg, wu, wd, quant):
    return mm(jax.nn.silu(mm(x, wg, quant)) * mm(x, wu, quant), wd, quant)


def expert_layer(x, gate, w, pre: str, hp: dict, quant):
    """Σ_e held here of gate[:, e] · SwiGLU_e(x), each expert over all
    tokens in turn."""
    lo = hp["expert_offset"]
    gates = gate[:, lo:lo + hp["experts_held"]].T           # [held, T]

    @jax.checkpoint
    def one(xs):
        wg, wu, wd, g = xs
        return g[:, None] * swiglu(x, wg, wu, wd, quant)

    return jnp.sum(jax.lax.map(one, (
        w[pre + "w_gate"], w[pre + "w_up"], w[pre + "w_down"], gates)), 0)


def dense_layer(x, w, pre: str, quant, block: int = DENSE_BLOCK):
    """The dense SwiGLU, a block of tokens at a time (memory only)."""
    n, h = x.shape
    blk = min(block, n)
    nb = -(-n // blk)
    xb = jnp.pad(x, ((0, nb * blk - n), (0, 0))).reshape(nb, blk, h)
    one = jax.checkpoint(lambda b: swiglu(
        b, w[pre + "w_gate"], w[pre + "w_up"], w[pre + "w_down"], quant))
    return jax.lax.map(one, xb).reshape(nb * blk, h)[:n]


def layer(x, w, i: int, hp: dict, quant):
    """One block on one sequence, ``x`` [T, h]; also the share of the
    token-slots routed to experts held here (``None`` on a dense layer)."""
    pre = f"layer_{i:02d}/"
    u = rmsnorm(x, w[pre + "norm_1"], hp["norm_eps"])
    mixer = short_conv if is_conv(hp, i) else qk_norm_attention
    x = x + mixer(u, w, pre, hp, quant)
    v2 = rmsnorm(x, w[pre + "norm_2"], hp["norm_eps"])
    if is_dense(hp, i):
        return x + dense_layer(v2, w, pre, quant), None
    gate, chosen = route(v2, w[pre + "w_router"], w[pre + "expert_bias"], hp)
    lo = hp["expert_offset"]
    share = jnp.sum(chosen[:, lo:lo + hp["experts_held"]]) / (
        chosen.shape[0] * hp["num_experts_per_tok"])
    return x + expert_layer(v2, gate, w, pre, hp, quant), share


def hidden(w, tokens, hp: dict, quant):
    """Final-normed hidden states of one sequence ``tokens`` [T] → [T, h],
    and the held share of each EXPERT layer."""
    x = w["embed"][tokens]
    shares = []
    for i in range(hp["num_hidden_layers"]):
        x, share = jax.checkpoint(
            lambda x, w, i=i: layer(x, w, i, hp, quant))(x, w)
        if share is not None:
            shares.append(share)
    return rmsnorm(x, w["final_norm"], hp["norm_eps"]), jnp.stack(shares)


def q_values(w, tokens, hp: dict, quant=None):
    """Q at every position of one sequence: [T, V] (small sizes only)."""
    return mm(hidden(w, tokens, hp, quant)[0], w["head"], quant)


# ---- loss and optimizer (reference/tokenq.py's, over this ``hidden``) ---

def sequence_loss(theta, target, seq, hp: dict, quant):
    """ONE window's term of the Double-DQN sequence loss: ``seq`` holds
    tokens [T+1], reward / discount / mask [T] and ``scale`` = its IS
    weight over the batch size. Returns (scale · masked mean Huber,
    (priority η max|TD| + (1-η) mean|TD|, Σ_a,t Q over the T steps, the
    held share by expert layer))."""
    tok = seq["tokens"]
    h_on, share = hidden(theta, tok, hp, quant)
    h_tg, _ = hidden(target, tok, hp, quant)
    actions = jnp.concatenate([tok[1:], jnp.zeros((1,), tok.dtype)])
    q_sa, q_boot, q_row = q_select(
        h_on, jax.lax.stop_gradient(h_tg), theta["head"], target["head"],
        actions, hp, quant)
    boot = jax.lax.stop_gradient(q_boot[1:])
    y = seq["reward"] + seq["discount"] * (
        value_rescale_inv(boot) if hp["value_rescale"] else boot)
    y = value_rescale(y) if hp["value_rescale"] else y
    mask = seq["mask"]
    td = (q_sa[:-1] - y) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    a = jnp.abs(jax.lax.stop_gradient(td))
    prio = (hp["priority_eta"] * jnp.max(a)
            + (1.0 - hp["priority_eta"]) * jnp.sum(a) / denom)
    loss = seq["scale"] * jnp.sum(huber(td, hp["huber_delta"]) * mask) / denom
    return loss, (prio, jnp.sum(jax.lax.stop_gradient(q_row[:-1])), share)


def make_step(hp: dict, quant=None):
    """One train step: the loss and its gradients A WINDOW AT A TIME (no
    batching; the windows' gradients are added up), clip by global norm,
    Adam, the target copy every ``target_update_period`` steps. Returns
    ``step(state, batch) -> (state, metrics, priority [B])``; ``batch``:
    tokens [B, T+1], reward / discount / mask [B, T], weight [B]; metrics
    carry per-leaf gradient norms (``grad_leaf_norm``, by name)."""
    def grad_one(theta, target, seq):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(sequence_loss, has_aux=True)(
                theta, target, seq, hp, quant)

    def apply(state, g):
        leaf = {k: jnp.sqrt(jnp.sum(v * v)) for k, v in g.items()}
        gnorm = jnp.sqrt(sum(v * v for v in leaf.values()))
        scale = jnp.minimum(1.0, hp["grad_clip_norm"]
                            / jnp.maximum(gnorm, 1e-12))
        n = state["step"] + 1
        c = n.astype(jnp.float32)
        theta, m, v = {}, {}, {}
        for k, gk in g.items():
            gk = gk * scale
            m[k] = ADAM_B1 * state["m"][k] + (1 - ADAM_B1) * gk
            v[k] = ADAM_B2 * state["v"][k] + (1 - ADAM_B2) * gk * gk
            upd = (m[k] / (1 - ADAM_B1 ** c)) / (
                jnp.sqrt(v[k] / (1 - ADAM_B2 ** c)) + hp["adam_eps"])
            theta[k] = state["theta"][k] - hp["lr"] * upd
        refresh = n % hp["target_update_period"] == 0
        target = {k: jnp.where(refresh, theta[k], state["target"][k])
                  for k in theta}
        return ({"theta": theta, "target": target, "m": m, "v": v,
                 "step": n}, leaf, gnorm)

    grad_one = jax.jit(grad_one)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0, 1))
    apply = jax.jit(apply, donate_argnums=0)

    def step(state, batch):
        b, t1 = batch["tokens"].shape
        acc, loss, prios, q_sum, shares = None, 0.0, [], 0.0, 0.0
        for s in range(b):
            seq = {k: batch[k][s] for k in
                   ("tokens", "reward", "discount", "mask")}
            seq["scale"] = batch["weight"][s] / b
            (l, (prio, qs, share)), g = grad_one(
                state["theta"], state["target"], seq)
            acc = g if acc is None else add(acc, g)
            loss, q_sum, shares = loss + l, q_sum + qs, shares + share / b
            prios.append(prio)
        state, leaf, gnorm = apply(state, acc)
        metrics = {"loss": loss, "grad_norm": gnorm, "grad_leaf_norm": leaf,
                   "q_mean": q_sum / (b * (t1 - 1) * hp["vocab_size"]),
                   "held_share": shares}
        return state, metrics, jnp.stack(prios)

    return step
