"""Plain reference of Moonlight-16B-A3B's block as a token-window Q-network
(family ``moonlight``; moonshotai, ``model_type`` deepseek_v3): its forward
pass with latent attention and a shared expert, the Double-DQN sequence
loss, gradients, clip, one Adam + target step, the PER weights and the
priority write-back — ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
batching trick. It imports nothing of the program and nothing of a
family's ``check.py``. What the other references offer unchanged is
imported from them (``reference/tokenq.py``: the seeded windows, the PER
arithmetic, the float8 product of the control, RMSNorm, the blockwise head;
``reference/lfm2.py``: the sigmoid router with its selection bias and its
scaled gates, the SwiGLU experts, the blockwise dense layer;
``reference/keye.py``: the TD loss on the last layer's outputs); what this
block changes is written here.

Layer l on one sequence, input x ``[T, h]`` (pre-norm residual, RMSNorm eps
``rms_norm_eps`` with a learned gain, no biases), ``u = rmsnorm_1(x)``:

- query: ``q = u W_q`` → ``num_attention_heads`` heads of
  ``qk_nope_head_dim + qk_rope_head_dim``, split ``q_n | q_r``;
- latent: ``[c | k_r] = u W_kva`` → ``kv_lora_rank | qk_rope_head_dim``;
  ``c~ = rmsnorm(c; kv_norm)``; ``[k_n | v] = c~ W_kvb`` → a head
  ``qk_nope_head_dim | v_head_dim``;
- rotary embedding on ``q_r`` (each head) and on ``k_r`` (ONE head, shared
  by all the query heads): with ``rope_interleave`` the pairs (2i, 2i+1)
  turn by ``t · rope_theta^(-2i/qk_rope_head_dim)``, positions 0..T-1 in
  the window; ``q_n``, ``k_n`` carry no position;
- ``score[h, t, s] = (dn + dr)^-1/2 · (q_n[h, t]·k_n[h, s] + q_r[h, t]·
  k_r[s])`` for ``s <= t``; softmax over s; ``o[h, t] = Σ_s p[h, t, s]
  v[h, s]``; ``x' = x + concat_h(o) W_o``. No rope scaling, so no mscale;
- ``w = rmsnorm_2(x')``. ``l < num_dense_layers``: ``f = (silu(w W_gate) *
  (w W_up)) W_down`` of width ``intermediate_size``;
- else ``s = sigmoid(w W_r)`` over all ``router_experts``; the
  ``num_experts_per_tok`` with the largest ``s + expert_bias`` are chosen;
  ``g_e = routed_scaling_factor · s_e / (Σ_chosen s + 1e-6)``;
  ``f = S(w) + Σ_{e chosen and held here} g_e · f_e(w)``, ``f_e`` SwiGLU of
  width ``moe_intermediate_size``, ``S`` ONE ungated SwiGLU of width
  ``n_shared_experts · moe_intermediate_size`` (as the deepseek_v3 code
  builds its shared experts);
- ``y = x' + f``. After the last layer the final RMSNorm, then ``Q =
  hidden W_out`` over the ``vocab_size`` rows held (untied).

Departures from the published description, each also under ``assumed`` in
the configuration file: the share (experts ``[expert_offset, expert_offset
+ experts_held)`` and a slice of the vocabulary are held, the router as
wide as published, the shared expert WHOLE: every member of the group
computes it for its own tokens — the share test in ``tests/`` adds the
parts up with it counted once); ``expert_bias`` is a seeded constant (no
gradient reaches it; the source's update of it is no part of
``config.json``); the router's epsilon is 1e-6 where the source adds
1e-20. Memory only, no arithmetic changed: attention a block of queries at
a time against all keys, the head, the dense layer and the shared expert a
block of tokens at a time, each expert in turn over all tokens.
``make_step`` runs a window a LAYER at a time (``programs``: one compiled
forward and one compiled backward for the dense layer and one of each for
the four expert layers, θ and θ⁻ alike, the chain rule between layers
written out, each layer's gradient added into the step's sum as it comes)
— the whole model as one program is five copies of a layer's code for the
chip's compile cache and two whole gradients beside the state;
``sequence_loss`` is that whole program, and at toy size the tests hold the
two to each other.

``hp["fault"]`` (absent: none) names a PLANTED FAULT for
``families/moonlight/faults.py``: ``"no_shared_expert"`` leaves ``S`` out;
``"rope_key_per_head"`` gives every head a rotary key of its own (head j's
is the shared one turned as if it stood j positions later) where the model
shares one. The two others are plain hyper-parameters:
``routed_scaling_factor`` 1.0 and ``rope_interleave`` false.

``quant="fp8"`` is the CONTROL: every matrix product the configuration
states in bfloat16 (``W_q``, ``W_kva``, ``W_kvb``, ``W_o``, attention's two
products, the dense layer's, the shared expert's and the experts' three,
the head) takes its operands through float8_e4m3 and its cotangents
through float8_e5m2. Router, norms, rotary, softmax, loss and Adam stay
float32 on both sides.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye import td_loss
from benchmark.reference.lfm2 import (  # noqa: F401 — the family's surface
    BIAS_STD, dense_layer, expert_layer, route)
from benchmark.reference.tokenq import (  # noqa: F401 — the family's surface
    ADAM_B1, ADAM_B2, EXACT_LIMITS, GEN_BLOCK, INIT_STD, betas_for,
    init_state, is_weights, mm, rmsnorm, seeded_windows, windows_at,
    written_priority)

Q_BLOCK = 128               # queries per attention block
MASKED = -1e30
ATTENTION_LEAVES = ("w_q", "w_kva", "kv_norm", "w_kvb", "w_o")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("w_router", "expert_bias", "w_gate", "w_up", "w_down",
                 "shared_gate", "shared_up", "shared_down")


# ---- seeded weights ----------------------------------------------------

def is_dense(hp: dict, i: int) -> bool:
    return i < hp["num_dense_layers"]


def layer_leaf_names(hp: dict, i: int) -> tuple[str, ...]:
    return ("norm_1", "norm_2", *ATTENTION_LEAVES,
            *(DENSE_LEAVES if is_dense(hp, i) else EXPERT_LEAVES))


def leaf_shapes(hp: dict) -> dict[str, tuple]:
    """The parameters by name (the program's per-path leaf names)."""
    h, v, hq = hp["hidden_size"], hp["vocab_size"], hp["num_attention_heads"]
    dn, dr, dv = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                  hp["v_head_dim"])
    r, fi = hp["kv_lora_rank"], hp["intermediate_size"]
    e, f = hp["experts_held"], hp["moe_intermediate_size"]
    fs = hp["n_shared_experts"] * f
    shapes = {
        "norm_1": (h,), "norm_2": (h,), "w_q": (h, hq * (dn + dr)),
        "w_kva": (h, r + dr), "kv_norm": (r,), "w_kvb": (r, hq * (dn + dv)),
        "w_o": (hq * dv, h)}
    dense = {"w_gate": (h, fi), "w_up": (h, fi), "w_down": (fi, h)}
    experts = {"w_router": (h, hp["router_experts"]),
               "expert_bias": (hp["router_experts"],),
               "w_gate": (e, h, f), "w_up": (e, h, f), "w_down": (e, f, h),
               "shared_gate": (h, fs), "shared_up": (h, fs),
               "shared_down": (fs, h)}
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v)}
    for i in range(hp["num_hidden_layers"]):
        kind = {**shapes, **(dense if is_dense(hp, i) else experts)}
        out.update({f"layer_{i:02d}/{k}": kind[k]
                    for k in layer_leaf_names(hp, i)})
    return out


def init_weights(seed: int, hp: dict) -> dict[str, np.ndarray]:
    """Seeded float32 weights by name: matrices N(0, 0.02²), norm gains
    (the latent's too) 1 + N(0, 0.1²), the expert bias N(0, 0.01²). One
    generator a leaf, so any leaf can be made alone."""
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

def rotary(x, theta: float, interleave: bool, first: int = 0):
    """Rotary embedding of ``x`` [..., T, D] at positions ``first`` ..
    ``first + T - 1``: pair i turns by ``t · theta^(-2i/D)``. With
    ``interleave`` pair i is elements (2i, 2i+1), else (i, i + D/2)."""
    d, t = x.shape[-1], x.shape[-2]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (first + jnp.arange(t, dtype=jnp.float32))[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(q_n, q_r, k_n, k_r, v, quant, q_block: int = Q_BLOCK):
    """Causal attention of one sequence with the scores in two parts:
    ``q_n`` / ``k_n`` [H, T, dn] a head, ``q_r`` [H, T, dr] against the
    rotary keys ``k_r`` ([T, dr]: ONE head shared by all; [H, T, dr] under
    the planted fault), ``v`` [H, T, dv] → [H, T, dv]. A block of queries
    at a time against ALL keys, masked."""
    hq, t, dn = q_n.shape
    dr, dv = q_r.shape[-1], v.shape[-1]
    scale = (dn + dr) ** -0.5
    nb = -(-t // q_block)

    def blocks(x):
        x = jnp.pad(x, ((0, 0), (0, nb * q_block - t), (0, 0)))
        return x.reshape(hq, nb, q_block, x.shape[-1]).transpose(1, 0, 2, 3)
    per_head = (((2,), (2,)), ((0,), (0,)))             # [H, q, s]
    rope_dims = per_head if k_r.ndim == 3 else (((2,), (1,)), ((), ()))
    s_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one(qn_b, qr_b, start):
        seen = (s_pos <= start + jnp.arange(q_block)[:, None])[None]
        s = (mm(qn_b, k_n, quant, per_head)
             + mm(qr_b, k_r, quant, rope_dims)) * scale
        p = jax.nn.softmax(jnp.where(seen, s, MASKED), axis=-1)
        p = jnp.where(seen, p, 0.0)
        return mm(p, v, quant, (((2,), (1,)), ((0,), (0,))))

    out = jax.lax.map(lambda xs: one(*xs), (
        blocks(q_n), blocks(q_r), jnp.arange(nb) * q_block))
    return out.transpose(1, 0, 2, 3).reshape(hq, nb * q_block, dv)[:, :t]


def latent_attention(u, w, pre: str, hp: dict, quant):
    """``concat_h(o) W_o`` of one sequence from its normed input ``u``
    [T, h]: keys and values expanded from the latent a head."""
    t = u.shape[0]
    hq, r = hp["num_attention_heads"], hp["kv_lora_rank"]
    dn, dr, dv = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                  hp["v_head_dim"])
    theta, pairs = hp["rope_theta"], hp["rope_interleave"]
    q = mm(u, w[pre + "w_q"], quant).reshape(t, hq, dn + dr).transpose(
        1, 0, 2)
    ckr = mm(u, w[pre + "w_kva"], quant)
    c = rmsnorm(ckr[:, :r], w[pre + "kv_norm"], hp["rms_norm_eps"])
    kv = mm(c, w[pre + "w_kvb"], quant).reshape(t, hq, dn + dv).transpose(
        1, 0, 2)
    q_r = rotary(q[..., dn:], theta, pairs)
    if hp.get("fault") == "rope_key_per_head":
        k_r = jnp.stack([rotary(ckr[:, r:], theta, pairs, first=j)
                         for j in range(hq)])
    else:
        k_r = rotary(ckr[:, r:], theta, pairs)
    a = attention(q[..., :dn], q_r, kv[..., :dn], k_r, kv[..., dn:], quant)
    return mm(a.transpose(1, 0, 2).reshape(t, hq * dv), w[pre + "w_o"],
              quant)


def shared_expert(x, w, pre: str, quant):
    """``S(x)``: one SwiGLU every token takes, a block of tokens at a
    time."""
    return dense_layer(x, {"w_gate": w[pre + "shared_gate"],
                           "w_up": w[pre + "shared_up"],
                           "w_down": w[pre + "shared_down"]}, "", quant)


def layer(x, w, pre: str, dense: bool, hp: dict, quant):
    """One block on one sequence, ``x`` [T, h], its leaves under ``pre``;
    also the share of the token-slots routed to experts held here (0 on a
    dense layer, which has none)."""
    u = rmsnorm(x, w[pre + "norm_1"], hp["rms_norm_eps"])
    x = x + latent_attention(u, w, pre, hp, quant)
    v2 = rmsnorm(x, w[pre + "norm_2"], hp["rms_norm_eps"])
    if dense:
        return x + dense_layer(v2, w, pre, quant), jnp.zeros(())
    gate, chosen = route(v2, w[pre + "w_router"], w[pre + "expert_bias"], hp)
    lo = hp["expert_offset"]
    share = jnp.sum(chosen[:, lo:lo + hp["experts_held"]]) / (
        chosen.shape[0] * hp["num_experts_per_tok"])
    f = expert_layer(v2, gate, w, pre, hp, quant)
    if hp["n_shared_experts"] and hp.get("fault") != "no_shared_expert":
        f = f + shared_expert(v2, w, pre, quant)
    return x + f, share


def hidden(w, tokens, hp: dict, quant, normed: bool = True):
    """Hidden states of one sequence ``tokens`` [T] → ([T, h] after the
    final norm — before it without ``normed`` —, the held share of each
    EXPERT layer)."""
    x = w["embed"][tokens]
    shares = []
    for i in range(hp["num_hidden_layers"]):
        x, share = jax.checkpoint(lambda x, w, i=i: layer(
            x, w, f"layer_{i:02d}/", is_dense(hp, i), hp, quant))(x, w)
        if not is_dense(hp, i):
            shares.append(share)
    if normed:
        x = rmsnorm(x, w["final_norm"], hp["rms_norm_eps"])
    return x, jnp.stack(shares)


def q_values(w, tokens, hp: dict, quant=None):
    """Q at every position of one sequence: [T, V] (small sizes only)."""
    return mm(hidden(w, tokens, hp, quant)[0], w["head"], quant)


# ---- loss and optimizer ------------------------------------------------

def sequence_loss(theta, target, seq, hp: dict, quant):
    """ONE window's term of the Double-DQN sequence loss as one function
    of θ: ``seq`` holds tokens [T+1], reward / discount / mask [T] and
    ``scale`` = its IS weight over the batch size. Returns (scale · masked
    mean Huber, (priority η max|TD| + (1-η) mean|TD|, Σ_a,t Q over the T
    steps, the held share by expert layer))."""
    tok = seq["tokens"]
    x_on, share = hidden(theta, tok, hp, quant, normed=False)
    x_tg = hidden(target, tok, hp, quant, normed=False)[0]
    loss, (prio, q_sum) = td_loss(x_on, x_tg, theta, target, seq, hp, quant)
    return loss, (prio, q_sum, share)


_PROGRAMS: dict = {}


def layer_leaves(w, i: int, hp: dict) -> dict:
    """Layer ``i``'s leaves of ``w`` under their bare names."""
    return {k: w[f"layer_{i:02d}/{k}"] for k in layer_leaf_names(hp, i)}


def programs(hp: dict, quant=None):
    """The compiled pieces a window goes through a layer at a time (they
    take a layer's leaves under their bare names; ``dense`` is static, so
    the dense layer and the expert layers are a program each, and θ and θ⁻
    share them): ``forward(x, leaves, dense) -> layer(...)``;
    ``backward(x, leaves, ct, dense)`` -> the cotangents of ``x`` and of
    the leaves from the layer computed again; ``top(x_on, x_tg, top,
    top_tg, seq)`` -> ``td_loss`` with its gradients by ``x_on`` and
    ``top``; ``embed(tokens, ct, like)`` -> the embedding's gradient."""
    key = (repr(sorted(hp.items())), quant)
    if key in _PROGRAMS:
        return _PROGRAMS[key]

    def forward(x, leaves, dense):
        with jax.default_matmul_precision("highest"):
            return layer(x, leaves, "", dense, hp, quant)

    def backward(x, leaves, ct, dense):
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(lambda x, leaves: layer(
                x, leaves, "", dense, hp, quant)[0], x, leaves)
            return vjp(ct)

    def top(x_on, x_tg, top, top_tg, seq):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(td_loss, (0, 2), has_aux=True)(
                x_on, x_tg, top, top_tg, seq, hp, quant)

    def embed(tokens, ct, like):
        return jnp.zeros_like(like).at[tokens].add(ct)

    _PROGRAMS.clear()       # one configuration's at a time
    _PROGRAMS[key] = (
        jax.jit(forward, static_argnames="dense"),
        jax.jit(backward, static_argnames="dense"), jax.jit(top),
        jax.jit(embed))
    return _PROGRAMS[key]


_ADD = jax.jit(jnp.add, donate_argnums=0)


def grad_one(theta, target, seq, hp: dict, quant=None, acc=None):
    """``jax.value_and_grad(sequence_loss, has_aux=True)`` of one window,
    a layer at a time: θ's forward pass keeping each layer's input, θ⁻'s,
    the TD loss with its gradients at the top, then the layers backwards,
    each computed again. The gradient is ADDED to ``acc`` (by name; a new
    one where ``acc`` is None) a layer at a time, so two whole gradients
    never stand side by side."""
    forward, backward, top, embed = programs(hp, quant)
    tok, n = seq["tokens"], hp["num_hidden_layers"]
    acc = {} if acc is None else acc

    def add(g: dict):
        for k, v in g.items():
            acc[k] = _ADD(acc[k], v) if k in acc else v

    xs, shares = [theta["embed"][tok]], []
    for i in range(n):
        x, share = forward(xs[-1], layer_leaves(theta, i, hp),
                           dense=is_dense(hp, i))
        xs.append(x)
        if not is_dense(hp, i):
            shares.append(share)
    x_tg = target["embed"][tok]
    for i in range(n):
        x_tg = forward(x_tg, layer_leaves(target, i, hp),
                       dense=is_dense(hp, i))[0]
    tops = ("final_norm", "head")
    (loss, (prio, q_sum)), (ct, g) = top(
        xs.pop(), x_tg, {k: theta[k] for k in tops},
        {k: target[k] for k in tops}, seq)
    del x_tg
    add(g)
    for i in reversed(range(n)):
        ct, g = backward(xs.pop(), layer_leaves(theta, i, hp), ct,
                         dense=is_dense(hp, i))
        add({f"layer_{i:02d}/{k}": v for k, v in g.items()})
    add({"embed": embed(tok, ct, theta["embed"])})
    return (loss, (prio, q_sum, jnp.stack(shares))), acc


def adam_and_target(state, g, hp: dict):
    """Clip by global norm, Adam, the target copy every
    ``target_update_period`` steps → (state, ‖g‖ by leaf, ‖g‖)."""
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
    return ({"theta": theta, "target": target, "m": m, "v": v, "step": n},
            leaf, gnorm)


def make_step(hp: dict, quant=None):
    """One train step: the loss and its gradients A WINDOW AT A TIME (no
    batching; the windows' gradients are added up; ``grad_one``: each
    window a layer at a time), clip by global norm, Adam, the target copy.
    Returns ``step(state, batch) -> (state, metrics, priority [B])``;
    ``batch``: tokens [B, T+1], reward / discount / mask [B, T], weight
    [B]; metrics carry per-leaf gradient norms (``grad_leaf_norm``, by
    name)."""
    apply = jax.jit(lambda state, g: adam_and_target(state, g, hp),
                    donate_argnums=0)

    def step(state, batch):
        b, t1 = batch["tokens"].shape
        acc, prios = None, []
        loss = q_sum = shares = 0.0
        for s in range(b):
            seq = {k: batch[k][s] for k in
                   ("tokens", "reward", "discount", "mask")}
            seq["scale"] = batch["weight"][s] / b
            (l, (prio, qs, share)), acc = grad_one(
                state["theta"], state["target"], seq, hp, quant, acc)
            loss, q_sum, shares = loss + l, q_sum + qs, shares + share / b
            prios.append(prio)
        state, leaf, gnorm = apply(state, acc)
        metrics = {"loss": loss, "grad_norm": gnorm, "grad_leaf_norm": leaf,
                   "q_mean": q_sum / (b * (t1 - 1) * hp["vocab_size"]),
                   "held_share": shares}
        return state, metrics, jnp.stack(prios)

    return step
