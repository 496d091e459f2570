"""Plain reference of Laguna-XS.2's block as a token-window Q-network
(family ``laguna``; poolside, ``model_type`` laguna): its forward pass with
a head count and a rotary embedding a KIND of layer (window and full
attention mixed), a sigmoid gate a head on the attention output and a
shared expert beside the routed ones, the Double-DQN sequence loss,
gradients, clip, one Adam + target step, the PER weights and the priority
write-back — ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
batching trick. It imports nothing of the program and nothing of a
family's ``check.py``. What the other references offer unchanged is
imported from them (``reference/tokenq.py``: the seeded windows, the PER
arithmetic, the float8 product of the control, RMSNorm, the blockwise
head; ``reference/lfm2.py``: the sigmoid router with renormalised, scaled
gates, one SwiGLU, the blockwise dense layer; ``reference/keye.py``: the
TD loss on the last layer's outputs; ``reference/moonlight.py``: the
shared expert, Adam and the target copy); what this block changes is
written here.

Layer l on one sequence, input x ``[T, h]`` (pre-norm residual, RMSNorm eps
``rms_norm_eps`` with a learned gain, no biases, no q/k norm), its kind
``layer_types[l]`` (``full_attention`` | ``sliding_attention``),
``H_l = num_attention_heads_per_layer[l]``, d = ``head_dim``,
``u = rmsnorm_1(x)``:

- ``q = u W_q`` → ``H_l`` heads of d; ``k = u W_k``, ``v = u W_v`` →
  ``num_key_value_heads`` heads of d; query head j reads key/value head
  ``j // (H_l / num_key_value_heads)``;
- rotary embedding (``rope_parameters[kind]``, positions 0..T-1 in the
  window), rotate-half over the FIRST ``r = partial_rotary_factor · d``
  columns of each head of q and k, the other ``d - r`` unturned and
  unscaled. ``rope_type`` default: pair i turns by ``t ·
  rope_theta^(-2i/r)``. ``yarn`` (``rope_table``: as ``transformers``
  computes it): with ``e_i = rope_theta^(-2i/r)`` and ``n_i = e_i /
  factor``, ``cd(n) = r · ln(original_max_position_embeddings / (2π n)) /
  (2 ln rope_theta)``, ``low = max(floor(cd(beta_fast)), 0)``, ``high =
  min(ceil(cd(beta_slow)), r - 1)``, ``ramp_i = clip((i - low) / (high -
  low), 0, 1)``: pair i turns by ``t · (n_i ramp_i + e_i (1 - ramp_i))``
  and cos and sin are both multiplied by ``attention_factor``;
- ``score[j, t, s] = d^-1/2 · q[j, t]·k[g(j), s]`` for ``s <= t``, on a
  sliding layer also ``t - sliding_window < s``; softmax over s;
  ``o[j, t] = Σ_s p[j, t, s] v[g(j), s]``;
- the gate (``gating``): ``γ = sigmoid(u W_g)`` [T, ``H_l``] in float32
  (stated float32: the control leaves it alone); ``o[j, t] ← γ[t, j] ·
  o[j, t]``; ``x' = x + concat_j(o) W_o``;
- ``w = rmsnorm_2(x')``. ``l < num_dense_layers``: ``f = (silu(w W_gate) *
  (w W_up)) W_down`` of width ``intermediate_size``;
- else ``s = sigmoid(w W_r)`` over all ``router_experts``; the
  ``num_experts_per_tok`` largest are chosen (no selection bias);
  ``g_e = routed_scaling_factor · s_e / (Σ_chosen s + 1e-6)``;
  ``f = S(w) + Σ_{e chosen and held here} g_e · f_e(w)``, ``f_e`` SwiGLU of
  width ``moe_intermediate_size``, ``S`` ONE ungated SwiGLU of width
  ``shared_expert_intermediate_size``;
- ``y = x' + f``. After the last layer the final RMSNorm, then ``Q =
  hidden W_out`` over the ``vocab_size`` rows held (untied).

Departures from the published description, each also under ``assumed`` in
the configuration file: the share (experts ``[expert_offset, expert_offset
+ experts_held)`` and a slice of the vocabulary are held, the router as
wide as published, the shared expert WHOLE on every member of the group —
the share test in ``tests/`` adds the parts up with it counted once); what
``gating`` gates, the router's scoring and the absence of q/k norms are
not keys of the source and are taken as the configuration argues. Memory
only, no arithmetic changed: attention a block of queries at a time
against all keys (the keys and values at their own head count), the head,
the dense layer and the shared expert a block of tokens at a time, each
expert in turn over all tokens, added up as it comes. ``make_step``
runs a window a LAYER at a time (``programs``: one compiled forward and
one compiled backward for each kind of layer, θ and θ⁻ alike, the chain
rule between layers written out, each layer's gradient added into the
step's sum as it comes), so that ONE gradient stands beside the state;
``sequence_loss`` is the whole model as one function, and at toy size the
tests hold the two to each other.

``hp["fault"] == "no_gate"`` is a PLANTED FAULT for
``families/laguna/faults.py``: γ = 1 (the leaf stays; its gradient is
zero). The three others are plain hyper-parameters (a kind's
``rope_parameters``, ``sliding_window``).

``quant="fp8"`` is the CONTROL: every matrix product the configuration
states in bfloat16 (``W_q``, ``W_k``, ``W_v``, ``W_o``, attention's two
products, the dense layer's, the shared expert's and the experts' three,
the head) takes its operands through float8_e4m3 and its cotangents
through float8_e5m2. Router, gate, norms, rotary, softmax, loss and Adam
stay float32 on both sides. The control's backward pass runs under a LOSS
SCALE, as float8 training does (``loss_scale``, a power of two: the loss
is multiplied by it before the backward pass, every gradient divided by it
as it is added up; exact in float32, so it moves nothing but what the
e5m2 casts see): a window's loss is a mean over its 16 384 steps, so the
head's cotangent is 6.1e-5 at most, four of e5m2's smallest steps
(2^-16), and most cotangents behind it lie under the smallest: unscaled,
the control's backward pass is no reading of a lower precision (on the
chip its first gradient was not finite; PERF.md section 2, PR 40).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye import td_loss
from benchmark.reference.lfm2 import (  # noqa: F401 — the family's surface
    dense_layer, route, swiglu)
from benchmark.reference.moonlight import (  # noqa: F401
    adam_and_target, shared_expert)
from benchmark.reference.tokenq import (  # noqa: F401 — the family's surface
    ADAM_B1, ADAM_B2, EXACT_LIMITS, GEN_BLOCK, INIT_STD, betas_for,
    init_state, is_weights, mm, rmsnorm, seeded_windows, windows_at,
    written_priority)

Q_BLOCK = 128               # queries per attention block
MASKED = -1e30
SLIDING = "sliding_attention"
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("w_router", "w_gate", "w_up", "w_down",
                 "shared_gate", "shared_up", "shared_down")


def loss_scale(hp: dict) -> float:
    """The control's loss scale: 64 x the window's steps, rounded up to a
    power of two (2^20 at 16 384), so that the head's largest cotangent
    (1 / T unscaled) stands at 64 at most: a thousandth of e5m2's largest
    (57 344), with 22 binary orders under it before a cotangent flushes
    to zero."""
    return 2.0 ** (6 + math.ceil(math.log2(hp["sequence_length"])))


# ---- seeded weights ----------------------------------------------------

def is_dense(hp: dict, i: int) -> bool:
    return i < hp["num_dense_layers"]


def layer_kind(hp: dict, i: int) -> tuple:
    """What tells layer ``i``'s program from another's: (dense
    feed-forward?, the attention kind, its query heads)."""
    return (is_dense(hp, i), hp["layer_types"][i],
            hp["num_attention_heads_per_layer"][i])


def layer_leaf_names(hp: dict, i: int) -> tuple[str, ...]:
    return ("norm_1", "norm_2", "w_q", "w_k", "w_v", "w_o",
            *(("w_g",) if hp["gating"] else ()),
            *(DENSE_LEAVES if is_dense(hp, i) else EXPERT_LEAVES))


def leaf_shapes(hp: dict) -> dict[str, tuple]:
    """The parameters by name (the program's per-path leaf names)."""
    h, v, d = hp["hidden_size"], hp["vocab_size"], hp["head_dim"]
    hkv, fi = hp["num_key_value_heads"], hp["intermediate_size"]
    e, f = hp["experts_held"], hp["moe_intermediate_size"]
    fs = hp["shared_expert_intermediate_size"]
    dense = {"w_gate": (h, fi), "w_up": (h, fi), "w_down": (fi, h)}
    experts = {"w_router": (h, hp["router_experts"]),
               "w_gate": (e, h, f), "w_up": (e, h, f), "w_down": (e, f, h),
               "shared_gate": (h, fs), "shared_up": (h, fs),
               "shared_down": (fs, h)}
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v)}
    for i in range(hp["num_hidden_layers"]):
        hq = hp["num_attention_heads_per_layer"][i]
        kind = {"norm_1": (h,), "norm_2": (h,), "w_q": (h, hq * d),
                "w_k": (h, hkv * d), "w_v": (h, hkv * d),
                "w_o": (hq * d, h), "w_g": (h, hq),
                **(dense if is_dense(hp, i) else experts)}
        out.update({f"layer_{i:02d}/{k}": kind[k]
                    for k in layer_leaf_names(hp, i)})
    return out


def init_weights(seed: int, hp: dict) -> dict[str, np.ndarray]:
    """Seeded float32 weights by name: matrices (the gate's too) N(0,
    0.02²), norm gains 1 + N(0, 0.1²). One generator a leaf, so any leaf
    can be made alone."""
    out = {}
    for i, (name, shape) in enumerate(sorted(leaf_shapes(hp).items())):
        rng = np.random.default_rng([int(seed), 11, i])
        x = rng.standard_normal(shape, np.float32)
        x = 1.0 + 0.1 * x if len(shape) == 1 else INIT_STD * x
        out[name] = x.astype(np.float32)
    return out


# ---- the forward pass --------------------------------------------------

def rope_table(rp: dict, head_dim: int):
    """One kind's rotary embedding → (inverse frequencies [r / 2] float32,
    the factor on cos and sin, r the columns that turn), from its
    ``rope_parameters`` alone; float64 arithmetic, rounded once."""
    r = int(round(head_dim * rp.get("partial_rotary_factor", 1.0)))
    base = float(rp["rope_theta"])
    pos = base ** (np.arange(0, r, 2, dtype=np.float64) / r)
    if rp.get("rope_type", "default") != "yarn":
        return (1.0 / pos).astype(np.float32), 1.0, r
    orig = rp["original_max_position_embeddings"]

    def correction_dim(rotations: float) -> float:
        return r * math.log(orig / (rotations * 2.0 * math.pi)) / (
            2.0 * math.log(base))
    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (rp["factor"] * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)
    return inv.astype(np.float32), float(rp["attention_factor"]), r


def rotary(x, rp: dict):
    """``x`` [heads, T, D]: the first r columns of each head rotate-half
    among themselves, cos and sin times the kind's factor; the rest pass."""
    inv, factor, r = rope_table(rp, x.shape[-1])
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., r:]], -1)


def attention(q, k, v, window: int, quant, q_block: int = Q_BLOCK):
    """Causal (and, with ``window`` > 0, windowed) attention of one
    sequence: ``q`` [Hq, T, D], ``k`` / ``v`` [Hkv, T, D] → [Hq, T, D]; query
    head j reads key/value head ``j // (Hq / Hkv)``. A block of queries at
    a time against ALL keys, masked; the keys and values stay at their own
    head count (at 64 query heads over 16 385 tokens a copy a query head
    is 537 MB each, and as much again for its cotangent)."""
    hq, t, d = q.shape
    hkv = k.shape[0]
    nb = -(-t // q_block)
    qp = jnp.pad(q, ((0, 0), (0, nb * q_block - t), (0, 0))).reshape(
        hkv, hq // hkv, nb, q_block, d).transpose(2, 0, 1, 3, 4)
    s_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one(qb, start):                 # qb [Hkv, group, q_block, D]
        t_pos = start + jnp.arange(q_block)[:, None]
        seen = s_pos <= t_pos
        if window:
            seen &= s_pos > t_pos - window
        s = mm(qb, k, quant, (((3,), (2,)), ((0,), (0,)))) * d ** -0.5
        # (a padded query of the last block may see no key: finite mask)
        p = jax.nn.softmax(jnp.where(seen, s, MASKED), axis=-1)
        p = jnp.where(seen, p, 0.0)
        return mm(p, v, quant, (((3,), (1,)), ((0,), (0,))))

    out = jax.lax.map(lambda xs: one(*xs), (qp, jnp.arange(nb) * q_block))
    return out.transpose(1, 2, 0, 3, 4).reshape(hq, nb * q_block, d)[:, :t]


def expert_layer(x, gate, w, pre: str, hp: dict, quant):
    """Σ_e held here of gate[:, e] · SwiGLU_e(x), each expert over all
    tokens in turn, added up as it comes (16 outputs of [T, h] side by
    side would be 2.1 GB at this cell's sizes)."""
    lo = hp["expert_offset"]
    gates = gate[:, lo:lo + hp["experts_held"]].T           # [held, T]

    @jax.checkpoint
    def one(acc, xs):
        wg, wu, wd, g = xs
        return acc + g[:, None] * swiglu(x, wg, wu, wd, quant), None

    return jax.lax.scan(one, jnp.zeros_like(x), (
        w[pre + "w_gate"], w[pre + "w_up"], w[pre + "w_down"], gates))[0]


def gated_attention(u, w, pre: str, kind: tuple, hp: dict, quant):
    """``concat_j(γ_j o_j) W_o`` of one sequence from its normed input
    ``u`` [T, h]; also the gate's mean over tokens and heads."""
    _, attn, hq = kind
    t, d, hkv = u.shape[0], hp["head_dim"], hp["num_key_value_heads"]
    rp = hp["rope_parameters"][attn]

    def heads(name, n):
        return mm(u, w[pre + name], quant).reshape(t, n, d).transpose(
            1, 0, 2)
    q, k = rotary(heads("w_q", hq), rp), rotary(heads("w_k", hkv), rp)
    o = attention(q, k, heads("w_v", hkv),
                  hp["sliding_window"] if attn == SLIDING else 0, quant)
    gate = jnp.ones((t, hq))
    if hp["gating"] and hp.get("fault") != "no_gate":
        gate = jax.nn.sigmoid(u @ w[pre + "w_g"])
    o = (o.transpose(1, 0, 2) * gate[:, :, None]).reshape(t, hq * d)
    # rows of zeros up to a multiple of 128, cut off again after the
    # product: ``o`` comes out of the attention with T as its minor
    # dimension, and on the chip the control's float8 copy of it in that
    # layout, contracted over a T that fills no whole tile, gave a W_o
    # gradient of NaN alone (PERF.md section 6, PR 40); arithmetic as before
    o = jnp.pad(o, ((0, -t % 128), (0, 0)))
    return mm(o, w[pre + "w_o"], quant)[:t], jnp.mean(gate)


def layer(x, w, pre: str, kind: tuple, hp: dict, quant):
    """One block on one sequence, ``x`` [T, h], its leaves under ``pre`` →
    (y, the share of the token-slots routed to experts held here — 0 on a
    dense layer —, the gate's mean)."""
    u = rmsnorm(x, w[pre + "norm_1"], hp["rms_norm_eps"])
    m, gate_mean = gated_attention(u, w, pre, kind, hp, quant)
    x = x + m
    v2 = rmsnorm(x, w[pre + "norm_2"], hp["rms_norm_eps"])
    if kind[0]:
        return x + dense_layer(v2, w, pre, quant), jnp.zeros(()), gate_mean
    gate, chosen = route(v2, w[pre + "w_router"], 0.0, hp)
    lo = hp["expert_offset"]
    share = jnp.sum(chosen[:, lo:lo + hp["experts_held"]]) / (
        chosen.shape[0] * hp["num_experts_per_tok"])
    f = expert_layer(v2, gate, w, pre, hp, quant) + shared_expert(
        v2, w, pre, quant)
    return x + f, share, gate_mean


def hidden(w, tokens, hp: dict, quant, normed: bool = True):
    """Hidden states of one sequence ``tokens`` [T] → ([T, h] after the
    final norm — before it without ``normed`` —, the held share of each
    EXPERT layer, the gate's mean of every layer)."""
    x = w["embed"][tokens]
    shares, gates = [], []
    for i in range(hp["num_hidden_layers"]):
        x, share, gate = jax.checkpoint(lambda x, w, i=i: layer(
            x, w, f"layer_{i:02d}/", layer_kind(hp, i), hp, quant))(x, w)
        gates.append(gate)
        if not is_dense(hp, i):
            shares.append(share)
    if normed:
        x = rmsnorm(x, w["final_norm"], hp["rms_norm_eps"])
    return x, jnp.stack(shares), jnp.stack(gates)


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
    x_on, share, _ = hidden(theta, tok, hp, quant, normed=False)
    x_tg = hidden(target, tok, hp, quant, normed=False)[0]
    loss, (prio, q_sum) = td_loss(x_on, x_tg, theta, target, seq, hp, quant)
    return loss, (prio, q_sum, share)


_PROGRAMS: dict = {}


def layer_leaves(w, i: int, hp: dict) -> dict:
    """Layer ``i``'s leaves of ``w`` under their bare names."""
    return {k: w[f"layer_{i:02d}/{k}"] for k in layer_leaf_names(hp, i)}


def programs(hp: dict, quant=None):
    """The compiled pieces a window goes through a layer at a time (they
    take a layer's leaves under their bare names; ``kind`` is static, so
    each kind of layer is a program, and θ and θ⁻ share them):
    ``forward(x, leaves, kind) -> layer(...)``; ``backward(x, leaves, ct,
    kind)`` -> the cotangents of ``x`` and of the leaves from the layer
    computed again; ``top(x_on, x_tg, top, top_tg, seq)`` -> ``td_loss``
    with its gradients by ``x_on`` and ``top``; ``embed(tokens, ct, like)``
    -> the embedding's gradient. Under ``quant`` the cotangents between
    them (``ct``) carry ``loss_scale``; the loss and every gradient are
    handed back without it."""
    key = (repr(sorted(hp.items())), quant)
    if key in _PROGRAMS:
        return _PROGRAMS[key]
    scale = loss_scale(hp) if quant else 1.0

    def unscaled(g):
        return jax.tree.map(lambda v: v / scale, g)

    def forward(x, leaves, kind):
        with jax.default_matmul_precision("highest"):
            return layer(x, leaves, "", kind, hp, quant)

    def backward(x, leaves, ct, kind):
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(lambda x, leaves: layer(
                x, leaves, "", kind, hp, quant)[0], x, leaves)
            ct, g = vjp(ct)
            return ct, unscaled(g)

    def top(x_on, x_tg, top, top_tg, seq):
        seq = {**seq, "scale": seq["scale"] * scale}
        with jax.default_matmul_precision("highest"):
            (loss, aux), (ct, g) = jax.value_and_grad(
                td_loss, (0, 2), has_aux=True)(
                    x_on, x_tg, top, top_tg, seq, hp, quant)
        return (loss / scale, aux), (ct, unscaled(g))

    def embed(tokens, ct, like):
        return jnp.zeros_like(like).at[tokens].add(ct / scale)

    _PROGRAMS.clear()       # one configuration's at a time
    _PROGRAMS[key] = (
        jax.jit(forward, static_argnames="kind"),
        jax.jit(backward, static_argnames="kind"), jax.jit(top),
        jax.jit(embed))
    return _PROGRAMS[key]


_ADD = jax.jit(jnp.add, donate_argnums=0)


def grad_one(theta, target, seq, hp: dict, quant=None, acc=None):
    """``jax.value_and_grad(sequence_loss, has_aux=True)`` of one window,
    a layer at a time: θ's forward pass keeping each layer's input, θ⁻'s,
    the TD loss with its gradients at the top, then the layers backwards,
    each computed again. The gradient is ADDED to ``acc`` (by name; a new
    one where ``acc`` is None) a layer at a time, so two whole gradients
    never stand side by side. The aux carries the gates' means too."""
    forward, backward, top, embed = programs(hp, quant)
    tok, n = seq["tokens"], hp["num_hidden_layers"]
    acc = {} if acc is None else acc

    def add(g: dict):
        for k, v in g.items():
            acc[k] = _ADD(acc[k], v) if k in acc else v

    xs, shares, gates = [theta["embed"][tok]], [], []
    for i in range(n):
        x, share, gate = forward(xs[-1], layer_leaves(theta, i, hp),
                                 kind=layer_kind(hp, i))
        xs.append(x)
        gates.append(gate)
        if not is_dense(hp, i):
            shares.append(share)
    x_tg = target["embed"][tok]
    for i in range(n):
        x_tg = forward(x_tg, layer_leaves(target, i, hp),
                       kind=layer_kind(hp, i))[0]
    tops = ("final_norm", "head")
    (loss, (prio, q_sum)), (ct, g) = top(
        xs.pop(), x_tg, {k: theta[k] for k in tops},
        {k: target[k] for k in tops}, seq)
    del x_tg
    add(g)
    for i in reversed(range(n)):
        ct, g = backward(xs.pop(), layer_leaves(theta, i, hp), ct,
                         kind=layer_kind(hp, i))
        add({f"layer_{i:02d}/{k}": v for k, v in g.items()})
    add({"embed": embed(tok, ct, theta["embed"])})
    return (loss, (prio, q_sum, jnp.stack(shares),
                   jnp.mean(jnp.stack(gates)))), acc


def make_step(hp: dict, quant=None):
    """One train step: the loss and its gradients A WINDOW AT A TIME (no
    batching; the windows' gradients are added up; ``grad_one``: each
    window a layer at a time), clip by global norm, Adam, the target copy.
    Returns ``step(state, batch) -> (state, metrics, priority [B])``;
    ``batch``: tokens [B, T+1], reward / discount / mask [B, T], weight
    [B]; metrics carry per-leaf gradient norms (``grad_leaf_norm``, by
    name) and ``attn_gate_mean``."""
    apply = jax.jit(lambda state, g: adam_and_target(state, g, hp),
                    donate_argnums=0)

    def step(state, batch):
        b, t1 = batch["tokens"].shape
        acc, prios = None, []
        loss = q_sum = shares = gate = 0.0
        for s in range(b):
            seq = {k: batch[k][s] for k in
                   ("tokens", "reward", "discount", "mask")}
            seq["scale"] = batch["weight"][s] / b
            (l, (prio, qs, share, g)), acc = grad_one(
                state["theta"], state["target"], seq, hp, quant, acc)
            loss, q_sum, shares = loss + l, q_sum + qs, shares + share / b
            gate = gate + g / b
            prios.append(prio)
        state, leaf, gnorm = apply(state, acc)
        metrics = {"loss": loss, "grad_norm": gnorm, "grad_leaf_norm": leaf,
                   "q_mean": q_sum / (b * (t1 - 1) * hp["vocab_size"]),
                   "held_share": shares, "attn_gate_mean": gate}
        return state, metrics, jnp.stack(prios)

    return step
