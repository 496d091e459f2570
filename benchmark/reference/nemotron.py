"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B's block as a
token-window Q-network (family ``nemotron``; nvidia, ``model_type``
nemotron_h): its forward pass with the Mamba-2 mixer as the SEQUENTIAL
recurrence, the Double-DQN sequence loss, gradients, clip, one Adam +
target step, the PER weights and the priority write-back — ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``, no kernel, no
cache, no chunked form, no batching trick. It imports nothing of the
program and nothing of a family's ``check.py``. What the other references
offer unchanged is imported from them (``reference/tokenq.py``: the seeded
windows, the PER arithmetic, the float8 product of the control, RMSNorm,
blockwise attention, the blockwise head; ``reference/lfm2.py``: the sigmoid
router with its selection bias and its scaled gates; ``reference/keye.py``:
the TD loss on the last layer's outputs; ``reference/moonlight.py``: clip +
Adam + target); what this block changes is written here.

Every layer is ONE part alone (``pattern``: a letter a layer), pre-norm
residual on one sequence, ``x`` [T, h], ``u = rmsnorm(x)`` (eps
``rms_norm_eps``, a learned gain, no biases), ``x ← x + f(u)``:

- ``M``, the Mamba-2 mixer: ``[z | xBC | dt] = u W_in`` (``d_inner`` |
  ``d_inner + 2 n_groups ssm_state_size`` | ``mamba_num_heads``);
  ``xBC ← silu(Σ_{j<L} w[:, j] · xBC_{t-(L-1)+j} + b)``, depthwise and
  causal (``L = conv_kernel`` shifted adds, zeros before the window);
  split x ``[T, H, P]``, B, C ``[T, G, N]``; head j reads group ``j //
  (H / G)``; ``Δ_t = softplus(dt_t + dt_bias)``, ``A = -exp(a_log)`` a
  head; ``h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t B_tᵀ`` (``[P, N]`` a head,
  ``h_{-1} = 0``: a window is one prefix), ``y_t = h_t C_t + D x_t`` — a
  ``lax.scan`` over POSITIONS, in blocks of ``SCAN_BLOCK`` rows each
  computed again in the backward pass (memory only); ``g = y · silu(z)``;
  RMSNorm over each GROUP's ``d_inner / n_groups`` channels, times
  ``gate_norm``; ``f = g W_out``.
- ``*``, attention: ``num_attention_heads`` query and
  ``num_key_value_heads`` key/value heads of ``head_dim``, NO positional
  embedding, causal ``softmax(q kᵀ head_dim^-½) v``; ``f = attn W_o``.
- ``E``, the expert layer: ``s = sigmoid(u W_r)`` over all
  ``router_experts``; the ``num_experts_per_tok`` with the largest ``s +
  expert_bias`` are chosen; ``g_e = routed_scaling_factor · s_e /
  (Σ_chosen s + 1e-6)``; ``f = S(u) + Σ_{e chosen and held here} g_e ·
  f_e(u)``, ``f_e(u) = act(u W_up,e) W_down,e`` with ``act(x) =
  relu(x)²`` (``mlp_hidden_act`` relu2) of width
  ``moe_intermediate_size`` — TWO matrices, no gate —, ``S`` the same form
  of width ``moe_shared_expert_intermediate_size``, ungated, every token.

After the last layer the final RMSNorm, then ``Q = hidden W_out`` over the
``vocab_size`` rows held (untied).

Departures from the published description, each also under ``assumed`` in
the configuration file: the share (experts ``[expert_offset, expert_offset
+ experts_held)`` and a slice of the vocabulary are held, the router as
wide as published, the shared expert WHOLE on every member of the group —
the share test in ``tests/`` adds the parts up with it counted once);
``expert_bias`` is a seeded constant; the router's epsilon is 1e-6 where
the source adds 1e-20; the residual stream is float32. Memory only, no
arithmetic changed: the scan in checkpointed blocks of rows, attention a
block of queries at a time, the head and the shared expert a block of
tokens at a time, each expert in turn over all tokens, and ``make_step``
a window a LAYER at a time (``programs``, as ``reference/moonlight.py``).

``hp["fault"]`` (absent: none) names a PLANTED FAULT for
``families/nemotron/faults.py``: ``"gate_norm_whole"`` norms all
``d_inner`` channels as one group; ``"head_group_mod"`` lets head j read
group ``j % G``; ``"no_conv_bias"`` leaves the convolution's bias out.
Two others are plain hyper-parameters: ``mlp_hidden_act`` "relu" and
``routed_scaling_factor`` 1.0.

``quant="fp8"`` is the CONTROL: every matrix product the configuration
states in bfloat16 (``W_in``, ``W_out``, the attention layer's four
projections and two products, the experts' and the shared expert's two,
the head) takes its operands through float8_e4m3 and its cotangents
through float8_e5m2; the scan's products have no counterpart in the
sequential form, so their operands — x, B and C — pass through the same
two roundings on their way into the recurrence. Router, norms,
convolution, Δ, decays, the state, loss and Adam stay float32 on both
sides.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye import td_loss
from benchmark.reference.lfm2 import BIAS_STD, route  # noqa: F401
from benchmark.reference.moonlight import adam_and_target
from benchmark.reference.tokenq import (  # noqa: F401 — the family's surface
    ADAM_B1, ADAM_B2, EXACT_LIMITS, GEN_BLOCK, INIT_STD, attention,
    betas_for, init_state, is_weights, mm, rmsnorm, seeded_windows,
    windows_at, written_priority)

SCAN_BLOCK = 64             # rows of the recurrence rematerialised together
TOKEN_BLOCK = 2048          # tokens per block of the shared expert
DT_RANGE = (1e-3, 1e-1)     # the seeded Δ (``time_step_min`` / ``_max``)
ACTS = {"relu2": lambda x: jnp.square(jax.nn.relu(x)), "relu": jax.nn.relu}
LEAVES = {
    "M": ("norm_1", "w_in", "ssm_conv_w", "ssm_conv_b", "a_log", "d_skip",
          "dt_bias", "gate_norm", "w_out"),
    "*": ("norm_1", "w_q", "w_k", "w_v", "w_o"),
    "E": ("norm_2", "w_router", "expert_bias", "w_up", "w_down",
          "shared_up", "shared_down")}


# ---- seeded weights ----------------------------------------------------

def kind(hp: dict, i: int) -> str:
    return hp["pattern"][i]


def d_inner(hp: dict) -> int:
    return hp["mamba_num_heads"] * hp["mamba_head_dim"]


def leaf_shapes(hp: dict) -> dict[str, tuple]:
    """The parameters by name (the program's per-path leaf names)."""
    h, v = hp["hidden_size"], hp["vocab_size"]
    nh, di = hp["mamba_num_heads"], d_inner(hp)
    xbc = di + 2 * hp["n_groups"] * hp["ssm_state_size"]
    hq, hkv, d = (hp["num_attention_heads"], hp["num_key_value_heads"],
                  hp["head_dim"])
    e, f = hp["experts_held"], hp["moe_intermediate_size"]
    fs = hp["moe_shared_expert_intermediate_size"]
    shapes = {
        "norm_1": (h,), "norm_2": (h,),
        "w_in": (h, di + xbc + nh), "ssm_conv_w": (xbc, hp["conv_kernel"]),
        "ssm_conv_b": (xbc,), "a_log": (nh,), "d_skip": (nh,),
        "dt_bias": (nh,), "gate_norm": (di,), "w_out": (di, h),
        "w_q": (h, hq * d), "w_k": (h, hkv * d), "w_v": (h, hkv * d),
        "w_o": (hq * d, h),
        "w_router": (h, hp["router_experts"]),
        "expert_bias": (hp["router_experts"],),
        "w_up": (e, h, f), "w_down": (e, f, h),
        "shared_up": (h, fs), "shared_down": (fs, h)}
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v)}
    for i in range(hp["num_hidden_layers"]):
        out.update({f"layer_{i:02d}/{k}": shapes[k]
                    for k in LEAVES[kind(hp, i)]})
    return out


def init_weights(seed: int, hp: dict) -> dict[str, np.ndarray]:
    """Seeded float32 weights by name: matrices N(0, 0.02²), gains (the
    norms', ``gate_norm``, ``d_skip``) 1 + N(0, 0.1²), the expert bias
    N(0, 0.01²); a Mamba-2 mixer's taps and their bias uniform within
    ``±conv_kernel^-½``, ``A`` uniform in [1, 16) (``a_log`` its log),
    ``dt_bias`` the inverse softplus of a Δ log-uniform in ``DT_RANGE`` —
    so its heads forget at rates three decades apart. One generator a
    leaf, so any leaf can be made alone."""
    out = {}
    for i, (name, shape) in enumerate(sorted(leaf_shapes(hp).items())):
        rng = np.random.default_rng([int(seed), 7, i])
        leaf = name.rsplit("/", 1)[-1]
        if leaf in ("ssm_conv_w", "ssm_conv_b"):
            bound = hp["conv_kernel"] ** -0.5
            x = rng.uniform(-bound, bound, shape)
        elif leaf == "a_log":
            x = np.log(rng.uniform(1.0, 16.0, shape))
        elif leaf == "dt_bias":
            dt = np.exp(rng.uniform(*np.log(DT_RANGE), shape))
            x = dt + np.log(-np.expm1(-dt))
        elif leaf == "expert_bias":
            x = BIAS_STD * rng.standard_normal(shape)
        elif len(shape) == 1:
            x = 1.0 + 0.1 * rng.standard_normal(shape)
        else:
            x = INIT_STD * rng.standard_normal(shape, np.float32)
        out[name] = np.asarray(x, np.float32)
    return out


# ---- the forward pass --------------------------------------------------

def _q8(x, dtype):
    return x.astype(dtype).astype(jnp.float32)


@jax.custom_vjp
def _through_fp8(x):
    return _q8(x, jnp.float8_e4m3fn)


_through_fp8.defvjp(lambda x: (_through_fp8(x), None),
                    lambda _, g: (_q8(g, jnp.float8_e5m2),))


def operand(x, quant):
    """What the chunked form hands a product as an operand: itself here,
    through float8 (and its cotangent through float8) in the control."""
    return x if quant is None else _through_fp8(x)


def recurrence(x, dt, a, bm, cm, d, group):
    """The state-space recurrence of one sequence, a position at a time:
    ``x`` [T, H, P], ``dt`` [T, H], ``a`` [H], ``bm`` / ``cm`` [T, G, N],
    ``d`` [H], ``group`` [H] (each head's group) → ``y`` [T, H, P]. The
    state ``[H, P, N]`` starts at zero."""
    t, h, p = x.shape
    n = bm.shape[-1]
    blk = min(SCAN_BLOCK, t)
    nb = -(-t // blk)

    def rows(v):    # rows past T: Δ = 0 and x = 0 pass the state on
        v = jnp.pad(v, ((0, nb * blk - t),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape((nb, blk) + v.shape[1:])

    def step(state, row):
        x_t, dt_t, b_t, c_t = row
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[group][:, None])
        y_t = jnp.sum(state * c_t[group][:, None], -1) + d[:, None] * x_t
        return state, y_t

    @jax.checkpoint
    def block(state, rows_):
        return jax.lax.scan(step, state, rows_)

    _, y = jax.lax.scan(block, jnp.zeros((h, p, n), jnp.float32),
                        (rows(x), rows(dt), rows(bm), rows(cm)))
    return y.reshape(nb * blk, h, p)[:t]


def mamba(u, w, pre: str, hp: dict, quant):
    """``g W_out`` of one sequence from its normed input ``u`` [T, h], and
    the mean Δ."""
    t = u.shape[0]
    nh, hd, g, n = (hp["mamba_num_heads"], hp["mamba_head_dim"],
                    hp["n_groups"], hp["ssm_state_size"])
    di, gn, taps = nh * hd, g * n, hp["conv_kernel"]
    fault = hp.get("fault")
    zxbcdt = mm(u, w[pre + "w_in"], quant)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * gn],
                  zxbcdt[:, 2 * di + 2 * gn:])
    lead = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = sum(w[pre + "ssm_conv_w"][:, j] * lead[j:j + t]
               for j in range(taps))
    if fault != "no_conv_bias":
        conv = conv + w[pre + "ssm_conv_b"]
    xbc = jax.nn.silu(conv)
    heads = jnp.arange(nh)
    group = heads % g if fault == "head_group_mod" else heads // (nh // g)
    delta = jax.nn.softplus(dt + w[pre + "dt_bias"])
    y = recurrence(
        operand(xbc[:, :di], quant).reshape(t, nh, hd), delta,
        -jnp.exp(w[pre + "a_log"]),
        operand(xbc[:, di:di + gn], quant).reshape(t, g, n),
        operand(xbc[:, di + gn:], quant).reshape(t, g, n),
        w[pre + "d_skip"], group)
    gated = y.reshape(t, di) * jax.nn.silu(z)
    groups = 1 if fault == "gate_norm_whole" else g
    gated = gated.reshape(t, groups, di // groups)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + hp["rms_norm_eps"])
    return (mm(normed.reshape(t, di) * w[pre + "gate_norm"],
               w[pre + "w_out"], quant), jnp.mean(delta))


def nope_attention(u, w, pre: str, hp: dict, quant):
    t = u.shape[0]
    hq, hkv, d = (hp["num_attention_heads"], hp["num_key_value_heads"],
                  hp["head_dim"])

    def heads(name, n):
        return mm(u, w[pre + name], quant).reshape(t, n, d).transpose(1, 0, 2)
    a = attention(heads("w_q", hq), heads("w_k", hkv), heads("w_v", hkv), 0,
                  quant)
    return mm(a.transpose(1, 0, 2).reshape(t, hq * d), w[pre + "w_o"],
              quant)


def mlp(x, w_up, w_down, hp: dict, quant):
    """``act(x W_up) W_down``: two matrices, no gate."""
    return mm(ACTS[hp["mlp_hidden_act"]](mm(x, w_up, quant)), w_down, quant)


def expert_layer(x, gate, w, pre: str, hp: dict, quant):
    """Σ_e held here of gate[:, e] · f_e(x), each expert over all tokens
    in turn."""
    lo = hp["expert_offset"]
    gates = gate[:, lo:lo + hp["experts_held"]].T           # [held, T]

    @jax.checkpoint
    def one(xs):
        w_up, w_down, g = xs
        return g[:, None] * mlp(x, w_up, w_down, hp, quant)

    return jnp.sum(jax.lax.map(one, (w[pre + "w_up"], w[pre + "w_down"],
                                     gates)), 0)


def shared_expert(x, w, pre: str, hp: dict, quant, block: int = TOKEN_BLOCK):
    """``S(x)``: what every token takes, a block of tokens at a time."""
    n, h = x.shape
    blk = min(block, n)
    nb = -(-n // blk)
    xb = jnp.pad(x, ((0, nb * blk - n), (0, 0))).reshape(nb, blk, h)
    one = jax.checkpoint(lambda b: mlp(b, w[pre + "shared_up"],
                                       w[pre + "shared_down"], hp, quant))
    return jax.lax.map(one, xb).reshape(nb * blk, h)[:n]


def layer(x, w, pre: str, letter: str, hp: dict, quant):
    """One layer on one sequence, ``x`` [T, h], its leaves under ``pre``
    → (x, the share of the token-slots routed to experts held here — 0
    where the layer has none —, the mean Δ — 0 off a Mamba layer)."""
    zero = jnp.zeros(())
    if letter == "E":
        u = rmsnorm(x, w[pre + "norm_2"], hp["rms_norm_eps"])
        gate, chosen = route(u, w[pre + "w_router"], w[pre + "expert_bias"],
                             hp)
        lo = hp["expert_offset"]
        share = jnp.sum(chosen[:, lo:lo + hp["experts_held"]]) / (
            chosen.shape[0] * hp["num_experts_per_tok"])
        return (x + expert_layer(u, gate, w, pre, hp, quant)
                + shared_expert(u, w, pre, hp, quant), share, zero)
    u = rmsnorm(x, w[pre + "norm_1"], hp["rms_norm_eps"])
    if letter == "M":
        mixed, dt_mean = mamba(u, w, pre, hp, quant)
        return x + mixed, zero, dt_mean
    return x + nope_attention(u, w, pre, hp, quant), zero, zero


def hidden(w, tokens, hp: dict, quant, normed: bool = True):
    """Hidden states of one sequence ``tokens`` [T] → ([T, h] after the
    final norm — before it without ``normed`` —, the held share of each
    EXPERT layer, the mean Δ of each MAMBA layer)."""
    x = w["embed"][tokens]
    shares, dts = [], []
    for i in range(hp["num_hidden_layers"]):
        x, share, dt = jax.checkpoint(lambda x, w, i=i: layer(
            x, w, f"layer_{i:02d}/", kind(hp, i), hp, quant))(x, w)
        if kind(hp, i) == "E":
            shares.append(share)
        if kind(hp, i) == "M":
            dts.append(dt)
    if normed:
        x = rmsnorm(x, w["final_norm"], hp["rms_norm_eps"])
    return x, jnp.stack(shares), jnp.stack(dts)


def q_values(w, tokens, hp: dict, quant=None):
    """Q at every position of one sequence: [T, V] (small sizes only)."""
    return mm(hidden(w, tokens, hp, quant)[0], w["head"], quant)


# ---- loss and optimizer ------------------------------------------------

def sequence_loss(theta, target, seq, hp: dict, quant):
    """ONE window's term of the Double-DQN sequence loss as one function
    of θ: ``seq`` holds tokens [T+1], reward / discount / mask [T] and
    ``scale`` = its IS weight over the batch size. Returns (scale · masked
    mean Huber, (priority η max|TD| + (1-η) mean|TD|, Σ_a,t Q over the T
    steps, the held share by expert layer, the mean Δ by Mamba layer))."""
    tok = seq["tokens"]
    x_on, share, dt = hidden(theta, tok, hp, quant, normed=False)
    x_tg = hidden(target, tok, hp, quant, normed=False)[0]
    loss, (prio, q_sum) = td_loss(x_on, x_tg, theta, target, seq, hp, quant)
    return loss, (prio, q_sum, share, dt)


_PROGRAMS: dict = {}


def layer_leaves(w, i: int, hp: dict) -> dict:
    """Layer ``i``'s leaves of ``w`` under their bare names."""
    return {k: w[f"layer_{i:02d}/{k}"] for k in LEAVES[kind(hp, i)]}


def programs(hp: dict, quant=None):
    """The compiled pieces a window goes through a layer at a time (they
    take a layer's leaves under their bare names; ``letter`` is static, so
    each kind of layer is a program, and θ and θ⁻ share them):
    ``forward(x, leaves, letter) -> layer(...)``; ``backward(x, leaves,
    ct, letter)`` -> the cotangents of ``x`` and of the leaves from the
    layer computed again; ``top(x_on, x_tg, top, top_tg, seq)`` ->
    ``td_loss`` with its gradients by ``x_on`` and ``top``;
    ``embed(tokens, ct, like)`` -> the embedding's gradient."""
    key = (repr(sorted(hp.items())), quant)
    if key in _PROGRAMS:
        return _PROGRAMS[key]

    def forward(x, leaves, letter):
        with jax.default_matmul_precision("highest"):
            return layer(x, leaves, "", letter, hp, quant)

    def backward(x, leaves, ct, letter):
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(lambda x, leaves: layer(
                x, leaves, "", letter, hp, quant)[0], x, leaves)
            return vjp(ct)

    def top(x_on, x_tg, top, top_tg, seq):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(td_loss, (0, 2), has_aux=True)(
                x_on, x_tg, top, top_tg, seq, hp, quant)

    def embed(tokens, ct, like):
        return jnp.zeros_like(like).at[tokens].add(ct)

    _PROGRAMS.clear()       # one configuration's at a time
    _PROGRAMS[key] = (
        jax.jit(forward, static_argnames="letter"),
        jax.jit(backward, static_argnames="letter"), jax.jit(top),
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

    xs, shares, dts = [theta["embed"][tok]], [], []
    for i in range(n):
        x, share, dt = forward(xs[-1], layer_leaves(theta, i, hp),
                               letter=kind(hp, i))
        xs.append(x)
        if kind(hp, i) == "E":
            shares.append(share)
        if kind(hp, i) == "M":
            dts.append(dt)
    x_tg = target["embed"][tok]
    for i in range(n):
        x_tg = forward(x_tg, layer_leaves(target, i, hp),
                       letter=kind(hp, i))[0]
    tops = ("final_norm", "head")
    (loss, (prio, q_sum)), (ct, g) = top(
        xs.pop(), x_tg, {k: theta[k] for k in tops},
        {k: target[k] for k in tops}, seq)
    del x_tg
    add(g)
    for i in reversed(range(n)):
        ct, g = backward(xs.pop(), layer_leaves(theta, i, hp), ct,
                         letter=kind(hp, i))
        add({f"layer_{i:02d}/{k}": v for k, v in g.items()})
    add({"embed": embed(tok, ct, theta["embed"])})
    return (loss, (prio, q_sum, jnp.stack(shares), jnp.stack(dts))), acc


def make_step(hp: dict, quant=None):
    """One train step: the loss and its gradients A WINDOW AT A TIME (no
    batching; the windows' gradients are added up; ``grad_one``: each
    window a layer at a time), clip by global norm, Adam, the target copy.
    Returns ``step(state, batch) -> (state, metrics, priority [B])``;
    ``batch``: tokens [B, T+1], reward / discount / mask [B, T], weight
    [B]; metrics carry per-leaf gradient norms (``grad_leaf_norm``, by
    name), ``held_share`` by expert layer and ``ssm_dt_mean``."""
    apply = jax.jit(lambda state, g: adam_and_target(state, g, hp),
                    donate_argnums=0)

    def step(state, batch):
        b, t1 = batch["tokens"].shape
        acc, prios = None, []
        loss = q_sum = shares = dts = 0.0
        for s in range(b):
            seq = {k: batch[k][s] for k in
                   ("tokens", "reward", "discount", "mask")}
            seq["scale"] = batch["weight"][s] / b
            (l, (prio, qs, share, dt)), acc = grad_one(
                state["theta"], state["target"], seq, hp, quant, acc)
            loss, q_sum = loss + l, q_sum + qs
            shares, dts = shares + share / b, dts + jnp.mean(dt) / b
            prios.append(prio)
        state, leaf, gnorm = apply(state, acc)
        metrics = {"loss": loss, "grad_norm": gnorm, "grad_leaf_norm": leaf,
                   "q_mean": q_sum / (b * (t1 - 1) * hp["vocab_size"]),
                   "held_share": shares, "ssm_dt_mean": dts}
        return state, metrics, jnp.stack(prios)

    return step
