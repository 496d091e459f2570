"""Plain reference of the token-window Q-network family (``tokenq``): the
SmallThinker block as a Q-network over token prefixes, its Double-DQN
sequence loss, gradients, clip, one Adam + target step, the PER weights and
the priority write-back — ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
batching trick. It imports nothing of the program and nothing of the
family's ``check.py``.

The forward pass, for layer l with input x ``[T, h]`` (h = ``hidden_size``,
RMSNorm eps ``rms_norm_eps``, no biases, untied head):

- ``u = rmsnorm_1(x)``. Router FROM THE LAYER'S NORMED INPUT, before
  attention: ``p = softmax(u W_r)`` over all ``moe_router_experts`` (the published ``moe_num_primary_experts``),
  the ``moe_num_active_primary_experts`` largest kept and renormalised.
- Grouped-query attention over ``u``: ``num_attention_heads`` query and
  ``num_key_value_heads`` key/value heads of ``head_dim``, scale
  head_dim^-1/2, causal. ``sliding_window_layout[l] = 0``: full attention,
  no positional encoding (``rope_layout[l] = 0``). Otherwise rotary
  embedding (theta ``rope_theta``) and a window: query t sees keys s with
  ``t - sliding_window_size < s <= t``. ``x' = x + attn W_o``.
- ``v = rmsnorm_2(x')``; expert e is ReGLU of width
  ``moe_ffn_hidden_size``: ``f_e(v) = (relu(v W_g,e) * (v W_u,e)) W_d,e``;
  ``y = x' + sum over the chosen experts HELD here of p_e f_e(v)``.
- Final RMSNorm, then ``Q = hidden W_out`` over the ``vocab_size`` rows
  held.

Departures from the published description, each also under ``assumed`` in
the configuration file:

1. The share. This is one member of an expert-parallel group: it holds
   experts ``[expert_offset, expert_offset + moe_experts_held)``
   of the layer and a slice of the vocabulary. The router is as wide as
   published; what the absent experts would add is left out and the
   partial result goes on to the next layer (``experts_held`` = all of them
   gives the whole layer: the share test in ``tests/`` adds the parts up).
2. "Secondary experts" and the "sparse ReGLU" predictor of the model card
   are inference-time savings with no key in ``config.json``: not modelled.
3. Rotary embedding in the rotate-half convention (the config gives theta
   and no layout); the window includes the query's own position and the
   ``sliding_window_size - 1`` keys before it.
4. Memory only, no arithmetic changed: attention runs a block of queries at
   a time against all keys, the head a block of tokens at a time, each
   expert in turn over all tokens — so that the published widths fit one
   chip in float32.

``quant="fp8"`` is the CONTROL: every matrix product the configuration
states in bfloat16 (projections, attention's two products, experts, head)
takes its operands through float8_e4m3 and its cotangents through
float8_e5m2, the nearest precision below. Router, norms, loss and Adam stay
float32 on both sides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# exact comparisons: what the sample program fed against the seeded ring
EXACT_LIMITS = {
    "windows_illegal": 0,           # drawn slots outside the filled ring
    "token_window_mismatch": 0,     # tokens (and so actions), bit for bit
    "validity_mismatch": 0,
    "reward_max_abs": 1e-5,
    "discount_max_abs": 1e-5,
}

ADAM_B1, ADAM_B2 = 0.9, 0.999
RESCALE_EPS = 1e-3
INIT_STD = 0.02
GEN_BLOCK = 256             # windows per seeded block
Q_BLOCK = 128               # queries per attention block
TOKEN_BLOCK = 512          # tokens per head block


# ---- seeded data: weights and windows ---------------------------------

def leaf_shapes(hp: dict) -> dict[str, tuple]:
    """The parameters by name (the program's per-path leaf names)."""
    h, d, v = hp["hidden_size"], hp["head_dim"], hp["vocab_size"]
    hq, hkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    e, f = hp["moe_experts_held"], hp["moe_ffn_hidden_size"]
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v)}
    for i in range(hp["num_hidden_layers"]):
        pre = f"layer_{i:02d}/"
        out.update({
            pre + "norm_1": (h,), pre + "norm_2": (h,),
            pre + "w_router": (h, hp["moe_router_experts"]),
            pre + "w_q": (h, hq * d), pre + "w_k": (h, hkv * d),
            pre + "w_v": (h, hkv * d), pre + "w_o": (hq * d, h),
            pre + "w_gate": (e, h, f), pre + "w_up": (e, h, f),
            pre + "w_down": (e, f, h)})
    return out


def init_weights(seed: int, hp: dict) -> dict[str, np.ndarray]:
    """Seeded float32 weights by name: matrices N(0, 0.02²), norm gains
    1 + N(0, 0.1²). One generator a leaf, so any leaf can be made alone."""
    out = {}
    for i, (name, shape) in enumerate(sorted(leaf_shapes(hp).items())):
        rng = np.random.default_rng([int(seed), 7, i])
        x = rng.standard_normal(shape, np.float32)
        out[name] = (1.0 + 0.1 * x if len(shape) == 1
                     else INIT_STD * x).astype(np.float32)
    return out


def seeded_windows(seed: int, block: int, hp: dict):
    """Block ``block`` of the ring's fill: ``GEN_BLOCK`` windows that all
    differ. Token ids uniform over the vocabulary rows held, rewards
    N(0, 1), an episode end with probability 1/1024 a step, and every
    fourth window (by draw) cut short: its last steps are padding.
    Returns (tokens [n, T+1] int32, reward [n, T] float32, done [n, T]
    bool, valid [n, T] bool)."""
    t, v = hp["sequence_length"], hp["vocab_size"]
    rng = np.random.default_rng([int(seed), 11, int(block)])
    tokens = rng.integers(0, v, (GEN_BLOCK, t + 1), dtype=np.int32)
    reward = rng.standard_normal((GEN_BLOCK, t), np.float32)
    done = rng.random((GEN_BLOCK, t), np.float32) < 1.0 / 1024.0
    short = rng.random(GEN_BLOCK) < 0.25
    length = np.where(short, rng.integers(t // 2, t + 1, GEN_BLOCK), t)
    valid = np.arange(t)[None, :] < length[:, None]
    return tokens, reward, done, valid


def windows_at(seed: int, slots: np.ndarray, hp: dict) -> dict:
    """The windows the fill put in ring slots ``slots`` (any shape), as the
    train step takes them: tokens, reward, discount γ(1-done), mask."""
    flat = np.asarray(slots).reshape(-1)
    made = {b: seeded_windows(seed, b, hp) for b in set(flat // GEN_BLOCK)}
    cols = [[made[s // GEN_BLOCK][c][s % GEN_BLOCK] for s in flat]
            for c in range(4)]
    tokens, reward, done, valid = (np.stack(c).reshape(
        slots.shape + c[0].shape) for c in cols)
    return {"tokens": tokens, "reward": reward,
            "discount": np.where(done, 0.0, hp["gamma"]).astype(np.float32),
            "mask": valid.astype(np.float32)}


# ---- PER arithmetic ----------------------------------------------------

def betas_for(first_sample: int, n: int, hp: dict) -> np.ndarray:
    """β of samples ``first_sample+1 .. first_sample+n`` (the anneal
    advances before each read)."""
    k = np.arange(first_sample + 1, first_sample + n + 1)
    frac = np.minimum(k / max(hp["priority_beta_steps"], 1), 1.0)
    return (hp["priority_beta0"] + frac * (1.0 - hp["priority_beta0"])
            ).astype(np.float32)


def is_weights(prio: np.ndarray, filled: int, idx: np.ndarray,
               betas: np.ndarray) -> np.ndarray:
    """``(N · p_i / Σp)^-β`` over the filled slots, normalised by each
    step's largest; ``idx`` [chain, b], ``betas`` [chain]."""
    p = prio[:filled].astype(np.float64)
    pr = np.maximum(p[idx] / p.sum(), 1e-12)
    w = (filled * pr) ** (-betas[:, None].astype(np.float64))
    return (w / w.max(axis=1, keepdims=True)).astype(np.float32)


def written_priority(td_priority: np.ndarray, hp: dict) -> np.ndarray:
    return (np.abs(td_priority) + hp["priority_eps"]) ** hp["priority_alpha"]


# ---- the forward pass --------------------------------------------------

def _q8(x, dtype):
    return x.astype(dtype).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm_fp8(a, w, dims):
    return jax.lax.dot_general(_q8(a, jnp.float8_e4m3fn),
                               _q8(w, jnp.float8_e4m3fn), dims)


def _mm_fp8_fwd(a, w, dims):
    return _mm_fp8(a, w, dims), (a, w)


def _mm_fp8_bwd(dims, res, g):
    a, w = res
    g = _q8(g, jnp.float8_e5m2)
    _, vjp = jax.vjp(lambda a, w: jax.lax.dot_general(a, w, dims),
                     _q8(a, jnp.float8_e4m3fn), _q8(w, jnp.float8_e4m3fn))
    return vjp(g)


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(a, w, quant, dims=None):
    """A product the configuration states in bfloat16: float32 here,
    through float8 in the control."""
    dims = dims or (((a.ndim - 1,), (0,)), ((), ()))
    if quant is None:
        return jax.lax.dot_general(a, w, dims)
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    return _mm_fp8(a, w, dims)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary(x, theta):
    """Rotate-half rotary embedding, ``x`` [heads, T, D]."""
    d, t = x.shape[-1], x.shape[-2]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def attention(q, k, v, window: int, quant, q_block: int = Q_BLOCK):
    """Causal (and, with ``window`` > 0, windowed) attention of one
    sequence: ``q`` [Hq, T, D], ``k``/``v`` [Hkv, T, D] → [Hq, T, D]. A
    block of queries at a time against ALL keys, masked."""
    hq, t, d = q.shape
    group = hq // k.shape[0]
    k, v = jnp.repeat(k, group, 0), jnp.repeat(v, group, 0)
    nb = -(-t // q_block)
    qp = jnp.pad(q, ((0, 0), (0, nb * q_block - t), (0, 0)))
    qp = qp.reshape(hq, nb, q_block, d).transpose(1, 0, 2, 3)
    s_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one(qb, start):
        t_pos = start + jnp.arange(q_block)[:, None]
        seen = s_pos <= t_pos
        if window:
            seen &= s_pos > t_pos - window
        s = mm(qb, k, quant, (((2,), (2,)), ((0,), (0,)))) * d ** -0.5
        # (a padded query of the last block may see no key: finite mask)
        p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        p = jnp.where(seen[None], p, 0.0)
        return mm(p, v, quant, (((2,), (1,)), ((0,), (0,))))

    out = jax.lax.map(lambda xs: one(*xs),
                      (qp, jnp.arange(nb) * q_block))
    return out.transpose(1, 0, 2, 3).reshape(hq, nb * q_block, d)[:, :t]


def route(u, w_router, top_k: int):
    """softmax over all experts, the top k kept and renormalised: dense
    weights ``[T, E]``, zero off the chosen experts."""
    p = jax.nn.softmax(u @ w_router, axis=-1)
    top_p, top_i = jax.lax.top_k(p, top_k)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    dense = jnp.zeros_like(p)
    return dense.at[jnp.arange(p.shape[0])[:, None], top_i].set(top_p)


def expert_layer(v, gate, w, prefix: str, hp: dict, quant):
    """Σ_e held here of gate[:, e] · ReGLU_e(v), each expert over all
    tokens in turn."""
    lo = hp["expert_offset"]
    gates = gate[:, lo:lo + hp["moe_experts_held"]].T        # [held, T]

    @jax.checkpoint
    def one(xs):
        wg, wu, wd, g = xs
        a = jax.nn.relu(mm(v, wg, quant)) * mm(v, wu, quant)
        return g[:, None] * mm(a, wd, quant)

    parts = jax.lax.map(one, (w[prefix + "w_gate"], w[prefix + "w_up"],
                              w[prefix + "w_down"], gates))
    return jnp.sum(parts, axis=0)


def layer(x, w, i: int, hp: dict, quant):
    """One block on one sequence, ``x`` [T, h]; also returns the dense
    routing weights (the counters are read from them)."""
    pre = f"layer_{i:02d}/"
    t = x.shape[0]
    hq, hkv, d = (hp["num_attention_heads"], hp["num_key_value_heads"],
                  hp["head_dim"])
    u = rmsnorm(x, w[pre + "norm_1"], hp["rms_norm_eps"])
    gate = route(u, w[pre + "w_router"],
                 hp["moe_num_active_primary_experts"])

    def heads(name, n):
        return mm(u, w[pre + name], quant).reshape(t, n, d).transpose(1, 0, 2)
    q, k, v = heads("w_q", hq), heads("w_k", hkv), heads("w_v", hkv)
    if hp["rope_layout"][i]:
        q, k = rotary(q, hp["rope_theta"]), rotary(k, hp["rope_theta"])
    window = hp["sliding_window_size"] if hp["sliding_window_layout"][i] \
        else 0
    a = attention(q, k, v, window, quant)
    x = x + mm(a.transpose(1, 0, 2).reshape(t, hq * d), w[pre + "w_o"],
               quant)
    v2 = rmsnorm(x, w[pre + "norm_2"], hp["rms_norm_eps"])
    return x + expert_layer(v2, gate, w, pre, hp, quant), gate


def hidden(w, tokens, hp: dict, quant):
    """Final-normed hidden states of one sequence ``tokens`` [T] → [T, h],
    and the share of token-slots routed to held experts, by layer."""
    x = w["embed"][tokens]
    lo = hp["expert_offset"]
    hi = lo + hp["moe_experts_held"]
    shares = []
    for i in range(hp["num_hidden_layers"]):
        x, gate = jax.checkpoint(
            lambda x, w, i=i: layer(x, w, i, hp, quant))(x, w)
        shares.append(jnp.sum(gate[:, lo:hi] > 0)
                      / (gate.shape[0]
                         * hp["moe_num_active_primary_experts"]))
    return rmsnorm(x, w["final_norm"], hp["rms_norm_eps"]), jnp.stack(shares)


def q_values(w, tokens, hp: dict, quant=None):
    """Q at every position of one sequence: [T, V] (small sizes only)."""
    return mm(hidden(w, tokens, hp, quant)[0], w["head"], quant)


def q_select(h_on, h_tg, head_on, head_tg, actions, hp: dict, quant):
    """A block of tokens at a time: Q_θ(p, a_p), Q_θ⁻(p, a*) with a* the
    argmax of Q_θ(p, ·) (Double-DQN) or of Q_θ⁻(p, ·), and Σ_a Q_θ(p, a)."""
    n = h_on.shape[0]
    blk = min(TOKEN_BLOCK, n)
    nb = -(-n // blk)

    def blocks(x):
        x = jnp.pad(x, ((0, nb * blk - n),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((nb, blk) + x.shape[1:])

    @jax.checkpoint
    def one(ho, ht, a):
        q_on, q_tg = mm(ho, head_on, quant), mm(ht, head_tg, quant)
        pick = jax.lax.stop_gradient(q_on) if hp["double_dqn"] else q_tg
        a_star = jnp.argmax(pick, -1)
        take = lambda q, i: jnp.take_along_axis(q, i[:, None], -1)[:, 0]  # noqa: E731
        return take(q_on, a), take(q_tg, a_star), jnp.sum(q_on, -1)

    q_sa, q_boot, q_row = jax.lax.map(
        lambda xs: one(*xs), (blocks(h_on), blocks(h_tg), blocks(actions)))
    return (q_sa.reshape(-1)[:n], q_boot.reshape(-1)[:n],
            q_row.reshape(-1)[:n])


# ---- loss and optimizer ------------------------------------------------

def value_rescale(x, eps=RESCALE_EPS):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def value_rescale_inv(x, eps=RESCALE_EPS):
    return jnp.sign(x) * (jnp.square(
        (jnp.sqrt(1.0 + 4.0 * eps * (jnp.abs(x) + 1.0 + eps)) - 1.0)
        / (2.0 * eps)) - 1.0)


def huber(x, delta):
    a = jnp.abs(x)
    q = jnp.minimum(a, delta)
    return 0.5 * q * q + delta * (a - q)


def sequence_loss(theta, target, seq, hp: dict, quant):
    """ONE window's term of the Double-DQN sequence loss: ``seq`` holds
    tokens [T+1], reward / discount / mask [T] and ``scale`` = its IS
    weight over the batch size. Returns (scale · masked mean Huber,
    (priority η max|TD| + (1-η) mean|TD|, Σ_a,t Q over the T steps, the
    held share by layer))."""
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


def init_state(theta: dict, target: dict) -> dict:
    def zeros():        # one buffer each: the step donates its state
        return {k: jnp.zeros_like(v) for k, v in theta.items()}
    return {"theta": dict(theta), "target": dict(target), "m": zeros(),
            "v": zeros(), "step": jnp.zeros((), jnp.int32)}


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
