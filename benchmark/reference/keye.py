"""Plain reference of Keye-VL-2.0-30B-A3B's language-model block as a
token-window Q-network (family ``keye``; Kwai-Keye, ``model_type``
KeyeVL2): its forward pass with the learned sparse attention, the
Double-DQN sequence loss and the indexer's own loss, gradients, clip, one
Adam + target step, the PER weights and the priority write-back —
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``,
no kernel, no cache, no batching trick. It imports nothing of the program
and nothing of a family's ``check.py``. What ``reference/tokenq.py`` and
``reference/lfm2.py`` offer unchanged is imported from there (the seeded
windows, the PER arithmetic, the float8 product of the control, RMSNorm,
the rotary embedding, the softmax router, the SwiGLU experts, the
blockwise head, the loss's pieces); what this block changes is written
here.

Layer l on one sequence, input x ``[T, h]`` (pre-norm residual, RMSNorm eps
``rms_norm_eps`` with a learned gain, no biases), ``u = rmsnorm_1(x)``:

- main heads: ``q = u W_q`` [``num_attention_heads`` x ``head_dim``], ``k
  = u W_k``, ``v = u W_v`` [``num_key_value_heads`` x ``head_dim``]; an
  RMSNorm over each head of q and of k (gains ``[head_dim]``); rotary
  embedding on q and k (theta ``rope_theta``, rotate-half, positions 0..T
  in the window);
- indexer, all float32, reading ``ū = stop_gradient(u)``: ``qI = ū W_iq``
  [``indexer_num_heads`` x ``indexer_head_dim``], ``kI = rmsnorm(ū W_ik)``
  (ONE key head), ``wI = ū W_iw`` [``indexer_num_heads``]; the same rotary
  on ``qI`` and ``kI``; ``I[t, s] = Hi^-½ · Di^-½ · Σ_j wI[t, j] ·
  relu(qI[t, j] · kI[s])`` for ``s <= t``;
- ``S_t`` = the ``topk`` keys ``s <= t`` with the largest ``I[t, s]``
  (every key while ``t < topk``), ties to the smaller ``s``: the
  ``topk``-th largest of the row from ``lax.top_k``, every key above it,
  and the keys equal to it in order of ``s`` until ``topk`` are kept;
- ``o[t, h] = Σ_{s in S_t} softmax_{s in S_t}(q[t, h] · k[s, g(h)] ·
  head_dim^-½) v[s, g(h)]``; ``x' = x + o W_o``;
- ``w = rmsnorm_2(x')``; ``p = softmax(w W_r)`` over all
  ``router_experts``, the ``num_experts_per_tok`` largest kept and
  renormalised (``norm_topk_prob``); ``y = x' + Σ over the chosen experts
  HELD here of p_e (silu(w W_gate,e) * (w W_up,e)) W_down,e``. No shared
  expert, no dense layer.
- ``L_I(l) = mean_t KL(p_t ‖ softmax_{s in S_t} I[t, s])``, ``p_t`` = the
  main heads' probabilities over ``S_t`` summed over the heads and
  divided by their number, under ``stop_gradient``. No gradient flows
  through ``S_t``.

After the last layer the final RMSNorm, then ``Q = hidden W_out`` over the
``vocab_size`` rows held (untied). The step minimises ``TD loss + Σ_l
L_I(l)`` (``L_I`` the mean over the batch's windows); the TD loss reaches
every leaf but the indexer's four, ``L_I`` only those.

Departures from the published description, each also under ``assumed`` in
the configuration file: the share (experts ``[expert_offset, expert_offset
+ experts_held)`` and a slice of the vocabulary; the router as wide as
published); M-RoPE with text-only positions is ordinary rotary; the
indexer's inputs, key norm, rotary and scale; the coefficient 1 of
``L_I``. Memory only, no arithmetic changed: attention, index scores,
selection and ``L_I`` a block of queries at a time against all keys, the
head a block of tokens at a time, each expert in turn over all tokens; a
layer's selection is kept for its backward pass. ``make_step`` and
``selection`` run the SAME functions a layer at a time (``programs``: one
compiled forward and one compiled backward serve every layer of θ and θ⁻,
each layer's leaves handed to them under layer 0's names; the chain rule
between layers is written out) — the whole model as one program is four
copies of a layer's code, 479 MB of it for the chip, more than the
machines' compile cache keeps; ``sequence_loss`` is that whole program,
and at toy size the tests hold the two to each other.

``hp["selection"]`` (absent: ``"exact"``) names a PLANTED FAULT for
``families/keye/faults.py``: ``"recent"`` keeps the ``topk`` most recent
keys (a window in place of the indexer), ``"approx"`` keeps what
``lax.approx_max_k`` returns at a recall target of ``APPROX_RECALL``.

``quant="fp8"`` is the CONTROL: every matrix product the configuration
states in bfloat16 (q/k/v/o, attention's two products, the experts, the
head) takes its operands through float8_e4m3 and its cotangents through
float8_e5m2. The indexer, the selection, ``L_I``, router, norms, loss and
Adam stay float32 on both sides.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from benchmark.reference.lfm2 import expert_layer  # noqa: F401
from benchmark.reference.tokenq import (  # noqa: F401 — the family's surface
    ADAM_B1, ADAM_B2, EXACT_LIMITS, GEN_BLOCK, INIT_STD, betas_for, huber,
    init_state, is_weights, mm, q_select, rmsnorm, rotary, route,
    seeded_windows, value_rescale, value_rescale_inv, windows_at,
    written_priority)

Q_BLOCK = 128               # queries per attention / indexer block
# the "approx" fault's recall target. On the TPU ``lax.approx_max_k``
# keeps the largest of every 2^j neighbouring keys, j = floor(log2(T ·
# ln(1 / recall) / (topk - 1))): at 2 048 of 16 385 keys any target over
# 0.779 gives j = 0 and IS exact (its default 0.95 and 0.8 both read as a
# sound seed on the chip); 0.75 keeps 8 320 candidates, the best of two
APPROX_RECALL = 0.75
MASKED = -1e30
KEEP = jax.checkpoint_policies.save_only_these_names("selection")


# ---- seeded weights ----------------------------------------------------

def leaf_shapes(hp: dict) -> dict[str, tuple]:
    """The parameters by name (the program's per-path leaf names)."""
    h, d, v = hp["hidden_size"], hp["head_dim"], hp["vocab_size"]
    hq, hkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    hi, di = hp["indexer_num_heads"], hp["indexer_head_dim"]
    e, f = hp["experts_held"], hp["moe_intermediate_size"]
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v)}
    for i in range(hp["num_hidden_layers"]):
        pre = f"layer_{i:02d}/"
        out.update({
            pre + "norm_1": (h,), pre + "norm_2": (h,),
            pre + "w_q": (h, hq * d), pre + "w_k": (h, hkv * d),
            pre + "w_v": (h, hkv * d), pre + "w_o": (hq * d, h),
            pre + "q_norm": (d,), pre + "k_norm": (d,),
            pre + "w_iq": (h, hi * di), pre + "w_ik": (h, di),
            pre + "w_iw": (h, hi), pre + "ik_norm": (di,),
            pre + "w_router": (h, hp["router_experts"]),
            pre + "w_gate": (e, h, f), pre + "w_up": (e, h, f),
            pre + "w_down": (e, f, h)})
    return out


INDEXER_LEAVES = ("w_iq", "w_ik", "w_iw", "ik_norm")


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


# ---- the forward pass --------------------------------------------------

def select(scores, t_pos, s_pos, hp: dict):
    """Rows of index scores ``[Bq, T]`` → the kept keys, bool ``[Bq, T]``."""
    k = min(hp["topk"], scores.shape[-1])
    seen = s_pos <= t_pos
    kind = hp.get("selection", "exact")
    if kind == "recent":
        return seen & (s_pos > t_pos - k)
    masked = jnp.where(seen, scores, -jnp.inf)
    if kind == "approx":
        _, idx = jax.lax.approx_max_k(masked, k,
                                      recall_target=APPROX_RECALL)
        rows = jnp.arange(scores.shape[0])[:, None]
        return jnp.zeros(scores.shape, bool).at[rows, idx].set(True) & seen
    if kind != "exact":
        raise ValueError(f"unknown selection {kind!r}")
    kth = jax.lax.top_k(masked, k)[0][:, -1:]
    above, equal = masked > kth, (masked == kth) & seen
    room = k - jnp.sum(above, -1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, -1) <= room))


def sparse_attention(u, w, pre: str, hp: dict, quant, kept=None):
    """One sequence, ``u`` [T, h] → (mixer output before ``W_o`` folded in:
    [T, h], Σ_t KL_t of this layer's indexer, the kept pairs bool [T, T]);
    with ``kept`` those pairs are taken as given (a backward pass on its
    own does not select again)."""
    t = u.shape[0]
    hq, hkv, d = (hp["num_attention_heads"], hp["num_key_value_heads"],
                  hp["head_dim"])
    hi, di = hp["indexer_num_heads"], hp["indexer_head_dim"]
    eps, theta = hp["rms_norm_eps"], hp["rope_theta"]

    def heads(name, n):
        return mm(u, w[pre + name], quant).reshape(t, n, d).transpose(1, 0, 2)
    q, k, v = heads("w_q", hq), heads("w_k", hkv), heads("w_v", hkv)
    q = rotary(rmsnorm(q, w[pre + "q_norm"], eps), theta)
    k = rotary(rmsnorm(k, w[pre + "k_norm"], eps), theta)
    k, v = jnp.repeat(k, hq // hkv, 0), jnp.repeat(v, hq // hkv, 0)

    ui = jax.lax.stop_gradient(u)
    q_i = rotary((ui @ w[pre + "w_iq"]).reshape(t, hi, di).transpose(
        1, 0, 2), theta)                                    # [Hi, T, Di]
    k_i = rotary(rmsnorm(ui @ w[pre + "w_ik"], w[pre + "ik_norm"],
                         eps)[None], theta)[0]              # [T, Di]
    w_i = ui @ w[pre + "w_iw"]                              # [T, Hi]

    nb = -(-t // Q_BLOCK)
    pad = nb * Q_BLOCK - t
    blocks = lambda x, axis: jnp.moveaxis(jnp.pad(  # noqa: E731
        x, [(0, pad) if a == axis else (0, 0) for a in range(x.ndim)]
    ).reshape(x.shape[:axis] + (nb, Q_BLOCK) + x.shape[axis + 1:]), axis, 0)
    s_pos = jnp.arange(t)[None, :]

    def scores_of(qb_i, wb_i):
        s = jnp.einsum("hqd,kd->hqk", qb_i, k_i)
        return (hi ** -0.5 * di ** -0.5) * jnp.sum(
            wb_i.T[:, :, None] * jax.nn.relu(s), axis=0)

    def pick(xs):
        qb_i, wb_i, start = xs
        t_pos = start + jnp.arange(Q_BLOCK)[:, None]
        return select(scores_of(qb_i, wb_i), t_pos, s_pos, hp)

    starts = jnp.arange(nb) * Q_BLOCK
    keep = blocks(kept, 0) if kept is not None else jax.lax.map(
        pick, (blocks(q_i, 1), blocks(w_i, 0), starts))
    keep = checkpoint_name(jax.lax.stop_gradient(keep), "selection")

    @jax.checkpoint
    def one(xs):
        qb, qb_i, wb_i, kept, start = xs
        s = mm(qb, k, quant, (((2,), (2,)), ((0,), (0,)))) * d ** -0.5
        p = jax.nn.softmax(jnp.where(kept[None], s, MASKED), axis=-1)
        p = jnp.where(kept[None], p, 0.0)
        out = mm(p, v, quant, (((2,), (1,)), ((0,), (0,))))
        # the indexer's loss on the same block
        log_q = jax.nn.log_softmax(
            jnp.where(kept, scores_of(qb_i, wb_i), MASKED), axis=-1)
        p_t = jax.lax.stop_gradient(jnp.sum(p, 0)) / hq
        kl = jnp.sum(jnp.where(
            kept, jax.scipy.special.xlogy(p_t, p_t) - p_t * log_q, 0.0), -1)
        real = start + jnp.arange(Q_BLOCK) < t
        return out, jnp.sum(jnp.where(real, kl, 0.0))

    out, kl = jax.lax.map(one, (blocks(q, 1), blocks(q_i, 1),
                                blocks(w_i, 0), keep, starts))
    out = out.transpose(1, 0, 2, 3).reshape(hq, nb * Q_BLOCK, d)[:, :t]
    return (out.transpose(1, 0, 2).reshape(t, hq * d), jnp.sum(kl),
            keep.reshape(nb * Q_BLOCK, t)[:t])


def layer(x, w, i: int, hp: dict, quant, kept=None):
    """One block on one sequence, ``x`` [T, h] → (x, the share of the
    token-slots routed to experts held here, Σ_t KL_t, the kept pairs)."""
    pre = f"layer_{i:02d}/"
    u = rmsnorm(x, w[pre + "norm_1"], hp["rms_norm_eps"])
    a, kl, keep = sparse_attention(u, w, pre, hp, quant, kept)
    x = x + mm(a, w[pre + "w_o"], quant)
    v2 = rmsnorm(x, w[pre + "norm_2"], hp["rms_norm_eps"])
    gate = route(v2, w[pre + "w_router"], hp["num_experts_per_tok"])
    lo = hp["expert_offset"]
    share = jnp.sum(gate[:, lo:lo + hp["experts_held"]] > 0) / (
        gate.shape[0] * hp["num_experts_per_tok"])
    return x + expert_layer(v2, gate, w, pre, hp, quant), share, kl, keep


def hidden(w, tokens, hp: dict, quant, normed: bool = True):
    """Hidden states of one sequence ``tokens`` [T] → ([T, h] after the
    final norm — before it without ``normed`` —, the held share by layer,
    Σ_layers mean_t KL_t)."""
    x = w["embed"][tokens]
    shares, kls = [], []
    for i in range(hp["num_hidden_layers"]):
        x, share, kl, _ = jax.checkpoint(
            lambda x, w, i=i: layer(x, w, i, hp, quant), policy=KEEP)(x, w)
        shares.append(share)
        kls.append(kl / tokens.shape[0])
    if normed:
        x = rmsnorm(x, w["final_norm"], hp["rms_norm_eps"])
    return x, jnp.stack(shares), sum(kls)


def q_values(w, tokens, hp: dict, quant=None):
    """Q at every position of one sequence: [T, V] (small sizes only)."""
    return mm(hidden(w, tokens, hp, quant)[0], w["head"], quant)


def selection(w, tokens, hp: dict, quant=None):
    """What one sequence's forward pass selects and what its indexers
    lose: ``(kept pairs bool [layers, T, T], Σ_layers L_I)``; a layer at
    a time (``programs``)."""
    forward = programs(hp, quant)[0]
    x, keeps, kl = w["embed"][tokens], [], 0.0
    for i in range(hp["num_hidden_layers"]):
        x, _, kl_i, keep = forward(x, layer_leaves(w, i))
        keeps.append(keep)
        kl = kl + kl_i / tokens.shape[0]
    return jnp.stack(keeps), kl


# ---- loss and optimizer ------------------------------------------------

def td_loss(x_on, x_tg, top: dict, top_tg: dict, seq, hp: dict, quant):
    """The last layer's outputs of θ and θ⁻ on ONE window → (``scale`` ·
    masked mean Huber, (priority η max|TD| + (1-η) mean|TD|, Σ_a,t Q over
    the T steps)); ``top`` / ``top_tg``: ``final_norm`` and ``head``."""
    tok = seq["tokens"]
    h_on = rmsnorm(x_on, top["final_norm"], hp["rms_norm_eps"])
    h_tg = rmsnorm(x_tg, top_tg["final_norm"], hp["rms_norm_eps"])
    actions = jnp.concatenate([tok[1:], jnp.zeros((1,), tok.dtype)])
    q_sa, q_boot, q_row = q_select(
        h_on, jax.lax.stop_gradient(h_tg), top["head"], top_tg["head"],
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
    return loss, (prio, jnp.sum(jax.lax.stop_gradient(q_row[:-1])))


def sequence_loss(theta, target, seq, hp: dict, quant):
    """ONE window's term of the step's loss as one function of θ: ``seq``
    holds tokens [T+1], reward / discount / mask [T], ``scale`` = its IS
    weight over the batch size and ``share`` = 1 over the batch size.
    Returns (scale · masked mean Huber + share · Σ_layers L_I, (the TD
    term alone, the priority, Σ_a,t Q over the T steps, the held share by
    layer, share · Σ_layers L_I))."""
    tok = seq["tokens"]
    x_on, share, kl = hidden(theta, tok, hp, quant, normed=False)
    x_tg = hidden(target, tok, hp, quant, normed=False)[0]
    loss, (prio, q_sum) = td_loss(x_on, x_tg, theta, target, seq, hp, quant)
    index_loss = seq["share"] * kl
    return loss + index_loss, (loss, prio, q_sum, share, index_loss)


LAYER_LEAVES = ("norm_1", "norm_2", "w_q", "w_k", "w_v", "w_o", "q_norm",
                "k_norm", *INDEXER_LEAVES, "w_router", "w_gate", "w_up",
                "w_down")
_PROGRAMS: dict = {}


def layer_leaves(w, i: int, to: int = 0) -> dict:
    """Layer ``i``'s leaves of ``w`` under layer ``to``'s names."""
    return {f"layer_{to:02d}/{k}": w[f"layer_{i:02d}/{k}"]
            for k in LAYER_LEAVES}


def programs(hp: dict, quant=None):
    """The compiled pieces a window goes through a layer at a time, one of
    each for every layer of θ and θ⁻ (they take a layer's leaves under
    layer 0's names): ``forward(x, leaves) -> layer(...)``;
    ``backward(x, leaves, kept, ct_x, ct_kl)`` -> the cotangents of ``x``
    and of the leaves, from the layer computed again under the kept
    selection; ``top(x_on, x_tg, top, top_tg, seq)`` -> ``td_loss`` with
    its gradients by ``x_on`` and ``top``; ``embed(tokens, ct, like)`` ->
    the embedding's gradient."""
    key = (repr(sorted(hp.items())), quant)
    if key in _PROGRAMS:
        return _PROGRAMS[key]

    def forward(x, leaves):
        with jax.default_matmul_precision("highest"):
            return layer(x, leaves, 0, hp, quant)

    def backward(x, leaves, kept, ct_x, ct_kl):
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(lambda x, leaves: layer(
                x, leaves, 0, hp, quant, kept)[::2], x, leaves)
            return vjp((ct_x, ct_kl))

    def top(x_on, x_tg, top, top_tg, seq):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(td_loss, (0, 2), has_aux=True)(
                x_on, x_tg, top, top_tg, seq, hp, quant)

    def embed(tokens, ct, like):
        return jnp.zeros_like(like).at[tokens].add(ct)

    _PROGRAMS[key] = tuple(jax.jit(f) for f in (
        forward, backward, top, embed))
    return _PROGRAMS[key]


def grad_one(theta, target, seq, hp: dict, quant=None):
    """``jax.value_and_grad(sequence_loss, has_aux=True)`` of one window,
    a layer at a time: θ's forward pass keeping each layer's input and
    selection, θ⁻'s, the TD loss with its gradients at the top, then the
    layers backwards — each computed again under its kept selection, its
    ``Σ_t KL_t`` entering with the cotangent ``share / (T+1)``."""
    forward, backward, top, embed = programs(hp, quant)
    tok, n = seq["tokens"], hp["num_hidden_layers"]
    xs, keeps, shares, kl = [theta["embed"][tok]], [], [], 0.0
    for i in range(n):
        x, share, kl_i, keep = forward(xs[-1], layer_leaves(theta, i))
        xs.append(x)
        keeps.append(keep)
        shares.append(share)
        kl = kl + kl_i / tok.shape[0]
    x_tg = target["embed"][tok]
    for i in range(n):
        x_tg = forward(x_tg, layer_leaves(target, i))[0]
    (loss, (prio, q_sum)), (ct, g) = top(xs.pop(), x_tg, theta, target, seq)
    g = {k: g[k] for k in ("final_norm", "head")}
    ct_kl = seq["share"] / tok.shape[0]
    for i in reversed(range(n)):
        ct, g_i = backward(xs.pop(), layer_leaves(theta, i), keeps.pop(),
                           ct, ct_kl)
        g.update(layer_leaves(g_i, 0, to=i))
    g["embed"] = embed(tok, ct, theta["embed"])
    index_loss = seq["share"] * kl
    return (loss + index_loss, (loss, prio, q_sum, jnp.stack(shares),
                                index_loss)), g


def make_step(hp: dict, quant=None):
    """One train step: the loss and its gradients A WINDOW AT A TIME (no
    batching; the windows' gradients are added up; ``grad_one``: each
    window a layer at a time), clip by global norm,
    Adam, the target copy every ``target_update_period`` steps. Returns
    ``step(state, batch) -> (state, metrics, priority [B])``; ``batch``:
    tokens [B, T+1], reward / discount / mask [B, T], weight [B]; metrics
    carry the TD loss (``loss``), the indexers' (``index_loss``) and
    per-leaf gradient norms (``grad_leaf_norm``, by name)."""
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

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0, 1))
    apply = jax.jit(apply, donate_argnums=0)

    def step(state, batch):
        b, t1 = batch["tokens"].shape
        acc, prios = None, []
        loss = q_sum = shares = index_loss = 0.0
        for s in range(b):
            seq = {k: batch[k][s] for k in
                   ("tokens", "reward", "discount", "mask")}
            seq["scale"] = batch["weight"][s] / b
            seq["share"] = jnp.asarray(1.0 / b, jnp.float32)
            (_, (l, prio, qs, share, il)), g = grad_one(
                state["theta"], state["target"], seq, hp, quant)
            acc = g if acc is None else add(acc, g)
            loss, q_sum, shares = loss + l, q_sum + qs, shares + share / b
            index_loss = index_loss + il
            prios.append(prio)
        state, leaf, gnorm = apply(state, acc)
        metrics = {"loss": loss, "grad_norm": gnorm, "grad_leaf_norm": leaf,
                   "q_mean": q_sum / (b * (t1 - 1) * hp["vocab_size"]),
                   "held_share": shares, "index_loss": index_loss}
        return state, metrics, jnp.stack(prios)

    return step
