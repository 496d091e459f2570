"""Plain reference of SDAR-30B-A3B-Chat's block (JetLM, ``model_type``
``sdar_moe``; arXiv:2510.06303, trained in the vectorised layout of Block
Diffusion, arXiv:2503.09573) as a token-window Q-network (family
``sdar``): the packed window, the three-part block mask, one decision a
block, block-to-block n-step returns, the Double-DQN loss, gradients,
clip, one Adam + target step, the PER weights and the priority write-back
— ``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``,
no kernel, no cache, no batching trick. It imports nothing of the program
and nothing of a family's ``check.py``. What ``reference/tokenq.py``,
``reference/lfm2.py`` and ``reference/moonlight.py`` offer unchanged is
imported (the seeded windows' generator, the PER arithmetic, the float8
product of the control, RMSNorm, the softmax router, the SwiGLU experts,
the loss's pieces, Adam and the target copy).

**The layer**, for a packed sequence of N rows, row i at position
``pos(i)``, input x ``[N, h]`` (pre-norm residual, RMSNorm eps
``rms_norm_eps`` with a learned gain, no biases): ``u = rmsnorm_1(x)``;
``q = u W_q`` [``num_attention_heads`` x ``head_dim``], ``k = u W_k``,
``v = u W_v`` [``num_key_value_heads`` x ``head_dim``]; an RMSNorm over
each head of q and of k (gains ``[head_dim]``); rotate-half rotary over
all of ``head_dim`` at ``pos(i)``, theta ``rope_theta``; ``o_i = Σ_s
softmax_s(q_i · k_s · head_dim^-½ over allowed s) v_s``; ``x' = x + o
W_o``; ``w = rmsnorm_2(x')``; ``p = softmax(w W_r)`` over all
``router_experts``, the ``num_experts_per_tok`` largest kept and
renormalised; ``y = x' + Σ over the chosen experts HELD here of p_e
(silu(w W_gate,e) * (w W_up,e)) W_down,e``. After the last layer the final
RMSNorm, then ``Q = hidden W_head`` over the ``vocab_size`` rows held
(untied).

**The packed window** (``rows``, ``pack``). A window is ``tok[0..T]``.
Position 0 is a block of its own (-1); position p >= 1 lies in block
``(p - 1) // B``, offset ``(p - 1) % B``, B = ``block_length``. ``reveal``
[G] gives each block's number of tokens already revealed (0..B-1, left to
right). Rows: the clean copy ``c_p = tok[p]``, p = 0..T, then the noised
copy ``n_p``, p = 1..G·B (G = ceil(T / B) whole blocks): ``tok[p]`` where
its offset is under its block's ``reveal`` and p <= T, else ``[MASK]``
(``mask_token_id``). Both copies carry position p. Allowed pairs, query →
key: ``c_p → c_s`` iff ``blk(s) <= blk(p)``; ``c_p → n_s`` never; ``n_p →
c_s`` iff ``blk(s) < blk(p)``; ``n_p → n_s`` iff ``blk(s) == blk(p)``
(``allowed``, evaluated on (copy, block) ids a block of queries at a
time).

**The loss** (``decisions``, ``span_returns``, ``td_loss``). Block b's
decision row is ``n_{1 + bB + reveal[b]}``, its first masked row: the
state is the prefix ``tok[0..p_b]``, ``p_b = bB + reveal[b]``, the action
``tok[p_b + 1]``. ``q_sa_b = Q_θ(d_b)[tok[p_b + 1]]``; ``a* = argmax
Q_θ(d_{b+1})`` over every column but ``[MASK]``'s; ``q_boot = Q_θ⁻(d_{b+1})
[a*]``; ``n_b = p_{b+1} - p_b``; ``R_b = Σ_{k<n_b} (Π_{i<k} discount[p_b +
i]) reward[p_b + k]``, ``Γ_b = Π_{i<n_b} discount[p_b + i]`` (a Python
loop); ``target_b = h(R_b + Γ_b h⁻¹(q_boot))``; a decision is valid iff
``mask`` is 1 on every step of its span (and the span ends inside the
window); the last block only bootstraps. Huber, the mean over valid
decisions, the IS weight a window, priority ``η max + (1 - η) mean`` of
|TD| over valid decisions. Mean Q is over decisions and the
``vocab_size - 1`` actions.

A step also hands back ``q_sa`` decision by decision (``q_sa_max_abs``
in the comparison): four keys more or fewer in a row's mask move no mean
over a window's thousands of decisions at the cell's length, and do move
the Q of the first blocks' decisions, whose rows see a handful of keys.

``reveal_draw`` is the reference's own draw of ``reveal`` from the key the
sample program was given (``jax.random``, the same stream: ``fold_in(key,
1)``, uniform on 0..B-1).

Memory and time only, no arithmetic changed: attention a block of queries
at a time against all keys (the clean queries against the clean keys
alone: the noised ones are masked for them every one), the head a block
of decisions at a time, each
expert in turn over all rows; ``make_step`` runs a window a LAYER at a
time (``programs``: one compiled forward and one compiled backward serve
every layer of θ and θ⁻, the chain rule between layers written out);
``sequence_loss`` is the whole model as one function, and at toy size the
tests hold the two to each other.

``hp["fault"]`` names a PLANTED FAULT for ``families/sdar/faults.py``:
``"causal"`` (plain causal attention over the packed rows' positions: the
program without the mechanism), ``"own_block_leak"`` (a noised row also
sees the clean rows of its OWN block: the answer leaks),
``"positions_continue"`` (the noised copy's positions continue after the
clean copy's instead of sharing them), ``"one_step_targets"`` (reward and
discount of ONE step, bootstrapped at the packed row after the decision's)
and ``"gamma_one_step"`` (``Γ_b`` = the first step's discount).

``quant="fp8"`` is the CONTROL: every matrix product the configuration
states in bfloat16 (``W_q``, ``W_k``, ``W_v``, ``W_o``, attention's two
products, the experts' three, the head) takes its operands through
float8_e4m3 and its cotangents through float8_e5m2. Router, norms, rotary,
softmax, loss and Adam stay float32 on both sides. Its backward pass runs
under a LOSS SCALE (``loss_scale``, a power of two, exact in float32) as
``reference/laguna.py``'s does: a window's loss is a mean over thousands
of decisions, and unscaled most cotangents lie under e5m2's smallest step.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import tokenq as _tokenq
from benchmark.reference.lfm2 import expert_layer  # noqa: F401
from benchmark.reference.moonlight import adam_and_target  # noqa: F401
from benchmark.reference.tokenq import (  # noqa: F401 — the family's surface
    ADAM_B1, ADAM_B2, GEN_BLOCK, INIT_STD, betas_for, huber, init_state,
    is_weights, mm, rmsnorm, route, value_rescale, value_rescale_inv,
    written_priority)

# exact comparisons: the feed against the seeded ring (the ``tokenq``
# family's), and what this family's feed adds
EXACT_LIMITS = {
    **_tokenq.EXACT_LIMITS,
    "reveal_mismatch": 0,           # the program's draw against this one's
    "decision_row_mismatch": 0,     # packed index of each block's decision
    "noised_id_mismatch": 0,        # token ids of the packed rows
    "span_return_max_abs": 1e-6,    # R_b
    "span_discount_max_abs": 1e-6,  # Γ_b
    "span_valid_mismatch": 0,       # which decisions carry a loss
    "decisions_valid_mismatch": 0,  # the step's own count of them
    "priority_slots_miswritten": 0,
}

Q_BLOCK = 128               # queries per attention block
DECISION_BLOCK = 512        # decisions per head block
MASKED = -1e30
FAULTS = ("causal", "own_block_leak", "positions_continue",
          "one_step_targets", "gamma_one_step")
LAYER_LEAVES = ("norm_1", "norm_2", "w_q", "w_k", "w_v", "w_o", "q_norm",
                "k_norm", "w_router", "w_gate", "w_up", "w_down")


def fault(hp: dict):
    f = hp.get("fault")
    if f is not None and f not in FAULTS:
        raise ValueError(f"unknown fault {f!r}")
    return f


def loss_scale(hp: dict) -> float:
    """The control's loss scale: 64 x the window's decisions, rounded up
    to a power of two (2^18 at 4 096), so that the head's largest
    cotangent (1 / decisions unscaled) stands at 64 at most."""
    return 2.0 ** (6 + math.ceil(math.log2(blocks(hp))))


# ---- seeded data: weights and windows ---------------------------------

def leaf_shapes(hp: dict) -> dict[str, tuple]:
    """The parameters by name (the program's per-path leaf names)."""
    h, d, v = hp["hidden_size"], hp["head_dim"], hp["vocab_size"]
    hq, hkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    e, f = hp["experts_held"], hp["moe_intermediate_size"]
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v)}
    for i in range(hp["num_hidden_layers"]):
        pre = f"layer_{i:02d}/"
        out.update({
            pre + "norm_1": (h,), pre + "norm_2": (h,),
            pre + "w_q": (h, hq * d), pre + "w_k": (h, hkv * d),
            pre + "w_v": (h, hkv * d), pre + "w_o": (hq * d, h),
            pre + "q_norm": (d,), pre + "k_norm": (d,),
            pre + "w_router": (h, hp["router_experts"]),
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


def _env_rows(hp: dict) -> dict:
    """``[MASK]`` is the last row held and no token of the env: the ring's
    token ids are drawn over the rows before it."""
    return {**hp, "vocab_size": hp["mask_token_id"]}


def seeded_windows(seed: int, block: int, hp: dict):
    """``reference/tokenq.seeded_windows`` with token ids uniform over
    rows 0 .. ``mask_token_id`` - 1."""
    return _tokenq.seeded_windows(seed, block, _env_rows(hp))


def windows_at(seed: int, slots: np.ndarray, hp: dict) -> dict:
    return _tokenq.windows_at(seed, slots, _env_rows(hp))


# ---- the packed window -------------------------------------------------

def blocks(hp: dict) -> int:
    return -(-hp["sequence_length"] // hp["block_length"])


def rows(hp: dict, copies: int = 2) -> dict:
    """The packed rows of one window → ``{"copy", "pos", "blk"}`` [N]
    int32 (numpy): the clean copy (0), positions 0..T, then (``copies`` =
    2) the noised copy (1), positions 1..G·B. ``copies`` = 1 is the acting
    path's layout: the clean copy alone, block-causal."""
    t, bl = hp["sequence_length"], hp["block_length"]
    noised = np.arange(1, blocks(hp) * bl + 1) if copies == 2 else \
        np.arange(0)
    pos = np.concatenate([np.arange(t + 1), noised])
    copy = np.concatenate([np.zeros(t + 1, int), np.ones(len(noised), int)])
    blk = np.where(pos == 0, -1, (pos - 1) // bl)
    out = {"copy": copy, "blk": blk, "pos": pos}
    if fault(hp) == "positions_continue":
        out["pos"] = np.where(copy == 1, t + pos, pos)
    return {k: v.astype(np.int32) for k, v in out.items()}


def allowed(copy_q, blk_q, pos_q, copy_k, blk_k, pos_k, hp: dict):
    """The four rules on (copy, block) ids, ``[Bq, 1]`` against
    ``[1, N]`` → bool ``[Bq, N]``."""
    f = fault(hp)
    if f == "causal":
        return pos_k <= pos_q
    clean_q, clean_k = copy_q == 0, copy_k == 0
    sees_clean = jnp.where(
        clean_q, blk_k <= blk_q,
        blk_k <= blk_q if f == "own_block_leak" else blk_k < blk_q)
    return (clean_k & sees_clean) | (~clean_q & ~clean_k & (blk_k == blk_q))


def pack(tokens, reveal, hp: dict):
    """One window's ``tokens`` [T+1] and ``reveal`` [G] → the packed token
    ids [T + 1 + G·B] (numpy): the clean copy, then the noised one."""
    t, bl = hp["sequence_length"], hp["block_length"]
    tokens, reveal = np.asarray(tokens), np.asarray(reveal)
    noised = np.full(blocks(hp) * bl, hp["mask_token_id"], tokens.dtype)
    for p in range(1, len(noised) + 1):
        if p <= t and (p - 1) % bl < reveal[(p - 1) // bl]:
            noised[p - 1] = tokens[p]
    return np.concatenate([tokens, noised])


def reveal_draw(key, batch: int, hp: dict) -> np.ndarray:
    """The reference's own draw of ``reveal`` [batch, G] from the raw key
    (uint32 [2]) the sample program was given for this step."""
    k = jax.random.fold_in(jnp.asarray(key, jnp.uint32), 1)
    return np.asarray(jax.random.randint(
        k, (batch, blocks(hp)), 0, hp["block_length"]))


def span_returns(reward, discount, mask, start: int, n: int):
    """``(R, Γ, valid)`` of the span ``[start, start + n)`` of one window,
    a Python loop in float32."""
    t = len(reward)
    ret, gamma, valid = np.float32(0.0), np.float32(1.0), start + n <= t
    for k in range(n):
        p = min(start + k, t - 1)
        ret = np.float32(ret + gamma * np.float32(reward[p]))
        gamma = np.float32(gamma * np.float32(discount[p]))
        valid = valid and mask[p] > 0
    return ret, gamma, bool(valid)


def decisions(seq: dict, hp: dict) -> dict:
    """What one window's loss reads, from its tokens, rewards, flags and
    ``reveal`` (numpy): the G decision rows (packed index), their actions,
    and for the first G - 1 the span's ``ret`` / ``gamma`` / ``valid`` and
    the row the target bootstraps at."""
    t, bl = hp["sequence_length"], hp["block_length"]
    tok, reveal = np.asarray(seq["tokens"]), np.asarray(seq["reveal"])
    step = np.arange(blocks(hp)) * bl + reveal                  # p_b
    dec = t + 1 + step
    f = fault(hp)
    spans = [span_returns(seq["reward"], seq["discount"], seq["mask"],
                          int(step[b]),
                          1 if f == "one_step_targets"
                          else int(step[b + 1] - step[b]))
             for b in range(len(step) - 1)]
    ret, gamma, valid = (np.asarray(x) for x in zip(*spans))
    boot = dec[1:]
    if f == "one_step_targets":
        boot = dec[:-1] + 1
    if f == "gamma_one_step":
        gamma = np.asarray(seq["discount"])[np.minimum(step[:-1], t - 1)]
    return {"dec_rows": dec.astype(np.int32),
            "boot_rows": boot.astype(np.int32),
            "actions": tok[np.minimum(step + 1, t)].astype(np.int32),
            "ret": ret.astype(np.float32),
            "gamma": gamma.astype(np.float32),
            "valid": valid.astype(np.float32)}


# ---- the forward pass --------------------------------------------------

def rotary_at(x, theta, pos):
    """Rotate-half rotary embedding, ``x`` [heads, N, D], row i at
    position ``pos[i]``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.asarray(pos, jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def attention(q, k, v, layout: dict, hp: dict, quant):
    """Attention of one packed sequence under the block mask: ``q`` [Hq,
    N, D], ``k`` / ``v`` [Hkv, N, D] → [Hq, N, D], the four rules
    evaluated on (copy, block) ids a block of queries at a time. Memory
    and time only: the clean queries run against the clean keys alone
    (rule 2 as a slice: a clean row sees no noised key, so those columns
    would be masked every one), the noised queries against all keys."""
    n_clean = int(np.sum(np.asarray(layout["copy"]) == 0))
    if fault(hp) == "causal" or n_clean == q.shape[1]:
        return _attend(q, k, v, layout, layout, hp, quant)

    def part(rows):
        return {key: x[rows] for key, x in layout.items()}
    clean, noised = slice(0, n_clean), slice(n_clean, None)
    return jnp.concatenate([
        _attend(q[:, clean], k[:, clean], v[:, clean], part(clean),
                part(clean), hp, quant),
        _attend(q[:, noised], k, v, part(noised), layout, hp, quant)], 1)


def _attend(q, k, v, rows_q: dict, rows_k: dict, hp: dict, quant,
            q_block: int = Q_BLOCK):
    """Queries ``q`` [Hq, Nq, D] (their ids ``rows_q``) against keys ``k``
    / ``v`` [Hkv, Nk, D] (``rows_k``): a block of queries at a time
    against ALL of these keys, masked by ``allowed``."""
    hq, n, d = q.shape
    group = hq // k.shape[0]
    k, v = jnp.repeat(k, group, 0), jnp.repeat(v, group, 0)
    nb = -(-n // q_block)
    pad = nb * q_block - n
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    qp = qp.reshape(hq, nb, q_block, d).transpose(1, 0, 2, 3)
    ids = {key: jnp.asarray(rows_k[key]) for key in ("copy", "blk", "pos")}
    # a padded query takes the last row's ids: never an empty softmax
    ids_q = {key: jnp.pad(jnp.asarray(rows_q[key]), (0, pad),
                          mode="edge").reshape(nb, q_block)
             for key in ("copy", "blk", "pos")}

    @jax.checkpoint
    def one(xs):
        qb, cq, bq, pq = xs
        seen = allowed(cq[:, None], bq[:, None], pq[:, None],
                       ids["copy"][None], ids["blk"][None],
                       ids["pos"][None], hp)
        s = mm(qb, k, quant, (((2,), (2,)), ((0,), (0,)))) * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None], s, MASKED), axis=-1)
        p = jnp.where(seen[None], p, 0.0)
        return mm(p, v, quant, (((2,), (1,)), ((0,), (0,))))

    out = jax.lax.map(one, (qp, ids_q["copy"], ids_q["blk"], ids_q["pos"]))
    return out.transpose(1, 0, 2, 3).reshape(hq, nb * q_block, d)[:, :n]


def layer(x, w, pre: str, layout: dict, hp: dict, quant):
    """One block on one packed sequence, ``x`` [N, h] → (x, the share of
    the token-slots routed to experts held here)."""
    n = x.shape[0]
    hq, hkv, d = (hp["num_attention_heads"], hp["num_key_value_heads"],
                  hp["head_dim"])
    eps, theta = hp["rms_norm_eps"], hp["rope_theta"]
    u = rmsnorm(x, w[pre + "norm_1"], eps)

    def heads(name, count):
        return mm(u, w[pre + name], quant).reshape(n, count, d).transpose(
            1, 0, 2)
    q, k, v = heads("w_q", hq), heads("w_k", hkv), heads("w_v", hkv)
    q = rotary_at(rmsnorm(q, w[pre + "q_norm"], eps), theta, layout["pos"])
    k = rotary_at(rmsnorm(k, w[pre + "k_norm"], eps), theta, layout["pos"])
    a = attention(q, k, v, layout, hp, quant)
    x = x + mm(a.transpose(1, 0, 2).reshape(n, hq * d), w[pre + "w_o"],
               quant)
    v2 = rmsnorm(x, w[pre + "norm_2"], eps)
    gate = route(v2, w[pre + "w_router"], hp["num_experts_per_tok"])
    lo = hp["expert_offset"]
    share = jnp.sum(gate[:, lo:lo + hp["experts_held"]] > 0) / (
        gate.shape[0] * hp["num_experts_per_tok"])
    return x + expert_layer(v2, gate, w, pre, hp, quant), share


def hidden(w, packed, layout: dict, hp: dict, quant, normed: bool = True):
    """Hidden states of one packed sequence ``packed`` [N] token ids →
    ([N, h] after the final norm — before it without ``normed`` —, the
    held share by layer)."""
    x = w["embed"][packed]
    shares = []
    for i in range(hp["num_hidden_layers"]):
        x, share = jax.checkpoint(
            lambda x, w, i=i: layer(x, w, f"layer_{i:02d}/", layout, hp,
                                    quant))(x, w)
        shares.append(share)
    if normed:
        x = rmsnorm(x, w["final_norm"], hp["rms_norm_eps"])
    return x, jnp.stack(shares)


def q_values(w, packed, layout: dict, hp: dict, quant=None):
    """Q at every packed row: [N, V] (small sizes only)."""
    return mm(hidden(w, packed, layout, hp, quant)[0], w["head"], quant)


def q_acting(w, prefix, hp: dict, quant=None):
    """Q(prefix, ·) [V] on ONE copy under the block-causal mask: the
    prefix, then ``[MASK]`` to the window's end, read at the first masked
    position (small sizes only; ``[MASK]``'s column is no action)."""
    n = len(prefix)
    window = np.full(hp["sequence_length"] + 1, hp["mask_token_id"],
                     np.int32)
    window[:n] = prefix
    return q_values(w, jnp.asarray(window), rows(hp, 1), hp, quant)[n]


# ---- loss and optimizer ------------------------------------------------

def q_select(h_on, h_tg, head_on, head_tg, actions, hp: dict, quant):
    """A block of decisions at a time: Q_θ(d, a_d), Q_θ⁻(d, a*) with a*
    the argmax of Q_θ(d, ·) (Double-DQN) or of Q_θ⁻(d, ·) over every
    column but ``[MASK]``'s, and Σ_a Q_θ(d, a) over the same columns."""
    n = h_on.shape[0]
    blk = min(DECISION_BLOCK, n)
    nb = -(-n // blk)
    skip = hp["mask_token_id"]

    def blocked(x):
        x = jnp.pad(x, ((0, nb * blk - n),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((nb, blk) + x.shape[1:])

    @jax.checkpoint
    def one(ho, ht, a):
        q_on, q_tg = mm(ho, head_on, quant), mm(ht, head_tg, quant)
        pick = jax.lax.stop_gradient(q_on) if hp["double_dqn"] else q_tg
        a_star = jnp.argmax(pick.at[:, skip].set(-jnp.inf), -1)
        take = lambda q, i: jnp.take_along_axis(q, i[:, None], -1)[:, 0]  # noqa: E731
        return (take(q_on, a), take(q_tg, a_star),
                jnp.sum(q_on, -1) - q_on[:, skip])

    q_sa, q_boot, q_row = jax.lax.map(
        lambda xs: one(*xs), (blocked(h_on), blocked(h_tg),
                              blocked(actions)))
    return (q_sa.reshape(-1)[:n], q_boot.reshape(-1)[:n],
            q_row.reshape(-1)[:n])


def td_loss(x_on, x_tg, top: dict, top_tg: dict, seq: dict, hp: dict,
            quant):
    """The last layer's outputs of θ and θ⁻ over ONE window's packed rows
    → (``scale`` · mean Huber over valid decisions, (priority η max|TD| +
    (1-η) mean|TD|, Σ_a,d Q over the deciding blocks, ``q_sa`` [G - 1]
    decision by decision)); ``seq`` carries
    ``decisions``' arrays and ``scale``; ``top`` / ``top_tg``:
    ``final_norm`` and ``head``."""
    # the rows the loss reads: every decision's, and where a fault
    # bootstraps elsewhere that row too
    at = jnp.concatenate([seq["dec_rows"], seq["boot_rows"]])
    g = seq["dec_rows"].shape[0]
    h_on = rmsnorm(x_on[at], top["final_norm"], hp["rms_norm_eps"])
    h_tg = rmsnorm(x_tg[at], top_tg["final_norm"], hp["rms_norm_eps"])
    actions = jnp.concatenate(
        [seq["actions"], jnp.zeros((g - 1,), seq["actions"].dtype)])
    q_sa, q_boot, q_row = q_select(
        h_on, jax.lax.stop_gradient(h_tg), top["head"], top_tg["head"],
        actions, hp, quant)
    boot = jax.lax.stop_gradient(q_boot[g:])
    y = seq["ret"] + seq["gamma"] * (
        value_rescale_inv(boot) if hp["value_rescale"] else boot)
    y = value_rescale(y) if hp["value_rescale"] else y
    mask = seq["valid"]
    td = (q_sa[:g - 1] - y) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    a = jnp.abs(jax.lax.stop_gradient(td))
    prio = (hp["priority_eta"] * jnp.max(a)
            + (1.0 - hp["priority_eta"]) * jnp.sum(a) / denom)
    loss = seq["scale"] * jnp.sum(huber(td, hp["huber_delta"]) * mask) / denom
    return loss, (prio, jnp.sum(jax.lax.stop_gradient(q_row[:g - 1])),
                  jax.lax.stop_gradient(q_sa[:g - 1]))


def loss_inputs(seq: dict, hp: dict) -> dict:
    """One window of a batch (numpy: tokens, reward, discount, mask,
    reveal, and ``scale``) → what ``sequence_loss`` / ``grad_one`` take:
    the packed token ids, ``decisions``' arrays, ``scale``."""
    out = {"packed": pack(seq["tokens"], seq["reveal"], hp),
           **decisions(seq, hp), "scale": np.float32(seq["scale"])}
    return {k: jnp.asarray(v) for k, v in out.items()}


def sequence_loss(theta, target, seq: dict, hp: dict, quant):
    """ONE window's term of the step's loss as one function of θ (``seq``
    from ``loss_inputs``) → (loss, (priority, Σ Q, the held share by
    layer, ``q_sa`` [G - 1]))."""
    layout = rows(hp)
    x_on, share = hidden(theta, seq["packed"], layout, hp, quant,
                         normed=False)
    x_tg = hidden(target, seq["packed"], layout, hp, quant, normed=False)[0]
    loss, (prio, q_sum, q_sa) = td_loss(x_on, x_tg, theta, target, seq, hp,
                                        quant)
    return loss, (prio, q_sum, share, q_sa)


_PROGRAMS: dict = {}


def layer_leaves(w, i: int) -> dict:
    """Layer ``i``'s leaves of ``w`` under their bare names."""
    return {k: w[f"layer_{i:02d}/{k}"] for k in LAYER_LEAVES}


def programs(hp: dict, quant=None):
    """The compiled pieces a window goes through a layer at a time (they
    take a layer's leaves under their bare names; θ and θ⁻ share them):
    ``forward(x, leaves) -> layer(...)``; ``backward(x, leaves, ct)`` ->
    the cotangents of ``x`` and of the leaves from the layer computed
    again; ``top(x_on, x_tg, top, top_tg, seq)`` -> ``td_loss`` with its
    gradients by ``x_on`` and ``top``; ``embed(packed, ct, like)`` -> the
    embedding's gradient. Under ``quant`` the cotangents between them
    carry ``loss_scale``; the loss and every gradient are handed back
    without it."""
    key = (repr(sorted(hp.items())), quant)
    if key in _PROGRAMS:
        return _PROGRAMS[key]
    scale = loss_scale(hp) if quant else 1.0
    layout = rows(hp)

    def unscaled(g):
        return jax.tree.map(lambda v: v / scale, g)

    def forward(x, leaves):
        with jax.default_matmul_precision("highest"):
            return layer(x, leaves, "", layout, hp, quant)

    def backward(x, leaves, ct):
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(lambda x, leaves: layer(
                x, leaves, "", layout, hp, quant)[0], x, leaves)
            ct, g = vjp(ct)
            return ct, unscaled(g)

    def top(x_on, x_tg, top, top_tg, seq):
        seq = {**seq, "scale": seq["scale"] * scale}
        with jax.default_matmul_precision("highest"):
            (loss, aux), (ct, g) = jax.value_and_grad(
                td_loss, (0, 2), has_aux=True)(
                    x_on, x_tg, top, top_tg, seq, hp, quant)
        return (loss / scale, aux), (ct, unscaled(g))

    def embed(packed, ct, like):
        return jnp.zeros_like(like).at[packed].add(ct / scale)

    _PROGRAMS.clear()       # one configuration's at a time
    _PROGRAMS[key] = tuple(jax.jit(f) for f in (
        forward, backward, top, embed))
    return _PROGRAMS[key]


_ADD = jax.jit(jnp.add, donate_argnums=0)


def grad_one(theta, target, seq: dict, hp: dict, quant=None, acc=None):
    """``jax.value_and_grad(sequence_loss, has_aux=True)`` of one window,
    a layer at a time: θ's forward pass keeping each layer's input, θ⁻'s,
    the TD loss with its gradients at the top, then the layers backwards,
    each computed again. The gradient is ADDED to ``acc`` (by name; a new
    one where ``acc`` is None) a layer at a time."""
    forward, backward, top, embed = programs(hp, quant)
    packed, n = seq["packed"], hp["num_hidden_layers"]
    acc = {} if acc is None else acc

    def add(g: dict):
        for k, v in g.items():
            acc[k] = _ADD(acc[k], v) if k in acc else v

    xs, shares = [theta["embed"][packed]], []
    for i in range(n):
        x, share = forward(xs[-1], layer_leaves(theta, i))
        xs.append(x)
        shares.append(share)
    x_tg = target["embed"][packed]
    for i in range(n):
        x_tg = forward(x_tg, layer_leaves(target, i))[0]
    tops = ("final_norm", "head")
    (loss, (prio, q_sum, q_sa)), (ct, g) = top(
        xs.pop(), x_tg, {k: theta[k] for k in tops},
        {k: target[k] for k in tops},
        {k: v for k, v in seq.items() if k != "packed"})
    del x_tg
    add(g)
    for i in reversed(range(n)):
        ct, g = backward(xs.pop(), layer_leaves(theta, i), ct)
        add({f"layer_{i:02d}/{k}": v for k, v in g.items()})
    add({"embed": embed(packed, ct, theta["embed"])})
    return (loss, (prio, q_sum, jnp.stack(shares), q_sa)), acc


def make_step(hp: dict, quant=None):
    """One train step: the loss and its gradients A WINDOW AT A TIME (no
    batching; the windows' gradients are added up; ``grad_one``: each
    window a layer at a time), clip by global norm, Adam, the target copy.
    Returns ``step(state, batch) -> (state, metrics, priority [B])``;
    ``batch`` (numpy): tokens [B, T+1], reward / discount / mask [B, T],
    reveal [B, G], weight [B]; metrics carry per-leaf gradient norms
    (``grad_leaf_norm``, by name), the decisions that carried a loss and
    ``q_sa`` [B, G - 1] decision by decision."""
    apply = jax.jit(lambda state, g: adam_and_target(state, g, hp),
                    donate_argnums=0)

    def step(state, batch):
        b = len(batch["tokens"])
        acc, prios, q_sas = None, [], []
        loss = q_sum = shares = valid = 0.0
        for s in range(b):
            seq = {k: np.asarray(batch[k][s]) for k in
                   ("tokens", "reward", "discount", "mask", "reveal")}
            seq["scale"] = np.float32(batch["weight"][s]) / b
            seq = loss_inputs(seq, hp)
            (l, (prio, qs, share, q_sa)), acc = grad_one(
                state["theta"], state["target"], seq, hp, quant, acc)
            loss, q_sum, shares = loss + l, q_sum + qs, shares + share / b
            valid = valid + float(jnp.sum(seq["valid"]))
            prios.append(prio)
            q_sas.append(q_sa)
        state, leaf, gnorm = apply(state, acc)
        metrics = {"loss": loss, "grad_norm": gnorm, "grad_leaf_norm": leaf,
                   "q_mean": q_sum / (b * (blocks(hp) - 1)
                                      * (hp["vocab_size"] - 1)),
                   "held_share": shares, "decisions_valid": valid,
                   "q_sa": jnp.stack(q_sas)}
        return state, metrics, jnp.stack(prios)

    return step
