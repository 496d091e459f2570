"""Generation by diffusion over blocks on the token-window Q-network
(``net.tokenq.block_length`` > 0; SDAR-30B-A3B-Chat's mechanism,
``model_type`` sdar_moe) at toy sizes on the CPU: h 64, the cell's own four
layers with 4 heads over 2 key/value heads of 16 and q/k norms, T 24 in 6
blocks of 4 (49 packed rows: a clean copy and a partly masked one under the
three-part block mask, the kernel in interpret mode), 8 SwiGLU experts top
2 behind a softmax router, vocabulary 64 with ``[MASK]`` its last row — the
program against ``benchmark/reference/sdar.py`` (plain jax.numpy float32,
imports nothing of the program), the mask against the four rules
brute-forced, the acting path against the training path, the span returns
against a Python loop, no leak inside a block, the family's refusals and
its counts.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families.sdar import check, counts, faults  # noqa: E402
from benchmark.reference import sdar as ref  # noqa: E402
from distributed_deep_q_tpu.config import (  # noqa: E402
    PRESETS, apply_overrides)
from distributed_deep_q_tpu.models import tokenq  # noqa: E402
from distributed_deep_q_tpu.ops import attention  # noqa: E402
from distributed_deep_q_tpu.ops.losses import span_returns  # noqa: E402
from distributed_deep_q_tpu.parallel.sequence_learner import (  # noqa: E402
    SequenceSolver)

T, B, SEED = 24, 4, 7
G = T // B
MASK = 63
F32 = jnp.float32
CONF = os.path.join(ROOT, "benchmark", "configs",
                    "sdar_30b_tokenq_ep16.json")


def toy_cfg(*more):
    """The preset itself at the family's toy sizes (what ``rehearse.py``
    walks), all 8 experts held unless ``more`` says otherwise."""
    cfg = apply_overrides(PRESETS["sdar_tokenq"](), [
        *check.TOY_OVERRIDES, "net.tokenq.experts_held=8",
        "net.tokenq.expert_offset=0", "replay.batch_size=2",
        f"train.seed={SEED}", *more])
    cfg.mesh.backend = "cpu"
    return cfg


def toy_hp(cfg, **over):
    tq = cfg.net.tokenq
    hp = {
        "hidden_size": tq.hidden_size,
        "num_hidden_layers": tq.num_hidden_layers,
        "num_attention_heads": tq.num_attention_heads,
        "num_key_value_heads": tq.num_key_value_heads,
        "head_dim": tq.head_dim, "rms_norm_eps": tq.rms_norm_eps,
        "rope_theta": tq.rope_theta, "qk_norm": tq.qk_norm,
        "block_length": tq.block_length, "mask_token_id": tokenq.mask_token(cfg.net),
        "moe_intermediate_size": tq.moe_ffn_hidden_size,
        "router_experts": tq.moe_num_primary_experts,
        "experts_held": tq.experts_held, "expert_offset": tq.expert_offset,
        "num_experts_per_tok": tq.moe_num_active_primary_experts,
        "norm_topk_prob": True, "vocab_size": cfg.net.num_actions,
        "sequence_length": cfg.replay.sequence_length,
        "batch_size": cfg.replay.batch_size,
        "fused_chain": cfg.replay.fused_chain, "gamma": cfg.train.gamma,
        "huber_delta": cfg.train.huber_delta,
        "double_dqn": cfg.train.double_dqn,
        "value_rescale": cfg.train.value_rescale,
        "priority_eta": cfg.train.priority_eta, "lr": cfg.train.lr,
        "adam_eps": cfg.train.adam_eps,
        "grad_clip_norm": cfg.train.grad_clip_norm,
        "target_update_period": cfg.train.target_update_period,
    }
    hp.update(over)
    return hp


def seeded_batch(hp, b, seed=0):
    tok, rew, done, valid = ref.seeded_windows(seed, 0, hp)
    rng = np.random.default_rng([seed, 5])
    return {"tokens": tok[:b], "reward": rew[:b],
            "discount": np.where(done[:b], 0.0, hp["gamma"]).astype(
                np.float32),
            "mask": valid[:b].astype(np.float32),
            "reveal": rng.integers(0, hp["block_length"],
                                   (b, ref.blocks(hp))).astype(np.int32),
            "weight": np.linspace(0.5, 1.0, b).astype(np.float32)}


def as_jnp(w):
    return {k: jnp.asarray(v) for k, v in w.items()}


def brute_force_mask(t, bl, copies):
    """The four rules, pair by pair, over ``attention.bd_rows``."""
    copy, _, blk = attention.bd_rows(t, bl, copies)
    n = len(copy)
    m = np.zeros((n, n), bool)
    for i in range(n):
        for j in range(n):
            if copy[i] == 0 and copy[j] == 0:
                m[i, j] = blk[j] <= blk[i]
            elif copy[i] == 1 and copy[j] == 0:
                m[i, j] = blk[j] < blk[i]
            elif copy[i] == 1 and copy[j] == 1:
                m[i, j] = blk[j] == blk[i]
    return m


# ---- (1) the mask and the kernel ------------------------------------------

@pytest.mark.parametrize("t,bl,copies", [
    (24, 4, 2), (26, 4, 2), (25, 4, 2), (24, 4, 1), (27, 4, 1), (9, 3, 2),
    (130, 4, 2)], ids=lambda v: str(v))
def test_the_kernels_mask_is_the_four_rules(t, bl, copies):
    """The mask object the kernel is built from, sliced dense, against the
    rules pair by pair — at lengths that do and do not fill a block of
    ``bl`` and of the kernel's 128; a padded row is a key to no real row
    and, as a query, never has an empty softmax."""
    copy, pos, blk = attention.bd_rows(t, bl, copies)
    n = len(copy)
    assert n == t + 1 + (copies - 1) * -(-t // bl) * bl
    assert blk[0] == -1 and blk[1] == 0 and blk[bl] == 0 and blk[bl + 1] == 1
    if copies == 2:         # the two copies share positions
        np.testing.assert_array_equal(pos[t + 1:t + 1 + t], pos[1:t + 1])
    n_pad = -(-n // 128) * 128
    dense = attention.bd_mask(n_pad, t, bl, copies)[
        (slice(0, n_pad), slice(0, n_pad))]
    want = brute_force_mask(t, bl, copies)
    np.testing.assert_array_equal(dense[:n, :n], want)
    # block by block, as the kernel's tables ask: the blocks answered from
    # the rows' key ranges (all empty, all allowed) are the dense mask's
    mask = attention.bd_mask(n_pad, t, bl, copies)
    for q0 in range(0, n_pad, 8):
        np.testing.assert_array_equal(
            np.concatenate([mask[slice(q0, q0 + 8), slice(k0, k0 + 4)]
                            for k0 in range(0, n_pad, 4)], axis=1),
            dense[q0:q0 + 8])
    assert not dense[:n, n:].any()
    assert dense[n:].any(axis=1).all() and want.any(axis=1).all()
    np.testing.assert_array_equal(
        want.sum(1), attention.bd_allowed_per_row(t, bl, copies))
    np.testing.assert_array_equal(want, np.asarray(ref.allowed(
        *(jnp.asarray(ref.rows({"sequence_length": t, "block_length": bl},
                               copies)[k])[:, None]
          for k in ("copy", "blk", "pos")),
        *(jnp.asarray(ref.rows({"sequence_length": t, "block_length": bl},
                               copies)[k])[None]
          for k in ("copy", "blk", "pos")), {})))


def _dense_attention(q, k, v, mask):
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, 1), jnp.repeat(v, g, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("copies,fused", [(2, False), (2, True), (1, False)],
                         ids=["packed-two-kernels", "packed-fused", "acting"])
def test_block_attention_matches_a_dense_masked_softmax(copies, fused):
    t = 26                  # a last block of 2: not filled
    n = len(attention.bd_rows(t, B, copies)[0])
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 4, n, 16))
    k = jax.random.normal(ks[1], (2, 2, n, 16))
    v = jax.random.normal(ks[2], (2, 2, n, 16))
    mask = brute_force_mask(t, B, copies)

    def loss(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2))(q, k, v)
    got, g_got = loss(lambda *a: attention.block_diffusion_attention(
        *a, t=t, block_length=B, block=128, fused_bwd=fused,
        interpret=True))
    want, g_want = loss(lambda *a: _dense_attention(*a, mask))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_empty_blocks_of_the_mask_are_never_run():
    """Where the packed rows span several kernel blocks the block table
    leaves out what the mask empties: the clean copy's rows see no noised
    key, a noised block no other noised block."""
    t = 2048                # 4097 packed rows: 33 blocks of 128, as the
    n = len(attention.bd_rows(t, B)[0])     # cell's 32 769 in 1 024s
    share = attention.bd_blocks_run_share(n, t, B, 2, block=128,
                                          interpret=True)
    pairs = attention.bd_allowed_per_row(t, B).sum() / n ** 2
    assert 0.24 < pairs < 0.27
    assert pairs < share < 0.36, share


# ---- (2) the step against the reference -----------------------------------

@pytest.fixture(scope="module")
def stepped():
    """ONE train step of the toy on two seeded windows, by the program
    (its ``_token_step_core``) and by the reference, from the same seeded
    weights and the same ``reveal``: every test of the step reads this."""
    cfg = toy_cfg()
    solver = SequenceSolver(cfg)
    hp = toy_hp(cfg)
    seeded = ref.init_weights(SEED, hp)
    solver.set_named_weights(seeded, target=True)
    batch = seeded_batch(hp, 2)
    core = jax.jit(shard_map(
        solver.learner._token_step_core, mesh=solver.mesh,
        in_specs=(P(), P("dp")), out_specs=(P(), P(), P("dp")),
        check_vma=False))
    state, metrics, priority = core(solver.state, batch)
    gold, gm, gprio = ref.make_step(hp)(
        ref.init_state(as_jnp(seeded), as_jnp(seeded)), batch)
    return dict(cfg=cfg, hp=hp, solver=solver, seeded=seeded, batch=batch,
                state=state, metrics=metrics, priority=priority, gold=gold,
                gm=gm, gprio=gprio)


def test_leaves_and_round_trip(stepped):
    named = stepped["solver"].get_named_weights()
    assert {k: v.shape for k, v in named.items()} == ref.leaf_shapes(
        stepped["hp"])
    assert named["embed"].shape == (64, 64)     # [MASK]'s row among them
    assert named["layer_00/q_norm"].shape == (16,)


def test_packed_forward_matches_the_reference(stepped):
    """``backbone`` over the packed rows — both copies — against the
    reference's hidden states, and the packed ids against ``pack``."""
    cfg, hp, solver = stepped["cfg"], stepped["hp"], stepped["solver"]
    batch = stepped["batch"]
    packed = np.asarray(tokenq.bd_pack(
        jnp.asarray(batch["tokens"]), jnp.asarray(batch["reveal"]),
        cfg.net))
    hid, c = jax.jit(lambda p, t, r: tokenq.backbone(
        p, t, cfg.net, interpret=True, reveal=r))(
        solver.state.params, batch["tokens"], batch["reveal"])
    assert hid.shape == (2, 2 * T + 1, 64) and c["slots"].shape == (4,)
    for s in range(2):
        gold_ids = ref.pack(batch["tokens"][s], batch["reveal"][s], hp)
        np.testing.assert_array_equal(packed[s], gold_ids)
        with jax.default_matmul_precision("highest"):
            gold, _ = ref.hidden(as_jnp(stepped["seeded"]),
                                 jnp.asarray(gold_ids), ref.rows(hp), hp,
                                 None)
        np.testing.assert_allclose(np.asarray(hid[s]), np.asarray(gold),
                                   atol=3e-5)


def test_one_step_loss_priorities_and_counters(stepped):
    m, gm = stepped["metrics"], stepped["gm"]
    assert abs(float(m["loss"]) - float(gm["loss"])) < 1e-5
    assert abs(float(m["q_mean"]) - float(gm["q_mean"])) < 1e-6
    np.testing.assert_allclose(np.asarray(stepped["priority"]),
                               np.asarray(stepped["gprio"]), rtol=1e-5)
    held = float(m["moe_slots_held"]) / float(m["moe_slots"])
    assert abs(held - float(jnp.mean(gm["held_share"]))) < 1e-6
    assert held == 1.0 and int(m["moe_overflow"]) == 0
    assert int(m["moe_slots"]) == 4 * 2 * (2 * T + 1) * 2   # packed rows
    # the block mask's counters
    assert float(m["bd_decisions_valid"]) == float(gm["decisions_valid"])
    reveal = stepped["batch"]["reveal"]
    assert abs(float(m["bd_reveal_mean"]) - reveal.mean()) < 1e-6
    step = np.arange(G) * B + reveal
    assert abs(float(m["bd_span_mean"])
               - np.diff(step, axis=1).mean()) < 1e-6
    # Q(d, a) decision by decision, and what shapes alone fix of the mask
    assert m["bd_q_sa"].shape == (2, G - 1)
    np.testing.assert_allclose(np.asarray(m["bd_q_sa"]),
                               np.asarray(gm["q_sa"]), atol=2e-5)
    from benchmark.families.sdar import program
    shares = program.mask_shares(stepped["solver"])
    n = 2 * T + 1
    assert abs(shares["bd_pairs_allowed_share"]
               - 100.0 * brute_force_mask(T, B, 2).sum() / n ** 2) < 1e-9
    assert shares["bd_blocks_run_share"] == 100.0    # one kernel block


def test_one_step_gradients_adam_and_target(stepped):
    """Gradients by leaf (norms, and element for element through Adam's
    first moment), θ after one Adam step and θ⁻."""
    state, gold, gm = stepped["state"], stepped["gold"], stepped["gm"]
    names = list(tokenq.named_leaves(state.params))
    np.testing.assert_allclose(
        np.asarray(stepped["metrics"]["grad_leaf_norm"]),
        [float(gm["grad_leaf_norm"][k]) for k in names], rtol=2e-4,
        atol=1e-7)
    from benchmark.check import _adam_mu
    mu = tokenq.named_leaves(_adam_mu(state.opt_state))
    theta = tokenq.named_leaves(state.params)
    target = tokenq.named_leaves(state.target_params)
    for k in names:     # m1 = (1 - b1) clip g: the gradient, by element
        scale = float(np.abs(np.asarray(gold["m"][k])).max()) + 1e-12
        np.testing.assert_allclose(np.asarray(mu[k]) / scale,
                                   np.asarray(gold["m"][k]) / scale,
                                   atol=2e-4, err_msg=k)
        np.testing.assert_allclose(np.asarray(target[k]),
                                   np.asarray(gold["target"][k]), atol=0)
    # the [MASK] row of the embedding learns (it is fed); its head column
    # gets no gradient from a decision's action
    assert float(np.abs(np.asarray(gold["m"]["embed"])[MASK]).max()) > 0
    for k in ("head", "embed", "layer_00/w_q", "layer_00/q_norm",
              "layer_03/w_k", "layer_02/w_o", "layer_00/w_down",
              "layer_02/w_gate", "layer_01/w_router"):
        big = np.abs(np.asarray(gold["m"][k])) > 1e-7
        assert big.any(), k
        np.testing.assert_allclose(np.asarray(theta[k])[big],
                                   np.asarray(gold["theta"][k])[big],
                                   atol=2e-6, err_msg=k)


def test_the_reference_a_layer_at_a_time_is_its_whole_program(stepped):
    hp = stepped["hp"]
    w = as_jnp(stepped["seeded"])
    tg = as_jnp(ref.init_weights(SEED + 1, hp))
    batch = stepped["batch"]
    seq = ref.loss_inputs({**{k: batch[k][1] for k in (
        "tokens", "reward", "discount", "mask", "reveal")}, "scale": 0.4},
        hp)
    with jax.default_matmul_precision("highest"):
        (loss, (prio, q_sum, share, q_sa)), g = jax.jit(jax.value_and_grad(
            lambda w, tg, seq: ref.sequence_loss(w, tg, seq, hp, None),
            has_aux=True))(w, tg, seq)
    (loss1, (prio1, q_sum1, share1, q_sa1)), g1 = ref.grad_one(w, tg, seq,
                                                               hp)
    np.testing.assert_allclose(loss1, loss, rtol=1e-6)
    np.testing.assert_allclose(prio1, prio, rtol=1e-6)
    np.testing.assert_allclose(q_sum1, q_sum, rtol=1e-5)
    np.testing.assert_array_equal(share1, share)
    np.testing.assert_allclose(q_sa1, q_sa, atol=1e-6)
    assert set(g1) == set(g)
    for k in g:
        scale = float(jnp.abs(g[k]).max()) + 1e-12
        np.testing.assert_allclose(g1[k] / scale, g[k] / scale, atol=2e-5,
                                   err_msg=k)


def test_the_control_reads_a_lower_precision_under_its_loss_scale(stepped):
    """The fp8 control at a loss as small a mean as the cell's: under
    ``loss_scale`` the loss handed back is the unscaled one and the
    gradient is finite and a few percent off the float32 one."""
    hp = stepped["hp"]
    w = as_jnp(stepped["seeded"])
    batch = stepped["batch"]
    seq = ref.loss_inputs({**{k: batch[k][0] for k in (
        "tokens", "reward", "discount", "mask", "reveal")},
        "scale": G / 4096}, hp)

    def off(g, gold):
        return np.sqrt(sum(float(jnp.sum((g[k] - gold[k]) ** 2))
                           for k in gold) / sum(
            float(jnp.sum(gold[k] ** 2)) for k in gold))
    (loss, _), gold = ref.grad_one(w, w, seq, hp)
    (loss8, _), g8 = ref.grad_one(w, w, seq, hp, "fp8")
    assert abs(float(loss8) / float(loss) - 1) < 0.05
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in g8.values())
    assert 0.01 < off(g8, gold) < 0.3, off(g8, gold)
    assert ref.loss_scale({"sequence_length": 16_384,
                           "block_length": 4}) == 2.0 ** 18


# ---- (3) the acting path is the training path ------------------------------

@pytest.mark.parametrize("j", range(B))
def test_acting_agrees_with_the_training_path(stepped, j):
    """``token_q_values(tok[0..p])`` is the packed forward's decision row
    when ``reveal`` puts block b's decision at p — for every j, at a block
    in the window's middle —, the reference's acting state says the same,
    and nothing past the block's end can move it."""
    cfg, hp, solver = stepped["cfg"], stepped["hp"], stepped["solver"]
    tok = ref.seeded_windows(1, 0, hp)[0][0]
    b = 3
    p = b * B + j
    reveal = np.full((1, G), 2, np.int32)
    reveal[0, b] = j
    hid, _ = jax.jit(lambda prm, t, r: tokenq.backbone(
        prm, t, cfg.net, interpret=True, reveal=r))(
        solver.state.params, tok[None], reveal)
    rows, step = tokenq.bd_decision_rows(jnp.asarray(reveal), T, B)
    assert int(step[0, b]) == p and int(rows[0, b]) == T + 1 + p
    trained = np.asarray(hid[0, int(rows[0, b])]
                         @ solver.state.params["head"])
    acted = solver.token_q_values(tok[:p + 1])
    np.testing.assert_allclose(acted, trained, atol=3e-5)
    with jax.default_matmul_precision("highest"):
        gold = ref.q_acting(as_jnp(stepped["seeded"]), tok[:p + 1], hp)
    np.testing.assert_allclose(acted, np.asarray(gold), atol=3e-5)
    # what follows the block's end is invisible: other tokens there, same Q
    window = np.full((1, T + 1), MASK, np.int32)
    window[0, :p + 1] = tok[:p + 1]
    other = window.copy()
    other[0, (b + 1) * B + 1:] = 5
    q0, q1 = (np.asarray(solver._fwd(solver.state.params, w, np.int32(p)))
              for w in (window, other))
    np.testing.assert_array_equal(q0, q1)
    np.testing.assert_allclose(q0[0], acted, atol=0)


def test_acting_pads_with_the_mask_token_and_never_picks_it(stepped):
    solver = stepped["solver"]
    tok = ref.seeded_windows(2, 0, stepped["hp"])[0][0]
    # q_at masks everything after the prefix itself: junk there is unseen
    junk = np.zeros((1, T + 1), np.int32)
    junk[0, :6] = tok[:6]
    np.testing.assert_array_equal(
        np.asarray(solver._fwd(solver.state.params, junk, np.int32(5)))[0],
        solver.token_q_values(tok[:6]))
    # an episode longer than the window: the tail starts a whole number
    # of blocks in and leaves a masked position
    long = list(range(40))
    tail = solver.acting_prefix(long)
    assert len(tail) <= T and (40 - len(tail)) % B == 0
    assert list(solver.acting_prefix(long[:10])) == long[:10]
    with pytest.raises(ValueError, match="no masked position"):
        solver.token_q_values(np.zeros(T + 1, np.int32))
    rng = np.random.default_rng(0)
    acts = {solver.token_act(tok[:6], eps, rng)
            for eps in (0.0, 1.0) for _ in range(200)}
    assert MASK not in acts and max(acts) == MASK - 1


# ---- (4) the span returns ---------------------------------------------------

def test_span_returns_against_a_python_loop():
    """An episode end inside a span, a window cut short, and spans of 1
    and of 7 steps: the program's ``span_returns`` against the reference's
    loop."""
    rng = np.random.default_rng(3)
    reward = rng.standard_normal((2, T)).astype(np.float32)
    done = np.zeros((2, T), bool)
    done[0, 6] = True                       # inside the span from step 4
    discount = np.where(done, 0.0, 0.99).astype(np.float32)
    mask = np.ones((2, T), np.float32)
    mask[1, 17:] = 0.0                      # a cut window
    # reveal 3, 0 -> a span of 1; 0, 3 -> a span of 7
    reveal = np.array([[3, 0, 0, 3, 1, 2], [0, 3, 3, 0, 0, 3]], np.int32)
    step = np.arange(G) * B + reveal
    span = np.diff(step, axis=1)
    assert span.min() == 1 and span.max() == 7
    ret, gamma, valid = (np.asarray(x) for x in span_returns(
        jnp.asarray(reward), jnp.asarray(discount), jnp.asarray(mask),
        jnp.asarray(step[:, :-1]), jnp.asarray(span), 2 * B - 1))
    for s in range(2):
        for g in range(G - 1):
            r, gm, v = ref.span_returns(reward[s], discount[s], mask[s],
                                        int(step[s, g]), int(span[s, g]))
            assert valid[s, g] == v, (s, g)
            np.testing.assert_allclose(ret[s, g], r, atol=1e-6)
            np.testing.assert_allclose(gamma[s, g], gm, atol=1e-6)
    assert gamma[0, 1] == 0.0               # the bootstrap is cut off
    assert not valid[1, 4] and valid[1, 3] and valid[0].all()
    # a span that leaves the window carries no loss
    _, _, v = span_returns(jnp.asarray(reward), jnp.asarray(discount),
                           jnp.asarray(mask), jnp.asarray([[22], [22]]),
                           jnp.asarray([[3], [2]]), 7)
    assert np.asarray(v).tolist() == [[0.0], [0.0]]
    dec = ref.decisions({"tokens": np.arange(T + 1), "reward": reward[0],
                         "discount": discount[0], "mask": mask[0],
                         "reveal": reveal[0]},
                        {"sequence_length": T, "block_length": B})
    np.testing.assert_array_equal(dec["dec_rows"], T + 1 + step[0])
    np.testing.assert_array_equal(dec["actions"], step[0] + 1)
    np.testing.assert_allclose(dec["ret"], ret[0], atol=1e-6)


# ---- (5) no leak inside a block --------------------------------------------

def test_a_noised_row_sees_no_masked_token_of_its_own_block(stepped):
    """Changing the clean tokens of a block at its MASKED offsets moves no
    noised row of that block (the answer does not leak); changing an
    earlier block's does."""
    cfg, solver = stepped["cfg"], stepped["solver"]
    tok = ref.seeded_windows(4, 0, stepped["hp"])[0][0]
    b, j = 3, 1
    reveal = np.full((1, G), j, np.int32)
    fwd = jax.jit(lambda t: tokenq.backbone(
        solver.state.params, t, cfg.net, interpret=True, reveal=reveal)[0])
    own = slice(T + 1 + b * B, T + 1 + (b + 1) * B)     # noised block b
    base = np.asarray(fwd(tok[None]))[0, own]
    leak = tok.copy()
    leak[b * B + 1 + j:(b + 1) * B + 1] = (leak[b * B + 1 + j:(b + 1) * B + 1]
                                           + 7) % MASK
    np.testing.assert_array_equal(np.asarray(fwd(leak[None]))[0, own], base)
    shown = tok.copy()          # a revealed token of the block IS seen
    shown[b * B + 1] = (shown[b * B + 1] + 7) % MASK
    assert np.abs(np.asarray(fwd(shown[None]))[0, own] - base).max() > 1e-4
    earlier = tok.copy()
    earlier[(b - 1) * B + 2] = (earlier[(b - 1) * B + 2] + 7) % MASK
    assert np.abs(np.asarray(fwd(earlier[None]))[0, own] - base).max() > 1e-4


def test_a_leak_inside_a_block_shows_decision_by_decision(stepped):
    """``q_sa_first_early_max_rel``'s two sides: the step's own ``bd_q_sa``
    against the reference's ``q_sa`` (equal on the sound side:
    ``test_one_step_loss_priorities_and_counters``). With the leak planted
    (a noised row also sees its own block's clean rows, the action's among
    them) single decisions move by tenths of the RMS."""
    hp, seeded = stepped["hp"], stepped["seeded"]
    _, gm, _ = ref.make_step({**hp, "fault": "own_block_leak"})(
        ref.init_state(as_jnp(seeded), as_jnp(seeded)), stepped["batch"])
    gold = np.asarray(gm["q_sa"], np.float64)
    gap = np.abs(np.asarray(stepped["metrics"]["bd_q_sa"]) - gold).max()
    assert gap / np.sqrt(np.mean(gold ** 2)) > 0.1


@pytest.mark.parametrize("name", list(faults.FAULTS))
def test_each_planted_fault_moves_the_step(stepped, name):
    """The reference with ONE thing wrong no longer agrees with the
    program's step."""
    wrong = faults.FAULTS[name][0](stepped["hp"])
    hp = {**stepped["hp"], **wrong}
    seeded = stepped["seeded"]
    _, gm, _ = ref.make_step(hp)(
        ref.init_state(as_jnp(seeded), as_jnp(seeded)), stepped["batch"])
    m = stepped["metrics"]
    rel = abs(float(gm["loss"]) / float(m["loss"]) - 1)
    grad = abs(float(gm["grad_norm"]) / float(m["grad_norm"]) - 1)
    assert max(rel, grad) > 1e-3, (name, rel, grad)


# ---- (6) shares, counts, refusals ------------------------------------------

def test_shares_of_one_expert_layer_add_up_to_the_uncut_layer(stepped):
    """THE share test on the packed rows under the block mask: the partial
    results of the 4 shares of 2 experts (the cell's: 16 shares of 8),
    with the residual counted once, are the uncut reference's layer."""
    cfg = toy_cfg("net.tokenq.experts_held=2")
    hp = stepped["hp"]                  # the uncut layer: all 8 held
    w = as_jnp(stepped["seeded"])
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 2 * T + 1, 64))
    pre = "layer_01/"
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.layer(x[0], w, pre, ref.rows(hp), hp, None)
    lp = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
    zero = {**lp, "w_down": jnp.zeros_like(lp["w_down"])[:2],
            "w_gate": lp["w_gate"][:2], "w_up": lp["w_up"][:2]}
    run = lambda p, net: tokenq.layer(  # noqa: E731
        x, p, net, False, True, True, bd_steps=T)
    residual, _ = run(zero, cfg.net)
    total = residual
    for e in range(0, 8, 2):
        net = dataclasses.replace(cfg.net, tokenq=dataclasses.replace(
            cfg.net.tokenq, expert_offset=e))
        share = {**lp, **{n: lp[n][e:e + 2]
                          for n in ("w_gate", "w_up", "w_down")}}
        out, c = run(share, net)
        total = total + (out - residual)
        assert int(c["overflow"]) == 0
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(whole),
                               atol=3e-5)


def test_counts_against_a_hand_count():
    """The cell's own hparams, by hand: 32 769 packed rows, the allowed
    pairs in closed form, 4 096 decision rows through the head."""
    import json
    with open(CONF) as fh:
        hp = json.load(fh)["hparams"]
    t, bl = 16_384, 4
    assert counts.packed_rows(hp) == 2 * t + 1 == 32_769
    assert counts.blocks(hp) == 4_096
    # clean p in block g (1-based) sees 4g + 1 keys, position 0 one;
    # noised p in block g sees 4(g - 1) + 1 + 4
    clean = 1 + sum(bl * (bl * g + 1) for g in range(1, t // bl + 1))
    noised = sum(bl * (bl * (g - 1) + 1 + bl) for g in range(1, t // bl + 1))
    assert counts.allowed_pairs(hp) == clean + noised
    assert abs(counts.allowed_share(hp) - 25.0) < 0.05
    assert counts.bd_core_flops(hp) == 4 * 1 * 4 * 4 * 32 * 128 * (
        clean + noised)
    assert counts.head_flops(hp) == 4 * 4_096 * 2 * 2048 * 18_992
    assert counts.expected_held_slots(hp) == 32_769 * 8 * 8 / 128
    assert counts.expert_ffn_flops(hp) == 4 * 4 * 6 * 2048 * 768 * (
        32_769 * 8 * 8 / 128)
    assert counts.attention_projection_flops(hp) == 4 * 32_769 * 4 * (
        2 * 2048 * (2 * 32 + 2 * 4) * 128)
    shares = counts.train_flop_shares(hp)
    assert abs(sum(shares.values()) - 1) < 1e-12 and shares["bd_core"] > 0.4
    # the counts' pairs are the mask's
    assert counts.allowed_pairs({"sequence_length": 26, "block_length": 4}) \
        == brute_force_mask(26, 4, 2).sum()


def test_the_configuration_file_is_the_preset():
    import json
    with open(CONF) as fh:
        conf = json.load(fh)
    cfg = PRESETS["sdar_tokenq"]()
    check.assert_hparams(conf, cfg)                 # raises on drift
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size", "env"]
    for key, width in (("hidden_size", 2048), ("num_attention_heads", 32),
                       ("num_key_value_heads", 4), ("head_dim", 128),
                       ("moe_intermediate_size", 768),
                       ("num_experts_per_tok", 8), ("rope_theta", 1e6)):
        assert conf[key] == width, key
    assert conf["hparams"]["router_experts"] == 128
    assert cfg.env.token_vocab == conf["hparams"]["mask_token_id"] == 18_991
    bad = json.loads(json.dumps(conf))
    bad["hparams"]["block_length"] = 8
    with pytest.raises(SystemExit, match="block_length"):
        check.assert_hparams(bad, cfg)
    assert set(ref.EXACT_LIMITS) >= {
        "reveal_mismatch", "decision_row_mismatch", "noised_id_mismatch",
        "span_return_max_abs", "span_discount_max_abs"}
    assert "q_sa_first_early_max_rel" in conf["limits"]
    assert not set(conf["limits"]) & set(ref.EXACT_LIMITS)


@pytest.mark.parametrize("override,match", [
    ("net.tokenq.sliding_window_layout=0,1,0,0", "block_length"),
    ("net.tokenq.layer_types=conv,full_attention,full_attention,"
     "full_attention", "block_length"),
    ("net.tokenq.gating=true", "block_length|gating"),
    ("net.tokenq.num_attention_heads_per_layer=4,4,4,4", "block_length"),
])
def test_what_blocks_are_held_to_no_reference_with_is_refused(override,
                                                               match):
    cfg = toy_cfg(override)
    with pytest.raises(ValueError, match=match):
        tokenq.param_shapes(cfg.net)


@pytest.mark.parametrize("blocks,rows,mask", [(0, 64, -1), (4, 65, 64)])
def test_the_mask_token_is_the_row_after_the_envs_tokens(blocks, rows, mask):
    """One knob: with blocks the net holds one row more than the env has
    tokens, the last, and that row is the mask token; without, none."""
    from distributed_deep_q_tpu.actors.game import make_env
    from distributed_deep_q_tpu.train import token_rows
    cfg = apply_overrides(PRESETS["tokenq"](),
                          [f"net.tokenq.block_length={blocks}"])
    env = make_env(cfg.env, seed=0)
    assert env.num_actions == 64
    cfg.net.num_actions = token_rows(cfg, env)
    assert (cfg.net.num_actions, tokenq.mask_token(cfg.net)) == (rows, mask)


def test_acting_never_takes_the_mask_token(stepped):
    """ε-greedy over the env's tokens alone: neither the random draw nor
    the argmax returns the mask token's row, even where its Q is largest."""
    solver = stepped["solver"]
    named = solver.get_named_weights()
    rng = np.random.default_rng(0)
    prefix = np.arange(5, dtype=np.int32)
    drawn = {solver.token_act(prefix, 1.0, rng) for _ in range(600)}
    assert drawn == set(range(MASK))
    best = int(np.argmax(solver.token_q_values(prefix)[:MASK]))
    head = named["head"].copy()
    head[:, MASK] = 3.0 * head[:, best]     # Q(mask) = 3 Q(best) > 0
    try:
        solver.set_named_weights({**named, "head": head})
        assert int(np.argmax(solver.token_q_values(prefix))) == MASK
        assert solver.token_act(prefix, 0.0, rng) != MASK
    finally:
        solver.set_named_weights(named)


def test_rotary_at_the_row_index_is_the_default():
    """Position ids default to the row index: the siblings' call."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 10, 16))
    for interleave in (False, True):
        np.testing.assert_array_equal(
            tokenq.rotary(x, 1e4, interleave),
            tokenq.rotary(x, 1e4, interleave, positions=np.arange(10)))
    # two rows at one position turn alike
    twice = jnp.concatenate([x, x], axis=2)
    pos = np.concatenate([np.arange(10), np.arange(10)])
    y = tokenq.rotary(twice, 1e6, positions=pos)
    np.testing.assert_array_equal(y[:, :, :10], y[:, :, 10:])
    np.testing.assert_allclose(
        y[0], ref.rotary_at(twice[0], 1e6, pos), atol=1e-6)


def test_the_toy_preset_trains_and_acts_from_the_command_line(tmp_path):
    """The normal path end to end: ``train.train_tokenq`` with the block
    mechanism switched on by data on the toy preset."""
    from distributed_deep_q_tpu.train import train_tokenq
    cfg = apply_overrides(PRESETS["tokenq"](), [
        "mesh.num_fake_devices=1", "train.total_steps=200",
        "net.tokenq.block_length=4",
        "net.tokenq.sliding_window_layout=0,0,0,0",
        "net.tokenq.rope_layout=1,1,1,1", "net.tokenq.num_hidden_layers=2",
        "replay.learn_start=96", "train.train_every=48",
        "replay.batch_size=2", "replay.fused_chain=2",
        "env.max_episode_steps=30", "train.eval_episodes=1"])
    cfg.mesh.backend = "cpu"
    out = train_tokenq(cfg, log_every=1)
    assert out["grad_steps"] >= 2 and np.isfinite(out["loss"])
    assert out["bd_reveal_mean"] >= 0 and out["bd_span_mean"] > 0
    assert out["solver"].config.net.num_actions == 65
