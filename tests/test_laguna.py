"""Laguna-XS.2's block on the token-window Q-network (``net.kind =
"tokenq"``, ``model_type`` laguna) at toy sizes on the CPU: h 64, the
cell's own five layers in the published pattern (full + dense, sliding x 3,
full) with 2 heads on the full layers and 4 on the sliding ones over 2
key/value heads of 16 — so the two kinds DIFFER in heads, rotary width (8
of 16 columns under YaRN at base 5e5 against all 16 at 1e4), and kernel
block (256 against 128) —, a sigmoid gate a head, window 8, a dense layer
of width 96, then four expert layers: 8 SwiGLU experts top 2 behind a
sigmoid router with gates x 2.5, a shared expert of 32 beside them,
vocabulary 64, T 24 — the program against ``benchmark/reference/laguna.py``
(plain jax.numpy float32, imports nothing of the program), the new
mechanisms one by one, the family's refusals and its counts.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import math
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

from benchmark.families.laguna import check, counts, faults  # noqa: E402
from benchmark.reference import laguna as ref  # noqa: E402
from distributed_deep_q_tpu.config import (  # noqa: E402
    PRESETS, RopeParameters, apply_overrides)
from distributed_deep_q_tpu.models import tokenq  # noqa: E402
from distributed_deep_q_tpu.parallel.sequence_learner import (  # noqa: E402
    SequenceSolver)

T, SEED = 24, 7
F32 = jnp.float32
CELL = "laguna_xs2_tokenq_ep16.seq_learner_only"
CONF = os.path.join(ROOT, "benchmark", "configs",
                    "laguna_xs2_tokenq_ep16.json")


def toy_cfg(*more):
    """The preset itself at the family's toy sizes (what ``rehearse.py``
    walks), all 8 experts held unless ``more`` says otherwise."""
    cfg = apply_overrides(PRESETS["laguna_tokenq"](), [
        *check.TOY_OVERRIDES, "net.tokenq.experts_held=8",
        "net.tokenq.expert_offset=0", "replay.batch_size=2",
        "replay.fused_chain=2", f"train.seed={SEED}", *more])
    cfg.mesh.backend = "cpu"
    return cfg


def toy_hp(cfg, **over):
    tq = cfg.net.tokenq
    n = tq.num_hidden_layers
    hp = {
        "hidden_size": tq.hidden_size, "num_hidden_layers": n,
        "layer_types": [check.KINDS[bool(w)]
                        for w in tq.sliding_window_layout[:n]],
        "num_attention_heads_per_layer": list(
            tq.num_attention_heads_per_layer[:n]),
        "num_key_value_heads": tq.num_key_value_heads,
        "head_dim": tq.head_dim, "sliding_window": tq.sliding_window_size,
        "rope_parameters": {k: check.rope_as_published(
            getattr(tq.rope_parameters, k)) for k in check.KINDS},
        "gating": tq.gating, "num_dense_layers": tq.num_dense_layers,
        "intermediate_size": tq.intermediate_size,
        "rms_norm_eps": tq.rms_norm_eps,
        "moe_intermediate_size": tq.moe_ffn_hidden_size,
        "shared_expert_intermediate_size":
            tq.n_shared_experts * tq.moe_ffn_hidden_size,
        "router_experts": tq.moe_num_primary_experts,
        "experts_held": tq.experts_held, "expert_offset": tq.expert_offset,
        "num_experts_per_tok": tq.moe_num_active_primary_experts,
        "norm_topk_prob": True,
        "routed_scaling_factor": tq.routed_scaling_factor,
        "vocab_size": cfg.net.num_actions,
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
    return {"tokens": tok[:b], "reward": rew[:b],
            "discount": np.where(done[:b], 0.0, hp["gamma"]).astype(
                np.float32),
            "mask": valid[:b].astype(np.float32),
            "weight": np.linspace(0.5, 1.0, b).astype(np.float32)}


def as_jnp(w):
    return {k: jnp.asarray(v) for k, v in w.items()}


@pytest.fixture(scope="module")
def stepped():
    """ONE train step of the toy on two seeded windows, by the program
    (its ``_token_step_core``) and by the reference, from the same seeded
    weights: every test of the step reads this."""
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
        ref.init_state(as_jnp(seeded), as_jnp(seeded)), as_jnp(batch))
    return dict(cfg=cfg, hp=hp, solver=solver, seeded=seeded, state=state,
                metrics=metrics, priority=priority, gold=gold, gm=gm,
                gprio=gprio)


@pytest.fixture(scope="module")
def gold_q(stepped):
    """One seeded window and the reference's Q at every position of it."""
    tok = ref.seeded_windows(1, 0, stepped["hp"])[0][0]
    with jax.default_matmul_precision("highest"):
        return tok, ref.q_values(as_jnp(stepped["seeded"]),
                                 jnp.asarray(tok), stepped["hp"])


def test_the_two_kinds_of_layer_differ_in_leaves_and_round_trip(stepped):
    solver, hp = stepped["solver"], stepped["hp"]
    named = solver.get_named_weights()
    assert {k: v.shape for k, v in named.items()} == ref.leaf_shapes(hp)
    # a full layer: 2 heads of 16; a sliding one: 4; the gate a head each
    assert named["layer_00/w_q"].shape == (64, 2 * 16)
    assert named["layer_00/w_g"].shape == (64, 2)
    assert named["layer_02/w_q"].shape == (64, 4 * 16)
    assert named["layer_02/w_o"].shape == (4 * 16, 64)
    assert named["layer_02/w_g"].shape == (64, 4)
    assert named["layer_04/w_o"].shape == (2 * 16, 64)
    assert named["layer_03/w_k"].shape == (64, 2 * 16)
    assert named["layer_01/shared_gate"].shape == (64, 32)
    assert not {"layer_00/w_router", "layer_00/shared_gate",
                "layer_01/expert_bias"} & set(named)


def test_q_at_every_position_matches_the_reference(stepped, gold_q):
    solver, cfg = stepped["solver"], stepped["cfg"]
    tok, gold = gold_q
    q = solver.token_q_values(tok)          # the acting path: Q at the end
    np.testing.assert_allclose(q, np.asarray(gold)[-1], atol=2e-5)
    # and every position at once, through the backbone the step runs
    hid, c = jax.jit(lambda p, t: tokenq.backbone(
        p, t, cfg.net, interpret=True))(solver.state.params, tok[None])
    assert c["slots"].shape == (4,) and c["attn_gate_mean"].shape == (5,)
    np.testing.assert_allclose(
        np.asarray(hid[0] @ solver.state.params["head"]), np.asarray(gold),
        atol=2e-5)


def test_one_step_loss_priorities_and_counters(stepped):
    m, gm = stepped["metrics"], stepped["gm"]
    assert abs(float(m["loss"]) - float(gm["loss"])) < 1e-5
    assert abs(float(m["q_mean"]) - float(gm["q_mean"])) < 1e-6
    np.testing.assert_allclose(np.asarray(stepped["priority"]),
                               np.asarray(stepped["gprio"]), rtol=1e-5)
    held = float(m["moe_slots_held"]) / float(m["moe_slots"])
    assert abs(held - float(jnp.mean(gm["held_share"]))) < 1e-6
    assert held == 1.0 and int(m["moe_overflow"]) == 0
    # the gate's counter: its mean over tokens, heads and the five layers
    assert abs(float(m["attn_gate_mean"]) - float(gm["attn_gate_mean"])) \
        < 1e-6
    assert 0.45 < float(m["attn_gate_mean"]) < 0.55


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
    for k in ("head", "layer_00/w_q", "layer_00/w_g", "layer_02/w_g",
              "layer_03/w_k", "layer_04/w_o", "layer_00/w_down",
              "layer_01/shared_gate", "layer_02/w_gate",
              "layer_01/w_router", "embed"):
        big = np.abs(np.asarray(gold["m"][k])) > 1e-7
        assert big.any(), k
        np.testing.assert_allclose(np.asarray(theta[k])[big],
                                   np.asarray(gold["theta"][k])[big],
                                   atol=2e-6, err_msg=k)


def test_the_reference_a_layer_at_a_time_is_its_whole_program(stepped):
    hp = stepped["hp"]
    w = as_jnp(stepped["seeded"])
    tg = as_jnp(ref.init_weights(SEED + 1, hp))
    batch = seeded_batch(hp, 2)
    seq = {k: jnp.asarray(batch[k][1]) for k in
           ("tokens", "reward", "discount", "mask")}
    seq["scale"] = jnp.asarray(0.4, F32)
    with jax.default_matmul_precision("highest"):
        (loss, (prio, q_sum, share)), g = jax.jit(jax.value_and_grad(
            lambda w, tg, seq: ref.sequence_loss(w, tg, seq, hp, None),
            has_aux=True))(w, tg, seq)
    (loss1, (prio1, q_sum1, share1, _)), g1 = ref.grad_one(w, tg, seq, hp)
    np.testing.assert_allclose(loss1, loss, rtol=1e-6)
    np.testing.assert_allclose(prio1, prio, rtol=1e-6)
    np.testing.assert_allclose(q_sum1, q_sum, rtol=1e-5)
    np.testing.assert_array_equal(share1, share)
    assert set(g1) == set(g)
    for k in g:
        scale = float(jnp.abs(g[k]).max()) + 1e-12
        np.testing.assert_allclose(g1[k] / scale, g[k] / scale, atol=2e-5,
                                   err_msg=k)


def test_the_control_reads_a_lower_precision_under_its_loss_scale(
        stepped, monkeypatch):
    """The fp8 control on a window whose loss is as small a mean as the
    cell's (its scale puts the head's cotangent at 1 / 16 384): under
    ``loss_scale`` the loss handed back is the unscaled one and the
    gradient is finite and a few percent off the float32 one — a reading
    of e5m2 cotangents; without the scale most of it flushes to zero."""
    hp = stepped["hp"]
    w = as_jnp(stepped["seeded"])
    batch = seeded_batch(hp, 1)
    seq = {k: jnp.asarray(batch[k][0]) for k in
           ("tokens", "reward", "discount", "mask")}
    seq["scale"] = jnp.asarray(T / 16_384, F32)

    def off(g, gold):       # the whole gradient's distance, relative
        return math.sqrt(sum(float(jnp.sum((g[k] - gold[k]) ** 2))
                             for k in gold) / sum(
            float(jnp.sum(gold[k] ** 2)) for k in gold))
    (loss, _), gold = ref.grad_one(w, w, seq, hp)
    (loss8, _), g8 = ref.grad_one(w, w, seq, hp, "fp8")
    assert abs(float(loss8) / float(loss) - 1) < 0.05
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in g8.values())
    assert 0.01 < off(g8, gold) < 0.3, off(g8, gold)
    assert ref.loss_scale({"sequence_length": 16_384}) == 2.0 ** 20
    monkeypatch.setattr(ref, "loss_scale", lambda hp: 1.0)
    ref._PROGRAMS.clear()
    flushed = ref.grad_one(w, w, seq, hp, "fp8")[1]
    ref._PROGRAMS.clear()
    assert off(flushed, gold) > 0.5, off(flushed, gold)


# ---- the rotary embedding a kind of layer ---------------------------------

PUBLISHED_FULL = RopeParameters(
    rope_type="yarn", rope_theta=500_000.0, partial_rotary_factor=0.5,
    factor=64.0, original_max_position_embeddings=4096, beta_fast=64.0,
    beta_slow=1.0, attention_factor=1.4158883083359672)


@pytest.mark.parametrize("table", [
    lambda: tokenq.rotary_table(PUBLISHED_FULL, 128),
    lambda: ref.rope_table(check.rope_as_published(PUBLISHED_FULL), 128)[:2]],
    ids=["program", "reference"])
def test_yarn_frequencies_against_a_hand_computed_table(table):
    """The published full-attention parameters at a head of 128: 64
    columns turn (32 pairs); ``cd(64)`` = 5.66 and ``cd(1)`` = 15.80, so
    the ramp runs from pair 5 to pair 16: pairs 0-5 keep their own
    frequency, pairs 16-31 are divided by 64, pair 10 or 11 lies between;
    the factor is ``attention_factor``."""
    inv, factor = table()
    assert inv.shape == (32,) and inv.dtype == np.float32
    assert factor == 1.4158883083359672
    e = [500_000.0 ** (-2.0 * i / 64) for i in range(32)]
    cd = lambda turns: 64 * math.log(4096 / (turns * 2 * math.pi)) / (  # noqa: E731
        2 * math.log(500_000.0))
    assert (math.floor(cd(64)), math.ceil(cd(1))) == (5, 16)
    np.testing.assert_allclose(inv[:6], e[:6], rtol=1e-6)       # ramp 0
    np.testing.assert_allclose(inv[16:], np.array(e[16:]) / 64, rtol=1e-6)
    for i in (6, 10, 15):                                       # between
        ramp = (i - 5) / 11
        np.testing.assert_allclose(
            inv[i], e[i] / 64 * ramp + e[i] * (1 - ramp), rtol=1e-6)
    assert inv[0] == 1.0 and abs(inv[5] / 0.12868737 - 1) < 1e-6
    assert abs(inv[16] * 64 / 500_000.0 ** -0.5 - 1) < 1e-6


@pytest.mark.parametrize("fn", [
    lambda x: tokenq.rotary_by_table(x, *tokenq.rotary_table(
        PUBLISHED_FULL, 16)),
    lambda x: ref.rotary(x[0], check.rope_as_published(PUBLISHED_FULL))[
        None]], ids=["program", "reference"])
def test_partial_rotary_turns_the_first_half_and_scales_only_that(fn):
    """At a head of 16: columns 0-7 turn among themselves (rotate-half:
    i with i + 4) and carry the factor; columns 8-15 pass bit for bit."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 3, 40, 16))
    y = fn(x)
    assert np.array_equal(np.asarray(y[..., 8:]), np.asarray(x[..., 8:]))
    np.testing.assert_allclose(          # position 0: no turn, the factor
        y[:, :, 0, :8], 1.4158883083359672 * x[:, :, 0, :8], rtol=1e-6)
    pair = lambda z, i: z[..., i] ** 2 + z[..., i + 4] ** 2  # noqa: E731
    for i in range(4):                   # a rotation of each pair, scaled
        np.testing.assert_allclose(
            pair(y, i), 1.4158883083359672 ** 2 * pair(x, i), rtol=1e-5)
    inv, _ = tokenq.rotary_table(PUBLISHED_FULL, 16)
    ang = 7 * inv[1]                     # pair 1 at position 7
    np.testing.assert_allclose(
        y[0, 0, 7, 1], 1.4158883083359672 * (
            x[0, 0, 7, 1] * np.cos(ang) - x[0, 0, 7, 5] * np.sin(ang)),
        rtol=1e-4, atol=1e-6)


def test_the_program_and_the_reference_turn_alike():
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 50, 16))
    for rp in (PUBLISHED_FULL, RopeParameters(
            rope_type="default", rope_theta=10_000.0)):
        np.testing.assert_allclose(
            tokenq.rotary_by_table(x, *tokenq.rotary_table(rp, 16))[0],
            ref.rotary(x[0], check.rope_as_published(rp)), atol=1e-6)
    # a kind that states nothing turns as every layer did: one theta over
    # all of the head
    np.testing.assert_allclose(
        tokenq.rotary(x, 1e4), tokenq.rotary_by_table(
            x, *tokenq.rotary_table(RopeParameters(
                rope_type="default", rope_theta=1e4), 16)), atol=1e-6)


# ---- the gate and the window, a mixer at a time ---------------------------

def _mixer_inputs(t=40):
    cfg = toy_cfg()
    hp = toy_hp(cfg)
    w = ref.init_weights(SEED, hp)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, t, 64))

    def leaves(i):
        pre = f"layer_{i:02d}/"
        return {k[len(pre):]: jnp.asarray(v) for k, v in w.items()
                if k.startswith(pre)}
    return cfg, hp, w, x, leaves


def test_a_gate_of_one_is_the_ungated_layer_bit_for_bit(monkeypatch):
    cfg, _, _, x, leaves = _mixer_inputs()
    kind = tokenq.layer_plan(cfg.net.tokenq)[2]     # a sliding layer
    kw = dict(heads=kind["heads"], rope_params=kind["rope_params"])
    gated, _, c = tokenq.mixer(x, leaves(2), cfg.net, True, True, True, **kw)
    assert 0.4 < float(c["attn_gate_mean"]) < 0.6
    plain_net = dataclasses.replace(cfg.net, tokenq=dataclasses.replace(
        cfg.net.tokenq, gating=False))
    plain, _, none = tokenq.mixer(x, leaves(2), plain_net, True, True, True,
                                  **kw)
    assert none is None
    assert float(jnp.max(jnp.abs(gated - plain))) > 1e-3
    monkeypatch.setattr(jax.nn, "sigmoid", lambda z: jnp.ones_like(z))
    open_, _, c = tokenq.mixer(x, leaves(2), cfg.net, True, True, True, **kw)
    assert float(c["attn_gate_mean"]) == 1.0
    assert np.array_equal(np.asarray(open_), np.asarray(plain))
    # and without ``gating`` there is no leaf for it
    assert "w_g" not in tokenq.param_shapes(plain_net)["layer_02"]
    assert tokenq.param_shapes(cfg.net)["layer_02"]["w_g"] == (64, 4)


def test_a_sliding_layer_differs_from_a_full_one_where_the_mask_says():
    """The same leaves and the same rotary parameters through the window
    of 8 at the sliding layers' own block (128) and through the full mask
    at the full layers' (256): the first 8 queries see the same keys, every
    later one does not; each against the reference's materialised mask."""
    cfg, hp, w, x, leaves = _mixer_inputs()
    tq = cfg.net.tokenq
    assert (tq.sliding_attn_block, tq.attn_block) == (128, 256)
    kind = tokenq.layer_plan(tq)[2]
    kw = dict(heads=kind["heads"], rope_params=kind["rope_params"])
    slid = tokenq.mixer(x, leaves(2), cfg.net, True, True, True, **kw)[0]
    full = tokenq.mixer(x, leaves(2), cfg.net, False, True, True, **kw)[0]
    gap = np.abs(np.asarray(slid - full))[0].max(-1)
    assert gap[:8].max() < 1e-6 and gap[8:].min() > 1e-6

    def gold(attn):
        u = ref.rmsnorm(x[0], leaves(2)["norm_1"], 1e-6)
        sliding = {**hp["rope_parameters"],
                   "full_attention": hp["rope_parameters"][ref.SLIDING]}
        with jax.default_matmul_precision("highest"):
            return x[0] + ref.gated_attention(
                u, leaves(2), "", (False, attn, 4),
                {**hp, "rope_parameters": sliding}, None)[0]
    np.testing.assert_allclose(slid[0], gold(ref.SLIDING), atol=2e-5)
    np.testing.assert_allclose(full[0], gold("full_attention"), atol=2e-5)


# ---- the expert layer's shares --------------------------------------------

def test_four_shares_of_an_expert_layer_add_up_to_the_uncut_one():
    """THE share test, at a router 16 wide, top 4: four shares of 4
    experts each (the cell: sixteen of 16 behind a router of 256). The
    partial results of all the shares, with what every chip computes alike
    — the residual and the SHARED EXPERT — counted once, are the uncut
    reference's feed-forward (all 16 experts held)."""
    wide = ["net.tokenq.moe_num_primary_experts=16",
            "net.tokenq.moe_num_active_primary_experts=4"]
    cfg = toy_cfg(*wide, "net.tokenq.experts_held=4")
    hp = toy_hp(toy_cfg(*wide, "net.tokenq.experts_held=16"))
    w = ref.init_weights(SEED, hp)
    pre = "layer_03/"
    lp = {k[len(pre):]: jnp.asarray(v) for k, v in w.items()
          if k.startswith(pre)}
    x = jax.random.normal(jax.random.PRNGKey(9), (1, T + 1, 64))
    with jax.default_matmul_precision("highest"):
        v2 = ref.rmsnorm(x[0], lp["norm_2"], 1e-6)
        gate, _ = ref.route(v2, lp["w_router"], 0.0, hp)
        shared = ref.shared_expert(v2, lp, "", None)
        whole = x[0] + ref.expert_layer(v2, gate, lp, "", hp, None) + shared
    routed = ("w_gate", "w_up", "w_down")

    def run(p, offset):
        net = dataclasses.replace(cfg.net, tokenq=dataclasses.replace(
            cfg.net.tokenq, expert_offset=offset))
        return tokenq.feed_forward(x, p, net, True)

    # what every member computes alike: the residual and the shared expert
    alike, _ = run({**lp, **{n: lp[n][:4] for n in routed},
                    "w_down": jnp.zeros_like(lp["w_down"])[:4]}, 0)
    np.testing.assert_allclose(alike[0], x[0] + shared, atol=1e-5)
    total, held = alike, 0
    for e in range(4):
        out, c = run({**lp, **{n: lp[n][4 * e:4 * e + 4] for n in routed}},
                     4 * e)
        total = total + (out - alike)
        held += int(c["slots_held"])
        assert int(c["overflow"]) == 0
    assert held == (T + 1) * 4      # every token-slot lands on one share
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(whole),
                               atol=2e-5)
    assert float(jnp.max(jnp.abs(shared))) > 1e-3   # no small part


# ---- planted faults at the toy size ---------------------------------------

@pytest.mark.parametrize("name", sorted(faults.FAULTS))
def test_a_planted_fault_of_the_reference_moves_q(stepped, gold_q, name):
    hp = stepped["hp"]
    tok, sound = gold_q
    wrong = faults.FAULTS[name][0](hp)
    with jax.default_matmul_precision("highest"):
        faulty = ref.q_values(as_jnp(stepped["seeded"]), jnp.asarray(tok),
                              {**hp, **wrong})
    gap = float(jnp.max(jnp.abs(faulty - sound)) / jnp.max(jnp.abs(sound)))
    assert gap > 1e-3, gap


def test_the_cell_walks_on_the_cpu_with_its_gate_counter():
    """``rehearse.py``'s walk of the cell at the family's toy sizes:
    driver, recorder, reference and verdict, float32 on both sides; the
    log rows carry the gate's mean."""
    import argparse

    from benchmark import rehearse, run

    ns = argparse.Namespace(workload=CELL, seed=2 ** 31 + 23, seconds=1.0,
                            trace=0)
    line = run.run_cell(ns, backend="cpu", conf_patch=rehearse.toy)
    assert line["correct"] and line["failed"] == 0
    worst = max(v for k, (v, _) in line["compared"].items())
    assert worst < 1e-4, line["compared"]
    assert "priority_first_max_rel" in line["compared"]     # judged
    assert line["metrics"]["grad_steps_per_s"]["value"] > 0


# ---- refusals -------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    {"num_attention_heads_per_layer": (2, 4, 4)},           # short
    {"num_attention_heads_per_layer": (2, 4, 3, 4, 2)},     # 2 kv heads
    {"layer_types": ("full_attention",) * 4 + ("conv",)},   # a gated conv
    {"layer_types": ("latent_attention",) * 5,
     "sliding_window_layout": (0,) * 5},
    {"layer_types": ("sparse_attention",) * 5, "gating": False,
     "sliding_window_layout": (0,) * 5},
    {"qk_norm": True},
    {"rope_full": {"partial_rotary_factor": 0.4375}},       # 7 columns
    {"rope_full": {"partial_rotary_factor": 1.5}},
    {"rope_full": {"rope_type": "linear"}},
    {"rope_full": {"original_max_position_embeddings": 0}}],
    ids=["heads_short", "heads_not_a_multiple", "gate_on_conv",
         "gate_on_latent", "heads_a_layer_on_sparse", "gate_with_qk_norm",
         "odd_rotary_width", "rotary_wider_than_the_head", "rope_type",
         "yarn_without_original_positions"])
def test_layer_plan_refuses_what_cannot_be(bad):
    tq = toy_cfg().net.tokenq
    tokenq.layer_plan(tq)
    if "rope_full" in bad:
        bad = {"rope_parameters": dataclasses.replace(
            tq.rope_parameters, full_attention=dataclasses.replace(
                tq.rope_parameters.full_attention, **bad["rope_full"]))}
    with pytest.raises(ValueError):
        tokenq.layer_plan(dataclasses.replace(tq, **bad))


def _count(shapes):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))


def test_the_laguna_preset_is_the_share_the_configuration_states():
    cfg = PRESETS["laguna_tokenq"]()
    shapes = tokenq.param_shapes(cfg.net)
    assert _count(shapes) == 490_297_344        # 490.3M, 7.84 GB at 16 B
    assert _count(shapes["layer_00"]) == 79_794_176
    assert _count(shapes["layer_01"]) == 91_885_568     # sliding, 64 heads
    assert _count(shapes["layer_04"]) == 83_464_192     # full, 48 heads
    plan = tokenq.layer_plan(cfg.net.tokenq)
    assert [(k["windowed"], k["heads"], k["dense"],
             k["rope_params"].rope_type) for k in plan] == [
        (False, 48, True, "yarn"), (True, 64, False, "default"),
        (True, 64, False, "default"), (True, 64, False, "default"),
        (False, 48, False, "yarn")]
    assert shapes["layer_00"]["w_q"] == (2048, 48 * 128)
    assert shapes["layer_00"]["w_g"] == (2048, 48)
    assert shapes["layer_00"]["w_gate"] == (2048, 8192)
    assert shapes["layer_02"]["w_o"] == (64 * 128, 2048)
    assert shapes["layer_02"]["w_k"] == (2048, 8 * 128)
    assert shapes["layer_02"]["w_gate"] == (16, 2048, 512)
    assert shapes["layer_02"]["shared_gate"] == (2048, 512)
    assert shapes["layer_02"]["w_router"] == (2048, 256)
    assert "expert_bias" not in shapes["layer_02"]
    assert shapes["head"] == (2048, 12_544)
    assert cfg.replay.capacity // cfg.replay.sequence_length == 8_192
    assert (cfg.replay.sequence_length, cfg.replay.batch_size) == (16_384, 1)
    with open(CONF) as fh:
        check.assert_hparams(json.load(fh), cfg)


def _drift(conf, path, value):
    conf = copy.deepcopy(conf)
    node = conf
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = value
    return conf


@pytest.mark.parametrize("path,value", [
    (("hparams", "num_attention_heads_per_layer"), [48, 64, 64, 64, 64]),
    (("hparams", "layer_types"), ["full_attention"] * 5),
    (("hparams", "sliding_window"), 1024),
    (("hparams", "gating"), False),
    (("hparams", "rope_parameters", "full_attention",
      "attention_factor"), 1.0),
    (("hparams", "rope_parameters", "full_attention",
      "partial_rotary_factor"), 1.0),
    (("hparams", "rope_parameters", "full_attention", "factor"), 32),
    (("hparams", "rope_parameters", "sliding_attention", "rope_theta"),
     500_000),
    (("hparams", "scoring_func"), "softmax"),
    (("hparams", "norm_topk_prob"), False),
    (("hparams", "routed_scaling_factor"), 1.0),
    (("hparams", "shared_expert_intermediate_size"), 1024),
    (("hparams", "fault"), "no_gate"),
    (("hparams", "sequence_length"), 8192),
    (("max_position_embeddings",), 8192),
    (("sliding_window",), 4096),
    (("num_attention_heads_per_layer",), [48] * 40),
    (("rope_parameters", "full_attention", "beta_fast"), 32),
    (("moe_routed_scaling_factor",), 1.0),
    (("mlp_layer_types",), ["sparse"] * 40)],
    ids=lambda v: ".".join(map(str, v)) if isinstance(v, tuple) else None)
def test_assert_hparams_refuses_a_drifted_key(path, value):
    with open(CONF) as fh:
        conf = json.load(fh)
    with pytest.raises(SystemExit):
        check.assert_hparams(_drift(conf, path, value),
                             PRESETS["laguna_tokenq"]())


def test_the_configuration_keeps_every_published_width():
    with open(CONF) as fh:
        conf = json.load(fh)
    assert (conf["hidden_size"], conf["head_dim"], conf["intermediate_size"],
            conf["num_key_value_heads"], conf["sliding_window"],
            conf["moe_intermediate_size"],
            conf["shared_expert_intermediate_size"],
            conf["num_experts_per_tok"]) == (2048, 128, 8192, 8, 512, 512,
                                             512, 8)
    assert conf["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert conf["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention", "sliding_attention",
                                    "sliding_attention",
                                    "sliding_attention"] * 9
    assert conf["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size", "env"]
    assert set(conf["reduced"]) == set(conf["reduced_why"])
    assert conf["published"]["num_experts"] == 256
    assert conf["hparams"]["router_experts"] == 256
    assert "16 chips share each layer" in conf["deployment"]
    for k in ("gating", "router_scoring", "qk_norm", "norm_placement"):
        assert k in conf["assumed"]


# ---- the counts -----------------------------------------------------------

def test_counts_against_a_hand_count():
    """6 tokens a window, 2 windows, 3 layers (full + dense with 2 heads,
    sliding with 4 at window 3, full with 2), by the formulas written
    out."""
    hp = dict(sequence_length=5, batch_size=2, num_hidden_layers=3,
              num_dense_layers=1, intermediate_size=24,
              layer_types=["full_attention", "sliding_attention",
                           "full_attention"],
              num_attention_heads_per_layer=[2, 4, 2],
              num_key_value_heads=2, head_dim=8, sliding_window=3,
              hidden_size=16, moe_intermediate_size=8,
              shared_expert_intermediate_size=8, num_experts_per_tok=3,
              experts_held=2, router_experts=8, vocab_size=32)
    tok = 2 * 6
    full = 4 * 2 * (4 * (2 + 2) * 8 * 21)       # 1+..+6 pairs a window
    band = 4 * 2 * (4 * 4 * 8 * (6 + 3 * 3))    # 1+2+3, then 3 a query
    assert counts.full_core_flops(hp) == full
    assert counts.window_core_flops(hp) == band
    proj = 4 * tok * sum(2 * 16 * (2 * hq + 4) * 8 + 2 * 16 * hq
                         for hq in (2, 4, 2))
    assert counts.attention_projection_flops(hp) == proj
    dense = 4 * tok * 6 * 16 * 24
    shared = 4 * tok * 2 * (6 * 16 * 8)
    slots = tok * 3 * 2 / 8
    experts = 4 * 2 * (6 * 16 * 8) * slots
    router = 4 * tok * 2 * (2 * 16 * 8)
    head = 4 * tok * 2 * 16 * 32
    assert counts.dense_ffn_flops(hp) == dense
    assert counts.shared_expert_flops(hp) == shared
    assert counts.expert_ffn_flops(hp) == experts
    assert counts.train_flops_per_step(hp) == (
        full + band + proj + dense + shared + experts + router + head)
    assert abs(sum(counts.train_flop_shares(hp).values()) - 1.0) < 1e-12
    # the band's count moves with no block size: there is none in it
    with open(CONF) as fh:
        real = json.load(fh)["hparams"]
    pairs = 512 * 513 / 2 + (16_385 - 512) * 512
    assert counts.window_core_flops(real) == 4 * 4 * 3 * 64 * 128 * pairs
