"""Moonlight-16B-A3B's block on the token-window Q-network (``net.kind =
"tokenq"``, ``model_type`` deepseek_v3) at toy sizes on the CPU: h 64, the
cell's own five layers (latent attention of 4 heads — scores 16 + 8 wide
over values of 16, a latent of rank 32, one shared rotary key head: the
published 128 | 64 | 128 over 512 in proportion —, a dense layer of width
96, then four expert layers: 8 SwiGLU experts top 2 behind a sigmoid router
with a selection bias and gates x 2.446, a shared expert of 2 x 32 beside
them), vocabulary 64, T 24 — the program against
``benchmark/reference/moonlight.py`` (plain jax.numpy float32, imports
nothing of the program), the new operators one by one, and the family's
counts.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
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

from benchmark.families.moonlight import check, counts  # noqa: E402
from benchmark.reference import lfm2 as lfm2_ref  # noqa: E402
from benchmark.reference import moonlight as ref  # noqa: E402
from distributed_deep_q_tpu.config import (  # noqa: E402
    PRESETS, TokenQConfig, apply_overrides)
from distributed_deep_q_tpu.models import tokenq  # noqa: E402
from distributed_deep_q_tpu.ops import moe  # noqa: E402
from distributed_deep_q_tpu.ops.attention import causal_attention  # noqa: E402
from distributed_deep_q_tpu.parallel.sequence_learner import (  # noqa: E402
    SequenceSolver)

T, V, SEED = 24, 64, 7
F32 = jnp.float32


def toy_cfg(**tq):
    cfg = PRESETS["tokenq"]()
    cfg.mesh.backend = "cpu"
    cfg.mesh.num_fake_devices = 1
    apply_overrides(cfg, ["replay.batch_size=2", "replay.fused_chain=2",
                          f"train.seed={SEED}"])
    cfg.net.tokenq = dataclasses.replace(TokenQConfig(
        hidden_size=64, num_hidden_layers=5, num_attention_heads=4,
        num_key_value_heads=4, rms_norm_eps=1e-5,
        layer_types=("latent_attention",) * 5,
        sliding_window_layout=(0,) * 5, rope_layout=(1,) * 5,
        rope_theta=5e4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_dense_layers=1, intermediate_size=96, hidden_act="silu",
        moe_primary_router_apply_softmax=False, use_expert_bias=True,
        router_input="ffn_norm", moe_ffn_hidden_size=32,
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        experts_held=8, routed_scaling_factor=2.446, n_shared_experts=2,
        # 50 tokens a step in blocks of 16: the dense layer, the shared
        # expert and the head all pad their last block
        head_block=16, moe_tile=8), **tq)
    return cfg


def toy_hp(cfg, **over):
    tq = cfg.net.tokenq
    n = tq.num_hidden_layers
    hp = {
        "hidden_size": tq.hidden_size, "num_hidden_layers": n,
        "layer_types": list(tq.layer_types[:n]),
        "num_dense_layers": tq.num_dense_layers,
        "intermediate_size": tq.intermediate_size,
        "num_attention_heads": tq.num_attention_heads,
        "kv_lora_rank": tq.kv_lora_rank,
        "qk_nope_head_dim": tq.qk_nope_head_dim,
        "qk_rope_head_dim": tq.qk_rope_head_dim,
        "v_head_dim": tq.v_head_dim, "rope_interleave": True,
        "rms_norm_eps": tq.rms_norm_eps, "rope_theta": tq.rope_theta,
        "moe_intermediate_size": tq.moe_ffn_hidden_size,
        "router_experts": tq.moe_num_primary_experts,
        "experts_held": tq.experts_held, "expert_offset": tq.expert_offset,
        "num_experts_per_tok": tq.moe_num_active_primary_experts,
        "n_shared_experts": tq.n_shared_experts,
        "use_expert_bias": tq.use_expert_bias, "norm_topk_prob": True,
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
def solver_and_hp():
    cfg = toy_cfg()
    solver = SequenceSolver(cfg)
    hp = toy_hp(cfg)
    solver.set_named_weights(ref.init_weights(SEED, hp))
    return solver, hp, cfg


def test_latent_and_shared_leaves_round_trip_through_weight_io(
        solver_and_hp):
    solver, hp, _ = solver_and_hp
    named = solver.get_named_weights()
    assert {k: v.shape for k, v in named.items()} == ref.leaf_shapes(hp)
    assert named["layer_00/w_q"].shape == (64, 4 * (16 + 8))
    assert named["layer_00/w_kva"].shape == (64, 32 + 8)
    assert named["layer_00/kv_norm"].shape == (32,)
    assert named["layer_00/w_kvb"].shape == (32, 4 * (16 + 16))
    assert named["layer_00/w_o"].shape == (4 * 16, 64)
    assert named["layer_01/shared_gate"].shape == (64, 2 * 32)
    assert named["layer_01/shared_down"].shape == (2 * 32, 64)
    # a latent layer has no w_k / w_v, the dense layer no shared expert
    assert not {"layer_00/w_k", "layer_00/w_v", "layer_00/shared_gate",
                "layer_00/w_router"} & set(named)
    solver.set_named_weights(named)
    again = solver.get_named_weights()
    assert all(np.array_equal(again[k], named[k]) for k in named)


def test_q_at_every_position_matches_the_reference(solver_and_hp):
    solver, hp, cfg = solver_and_hp
    w = ref.init_weights(SEED, hp)
    tok = ref.seeded_windows(1, 0, hp)[0][0]
    hid, counters = tokenq.backbone(solver.state.params, tok[None], cfg.net,
                                    interpret=True)
    assert counters["slots"].shape == (4,)      # the four expert layers
    q = hid[0] @ solver.state.params["head"]
    with jax.default_matmul_precision("highest"):
        gold = ref.q_values(as_jnp(w), jnp.asarray(tok), hp)
    np.testing.assert_allclose(np.asarray(q), np.asarray(gold), atol=2e-5)
    q5 = solver.token_q_values(tok[:6])
    np.testing.assert_allclose(q5, np.asarray(gold)[5], atol=2e-5)


def test_one_step_loss_gradients_adam_and_target(solver_and_hp):
    """Both forwards, loss, priorities, gradients by leaf (through Adam's
    first moment), θ after one Adam step and θ⁻, element for element; the
    expert bias stays as seeded."""
    solver, hp, cfg = solver_and_hp
    batch = seeded_batch(hp, 2)
    core = jax.jit(shard_map(
        solver.learner._token_step_core, mesh=solver.mesh,
        in_specs=(P(), P("dp")), out_specs=(P(), P(), P("dp")),
        check_vma=False))
    state, metrics, priority = core(solver.state, batch)

    seeded = ref.init_weights(SEED, hp)
    gold, gm, gprio = ref.make_step(hp)(
        ref.init_state(as_jnp(seeded), as_jnp(seeded)), as_jnp(batch))
    assert abs(float(metrics["loss"]) - float(gm["loss"])) < 1e-5
    assert abs(float(metrics["q_mean"]) - float(gm["q_mean"])) < 1e-6
    np.testing.assert_allclose(np.asarray(priority), np.asarray(gprio),
                               rtol=1e-5)
    held = float(metrics["moe_slots_held"]) / float(metrics["moe_slots"])
    assert abs(held - float(jnp.mean(gm["held_share"]))) < 1e-6
    assert int(metrics["moe_overflow"]) == 0
    names = list(tokenq.named_leaves(state.params))
    np.testing.assert_allclose(
        np.asarray(metrics["grad_leaf_norm"]),
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
    for k in ("head", "layer_00/w_q", "layer_00/w_kva", "layer_00/kv_norm",
              "layer_02/w_kvb", "layer_04/w_o", "layer_00/w_down",
              "layer_01/shared_gate", "layer_03/shared_down",
              "layer_02/w_gate", "layer_01/w_router", "embed"):
        big = np.abs(np.asarray(gold["m"][k])) > 1e-7
        assert big.any(), k
        np.testing.assert_allclose(np.asarray(theta[k])[big],
                                   np.asarray(gold["theta"][k])[big],
                                   atol=2e-6, err_msg=k)
    # no gradient reaches the selection bias: Adam leaves it where it is
    for i in range(1, 5):
        k = f"layer_{i:02d}/expert_bias"
        assert not np.asarray(mu[k]).any()
        assert np.array_equal(np.asarray(theta[k]), seeded[k])


def test_the_reference_a_layer_at_a_time_is_its_whole_program(
        solver_and_hp):
    """``grad_one`` (what ``make_step`` runs: a compiled forward and
    backward a KIND of layer, the chain rule between layers written out,
    gradients added into the step's sum as they come) against
    ``jax.value_and_grad(sequence_loss)``."""
    _, hp, _ = solver_and_hp
    w = as_jnp(ref.init_weights(SEED, hp))
    tg = as_jnp(ref.init_weights(SEED + 1, hp))
    batch = seeded_batch(hp, 2)
    seq = {k: jnp.asarray(batch[k][1]) for k in
           ("tokens", "reward", "discount", "mask")}
    seq["scale"] = jnp.asarray(0.4, F32)
    with jax.default_matmul_precision("highest"):
        (loss, (prio, q_sum, share)), g = jax.value_and_grad(
            ref.sequence_loss, has_aux=True)(w, tg, seq, hp, None)
    (loss1, (prio1, q_sum1, share1)), g1 = ref.grad_one(w, tg, seq, hp)
    np.testing.assert_allclose(loss1, loss, rtol=1e-6)
    np.testing.assert_allclose(prio1, prio, rtol=1e-6)
    np.testing.assert_allclose(q_sum1, q_sum, rtol=1e-5)
    np.testing.assert_array_equal(share1, share)
    assert set(g1) == set(g)
    for k in g:
        scale = float(jnp.abs(g[k]).max()) + 1e-12
        np.testing.assert_allclose(g1[k] / scale, g[k] / scale, atol=2e-5,
                                   err_msg=k)
    # a second window's gradient is ADDED into the first's, by name
    acc = {k: jnp.array(v) for k, v in g1.items()}
    _, acc = ref.grad_one(w, tg, seq, hp, acc=acc)
    for k in ("embed", "head", "layer_00/w_kva", "layer_03/shared_up"):
        np.testing.assert_allclose(acc[k], 2.0 * g1[k], rtol=1e-6)


# ---- the latent mixer ----------------------------------------------------

def _mixer_inputs(t=150, h=32, hq=4, dn=16, dr=8, dv=16, r=24):
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    p = {"w_q": jax.random.normal(ks[0], (h, hq * (dn + dr))) * 0.3,
         "w_kva": jax.random.normal(ks[1], (h, r + dr)) * 0.3,
         "kv_norm": 1.0 + 0.1 * jax.random.normal(ks[2], (r,)),
         "w_kvb": jax.random.normal(ks[3], (r, hq * (dn + dv))) * 0.3,
         "w_o": jax.random.normal(ks[4], (hq * dv, h)) * 0.3}
    u = jax.random.normal(ks[5], (1, t, h))
    net = dataclasses.replace(PRESETS["tokenq"]().net, tokenq=TokenQConfig(
        hidden_size=h, num_attention_heads=hq, kv_lora_rank=r,
        qk_nope_head_dim=dn, qk_rope_head_dim=dr, v_head_dim=dv,
        rope_theta=5e4, rms_norm_eps=1e-5))
    hp = dict(num_attention_heads=hq, kv_lora_rank=r, qk_nope_head_dim=dn,
              qk_rope_head_dim=dr, v_head_dim=dv, rope_theta=5e4,
              rope_interleave=True, rms_norm_eps=1e-5)
    return u, p, net, hp


def test_latent_mixer_forward_backward_interpret_against_materialised():
    """The program's latent mixer (its five products, the latent's norm,
    both rotations, the blockwise kernel at scores 24 wide over values of
    16 in interpret mode) against the reference's materialised scores; 150
    tokens, block 128: two blocks."""
    u, p, net, hp = _mixer_inputs()

    def program(u, p):
        return tokenq.latent_attention(u, p, net, True, True)

    def reference(u, p):
        return ref.latent_attention(u[0], p, "", hp, None)[None]

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(program(u, p), reference(u, p),
                                   atol=2e-5)
        f = lambda fn: jax.grad(  # noqa: E731
            lambda u, p: jnp.sum(jnp.sin(fn(u, p))), argnums=(0, 1))(u, p)
        (gu, gp), (wu, wp) = f(program), f(reference)
    np.testing.assert_allclose(gu, wu, atol=1e-4)
    for k in p:
        np.testing.assert_allclose(gp[k], wp[k], atol=2e-4, err_msg=k)


def test_causal_attention_takes_values_narrower_than_the_scores():
    """``causal_attention`` at a score width of 24 and a value width of 16
    against plain softmax attention: the scale is the SCORE width's."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (2, 4, 130, 24))
    k = jax.random.normal(ks[1], (2, 2, 130, 24))
    v = jax.random.normal(ks[2], (2, 2, 130, 16))

    def plain(q, k, v):
        kk, vv = jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1)
        s = jnp.einsum("bhtd,bhsd->bhts", q, kk) * 24 ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((130, 130), bool)), s, -1e30)
        return jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, -1), vv)

    with jax.default_matmul_precision("highest"):
        out = causal_attention(q, k, v, interpret=True)
        assert out.shape == (2, 4, 130, 16)
        np.testing.assert_allclose(out, plain(q, k, v), atol=2e-5)
        f = lambda fn: jax.grad(  # noqa: E731
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2))(q, k, v)
        for g, want in zip(f(lambda *a: causal_attention(
                *a, interpret=True)), f(plain)):
            np.testing.assert_allclose(g, want, atol=1e-4)


@pytest.mark.parametrize("fn", [tokenq.rotary, ref.rotary],
                         ids=["program", "reference"])
def test_interleaved_rotary_is_a_complex_rotation_of_the_pairs(fn):
    """Pair i = elements (2i, 2i+1) as one complex number, turned by
    ``t · theta^(-2i/d)``; rotate-half pairs (i, i + d/2)."""
    d, t, theta = 8, 11, 5e4
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (2, 3, t, d)))
    ang = np.arange(t)[:, None] * theta ** (-np.arange(0, d, 2) / d)
    turn = np.exp(1j * ang)                                 # [t, d/2]
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * turn
    want = np.stack([z.real, z.imag], -1).reshape(x.shape)
    np.testing.assert_allclose(fn(jnp.asarray(x), theta, True), want,
                               atol=1e-5)
    z = (x[..., :d // 2] + 1j * x[..., d // 2:]) * turn
    half = fn(jnp.asarray(x), theta, False)
    np.testing.assert_allclose(half, np.concatenate([z.real, z.imag], -1),
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(half - want))) > 0.1
    # position 0 is left as it is; norms of the pairs are kept
    np.testing.assert_allclose(want[..., 0, :], x[..., 0, :], atol=1e-6)


def test_the_one_rotary_key_head_is_shared_by_every_head():
    """``w_kva``'s last ``qk_rope_head_dim`` columns make ONE key head:
    moving them moves the scores of EVERY head (the mixer's output through
    each head's slice of ``W_o``), and the rotary part of a query sees the
    keys' POSITIONS (a latent mixer without it is a bag of earlier
    tokens)."""
    u, p, net, _ = _mixer_inputs(t=40)
    hq, dv, r = 4, 16, 24
    base = tokenq.latent_attention(u, p, net, True, True)
    moved = {**p, "w_kva": p["w_kva"].at[:, r:].add(0.5)}

    def per_head(p):        # each head's own contribution: the rest of W_o off
        return [tokenq.latent_attention(u, {**p, "w_o": p["w_o"] * (
            jnp.arange(hq * dv)[:, None] // dv == j)}, net, True, True)
            for j in range(hq)]
    for a, b in zip(per_head(p), per_head(moved)):
        assert float(jnp.max(jnp.abs(a - b))) > 1e-3
    np.testing.assert_allclose(sum(per_head(p)), base, atol=1e-5)
    # the latent columns leave the rotary key alone: with the rope part of
    # the query zeroed, moving the key head changes nothing
    no_rope = {**p, "w_q": p["w_q"].reshape(-1, hq, 24).at[..., 16:].set(
        0.0).reshape(p["w_q"].shape)}
    np.testing.assert_allclose(
        tokenq.latent_attention(
            u, {**no_rope, "w_kva": moved["w_kva"]}, net, True, True),
        tokenq.latent_attention(u, no_rope, net, True, True), atol=1e-6)
    # without the rotation the scores forget where a key stands
    flat = tokenq.latent_attention(u, p, net, False, True)
    assert float(jnp.max(jnp.abs(flat - base))) > 1e-3


# ---- the router's scale ---------------------------------------------------

def test_route_with_scale_one_is_bit_equal_to_the_unscaled_router():
    """LFM2's settings (sigmoid, a selection bias, top 4 of 64): ``scale``
    1.0 — explicit or the default — returns the bits the router returned
    before it had a scale (the chosen scores over their sum + 1e-6), and
    2.446 returns 2.446 times them; the softmax branch likewise."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (96, 32))
    wr = jax.random.normal(ks[1], (32, 64)) * 0.5
    bias = 0.01 * jax.random.normal(ks[2], (64,))
    s = jax.nn.sigmoid(jnp.dot(x, wr, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32))
    _, top_i = jax.lax.top_k(s + bias, 4)
    top_p = jnp.take_along_axis(s, top_i, -1)
    before = top_p / (jnp.sum(top_p, -1, keepdims=True) + 1e-6)
    for kw in ({}, {"scale": 1.0}):
        idx, p = moe.route(x, wr, 4, softmax=False, bias=bias, **kw)
        assert np.array_equal(np.asarray(idx), np.asarray(top_i))
        assert np.array_equal(np.asarray(p), np.asarray(before))
    idx, p = moe.route(x, wr, 4, softmax=False, bias=bias, scale=2.446)
    assert np.array_equal(np.asarray(idx), np.asarray(top_i))
    np.testing.assert_allclose(p, 2.446 * before, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(p, -1), 2.446, rtol=1e-5)
    hp = {"num_experts_per_tok": 4, "norm_topk_prob": True,
          "routed_scaling_factor": 2.446}
    with jax.default_matmul_precision("highest"):
        dense = np.zeros((96, 64), np.float32)
        np.put_along_axis(dense, np.asarray(idx), np.asarray(p), -1)
        np.testing.assert_allclose(dense, lfm2_ref.route(x, wr, bias, hp)[0],
                                   atol=1e-6)
    soft = moe.route(x, wr, 4)
    for a, b in zip(soft, moe.route(x, wr, 4, scale=1.0)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---- the expert layer's shares --------------------------------------------

def test_eight_shares_of_a_64_wide_expert_layer_add_up_to_the_uncut_one():
    """THE share test: a router 64 wide, top 6, eight shares of 8 experts
    each. The partial results of all eight shares, with what every chip
    computes alike — the residual and the SHARED EXPERT — counted once, are
    the uncut reference's layer (all 64 experts held)."""
    wide = dict(moe_num_primary_experts=64,
                moe_num_active_primary_experts=6)
    cfg = toy_cfg(experts_held=8, **wide)
    hp = toy_hp(toy_cfg(experts_held=64, **wide))   # the uncut layer
    w = ref.init_weights(SEED, hp)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, T + 1, 64))
    pre = "layer_02/"
    with jax.default_matmul_precision("highest"):
        whole, share_all = ref.layer(x[0], as_jnp(w), pre, False, hp, None)
    assert float(share_all) == 1.0
    lp = {k[len(pre):]: jnp.asarray(v) for k, v in w.items()
          if k.startswith(pre)}
    routed = ("w_gate", "w_up", "w_down")

    def run(p, offset):
        net = dataclasses.replace(cfg.net, tokenq=dataclasses.replace(
            cfg.net.tokenq, expert_offset=offset))
        return tokenq.layer(x, p, net, False, True, True, latent=True)

    # what every member computes alike: residual, mixer, shared expert
    alike, _ = run({**lp, **{n: lp[n][:8] for n in routed},
                    "w_down": jnp.zeros_like(lp["w_down"])[:8]}, 0)
    total, held = alike, 0
    for e in range(8):
        share = {**lp, **{n: lp[n][8 * e:8 * e + 8] for n in routed}}
        out, c = run(share, 8 * e)
        total = total + (out - alike)
        held += int(c["slots_held"])
        assert int(c["overflow"]) == 0
    assert held == (T + 1) * 6      # every token-slot lands on one share
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(whole),
                               atol=2e-5)
    # counted eight times the shared expert would not: it is no small part
    with jax.default_matmul_precision("highest"):
        s = ref.shared_expert(
            ref.rmsnorm(whole * 0 + x[0], lp["norm_2"], 1e-5), lp, "", None)
    assert float(jnp.max(jnp.abs(s))) > 1e-3


def test_the_shared_expert_is_every_tokens_and_ungated():
    """With the routed experts' down projections at zero the expert layer
    adds ``S(w)`` alone: the reference's one SwiGLU of width 2 x 32 over
    the second norm's output, whatever the router chose."""
    cfg = toy_cfg()
    hp = toy_hp(cfg)
    w = ref.init_weights(SEED, hp)
    pre = "layer_03/"
    lp = {k[len(pre):]: jnp.asarray(v) for k, v in w.items()
          if k.startswith(pre)}
    lp["w_down"] = jnp.zeros_like(lp["w_down"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T + 1, 64))
    y, _ = tokenq.feed_forward(x, lp, cfg.net, True)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([xs + ref.shared_expert(
            ref.rmsnorm(xs, lp["norm_2"], 1e-5), lp, "", None) for xs in x])
    np.testing.assert_allclose(y, want, atol=1e-5)


# ---- planted faults and the control at the toy size -----------------------

@pytest.mark.parametrize("wrong", [
    {"fault": "no_shared_expert"}, {"routed_scaling_factor": 1.0},
    {"rope_interleave": False}, {"fault": "rope_key_per_head"}],
    ids=["no_shared_expert", "scale_1", "rotate_half", "rope_key_per_head"])
def test_a_planted_fault_of_the_reference_moves_q(solver_and_hp, wrong):
    _, hp, _ = solver_and_hp
    w = as_jnp(ref.init_weights(SEED, hp))
    tok = jnp.asarray(ref.seeded_windows(1, 0, hp)[0][0])
    with jax.default_matmul_precision("highest"):
        sound = ref.q_values(w, tok, hp)
        faulty = ref.q_values(w, tok, {**hp, **wrong})
    gap = float(jnp.max(jnp.abs(faulty - sound)) / jnp.max(jnp.abs(sound)))
    assert gap > 1e-3, gap


def test_the_fp8_toy_control_reads_over_the_toy_limits(solver_and_hp):
    """The reference one precision down (fp8 operands, e5m2 cotangents)
    against itself on one step at the toy size: loss and gradient norm lie
    further apart than ``check.TOY_LIMIT`` allows the PROGRAM to lie."""
    _, hp, _ = solver_and_hp
    seeded = ref.init_weights(SEED, hp)
    batch = as_jnp(seeded_batch(hp, 2))
    gm, cm = (ref.make_step(hp, quant)(
        ref.init_state(as_jnp(seeded), as_jnp(seeded)), batch)[1]
        for quant in (None, "fp8"))

    def rel(k):
        return abs(float(cm[k]) - float(gm[k])) / abs(float(gm[k]))
    leaf = max(abs(float(cm["grad_leaf_norm"][k]) - float(v))
               / max(float(v), float(gm["grad_norm"]) / 30)
               for k, v in gm["grad_leaf_norm"].items())
    assert max(rel("grad_norm"), leaf) > check.TOY_LIMIT
    assert rel("loss") > 1e-4


def test_the_moonlight_familys_planted_faults_are_not_correct():
    """``families/moonlight/faults.py`` at the toy sizes: the shared expert
    left out, the gates not scaled, rotate-half for the interleaved pairs
    and a rotary key a head for the shared one each move the number they
    are read for far past what the sound program reads there (under
    1e-5); the two faults of the expert layer move Adam's first moment by
    more than half on the worst leaf (a shared expert that is left out
    leaves its three leaves no gradient at all)."""
    from benchmark import rehearse
    from benchmark.families.moonlight import faults

    rs = faults.readings("moonlight_16b_tokenq_ep8.seq_learner_only",
                         [2 ** 31 + 5], backend="cpu",
                         conf_patch=rehearse.toy, prefill=256)
    table = faults.summarize(rs)
    assert set(table) == set(faults.FAULTS)
    for name, row in table.items():
        assert row["smallest"][row["planted_for"]] > 1e-3, (name, row)
    for name in ("no_shared_expert", "routed_scaling_factor_1"):
        assert table[name]["smallest"]["moment_first_worst_leaf"] > 0.5


# ---- the counts and the presets -------------------------------------------

def test_counts_against_a_hand_count():
    """4 tokens a window, 2 windows, 3 layers (one dense, two expert), by
    the formulas written out."""
    hp = dict(sequence_length=3, batch_size=2, num_hidden_layers=3,
              num_dense_layers=1, intermediate_size=24,
              num_attention_heads=4, kv_lora_rank=12, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=6, hidden_size=16,
              moe_intermediate_size=8, n_shared_experts=2,
              num_experts_per_tok=3, experts_held=2, router_experts=8,
              vocab_size=32)
    tok = 2 * 4
    core = 4 * 2 * 3 * (4 * 2 * (8 + 4 + 6) * 10)   # 1+2+3+4 pairs a window
    assert counts.mla_core_flops(hp) == core
    proj = 4 * tok * 3 * 2 * (16 * 4 * 12 + 16 * (12 + 4)
                              + 12 * 4 * (8 + 6) + 4 * 6 * 16)
    assert counts.mla_projection_flops(hp) == proj
    dense = 4 * tok * 6 * 16 * 24
    assert counts.dense_ffn_flops(hp) == dense
    shared = 4 * tok * 2 * (6 * 16 * 2 * 8)
    assert counts.shared_expert_flops(hp) == shared
    slots = tok * 3 * 2 / 8
    assert counts.expected_held_slots(hp) == slots
    experts = 4 * 2 * (6 * 16 * 8) * slots
    assert counts.expert_ffn_flops(hp) == experts
    router = 4 * tok * 2 * (2 * 16 * 8)
    head = 4 * tok * 2 * 16 * 32
    assert counts.head_flops(hp) == head
    assert counts.train_flops_per_step(hp) == (
        core + proj + dense + shared + experts + router + head)
    assert abs(sum(counts.train_flop_shares(hp).values()) - 1.0) < 1e-12


def _count(shapes):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))


def test_the_moonlight_preset_is_the_share_the_configuration_states():
    cfg = PRESETS["moonlight_tokenq"]()
    shapes = tokenq.param_shapes(cfg.net)
    assert _count(shapes) == 568_484_608        # 568.5M, 9.10 GB at 16 B
    assert _count(shapes["layer_00"]) == 82_973_184
    assert _count(shapes["layer_01"]) == 100_405_824
    assert [(k["latent"], k["dense"]) for k in tokenq.layer_plan(
        cfg.net.tokenq)] == [(True, True)] + [(True, False)] * 4
    assert shapes["layer_00"] == {
        "norm_1": (2048,), "norm_2": (2048,), "w_q": (2048, 16 * 192),
        "w_kva": (2048, 512 + 64), "kv_norm": (512,),
        "w_kvb": (512, 16 * 256), "w_o": (16 * 128, 2048),
        "w_gate": (2048, 11_264), "w_up": (2048, 11_264),
        "w_down": (11_264, 2048)}
    assert shapes["layer_03"]["w_gate"] == (8, 2048, 1408)
    assert shapes["layer_03"]["shared_gate"] == (2048, 2 * 1408)
    assert shapes["layer_03"]["shared_down"] == (2 * 1408, 2048)
    assert shapes["layer_03"]["w_router"] == (2048, 64)
    assert shapes["layer_03"]["expert_bias"] == (64,)
    assert shapes["head"] == (2048, 20_480)
    assert cfg.replay.sequence_length + 1 == 8192   # the published context
    assert cfg.replay.capacity // cfg.replay.sequence_length == 16_384
    # and it is what the configuration file says the program runs
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "moonlight_16b_tokenq_ep8.json")) as fh:
        conf = json.load(fh)
    check.assert_hparams(conf, cfg)
    for wrong in ({"routed_scaling_factor": 1.0}, {"n_shared_experts": 1},
                  {"rope_interleave": False}, {"v_head_dim": 192},
                  {"qk_rope_head_dim": 128}, {"fault": "no_shared_expert"}):
        bad = {**conf, "hparams": {**conf["hparams"], **wrong}}
        with pytest.raises(SystemExit):
            check.assert_hparams(bad, cfg)


def test_a_latent_layer_takes_no_window_and_no_qk_norm():
    tq = PRESETS["moonlight_tokenq"]().net.tokenq
    tokenq.layer_plan(tq)
    for bad in ({"sliding_window_layout": (0, 1, 0, 0, 0)},
                {"qk_norm": True},
                {"layer_types": ("latent",) * 5}):
        with pytest.raises(ValueError):
            tokenq.layer_plan(dataclasses.replace(tq, **bad))


@pytest.mark.parametrize("preset,params,leaves,plan", [
    ("tokenq", None,
     {"norm_1", "norm_2", "w_router", "w_q", "w_k", "w_v", "w_o", "w_gate",
      "w_up", "w_down"}, {}),
    ("smallthinker_tokenq", 370_547_200,
     {"norm_1", "norm_2", "w_router", "w_q", "w_k", "w_v", "w_o", "w_gate",
      "w_up", "w_down"}, {}),
    ("lfm2_tokenq", 486_062_464,
     {"norm_1", "norm_2", "w_router", "expert_bias", "w_q", "w_k", "w_v",
      "w_o", "q_norm", "k_norm", "w_gate", "w_up", "w_down"}, {}),
    ("keye_tokenq", None,
     {"norm_1", "norm_2", "w_router", "w_q", "w_k", "w_v", "w_o", "q_norm",
      "k_norm", "w_iq", "w_ik", "w_iw", "ik_norm", "w_gate", "w_up",
      "w_down"}, {"sparse"})],
    ids=["tokenq", "smallthinker", "lfm2", "keye"])
def test_the_other_presets_keep_their_leaves_and_their_plan(
        preset, params, leaves, plan):
    """The four presets that were there: no latent leaf, no shared expert,
    a gate scale of 1, the same ``layer_plan`` (with ``latent`` false on
    every layer)."""
    cfg = PRESETS[preset]()
    tq = cfg.net.tokenq
    shapes = tokenq.param_shapes(cfg.net)
    if params is not None:
        assert _count(shapes) == params
    attn = 1 if preset == "lfm2_tokenq" else 0      # lfm2: layer 1
    assert set(shapes[f"layer_{attn:02d}"]) == leaves
    assert (tq.routed_scaling_factor, tq.n_shared_experts) == (1.0, 0)
    kinds = tokenq.layer_plan(tq)
    assert not any(k["latent"] for k in kinds)
    assert all(k["sparse"] == ("sparse" in plan) for k in kinds)
    assert set(kinds[0]) == {"windowed", "rope", "conv", "sparse", "latent",
                             "mamba", "mixer", "ffn", "dense", "heads",
                             "rope_params"}
    assert all(k["mixer"] and k["ffn"] and not k["mamba"] for k in kinds)
    assert all(k["heads"] == tq.num_attention_heads
               and k["rope_params"] is None for k in kinds)
    if preset == "lfm2_tokenq":
        assert [(k["conv"], k["dense"]) for k in kinds] == [
            (True, True), (False, False), (True, False), (True, False),
            (True, False)]
    if preset in ("tokenq", "smallthinker_tokenq"):
        assert [(k["windowed"], k["rope"]) for k in kinds] == [
            (False, False), (True, True), (True, True), (True, True)]


MOONLIGHT_TOY = [
    *check.TOY_OVERRIDES, "net.tokenq.experts_held=8",
    "net.tokenq.expert_offset=0", "replay.batch_size=2",
    "replay.learn_start=240", "train.train_every=48",
    "train.total_steps=600", "env.max_episode_steps=48",
    "actors.eps_decay_steps=300"]


def test_main_train_runs_the_moonlight_preset_from_the_command_line():
    """``main train --preset moonlight_tokenq`` at toy widths: the normal
    path (``train.train_tokenq`` → ``SequenceSolver`` → the fused token
    step), the preset's own mechanisms (five latent layers, a dense one
    first, shared experts, scaled gates)."""
    cmd = [sys.executable, "-m", "distributed_deep_q_tpu.main", "train",
           "--preset", "moonlight_tokenq", "--backend", "cpu", "--set",
           *MOONLIGHT_TOY]
    done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    summary = json.loads(done.stdout.strip().splitlines()[-1])
    assert summary["mode"] == "train" and summary["grad_steps"] >= 4
