"""LFM2's block on the token-window Q-network (``net.kind = "tokenq"``,
``model_type`` lfm2_moe) at toy sizes on the CPU: h 64, the cell's own five
layers (conv + dense, then full attention and three convolutions with
experts), 8 SwiGLU experts top 2 behind a sigmoid router with a selection
bias, dense width 96, vocabulary 64, T 24 — the program against
``benchmark/reference/lfm2.py`` (plain jax.numpy float32, imports nothing
of the program), the new operators one by one, and the family's counts.
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

from benchmark.families.lfm2 import counts  # noqa: E402
from benchmark.reference import lfm2 as ref  # noqa: E402
from distributed_deep_q_tpu.config import (  # noqa: E402
    PRESETS, TokenQConfig, apply_overrides)
from distributed_deep_q_tpu.models import tokenq  # noqa: E402
from distributed_deep_q_tpu.ops import moe  # noqa: E402
from distributed_deep_q_tpu.ops.attention import causal_attention  # noqa: E402
from distributed_deep_q_tpu.ops.short_conv import short_conv_mix  # noqa: E402
from distributed_deep_q_tpu.parallel.sequence_learner import (  # noqa: E402
    SequenceSolver)

T, V, SEED = 24, 64, 7
LAYERS = ("conv", "full_attention", "conv", "conv", "conv")
F32 = jnp.float32


def toy_cfg(**tq):
    cfg = PRESETS["tokenq"]()
    cfg.mesh.backend = "cpu"
    cfg.mesh.num_fake_devices = 1
    apply_overrides(cfg, ["replay.batch_size=2", "replay.fused_chain=2",
                          f"train.seed={SEED}"])
    cfg.net.tokenq = dataclasses.replace(TokenQConfig(
        hidden_size=64, num_hidden_layers=5, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-5,
        layer_types=LAYERS, sliding_window_layout=(0,) * 5,
        rope_layout=(1,) * 5, rope_theta=1e6, qk_norm=True,
        num_dense_layers=1, intermediate_size=96, hidden_act="silu",
        moe_primary_router_apply_softmax=False, use_expert_bias=True,
        router_input="ffn_norm",
        moe_ffn_hidden_size=32, moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, experts_held=8,
        # 50 tokens a step in blocks of 16: the dense layer and the head
        # both pad their last block
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
        "conv_L_cache": tokenq.CONV_TAPS,
        "num_attention_heads": tq.num_attention_heads,
        "num_key_value_heads": tq.num_key_value_heads,
        "head_dim": tq.head_dim, "norm_eps": tq.rms_norm_eps,
        "rope_theta": tq.rope_theta,
        "moe_intermediate_size": tq.moe_ffn_hidden_size,
        "router_experts": tq.moe_num_primary_experts,
        "experts_held": tq.experts_held, "expert_offset": tq.expert_offset,
        "num_experts_per_tok": tq.moe_num_active_primary_experts,
        "use_expert_bias": tq.use_expert_bias,
        "norm_topk_prob": True, "routed_scaling_factor": 1.0,
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


def test_new_leaf_names_round_trip_through_weight_io(solver_and_hp):
    solver, hp, _ = solver_and_hp
    named = solver.get_named_weights()
    assert {k: v.shape for k, v in named.items()} == ref.leaf_shapes(hp)
    for leaf in ("layer_00/w_in", "layer_00/w_conv", "layer_00/w_out",
                 "layer_00/w_gate", "layer_01/q_norm", "layer_01/k_norm",
                 "layer_01/expert_bias", "layer_02/w_router"):
        assert leaf in named
    # the dense layer has no router, a conv layer no attention leaves
    assert "layer_00/w_router" not in named and \
        "layer_02/w_q" not in named
    assert named["layer_00/w_gate"].shape == (64, 96)
    solver.set_named_weights(named)
    again = solver.get_named_weights()
    assert all(np.array_equal(again[k], named[k]) for k in named)
    with pytest.raises(KeyError):
        solver.set_named_weights({k: v for k, v in named.items()
                                  if k != "layer_01/expert_bias"})


def test_q_at_every_position_matches_the_reference(solver_and_hp):
    solver, hp, cfg = solver_and_hp
    w = ref.init_weights(SEED, hp)
    tok = ref.seeded_windows(1, 0, hp)[0][0]
    hid, counters = tokenq.backbone(solver.state.params, tok[None], cfg.net,
                                    interpret=True)
    # the counters cover the four expert layers only
    assert counters["slots"].shape == (4,)
    q = hid[0] @ solver.state.params["head"]
    with jax.default_matmul_precision("highest"):
        gold = ref.q_values(as_jnp(w), jnp.asarray(tok), hp)
    np.testing.assert_allclose(np.asarray(q), np.asarray(gold), atol=2e-5)
    q5 = solver.token_q_values(tok[:6])
    np.testing.assert_allclose(q5, np.asarray(gold)[5], atol=2e-5)


def test_one_step_loss_gradients_adam_and_target(solver_and_hp):
    """Loss, priorities, gradients by leaf (through Adam's first moment),
    θ after one Adam step and θ⁻, element for element; the expert bias
    stays as seeded."""
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
    for k in ("head", "layer_00/w_in", "layer_00/w_down", "layer_01/w_q",
              "layer_03/w_conv", "layer_02/w_gate", "embed"):
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


# ---- the gated short convolution ---------------------------------------

def _conv_inputs(taps, t=19, h=16):
    ks = jax.random.split(jax.random.PRNGKey(taps), 4)
    return (jax.random.normal(ks[0], (t, h)),
            jax.random.normal(ks[1], (h, 3 * h)) * 0.3,
            jax.random.normal(ks[2], (h, taps)) * 0.5,
            jax.random.normal(ks[3], (h, h)) * 0.3)


def _conv_program(u, w_in, w_conv, w_out):
    mixed = short_conv_mix(tokenq._mm(u, w_in, F32), w_conv)
    return tokenq._mm(mixed, w_out, F32)


def _conv_reference(u, w_in, w_conv, w_out):
    return ref.short_conv(
        u, {"w_in": w_in, "w_conv": w_conv, "w_out": w_out}, "",
        {"conv_L_cache": w_conv.shape[1]}, None)


@pytest.mark.parametrize("taps", [2, 3, 4])
def test_short_conv_forward_and_backward_match_the_reference(taps):
    args = _conv_inputs(taps)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(_conv_program(*args),
                                   _conv_reference(*args), atol=1e-5)
        f = lambda fn: jax.grad(  # noqa: E731
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3))(*args)
        for g, want, name in zip(f(_conv_program), f(_conv_reference),
                                 "u w_in w_conv w_out".split()):
            np.testing.assert_allclose(g, want, atol=2e-5, err_msg=name)
    # a leading batch axis is the same operator a sequence at a time
    bcz = jax.random.normal(jax.random.PRNGKey(0), (3, 11, 48))
    np.testing.assert_array_equal(
        short_conv_mix(bcz, args[2]),
        jnp.stack([short_conv_mix(s, args[2]) for s in bcz]))


@pytest.mark.parametrize("taps", [2, 3, 4])
def test_short_conv_is_causal_and_starts_from_zeros(taps):
    h, t = 8, 12
    ks = jax.random.split(jax.random.PRNGKey(10 + taps), 3)
    bcz = jax.random.normal(ks[0], (t, 3 * h))
    w = jax.random.normal(ks[1], (h, taps))
    out = short_conv_mix(bcz, w)
    # output t is unmoved by tokens after t
    for cut in (1, 5, t - 1):
        later = bcz.at[cut:].set(jax.random.normal(ks[2], (t - cut, 3 * h)))
        np.testing.assert_array_equal(short_conv_mix(later, w)[:cut],
                                      out[:cut])
    # and moved by each of the taps - 1 tokens before it, no further back
    moved = short_conv_mix(bcz.at[3].add(1.0), w)
    changed = np.abs(np.asarray(moved - out)).max(-1) > 0
    assert changed[3:3 + taps].all() and not changed[:3].any() and \
        not changed[3 + taps:].any()
    # the first positions see zeros before the window
    b, c, z = bcz[:, :h], bcz[:, h:2 * h], bcz[:, 2 * h:]
    g = b * z
    np.testing.assert_allclose(out[0], c[0] * w[:, -1] * g[0], rtol=1e-6)
    np.testing.assert_allclose(
        out[1], c[1] * (w[:, -1] * g[1] + w[:, -2] * g[0]), rtol=1e-6)


# ---- the sigmoid router with a selection bias --------------------------

def test_sigmoid_router_selects_by_score_plus_bias_and_weights_by_score():
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(ks[0], (40, 16))
    wr = jax.random.normal(ks[1], (16, 8)) * 0.5
    s = np.asarray(jax.nn.sigmoid(
        jnp.dot(x, wr, precision=jax.lax.Precision.HIGHEST)))
    hp = {"num_experts_per_tok": 2, "norm_topk_prob": True,
          "routed_scaling_factor": 1.0}

    def dense(idx, p):
        out = np.zeros_like(s)
        np.put_along_axis(out, np.asarray(idx), np.asarray(p), -1)
        return out

    zero = jnp.zeros(8)
    idx0, p0 = moe.route(x, wr, 2, softmax=False, bias=zero)
    assert np.array_equal(np.sort(np.asarray(idx0), -1),
                          np.sort(np.argsort(-s, -1)[:, :2], -1))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(dense(idx0, p0),
                                   ref.route(x, wr, zero, hp)[0], atol=1e-6)
    # a bias that lifts the LEAST likely expert of token 0 over the rest
    last = int(np.argmin(s[0]))
    bias = zero.at[last].set(2.0)
    idx1, p1 = moe.route(x, wr, 2, softmax=False, bias=bias)
    assert last in np.asarray(idx1[0]) and last not in np.asarray(idx0[0])
    # ... changes the SET and not where the chosen weights come from: they
    # are the sigmoid scores WITHOUT the bias, over their sum + 1e-6
    chosen = np.take_along_axis(s, np.asarray(idx1), -1)
    np.testing.assert_allclose(
        p1, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(dense(idx1, p1),
                                   ref.route(x, wr, bias, hp)[0], atol=1e-6)
    # without a bias (``use_expert_bias`` false) the scores choose alone
    idx2, p2 = moe.route(x, wr, 2, softmax=False)
    assert np.array_equal(np.asarray(idx2), np.asarray(idx0))
    np.testing.assert_allclose(p2, p0, rtol=1e-6)
    # no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(moe.route(
        x, wr, 2, softmax=False, bias=b)[1] ** 2))(bias)
    assert not np.asarray(g).any()


def test_softmax_router_is_what_it_was():
    """SmallThinker's setting is the default: softmax, top k, renormalised
    to sum 1."""
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (30, 16))
    wr = jax.random.normal(ks[1], (16, 8))
    idx, p = moe.route(x, wr, 3)
    soft = np.asarray(jax.nn.softmax(
        jnp.dot(x, wr, precision=jax.lax.Precision.HIGHEST), -1))
    top = np.argsort(-soft, -1)[:, :3]
    assert np.array_equal(np.asarray(idx), top)
    kept = np.take_along_axis(soft, top, -1)
    np.testing.assert_allclose(p, kept / kept.sum(-1, keepdims=True),
                               rtol=1e-6)


# ---- the SwiGLU expert layer's shares ----------------------------------

def test_shares_of_one_swiglu_expert_layer_add_up_to_the_uncut_layer():
    """THE share test: the partial results of all 8 shares (1 expert
    each), with the residual counted once, are the uncut reference's
    layer (layer 2: a convolution and the experts)."""
    cfg = toy_cfg(experts_held=1)
    hp = toy_hp(toy_cfg())          # the uncut layer: all 8 held
    w = ref.init_weights(SEED, hp)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, T + 1, 64))
    pre = "layer_02/"
    with jax.default_matmul_precision("highest"):
        whole, share_all = ref.layer(x[0], as_jnp(w), 2, hp, None)
    assert float(share_all) == 1.0
    lp = {k[len(pre):]: jnp.asarray(v) for k, v in w.items()
          if k.startswith(pre)}

    def run(p, offset):
        net = dataclasses.replace(cfg.net, tokenq=dataclasses.replace(
            cfg.net.tokenq, expert_offset=offset))
        return tokenq.layer(x, p, net, False, True, True, conv=True)

    zero = {**lp, "w_down": jnp.zeros_like(lp["w_down"])[:1],
            "w_gate": lp["w_gate"][:1], "w_up": lp["w_up"][:1]}
    residual, _ = run(zero, 0)
    total, held = residual, 0
    for e in range(8):
        share = {**lp, **{n: lp[n][e:e + 1]
                          for n in ("w_gate", "w_up", "w_down")}}
        out, c = run(share, e)
        total = total + (out - residual)
        held += int(c["slots_held"])
        assert int(c["overflow"]) == 0
    assert held == (T + 1) * 2      # every token-slot lands on one share
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(whole),
                               atol=2e-5)


# ---- the dense feed-forward, blockwise ---------------------------------

def test_blockwise_dense_layer_equals_the_unblocked_one():
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    n, h, f = 50, 16, 40
    x = jax.random.normal(ks[0], (n, h))
    wg, wu = (jax.random.normal(k, (h, f)) * 0.3 for k in ks[1:3])
    wd = jax.random.normal(ks[3], (f, h)) * 0.3
    act = jax.nn.silu

    def blocked(block):
        return lambda *a: tokenq.dense_ffn(*a, act=act, block=block,
                                           dtype=F32)

    def plain(x, wg, wu, wd):
        return (act(x @ wg) * (x @ wu)) @ wd

    f_ = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3))(
        x, wg, wu, wd)
    with jax.default_matmul_precision("highest"):
        want, gwant = plain(x, wg, wu, wd), f_(plain)
        for block in (16, 50, 64):      # 4 blocks with padding, 1, 1
            np.testing.assert_allclose(blocked(block)(x, wg, wu, wd), want,
                                       atol=1e-5)
            for g, gw in zip(f_(blocked(block)), gwant):
                np.testing.assert_allclose(g, gw, atol=2e-5)
        np.testing.assert_allclose(
            ref.dense_layer(x, {"w_gate": wg, "w_up": wu, "w_down": wd}, "",
                            None, block=16), want, atol=1e-5)


# ---- q/k-norm attention at head size 64 --------------------------------

def test_qk_norm_attention_head_64_forward_backward_interpret():
    """Per-head RMSNorm of q and k, rotary, then the blockwise kernel at
    head size 64 in interpret mode, against the reference; 150 tokens,
    block 128: two blocks."""
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    q = jax.random.normal(ks[0], (1, 4, 150, 64))
    k = jax.random.normal(ks[1], (1, 2, 150, 64))
    v = jax.random.normal(ks[2], (1, 2, 150, 64))
    gq = 1.0 + 0.1 * jax.random.normal(ks[3], (64,))
    gk = 1.0 + 0.1 * jax.random.normal(ks[4], (64,))

    def program(q, k, v, gq, gk):
        q = tokenq.rotary(tokenq.rmsnorm(q, gq, 1e-5), 1e6)
        k = tokenq.rotary(tokenq.rmsnorm(k, gk, 1e-5), 1e6)
        return causal_attention(q, k, v, window=0, interpret=True)

    def reference(q, k, v, gq, gk):
        q = ref.rotary(ref.rmsnorm(q[0], gq, 1e-5), 1e6)
        k = ref.rotary(ref.rmsnorm(k[0], gk, 1e-5), 1e6)
        return ref.attention(q, k, v[0], 0, None, q_block=64)[None]

    args = (q, k, v, gq, gk)
    np.testing.assert_allclose(program(*args), reference(*args), atol=2e-5)
    f = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4))(*args)
    for g, want, name in zip(f(program), f(reference),
                             "q k v q_norm k_norm".split()):
        np.testing.assert_allclose(g, want, atol=1e-4, err_msg=name)


# ---- the counts and the presets ----------------------------------------

def test_counts_against_a_hand_count():
    """4 tokens a window, 2 windows, 3 layers (conv + dense, attention +
    experts, conv + experts), by the formulas written out."""
    hp = dict(sequence_length=3, batch_size=2, num_hidden_layers=3,
              layer_types=["conv", "full_attention", "conv"],
              num_dense_layers=1, intermediate_size=24, conv_L_cache=3,
              num_attention_heads=4, num_key_value_heads=2, head_dim=8,
              hidden_size=16, moe_intermediate_size=8,
              num_experts_per_tok=2, experts_held=2, router_experts=8,
              vocab_size=32)
    tok = 2 * 4
    conv = 4 * tok * 2 * (2 * 16 * 48 + 2 * 16 * 16 + (2 + 2 * 3) * 16)
    assert counts.short_conv_flops(hp) == conv
    # forward reads 3h, writes h (θ and θ⁻); backward reads h + 3h, writes 3h
    assert counts.short_conv_mix_bytes(hp) == tok * 2 * 4 * 16 * (
        2 * (3 + 1) + (1 + 3) + 3)
    dense = 4 * tok * 6 * 16 * 24
    assert counts.dense_ffn_flops(hp) == dense
    attn = 4 * 2 * (4 * 4 * 8 * 10)         # 1+2+3+4 pairs a window
    assert counts.attention_flops(hp) == attn
    proj = 4 * tok * (2 * 16 * (4 + 4) * 8 + 2 * 4 * 8 * 16)
    assert counts.attention_projection_flops(hp) == proj
    slots = tok * 2 * 2 / 8
    assert counts.expected_held_slots(hp) == slots
    experts = 4 * 2 * (6 * 16 * 8) * slots
    assert counts.expert_ffn_flops(hp) == experts
    router = 4 * tok * 2 * (2 * 16 * 8)
    head = 4 * tok * 2 * 16 * 32
    assert counts.train_flops_per_step(hp) == (
        conv + dense + attn + proj + experts + router + head)
    assert abs(sum(counts.train_flop_shares(hp).values()) - 1.0) < 1e-12


def _count(shapes):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))


def test_the_lfm2_preset_is_the_share_the_configuration_states():
    cfg = PRESETS["lfm2_tokenq"]()
    shapes = tokenq.param_shapes(cfg.net)
    assert _count(shapes) == 486_062_464
    assert [(k["conv"], k["dense"]) for k in tokenq.layer_plan(
        cfg.net.tokenq)] == [(True, True), (False, False), (True, False),
                             (True, False), (True, False)]
    assert shapes["layer_00"]["w_gate"] == (2048, 11_776)
    assert shapes["layer_01"]["q_norm"] == (64,)
    assert shapes["layer_02"]["w_conv"] == (2048, 3)
    assert shapes["layer_02"]["w_gate"] == (8, 2048, 1536)
    assert shapes["layer_02"]["expert_bias"] == (64,)
    assert shapes["head"] == (2048, 8192)
    with pytest.raises(ValueError):
        tokenq.layer_plan(dataclasses.replace(
            cfg.net.tokenq, layer_types=("conv", "attention") * 3))


@pytest.mark.parametrize("fields", [
    {"qk_norm": True}, {"hidden_act": "silu"}, {"router_input": "ffn_norm"},
    {"qk_norm": True, "hidden_act": "silu", "router_input": "ffn_norm",
     "moe_primary_router_apply_softmax": False}],
    ids=["qk_norm", "silu", "ffn_norm_router", "all_three_sigmoid"])
def test_the_three_mechanisms_are_fields_that_recombine(fields):
    """``qk_norm``, ``hidden_act`` and ``router_input`` are mechanisms a
    third model sets as it likes: on SmallThinker's attention-only toy
    each one alone changes Q, only ``qk_norm`` changes the leaves, and a
    value the backbone does not know is refused."""
    base = PRESETS["tokenq"]().net
    net = dataclasses.replace(base, tokenq=dataclasses.replace(
        base.tokenq, **fields))
    shapes = tokenq.param_shapes(net)
    extra = {"q_norm", "k_norm"} if fields.get("qk_norm") else set()
    assert set(shapes["layer_00"]) - set(
        tokenq.param_shapes(base)["layer_00"]) == extra
    tok = jax.random.randint(jax.random.PRNGKey(0), (2, 12), 0,
                             base.num_actions)
    theta = tokenq.init_params(net, 3)
    old = tokenq.init_params(base, 3)
    # the leaves both have, equal: what differs is the mechanism alone
    theta = {k: {**v, **old[k]} if isinstance(v, dict) else old[k]
             for k, v in theta.items()}
    x, _ = tokenq.backbone(theta, tok, net, interpret=True)
    x0, _ = tokenq.backbone(old, tok, base, interpret=True)
    assert np.isfinite(np.asarray(x)).all()
    assert float(jnp.max(jnp.abs(x - x0))) > 1e-4
    for bad in ({"hidden_act": "gelu"}, {"router_input": "post_mixer"}):
        with pytest.raises(ValueError):
            tokenq.layer_plan(dataclasses.replace(net.tokenq, **bad))


def test_layer_types_can_be_set_from_the_command_line():
    """``--set net.tokenq.layer_types=conv,full_attention``: the field's
    default is an EMPTY tuple, which has no first element to take the
    type from; its entries are strings."""
    cfg = apply_overrides(PRESETS["tokenq"](), [
        "net.tokenq.layer_types=conv,full_attention,conv,conv",
        "net.tokenq.rope_layout=1,1,1,1", "net.tokenq.hidden_act=silu",
        "net.tokenq.qk_norm=true"])
    tq = cfg.net.tokenq
    assert tq.layer_types == ("conv", "full_attention", "conv", "conv")
    assert (tq.rope_layout, tq.hidden_act, tq.qk_norm) == (
        (1, 1, 1, 1), "silu", True)
    assert [k["conv"] for k in tokenq.layer_plan(tq)] == [
        True, False, True, True]


def test_the_smallthinker_preset_keeps_its_leaves():
    cfg = PRESETS["smallthinker_tokenq"]()
    shapes = tokenq.param_shapes(cfg.net)
    assert _count(shapes) == 370_547_200
    assert set(shapes["layer_00"]) == {
        "norm_1", "norm_2", "w_router", "w_q", "w_k", "w_v", "w_o",
        "w_gate", "w_up", "w_down"}
    assert all(shapes[f"layer_{i:02d}"] == shapes["layer_00"]
               for i in range(4))
    assert not any(k["conv"] or k["dense"]
                   for k in tokenq.layer_plan(cfg.net.tokenq))
