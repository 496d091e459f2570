"""NVIDIA-Nemotron-3-Nano-30B-A3B's block on the token-window Q-network
(``net.kind = "tokenq"``, ``model_type`` nemotron_h) at toy sizes on the
CPU: h 64, the cell's own seven layers ``MEMEM*E`` (Mamba-2 mixers of 4
heads of 16, state 16, 2 groups, a convolution of 4 taps, chunks of 8 in
segments of 16; attention of 4 / 2 heads of 16 with no positional
embedding; 8 two-matrix relu² experts of width 40 top 2 behind a sigmoid
router with a selection bias and gates x 2.5, a shared expert of 48),
vocabulary 64, T 24 — the program against
``benchmark/reference/nemotron.py`` (plain jax.numpy float32, the
SEQUENTIAL recurrence, imports nothing of the program), the new operators
one by one, the layer plan, the share, and the family's counts.
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

from benchmark.families.nemotron import check, counts  # noqa: E402
from benchmark.reference import nemotron as ref  # noqa: E402
from distributed_deep_q_tpu.config import (  # noqa: E402
    PRESETS, TokenQConfig, apply_overrides)
from distributed_deep_q_tpu.models import tokenq  # noqa: E402
from distributed_deep_q_tpu.ops import moe, ssd  # noqa: E402
from distributed_deep_q_tpu.parallel.sequence_learner import (  # noqa: E402
    SequenceSolver)

T, V, SEED = 24, 64, 7
F32 = jnp.float32
PATTERN = "MEMEM*E"


def toy_cfg(**tq):
    cfg = PRESETS["tokenq"]()
    cfg.mesh.backend = "cpu"
    cfg.mesh.num_fake_devices = 1
    apply_overrides(cfg, ["replay.batch_size=2", "replay.fused_chain=2",
                          f"train.seed={SEED}"])
    cfg.net.tokenq = dataclasses.replace(TokenQConfig(
        hidden_size=64, num_hidden_layers=7,
        hybrid_override_pattern=PATTERN + "MEMEM*E", num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-5,
        sliding_window_layout=(0,) * 7, rope_layout=(0,) * 7,
        mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16, n_groups=2,
        conv_kernel=4, chunk_size=8, ssm_segment=16, hidden_act="relu2",
        ffn_gated=False, moe_primary_router_apply_softmax=False,
        use_expert_bias=True, router_input="ffn_norm",
        moe_ffn_hidden_size=40, moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, experts_held=8,
        routed_scaling_factor=2.5, n_shared_experts=1,
        moe_shared_expert_intermediate_size=48,
        # 50 tokens a step in blocks of 16: the shared expert and the head
        # pad their last block; 25 rows pad the chunks and the segments
        head_block=16, moe_tile=8), **tq)
    return cfg


def toy_hp(cfg, **over):
    tq = cfg.net.tokenq
    n = tq.num_hidden_layers
    hp = {
        "hidden_size": tq.hidden_size, "num_hidden_layers": n,
        "pattern": tq.hybrid_override_pattern[:n],
        "mamba_num_heads": tq.mamba_num_heads,
        "mamba_head_dim": tq.mamba_head_dim,
        "ssm_state_size": tq.ssm_state_size, "n_groups": tq.n_groups,
        "conv_kernel": tq.conv_kernel, "chunk_size": tq.chunk_size,
        "num_attention_heads": tq.num_attention_heads,
        "num_key_value_heads": tq.num_key_value_heads,
        "head_dim": tq.head_dim, "rms_norm_eps": tq.rms_norm_eps,
        "moe_intermediate_size": tq.moe_ffn_hidden_size,
        "moe_shared_expert_intermediate_size":
            tq.moe_shared_expert_intermediate_size,
        "router_experts": tq.moe_num_primary_experts,
        "experts_held": tq.experts_held, "expert_offset": tq.expert_offset,
        "num_experts_per_tok": tq.moe_num_active_primary_experts,
        "use_expert_bias": tq.use_expert_bias, "norm_topk_prob": True,
        "mlp_hidden_act": tq.hidden_act,
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


# ---- the chunked scan against the sequential recurrence --------------------

def _scan_inputs(t, b=2, h=4, p=8, g=2, n=16, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), F32)
    dt = jax.nn.softplus(normal(b, t, h) - 2.0)
    a = -jnp.exp(normal(h))
    return (normal(b, t, h, p), dt, a, normal(b, t, g, n), normal(b, t, g, n),
            normal(h))


def _chunked(chunk):
    def run(x, dt, a, bm, cm, d):
        b, _, h, p = x.shape
        g, n = bm.shape[2:]
        state = jnp.zeros((b, g, h // g, p, n), F32)
        return ssd.ssd_scan(x, dt, a, bm, cm, d, state, chunk=chunk,
                            dtype=F32)[0]
    return run


def _sequential(x, dt, a, bm, cm, d):
    h, g = x.shape[2], bm.shape[2]
    group = jnp.arange(h) // (h // g)
    return jax.vmap(lambda x, dt, bm, cm: ref.recurrence(
        x, dt, a, bm, cm, d, group))(x, dt, bm, cm)


@pytest.mark.parametrize("t,chunk", [(32, 8), (37, 8), (20, 64), (70, 16)],
                         ids=["divides", "does_not_divide", "one_chunk",
                              "many_chunks_past_a_scan_block"])
def test_the_chunked_scan_is_the_sequential_recurrence(t, chunk):
    """Forward and every gradient: a window the chunk divides, one it
    does not (rows of Δ = 0 pass the state on), one chunk, many."""
    args = _scan_inputs(t)
    with jax.default_matmul_precision("highest"):
        y, gold = _chunked(chunk)(*args), _sequential(*args)
        np.testing.assert_allclose(y, gold, atol=2e-5)
        g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(_chunked(chunk)(*a))),
                      argnums=range(6))(*args)
        g0 = jax.grad(lambda *a: jnp.sum(jnp.sin(_sequential(*a))),
                      argnums=range(6))(*args)
    for got, want in zip(g1, g0):
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(got / scale, want / scale, atol=2e-5)


def test_a_carried_state_and_tail_continue_the_window():
    """Two halves, the state and the convolution's last rows handed on,
    are the whole window."""
    x, dt, a, bm, cm, d = _scan_inputs(40)
    b, _, h, p = x.shape
    g, n = bm.shape[2:]
    zero = jnp.zeros((b, g, h // g, p, n), F32)
    kw = dict(chunk=8, dtype=F32)
    with jax.default_matmul_precision("highest"):
        whole, end = ssd.ssd_scan(x, dt, a, bm, cm, d, zero, **kw)
        y1, s1 = ssd.ssd_scan(x[:, :24], dt[:, :24], a, bm[:, :24],
                              cm[:, :24], d, zero, **kw)
        y2, s2 = ssd.ssd_scan(x[:, 24:], dt[:, 24:], a, bm[:, 24:],
                              cm[:, 24:], d, s1, **kw)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), whole,
                               atol=2e-5)
    np.testing.assert_allclose(s2, end, atol=2e-5)
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.standard_normal((2, 40, 6)), F32)
    w = jnp.asarray(rng.standard_normal((6, 4)), F32)
    bias = jnp.asarray(rng.standard_normal(6), F32)
    tail = jnp.zeros((2, 3, 6), F32)
    whole, _ = ssd.causal_conv(u, w, bias, tail)
    c1, tail = ssd.causal_conv(u[:, :24], w, bias, tail)
    c2, _ = ssd.causal_conv(u[:, 24:], w, bias, tail)
    np.testing.assert_allclose(jnp.concatenate([c1, c2], 1), whole,
                               atol=1e-6)
    # causal: row t reads rows t-3..t, the first rows zeros before them
    lead = jnp.pad(u, ((0, 0), (3, 0), (0, 0)))
    gold = jax.nn.silu(sum(w[:, j] * lead[:, j:j + 40] for j in range(4))
                       + bias)
    np.testing.assert_allclose(whole, gold, atol=1e-6)


# ---- the program against the reference -------------------------------------

def test_one_norm_leaves_round_trip_through_weight_io(solver_and_hp):
    solver, hp, _ = solver_and_hp
    named = solver.get_named_weights()
    assert {k: v.shape for k, v in named.items()} == ref.leaf_shapes(hp)
    assert named["layer_00/w_in"].shape == (64, 64 + (64 + 2 * 2 * 16) + 4)
    assert named["layer_00/ssm_conv_w"].shape == (128, 4)
    assert named["layer_00/gate_norm"].shape == (64,)
    assert named["layer_05/w_q"].shape == (64, 64)
    assert named["layer_01/w_up"].shape == (8, 64, 40)
    assert named["layer_01/shared_down"].shape == (48, 64)
    # ONE norm a layer; no gate matrix anywhere
    assert "layer_00/norm_2" not in named and "layer_01/norm_1" not in named
    assert not [k for k in named if k.endswith(("w_gate", "shared_gate"))]
    solver.set_named_weights(named)
    again = solver.get_named_weights()
    assert all(np.array_equal(again[k], named[k]) for k in named)


def test_q_at_every_position_matches_the_reference(solver_and_hp):
    solver, hp, cfg = solver_and_hp
    w = ref.init_weights(SEED, hp)
    tok = ref.seeded_windows(1, 0, hp)[0][0]
    hid, counters = tokenq.backbone(solver.state.params, tok[None], cfg.net,
                                    interpret=True)
    assert counters["slots"].shape == (3,)          # the expert layers
    assert counters["ssm_dt_mean"].shape == (3,)    # the Mamba layers
    q = hid[0] @ solver.state.params["head"]
    with jax.default_matmul_precision("highest"):
        gold = ref.q_values(as_jnp(w), jnp.asarray(tok), hp)
        dts = ref.hidden(as_jnp(w), jnp.asarray(tok), hp, None)[2]
    np.testing.assert_allclose(np.asarray(q), np.asarray(gold), atol=2e-5)
    np.testing.assert_allclose(counters["ssm_dt_mean"], dts, rtol=1e-5)
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
    np.testing.assert_allclose(float(metrics["ssm_dt_mean"]),
                               float(gm["ssm_dt_mean"]), rtol=1e-5)
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
    for k in ("head", "layer_00/w_in", "layer_00/ssm_conv_w",
              "layer_00/ssm_conv_b", "layer_02/a_log", "layer_02/dt_bias",
              "layer_04/d_skip", "layer_04/gate_norm", "layer_04/w_out",
              "layer_05/w_q", "layer_05/w_o", "layer_01/w_up",
              "layer_03/w_down", "layer_06/shared_up", "layer_01/w_router",
              "embed"):
        big = np.abs(np.asarray(gold["m"][k])) > 1e-7
        assert big.any(), k
        np.testing.assert_allclose(np.asarray(theta[k])[big],
                                   np.asarray(gold["theta"][k])[big],
                                   atol=2e-6, err_msg=k)
    # no gradient reaches the selection bias: Adam leaves it where it is
    for i in (1, 3, 6):
        k = f"layer_{i:02d}/expert_bias"
        assert not np.asarray(mu[k]).any()
        assert np.array_equal(np.asarray(theta[k]), seeded[k])


def test_the_reference_a_layer_at_a_time_is_its_whole_program(
        solver_and_hp):
    """``grad_one`` (what ``make_step`` runs: a compiled forward and
    backward a KIND of layer, the chain rule between layers written out)
    against ``jax.value_and_grad(sequence_loss)``."""
    _, hp, _ = solver_and_hp
    w = as_jnp(ref.init_weights(SEED, hp))
    tg = as_jnp(ref.init_weights(SEED + 1, hp))
    batch = seeded_batch(hp, 2)
    seq = {k: jnp.asarray(batch[k][1]) for k in
           ("tokens", "reward", "discount", "mask")}
    seq["scale"] = jnp.asarray(0.4, F32)
    with jax.default_matmul_precision("highest"):
        (loss, (prio, q_sum, share, dt)), g = jax.value_and_grad(
            ref.sequence_loss, has_aux=True)(w, tg, seq, hp, None)
    (loss1, (prio1, q_sum1, share1, dt1)), g1 = ref.grad_one(w, tg, seq, hp)
    np.testing.assert_allclose(loss1, loss, rtol=1e-6)
    np.testing.assert_allclose(prio1, prio, rtol=1e-6)
    np.testing.assert_allclose(q_sum1, q_sum, rtol=1e-5)
    np.testing.assert_array_equal(share1, share)
    np.testing.assert_allclose(dt1, dt, rtol=1e-6)
    assert set(g1) == set(g)
    for k in g:
        scale = float(jnp.abs(g[k]).max()) + 1e-12
        np.testing.assert_allclose(g1[k] / scale, g[k] / scale, atol=2e-5,
                                   err_msg=k)


@pytest.mark.parametrize("fault,moves", [
    ({"fault": "gate_norm_whole"}, True), ({"fault": "head_group_mod"}, True),
    ({"fault": "no_conv_bias"}, True), ({"mlp_hidden_act": "relu"}, True),
    ({"routed_scaling_factor": 1.0}, True), ({}, False)],
    ids=["gate_norm_whole", "head_group_mod", "no_conv_bias", "relu",
         "gates_not_scaled", "sound"])
def test_a_planted_fault_moves_the_reference(solver_and_hp, fault, moves):
    """Each fault ``families/nemotron/faults.py`` plants changes Q at the
    toy size (a fault that read like the sound model would prove
    nothing)."""
    _, hp, _ = solver_and_hp
    w = as_jnp(ref.init_weights(SEED, hp))
    tok = jnp.asarray(ref.seeded_windows(1, 0, hp)[0][0])
    with jax.default_matmul_precision("highest"):
        sound = ref.q_values(w, tok, hp)
        got = ref.q_values(w, tok, {**hp, **fault})
    gap = float(jnp.abs(got - sound).max() / jnp.abs(sound).max())
    assert (gap > 1e-3) == moves, gap


# ---- the layer plan --------------------------------------------------------

def test_a_layer_plan_from_a_pattern_string():
    tq = toy_cfg().net.tokenq
    plan = tokenq.layer_plan(tq)
    assert [(k["mamba"], k["mixer"], k["ffn"], k["dense"]) for k in plan] \
        == [{"M": (True, True, False, False), "E": (False, False, True, False),
             "*": (False, True, False, False)}[c] for c in PATTERN]
    shapes = tokenq.param_shapes(toy_cfg().net)
    for i, c in enumerate(PATTERN):     # one norm each
        names = set(shapes[tokenq.layer_name(i)])
        assert ("norm_1" in names, "norm_2" in names) == (c != "E", c == "E")
    for bad in (dict(hybrid_override_pattern="MEXEM*E"),      # a bad letter
                dict(hybrid_override_pattern="ME-EM*E"),      # dense alone
                dict(hybrid_override_pattern="MEM"),           # too short
                dict(router_input="pre_mixer"),
                dict(layer_types=("conv",) * 7),
                dict(num_dense_layers=1, intermediate_size=8),
                dict(n_groups=3)):
        with pytest.raises(ValueError):
            tokenq.layer_plan(dataclasses.replace(tq, **bad))
    with pytest.raises(ValueError):
        tokenq.layer_plan(dataclasses.replace(tq, hidden_act="relu3"))
    # the state-space mixer comes from the pattern alone (a layer with it
    # has no feed-forward): ``layer_types`` cannot name it
    with pytest.raises(ValueError, match="layer_types must name"):
        tokenq.layer_plan(dataclasses.replace(
            tq, hybrid_override_pattern="", layer_types=("mamba",) * 7))


OLDER = ("tokenq", "smallthinker_tokenq", "lfm2_tokenq", "keye_tokenq",
         "moonlight_tokenq", "laguna_tokenq", "sdar_tokenq")
OLDER_LEAVES = os.path.join(os.path.dirname(__file__), "fixtures",
                            "token_preset_leaves_at_pr47.json")


@pytest.mark.parametrize("preset", OLDER)
def test_an_older_presets_leaves_are_what_they_were(preset):
    """``param_shapes`` and the leaf names of the presets that were there
    before this family, as the parent commit gave them (the fixture was
    written there): what the family added is data whose defaults are what
    they ran — two norms and a mixer AND a feed-forward a layer, a gate
    matrix in every feed-forward."""
    cfg = PRESETS[preset]()
    tq = cfg.net.tokenq
    assert (tq.hybrid_override_pattern, tq.ffn_gated, tq.ssm_segment,
            tq.moe_shared_expert_intermediate_size) == ("", True, 0, 0)
    assert all(k["mixer"] and k["ffn"] and not k["mamba"]
               for k in tokenq.layer_plan(tq))
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tokenq.param_shapes(cfg.net), is_leaf=lambda x: isinstance(x, tuple))
    now = {"/".join(str(k.key) for k in path): list(shape)
           for path, shape in flat}
    with open(OLDER_LEAVES) as fh:
        assert now == json.load(fh)[preset]


# ---- the two-matrix walk ---------------------------------------------------

def test_fit_never_hands_the_kernel_a_tile_off_the_lanes():
    assert moe._fit(1856) == 384 and -(-1856 // 384) * 384 == 1920
    assert [moe._fit(d) for d in (512, 768, 1408, 1536, 2048, 2560, 2688)] \
        == [512, 384, 128, 512, 512, 512, 384]      # as before
    assert moe._fit(200) == 128 and moe._fit(40) == 40
    assert all(moe._fit(d) % 128 == 0 for d in range(128, 4097, 8))


@pytest.mark.parametrize("f", [200, 40], ids=["no_128_divides", "toy"])
def test_the_two_matrix_relu2_walk_is_a_dense_loop(f):
    """``held_experts_ffn`` without a gate matrix against a loop over the
    held experts, forward and gradients, at a width no multiple of 128
    divides (the up product reads its weights transposed and the last
    tile overhangs) and at a toy's."""
    rng = np.random.default_rng(0)
    h, e, held, off, k, b, t = 64, 8, 3, 2, 2, 2, 24
    x = jnp.asarray(rng.standard_normal((b, t, h)), F32)
    wr = jnp.asarray(rng.standard_normal((h, e)), F32)
    wu = jnp.asarray(rng.standard_normal((held, h, f)) * 0.1, F32)
    wd = jnp.asarray(rng.standard_normal((held, f, h)) * 0.1, F32)
    act = tokenq.ACTS["relu2"]
    idx, p = moe.route(x.reshape(-1, h), wr, k, softmax=False,
                       bias=jnp.zeros(e), scale=2.5)

    def walk(x, wu, wd):
        return moe.held_experts_ffn(
            x, idx, p, None, wu, wd, offset=off,
            rows=moe.buffer_rows(b * t, k, held, 8), tile=8,
            compute_dtype=F32, interpret=True, act=act)[0]

    def loop(x, wu, wd):
        flat = x.reshape(-1, h)
        y = jnp.zeros_like(flat)
        for j in range(held):
            g = jnp.sum(jnp.where(idx == j + off, p, 0.0), -1)
            y = y + g[:, None] * (act(flat @ wu[j]) @ wd[j])
        return y.reshape(x.shape)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(walk(x, wu, wd), loop(x, wu, wd),
                                   atol=1e-5)
        g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(walk(*a))), (0, 1, 2))(
            x, wu, wd)
        g0 = jax.grad(lambda *a: jnp.sum(jnp.sin(loop(*a))), (0, 1, 2))(
            x, wu, wd)
    for got, want in zip(g1, g0):
        np.testing.assert_allclose(got, want, atol=1e-4)


# ---- the share -------------------------------------------------------------

def test_the_sixteen_shares_and_the_shared_expert_once_are_the_layer():
    """The routed parts of every share of an expert layer (here 4 shares
    of 2 experts) plus the shared expert ONCE add up to the uncut layer."""
    cfg = toy_cfg()
    hp = toy_hp(cfg)
    w = as_jnp(ref.init_weights(SEED, hp))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((T + 1, 64)), F32)
    pre = "layer_01/"
    with jax.default_matmul_precision("highest"):
        whole = ref.layer(x, w, pre, "E", hp, None)[0] - x
        u = ref.rmsnorm(x, w[pre + "norm_2"], hp["rms_norm_eps"])
        shared = ref.shared_expert(u, w, pre, hp, None)
        parts, shares = shared, 0.0
        for off in range(0, 8, 2):
            part = {**hp, "experts_held": 2, "expert_offset": off}
            cut = {**w, pre + "w_up": w[pre + "w_up"][off:off + 2],
                   pre + "w_down": w[pre + "w_down"][off:off + 2]}
            y, share, _ = ref.layer(x, cut, pre, "E", part, None)
            parts = parts + (y - x - shared)
            shares += float(share)
    np.testing.assert_allclose(parts, whole, atol=1e-5)
    assert abs(shares - 1.0) < 1e-6
    # and the program's share of it: experts 2-3 through the walk
    cut_cfg = toy_cfg(experts_held=2, expert_offset=2)
    p = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
    p.update(w_up=p["w_up"][2:4], w_down=p["w_down"][2:4])
    y, _ = tokenq.feed_forward(x[None], p, cut_cfg.net, True)
    part = {**hp, "experts_held": 2, "expert_offset": 2}
    cut = {**w, pre + "w_up": p["w_up"], pre + "w_down": p["w_down"]}
    with jax.default_matmul_precision("highest"):
        gold = ref.layer(x, cut, pre, "E", part, None)[0]
    np.testing.assert_allclose(y[0], gold, atol=2e-5)


# ---- the configuration, the counts, the preset -----------------------------

def _conf():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano_30b_tokenq_ep16.json")) as fh:
        return json.load(fh)


def test_the_configuration_is_what_the_preset_runs():
    from benchmark import program
    conf = _conf()
    cfg = program.make_cfg(conf, 0, "cpu", [])
    check.assert_hparams(conf, cfg)
    hp = conf["hparams"]
    shapes = ref.leaf_shapes(hp)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 528_093_120
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tokenq.param_shapes(cfg.net), is_leaf=lambda x: isinstance(x, tuple))
    assert {"/".join(str(k.key) for k in path): shape
            for path, shape in flat} == shapes
    assert conf["hybrid_override_pattern"][:35] == hp["pattern"] * 5
    for wrong in ({"pattern": "MEMEME*"}, {"n_groups": 4},
                  {"chunk_size": 256}, {"conv_kernel": 3},
                  {"mlp_hidden_act": "relu"}, {"mamba_num_heads": 32},
                  {"ssm_state_size": 64}, {"routed_scaling_factor": 1.0},
                  {"experts_held": 16}, {"fault": "no_conv_bias"}):
        bad = {**conf, "hparams": {**hp, **wrong}}
        with pytest.raises(SystemExit):
            check.assert_hparams(bad, cfg)


def test_counts_at_the_published_sizes():
    hp = _conf()["hparams"]
    tokens = 2 * 8192
    assert counts.layers(hp, "M") == counts.layers(hp, "E") == 3
    assert counts.layers(hp, "*") == 1
    # two products at the published 1 856, never the tiles' 1 920
    assert counts.expert_ffn_flops(hp) == 4 * 3 * 4 * 2688 * 1856 * (
        tokens * 6 * 8 / 128)
    per_token = counts.ssm_scan_flops(hp) / (4 * tokens * 3)
    assert per_token == 64.5 * (2 * 128 * 8 + 2 * 64 * 64) \
        + 4 * 64 * 128 * 64
    per_token_bytes = counts.ssm_scan_bytes(hp) / (tokens * 3)
    assert per_token_bytes == 4 * (2 * (6208 + 4096) + 6208 + 4096 + 6208)
    # the scan is bound by BYTES on this chip: the metric's ``bound``
    assert (counts.ssm_scan_bytes(hp) / 819e9
            > counts.ssm_scan_flops(hp) / 197e12)
    shares = counts.train_flop_shares(hp)
    assert abs(sum(shares.values()) - 1.0) < 1e-9
    mamba = sum(shares[k] for k in ("ssm_projections", "ssm_conv",
                                    "ssm_scan"))
    assert mamba > max(shares["attention_kernel"]
                       + shares["attention_projections"],
                       shares["experts_held"] + shares["shared_expert"],
                       shares["head"])
    assert counts.expected_slots_held_share(hp) == 6.25


def test_main_train_runs_the_nemotron_preset_from_the_command_line():
    """``main train --preset nemotron_tokenq`` at the family's toy widths:
    the normal path (``train.train_tokenq`` → ``SequenceSolver`` → the
    fused token step), the preset's own pattern and two-matrix experts."""
    cmd = [sys.executable, "-m", "distributed_deep_q_tpu.main", "train",
           "--preset", "nemotron_tokenq", "--backend", "cpu", "--set",
           *check.TOY_OVERRIDES, "net.tokenq.experts_held=8",
           "net.tokenq.expert_offset=0", "replay.batch_size=2",
           "replay.learn_start=240", "train.train_every=48",
           "train.total_steps=600", "env.max_episode_steps=48",
           "actors.eps_decay_steps=300"]
    done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    summary = json.loads(done.stdout.strip().splitlines()[-1])
    assert summary["mode"] == "train" and summary["grad_steps"] >= 4
