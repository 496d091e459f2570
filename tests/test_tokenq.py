"""The token-window Q-network (``net.kind = "tokenq"``) at toy sizes on the
CPU: h 64, 4 layers with layout [0,1,1,1], window 8 on T 24, 8 experts top
2, vocabulary 64 — the program against ``benchmark/reference/tokenq.py``
(plain jax.numpy float32, imports nothing of the program), the expert
layer's share arithmetic, the token ring, and the benchmark's counts.
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

from benchmark.families.tokenq import counts  # noqa: E402
from benchmark.reference import tokenq as ref  # noqa: E402
from distributed_deep_q_tpu.config import (  # noqa: E402
    PRESETS, apply_overrides)
from distributed_deep_q_tpu.models import tokenq  # noqa: E402
from distributed_deep_q_tpu.ops import moe  # noqa: E402
from distributed_deep_q_tpu.ops.attention import causal_attention  # noqa: E402
from distributed_deep_q_tpu.parallel.sequence_learner import (  # noqa: E402
    SequenceSolver)
from distributed_deep_q_tpu.replay.device_tokens import (  # noqa: E402
    DeviceTokenReplay)

T, V, SEED = 24, 64, 5


def toy_cfg(**tq):
    cfg = PRESETS["tokenq"]()
    cfg.mesh.backend = "cpu"
    cfg.mesh.num_fake_devices = 1
    apply_overrides(cfg, ["replay.batch_size=2", "replay.fused_chain=2",
                          f"train.seed={SEED}"])
    cfg.net.tokenq = dataclasses.replace(cfg.net.tokenq, **tq)
    return cfg


def toy_hp(cfg, **over):
    tq = cfg.net.tokenq
    n = tq.num_hidden_layers
    hp = {
        "hidden_size": tq.hidden_size, "num_hidden_layers": n,
        "num_attention_heads": tq.num_attention_heads,
        "num_key_value_heads": tq.num_key_value_heads,
        "head_dim": tq.head_dim, "rms_norm_eps": tq.rms_norm_eps,
        "sliding_window_layout": list(tq.sliding_window_layout[:n]),
        "rope_layout": list(tq.rope_layout[:n]),
        "sliding_window_size": tq.sliding_window_size,
        "rope_theta": tq.rope_theta,
        "moe_ffn_hidden_size": tq.moe_ffn_hidden_size,
        "moe_router_experts": tq.moe_num_primary_experts,
        "moe_experts_held": tq.experts_held,
        "expert_offset": tq.expert_offset,
        "moe_num_active_primary_experts":
            tq.moe_num_active_primary_experts,
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
        "priority_alpha": cfg.replay.priority_alpha,
        "priority_eps": cfg.replay.priority_eps,
        "priority_beta0": cfg.replay.priority_beta0,
        "priority_beta_steps": cfg.replay.priority_beta_steps,
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


@pytest.fixture(scope="module")
def solver_and_hp():
    cfg = toy_cfg()
    solver = SequenceSolver(cfg)
    hp = toy_hp(cfg)
    solver.set_named_weights(ref.init_weights(SEED, hp))
    return solver, hp, cfg


def test_leaf_names_are_the_references(solver_and_hp):
    solver, hp, _ = solver_and_hp
    named = solver.get_named_weights()
    assert {k: v.shape for k, v in named.items()} == ref.leaf_shapes(hp)
    # weight IO by name: a round trip through the names changes nothing
    solver.set_named_weights(named)
    again = solver.get_named_weights()
    assert all(np.array_equal(again[k], named[k]) for k in named)
    with pytest.raises(KeyError):
        solver.set_named_weights({k: v for k, v in named.items()
                                  if k != "head"})


def test_q_at_every_position_matches_the_reference(solver_and_hp):
    solver, hp, cfg = solver_and_hp
    w = ref.init_weights(SEED, hp)
    tok = ref.seeded_windows(1, 0, hp)[0][0]
    hid, _ = tokenq.backbone(solver.state.params, tok[None], cfg.net,
                             interpret=True)
    q = hid[0] @ solver.state.params["head"]
    with jax.default_matmul_precision("highest"):
        gold = ref.q_values({k: jnp.asarray(v) for k, v in w.items()},
                            jnp.asarray(tok), hp)
    np.testing.assert_allclose(np.asarray(q), np.asarray(gold), atol=2e-5)
    # the acting path reads the same Q at the prefix's end, padding or not
    q5 = solver.token_q_values(tok[:6])
    np.testing.assert_allclose(q5, np.asarray(gold)[5], atol=2e-5)


def test_one_step_loss_gradients_adam_and_target(solver_and_hp):
    """Loss, priorities, gradients by leaf (through Adam's first moment),
    θ after one Adam step and θ⁻, element for element."""
    solver, hp, cfg = solver_and_hp
    batch = seeded_batch(hp, 2)
    learner = solver.learner
    core = jax.jit(shard_map(
        learner._token_step_core, mesh=solver.mesh,
        in_specs=(P(), P("dp")), out_specs=(P(), P(), P("dp")),
        check_vma=False))
    state, metrics, priority = core(solver.state, batch)

    w = {k: jnp.asarray(v) for k, v in ref.init_weights(SEED, hp).items()}
    gold, gm, gprio = ref.make_step(hp)(
        ref.init_state(w, {k: jnp.array(v) for k, v in w.items()}),
        {k: jnp.asarray(v) for k, v in batch.items()})
    assert abs(float(metrics["loss"]) - float(gm["loss"])) < 1e-5
    assert abs(float(metrics["q_mean"]) - float(gm["q_mean"])) < 1e-6
    np.testing.assert_allclose(np.asarray(priority), np.asarray(gprio),
                               rtol=1e-5)
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
    # Adam's first step is sign-like: where the gradient is not tiny the
    # new θ is the reference's
    for k in ("head", "layer_01/w_q", "layer_02/w_gate", "embed"):
        big = np.abs(np.asarray(gold["m"][k])) > 1e-7
        np.testing.assert_allclose(np.asarray(theta[k])[big],
                                   np.asarray(gold["theta"][k])[big],
                                   atol=2e-6, err_msg=k)


def _attn_ref(q, k, v, window):
    return jnp.stack([ref.attention(q[i], k[i], v[i], window, None,
                                    q_block=64) for i in range(q.shape[0])])


@pytest.mark.parametrize("window", [0, 40])
def test_attention_kernel_forward_backward_interpret(window):
    """The blockwise kernel in interpret mode against the reference, T not
    a multiple of the block (150 tokens, block 128: two blocks)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 4, 150, 16))
    k = jax.random.normal(ks[1], (1, 2, 150, 16))
    v = jax.random.normal(ks[2], (1, 2, 150, 16))
    out = causal_attention(q, k, v, window=window, interpret=True)
    np.testing.assert_allclose(out, _attn_ref(q, k, v, window), atol=2e-5)
    f = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2))(q, k, v)
    got = f(lambda *a: causal_attention(*a, window=window, interpret=True))
    want = f(lambda *a: _attn_ref(*a, window))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_window_layer_differs_from_full_exactly_where_the_mask_says():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (1, 2, 24, 16))
    k = jax.random.normal(ks[1], (1, 2, 24, 16))
    v = jax.random.normal(ks[2], (1, 2, 24, 16))
    full = causal_attention(q, k, v, window=0, interpret=True)
    win = causal_attention(q, k, v, window=8, interpret=True)
    same = np.abs(np.asarray(full - win)).max(axis=(0, 1, 3)) < 1e-6
    # query t sees every causal key while t < 8; from t = 8 on it loses some
    assert same[:8].all() and not same[8:].any()


def _moe_inputs(n=96, h=64, f=32, e=8, bias_to=None):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (n, h))
    wr = jax.random.normal(ks[1], (h, e)) * 0.3
    if bias_to is not None:     # a router skewed towards one expert
        x = x.at[:, 0].set(4.0)
        wr = wr.at[0, bias_to].set(3.0)
    wg = jax.random.normal(ks[2], (e, h, f)) * 0.1
    wu = jax.random.normal(ks[3], (e, h, f)) * 0.1
    wd = jax.random.normal(ks[4], (e, f, h)) * 0.1
    return x, wr, wg, wu, wd


def _dense_share(x, wr, wg, wu, wd, lo, hi, k=2):
    gate = ref.route(x, wr, k)
    return sum(gate[:, e:e + 1] * ((jax.nn.relu(x @ wg[e]) * (x @ wu[e]))
                                   @ wd[e]) for e in range(lo, hi))


def _held(x, wr, wg, wu, wd, lo, hi, rows=None, k=2):
    """``rows`` None: the layer's own worst-case buffer."""
    idx, p = moe.route(x, wr, k)
    rows = rows or moe.buffer_rows(x.shape[0], k, hi - lo, 8)
    return moe.held_experts_ffn(
        x, idx, p, wg[lo:hi], wu[lo:hi], wd[lo:hi], offset=lo, rows=rows,
        tile=8, compute_dtype=jnp.float32, interpret=True)


def test_shares_of_one_expert_layer_add_up_to_the_uncut_layer():
    """THE share test: the partial results of all 8 shares (1 expert
    each), with the residual counted once, are the uncut reference's
    layer."""
    cfg = toy_cfg(experts_held=1)
    hp = toy_hp(toy_cfg())          # the uncut layer: all 8 held
    w = ref.init_weights(SEED, hp)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, T + 1, 64))
    pre = "layer_01/"
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.layer(x[0], {k: jnp.asarray(v) for k, v in w.items()},
                             1, hp, None)
    lp = {k[len(pre):]: jnp.asarray(v) for k, v in w.items()
          if k.startswith(pre)}
    zero = {**lp, "w_down": jnp.zeros_like(lp["w_down"])[:1],
            "w_gate": lp["w_gate"][:1], "w_up": lp["w_up"][:1]}
    residual, _ = tokenq.layer(x, zero, cfg.net, True, True, True)
    total = residual
    for e in range(8):
        net = dataclasses.replace(cfg.net, tokenq=dataclasses.replace(
            cfg.net.tokenq, expert_offset=e))
        share = {**lp, **{n: lp[n][e:e + 1]
                          for n in ("w_gate", "w_up", "w_down")}}
        out, c = tokenq.layer(x, share, net, True, True, True)
        total = total + (out - residual)
        assert int(c["overflow"]) == 0
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(whole),
                               atol=2e-5)


@pytest.mark.parametrize("rows", [None, 144], ids=["worst_case", "cut"])
def test_dropless_under_a_skewed_router(rows):
    """One held expert takes > 50 % of the held slots: nothing is dropped
    (the buffer has no per-expert capacity) and the counters say so — at
    the worst-case buffer (192 rows) and at one cut to what is held."""
    x, wr, wg, wu, wd = _moe_inputs(bias_to=1)
    y, c = _held(x, wr, wg, wu, wd, 0, 4, rows)
    load = np.asarray(c["load"])
    assert load[1] > 0.5 * load.sum() and int(c["overflow"]) == 0
    assert int(c["slots_held"]) == load.sum() and int(c["slots"]) == 192
    np.testing.assert_allclose(y, _dense_share(x, wr, wg, wu, wd, 0, 4),
                               atol=2e-5)


def test_a_cut_buffer_counts_what_it_could_not_hold():
    x, wr, wg, wu, wd = _moe_inputs(bias_to=1)
    _, c = _held(x, wr, wg, wu, wd, 0, 4, rows=48)      # 48 rows < 96 held
    assert int(c["overflow"]) == int(c["slots_held"]) - 48 > 0


def test_held_experts_gradients_match_dense():
    x, wr, wg, wu, wd = _moe_inputs()
    f = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4))(
        x, wr, wg, wu, wd)
    got = f(lambda *a: _held(*a, 2, 5)[0])
    want = f(lambda *a: _dense_share(*a, 2, 5))
    for g, w, name in zip(got, want, "x wr wg wu wd".split()):
        np.testing.assert_allclose(g, w, atol=2e-5, err_msg=name)


def _forced_inputs(first, second, h=64, f=32, e=8):
    """Inputs whose router picks expert ``first[i]`` and then ``second[i]``
    for token ``i``: the first ``e`` features of ``x`` name the two, and
    the router reads them by an identity."""
    first, second = np.asarray(first), np.asarray(second)
    x, wr, wg, wu, wd = _moe_inputs(len(first), h, f, e)
    x = x.at[:, :e].set(
        6.0 * jax.nn.one_hot(first, e) + 4.0 * jax.nn.one_hot(second, e))
    wr = (wr / 6.0).at[:e].set(2.0 * jnp.eye(e))
    return x, wr, wg, wu, wd


_I = np.arange(128)
# name: (first choice, second choice, experts held, load of the held);
# 128 tokens top 2 at m-tile 8: blocks of 128 rows
WALKS = {
    "no_slot_held": (0 * _I, 0 * _I + 1, (4, 8), [0, 0, 0, 0]),
    "every_slot_held": (_I % 8, (_I + 1) % 8, (0, 8), [32] * 8),
    "one_expert_takes_everything": (0 * _I + 1, 0 * _I + 5, (0, 4),
                                    [0, 128, 0, 0]),
    "group_boundary_on_a_block_edge": (0 * _I, np.where(_I < 64, 1, 5),
                                       (0, 4), [128, 64, 0, 0]),
    "last_block_held_by_one_row": (0 * _I, np.where(_I == 0, 1, 5), (0, 4),
                                   [128, 1, 0, 0]),
}


@pytest.mark.parametrize("case", list(WALKS))
def test_the_walk_is_exact_under_any_routing(case):
    """The blocks that hold a slot run, no other, and what they add up to
    is the dense share: value and all five gradients, nothing beyond the
    buffer, whatever the router does."""
    first, second, (lo, hi), load = WALKS[case]
    a = _forced_inputs(first, second)
    rows = moe.buffer_rows(128, 2, hi - lo, 8)
    block = moe.block_rows(rows, 8)
    assert (rows, block) == (256, 128)
    y, c = _held(*a, lo, hi)
    held = sum(load)
    assert list(np.asarray(c["load"])) == load
    assert int(c["slots_held"]) == held and int(c["overflow"]) == 0
    assert int(c["rows_run"]) == -(-held // block) * block
    if case == "every_slot_held":
        assert int(c["rows_run"]) == rows
    f = lambda fn: jax.grad(  # noqa: E731
        lambda *b: jnp.sum(jnp.sin(fn(*b))), argnums=(0, 1, 2, 3, 4))(*a)
    got = f(lambda *b: _held(*b, lo, hi)[0])
    want = f(lambda *b: _dense_share(*b, lo, hi))
    np.testing.assert_allclose(y, _dense_share(*a, lo, hi), atol=2e-5)
    for g, w, name in zip(got, want, "x wr wg wu wd".split()):
        # one expert's gradient sums 128 tokens here: entries of 1e2
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-5, err_msg=name)
    if not held:        # zero blocks ran: nothing, exactly
        assert not np.asarray(y).any()
        assert not any(np.asarray(g).any() for g in got)


def test_a_batch_is_walked_whole_and_adds_up_to_its_sequences():
    """``feed_forward`` sorts the whole batch's token-slots once: what it
    returns is each sequence's own layer, and its counters are the
    batch's."""
    cfg = toy_cfg(experts_held=4)
    w = ref.init_weights(SEED, toy_hp(toy_cfg()))
    lp = {k[len("layer_01/"):]: jnp.asarray(v) for k, v in w.items()
          if k.startswith("layer_01/")}
    lp = {**lp, **{n: lp[n][:4] for n in ("w_gate", "w_up", "w_down")}}
    x = jax.random.normal(jax.random.PRNGKey(3), (3, T + 1, 64))
    whole, c = tokenq.feed_forward(x, lp, cfg.net, True)
    parts = [tokenq.feed_forward(x[i:i + 1], lp, cfg.net, True)
             for i in range(3)]
    np.testing.assert_allclose(
        whole, jnp.concatenate([y for y, _ in parts]), atol=2e-5)
    for name in ("load", "slots_held", "slots"):
        assert np.array_equal(c[name], sum(ci[name] for _, ci in parts))
    assert int(c["overflow"]) == 0
    tile = cfg.net.tokenq.moe_tile
    block = moe.block_rows(moe.buffer_rows(3 * (T + 1), 2, 4, tile), tile)
    assert int(c["rows_run"]) == -(-int(c["slots_held"]) // block) * block


def test_vocabulary_slice_is_a_smaller_vocabulary(solver_and_hp):
    """Ids, argmax and loss are over the rows held: the head has V
    columns, the embedding V rows, and greedy actions lie in [0, V)."""
    solver, hp, cfg = solver_and_hp
    named = solver.get_named_weights()
    assert named["embed"].shape[0] == named["head"].shape[1] == V == \
        cfg.net.num_actions
    rng = np.random.default_rng(0)
    acts = [solver.token_act(rng.integers(0, V, 5), 0.0, rng)
            for _ in range(4)]
    assert all(0 <= a < V for a in acts)
    # a token outside the slice is not a row of this embedding
    assert ref.seeded_windows(0, 0, hp)[0].max() < V


def test_token_ring_round_trip_and_per_writeback(solver_and_hp):
    solver, hp, cfg = solver_and_hp
    solver = SequenceSolver(cfg)        # a fresh state: this test trains
    ring = DeviceTokenReplay(64, T, solver.mesh, cfg.train.gamma,
                             alpha=cfg.replay.priority_alpha,
                             eps=cfg.replay.priority_eps, write_chunk=16)
    tok, rew, done, valid = (a[:40] for a in ref.seeded_windows(2, 0, hp))
    ring.add_windows(tok, rew, done, valid)
    assert ring.ready(40) and len(ring) == 0 and ring.pending_rows() == 40
    ring.flush()
    assert len(ring) == 40 and ring.pending_rows() == 0
    assert np.array_equal(np.asarray(ring.ring["tokens"])[:40], tok)
    assert np.array_equal(np.asarray(ring.dmeta["prio"])[:41],
                          [1.0] * 40 + [0.0])

    from benchmark.families.tokenq.check import recording
    with recording(solver, ring, 2) as rec:
        m = solver.train_steps_device_per(ring, chain=2)
    batch, idx = rec.calls[0]
    idx = np.asarray(idx)
    assert idx.shape == (2, 2) and idx.max() < 40
    assert np.array_equal(np.asarray(batch["tokens"]), tok[idx])
    np.testing.assert_allclose(np.asarray(batch["reward"]), rew[idx])
    assert np.array_equal(np.asarray(batch["mask"]),
                          valid[idx].astype(np.float32))
    assert np.array_equal(np.asarray(batch["discount"]),
                          np.where(done[idx], 0.0, cfg.train.gamma
                                   ).astype(np.float32))
    assert np.isfinite(np.asarray(m["loss"])).all()
    assert np.asarray(m["moe_overflow"]).max() == 0
    assert (np.asarray(m["moe_rows_run"]) >= np.asarray(
        m["moe_slots_held"])).all()
    prio = np.asarray(ring.dmeta["prio"])
    drawn = np.zeros(64, bool)
    drawn[idx.reshape(-1)] = True
    assert (prio[:40][~drawn[:40]] == 1.0).all()        # untouched
    assert (prio[drawn] != 1.0).all() and (prio[drawn] > 0).all()
    assert float(ring.dmaxp) >= 1.0 and int(solver.state.step) == 2


def test_counts_against_a_hand_count():
    """4 tokens, window 2: full pairs 1+2+3+4 = 10, window pairs 1+2+2+2 =
    7; the rest by the formulas written out."""
    assert counts.causal_pairs(4) == 10 and counts.causal_pairs(4, 2) == 7
    hp = dict(sequence_length=3, batch_size=2, num_hidden_layers=2,
              sliding_window_layout=[0, 1], sliding_window_size=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=8,
              hidden_size=16, moe_ffn_hidden_size=8,
              moe_num_active_primary_experts=2, moe_experts_held=2,
              moe_router_experts=8, vocab_size=32)
    attn = 4 * 2 * (4 * 4 * 8 * 10 + 4 * 4 * 8 * 7)
    assert counts.window_attention_flops(hp) == attn
    slots = 2 * 4 * 2 * 2 / 8
    experts = 4 * 2 * (6 * 16 * 8) * slots
    assert counts.expert_ffn_flops(hp) == experts
    head = 4 * 2 * 4 * 2 * 16 * 32
    dense = 4 * 8 * 2 * (2 * 16 * (4 + 4) * 8 + 2 * 4 * 8 * 16 + 2 * 16 * 8)
    assert counts.train_flops_per_step(hp) == attn + experts + head + dense
    shares = counts.train_flop_shares(hp)
    assert abs(sum(shares.values()) - 1.0) < 1e-12


def test_token_window_builder_and_env():
    from distributed_deep_q_tpu.actors.game import TokenEnv
    from distributed_deep_q_tpu.train import TokenWindowBuilder

    env = TokenEnv(vocab=16, episode_len=5, seed=0)
    s = int(env.reset()[0])
    b = TokenWindowBuilder(3)
    b.reset(s)
    out, toks = [], [s]
    for _ in range(5):
        a = env.best_action(toks[-1])
        obs, r, done, over = env.step(a)
        assert r == 1.0 and int(obs[0]) == a
        toks.append(a)
        w = b.on_step(a, r, done, over)
        if w is not None:
            out.append(w)
    assert over and len(out) == 2
    assert list(out[0][0]) == toks[:4] and out[0][3].all()
    # the second window starts at the first's last token; 2 real steps
    assert list(out[1][0][:3]) == toks[3:6] and list(out[1][3]) == [
        True, True, False]
    assert list(out[1][2]) == [False, True, False]
