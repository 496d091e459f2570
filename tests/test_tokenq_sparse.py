"""Keye-VL-2.0's block on the token-window Q-network (``net.kind =
"tokenq"``, ``model_type`` KeyeVL2) at toy sizes on the CPU: h 64, sparse
attention (an indexer of 4 heads of 8 selecting a few keys a query, query
blocks of 32) with q/k norms and rope on every layer, SwiGLU experts behind
a softmax router — the program against ``benchmark/reference/keye.py``
(plain jax.numpy float32, imports nothing of the program), the new
operator's pieces one by one, the share test at 128-wide routing, and the
family's counts.
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

from benchmark.families.keye import counts  # noqa: E402
from benchmark.reference import keye as ref  # noqa: E402
from benchmark.reference import tokenq as ref_tokenq  # noqa: E402
from distributed_deep_q_tpu.config import (  # noqa: E402
    PRESETS, TokenQConfig, apply_overrides)
from distributed_deep_q_tpu.models import tokenq  # noqa: E402
from distributed_deep_q_tpu.ops import sparse_attention as sa  # noqa: E402
from distributed_deep_q_tpu.parallel.sequence_learner import (  # noqa: E402
    SequenceSolver)

T, V, SEED, BLOCK = 70, 64, 7, 32
F32 = jnp.float32


def toy_cfg(topk=8, layers=2, **tq):
    cfg = PRESETS["tokenq"]()
    cfg.mesh.backend = "cpu"
    cfg.mesh.num_fake_devices = 1
    apply_overrides(cfg, ["replay.batch_size=2", "replay.fused_chain=2",
                          f"replay.sequence_length={T}",
                          f"replay.capacity={64 * T}",
                          f"train.seed={SEED}"])
    cfg.net.tokenq = dataclasses.replace(TokenQConfig(
        hidden_size=64, num_hidden_layers=layers, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
        layer_types=("sparse_attention",) * layers,
        sliding_window_layout=(0,) * layers, rope_layout=(1,) * layers,
        rope_theta=1e7, qk_norm=True, indexer_num_heads=4,
        indexer_head_dim=8, indexer_topk=topk, indexer_q_chunk=BLOCK,
        hidden_act="silu", router_input="ffn_norm",
        moe_ffn_hidden_size=32, moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, experts_held=8,
        head_block=16, moe_tile=8), **tq)
    return cfg


def toy_hp(cfg, **over):
    tq = cfg.net.tokenq
    n = tq.num_hidden_layers
    hp = {
        "hidden_size": tq.hidden_size, "num_hidden_layers": n,
        "layer_types": list(tq.layer_types[:n]),
        "num_attention_heads": tq.num_attention_heads,
        "num_key_value_heads": tq.num_key_value_heads,
        "head_dim": tq.head_dim, "rms_norm_eps": tq.rms_norm_eps,
        "rope_theta": tq.rope_theta,
        "indexer_num_heads": tq.indexer_num_heads,
        "indexer_head_dim": tq.indexer_head_dim, "topk": tq.indexer_topk,
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
    return {"tokens": tok[:b], "reward": rew[:b],
            "discount": np.where(done[:b], 0.0, hp["gamma"]).astype(
                np.float32),
            "mask": valid[:b].astype(np.float32),
            "weight": np.linspace(0.5, 1.0, b).astype(np.float32)}


def as_jnp(w):
    return {k: jnp.asarray(v) for k, v in w.items()}


def solver_for(topk):
    cfg = toy_cfg(topk)
    solver = SequenceSolver(cfg)
    hp = toy_hp(cfg)
    solver.set_named_weights(ref.init_weights(SEED, hp))
    return solver, hp, cfg


# selection bites (8 of up to 71 keys) | every key kept (T + 1 <= topk)
TOPKS = pytest.mark.parametrize("topk", [8, 128], ids=["top8", "keep_all"])


# ---- the program against the reference ---------------------------------

@TOPKS
def test_q_and_the_selected_sets_match_the_reference(topk):
    solver, hp, cfg = solver_for(topk)
    w = as_jnp(ref.init_weights(SEED, hp))
    tok = ref.seeded_windows(1, 0, hp)[0][0]
    hid, counters = tokenq.backbone(solver.state.params, tok[None], cfg.net,
                                    interpret=True)
    q = hid[0] @ solver.state.params["head"]
    with jax.default_matmul_precision("highest"):
        gold = ref.q_values(w, jnp.asarray(tok), hp)
    np.testing.assert_allclose(np.asarray(q), np.asarray(gold), atol=2e-5)
    keep, kl = ref.selection(w, jnp.asarray(tok), hp)
    got = np.stack([sa.unpack_selection(b[0], BLOCK)[:T + 1, :T + 1]
                    for b in np.asarray(counters["dsa_bits"])])
    assert np.array_equal(got, np.asarray(keep))
    t = np.arange(T + 1)
    assert np.array_equal(got.sum(-1), np.broadcast_to(
        np.minimum(t + 1, topk), got.shape[:2]))
    assert float(counters["dsa_selected"].sum()) == got.sum()
    assert float(counters["dsa_causal"][0]) == (T + 1) * (T + 2) / 2
    np.testing.assert_allclose(float(counters["dsa_index_loss"].sum()),
                               float(kl), rtol=1e-5)
    # the acting path asks for no loss and gets the same Q
    np.testing.assert_allclose(solver.token_q_values(tok[:6]),
                               np.asarray(gold)[5], atol=2e-5)


@TOPKS
def test_one_step_both_losses_gradients_adam_and_target(topk):
    """TD loss, the indexers' loss, priorities, gradients by leaf (through
    Adam's first moment), θ⁻, element for element."""
    solver, hp, _ = solver_for(topk)
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
    assert abs(float(metrics["dsa_index_loss"])
               - float(gm["index_loss"])) < 1e-5
    assert float(gm["index_loss"]) > 1e-3
    assert abs(float(metrics["q_mean"]) - float(gm["q_mean"])) < 1e-6
    np.testing.assert_allclose(np.asarray(priority), np.asarray(gprio),
                               rtol=1e-5)
    pairs = 2 * 2 * counts.pairs_selected(hp)       # layers x windows
    assert float(metrics["dsa_pairs_selected"]) == pairs
    assert float(metrics["dsa_pairs_causal"]) == \
        2 * 2 * counts.pairs_causal(hp)
    names = list(tokenq.named_leaves(state.params))
    np.testing.assert_allclose(
        np.asarray(metrics["grad_leaf_norm"]),
        [float(gm["grad_leaf_norm"][k]) for k in names], rtol=2e-4,
        atol=1e-7)
    from benchmark.check import _adam_mu
    mu = tokenq.named_leaves(_adam_mu(state.opt_state))
    target = tokenq.named_leaves(state.target_params)
    for k in names:     # m1 = (1 - b1) clip g: the gradient, by element
        scale = float(np.abs(np.asarray(gold["m"][k])).max()) + 1e-12
        np.testing.assert_allclose(np.asarray(mu[k]) / scale,
                                   np.asarray(gold["m"][k]) / scale,
                                   atol=2e-4, err_msg=k)
        np.testing.assert_allclose(np.asarray(target[k]),
                                   np.asarray(gold["target"][k]), atol=0)
    for k in names:     # the indexer's leaves learn, from L_I alone
        if k.rsplit("/", 1)[-1] in ref.INDEXER_LEAVES:
            assert float(np.abs(np.asarray(mu[k])).max()) > 0, k


@TOPKS
def test_the_reference_a_layer_at_a_time_is_the_reference_whole(topk):
    """``ref.grad_one`` (one compiled layer forward and one backward for
    every layer of θ and θ⁻, the chain rule between them written out) is
    ``jax.value_and_grad(ref.sequence_loss)``: values, every gradient by
    element, and ``ref.selection`` the whole forward pass's kept pairs."""
    hp = toy_hp(toy_cfg(topk))
    theta = as_jnp(ref.init_weights(SEED, hp))
    target = as_jnp(ref.init_weights(SEED + 1, hp))
    batch = as_jnp(seeded_batch(hp, 1))
    seq = {k: batch[k][0] for k in ("tokens", "reward", "discount", "mask")}
    seq.update(scale=jnp.asarray(0.7, F32), share=jnp.asarray(0.5, F32))
    with jax.default_matmul_precision("highest"):
        (want, want_aux), want_g = jax.jit(jax.value_and_grad(
            lambda a, b, c: ref.sequence_loss(a, b, c, hp, None),
            has_aux=True))(theta, target, seq)
    (got, got_aux), got_g = ref.grad_one(theta, target, seq, hp)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, e in zip(got_aux, want_aux):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), rtol=1e-6)
    assert sorted(got_g) == sorted(theta)
    for k in theta:
        scale = float(jnp.abs(want_g[k]).max()) + 1e-12
        np.testing.assert_allclose(np.asarray(got_g[k]) / scale,
                                   np.asarray(want_g[k]) / scale,
                                   atol=1e-5, err_msg=k)
    keep, kl = ref.selection(theta, seq["tokens"], hp)
    assert keep.shape == (2, T + 1, T + 1)
    assert int(keep.sum()) == 2 * counts.pairs_selected(hp)
    np.testing.assert_allclose(float(kl) * 0.5, float(want_aux[4]),
                               rtol=1e-6)


def test_keeping_every_key_is_plain_causal_attention_in_the_reference():
    """T + 1 <= topk: the reference's kept set is the causal triangle, and
    its mixer is the ``tokenq`` reference's plain causal attention bit for
    bit."""
    cfg = toy_cfg(128)
    hp = toy_hp(cfg)
    w = as_jnp(ref.init_weights(SEED, hp))
    u = jax.random.normal(jax.random.PRNGKey(3), (T + 1, 64))
    with jax.default_matmul_precision("highest"):
        out, _, keep = ref.sparse_attention(u, w, "layer_00/", hp, None)

        def heads(name, n):
            return (u @ w["layer_00/" + name]).reshape(T + 1, n, 16) \
                .transpose(1, 0, 2)
        q = ref.rotary(ref.rmsnorm(heads("w_q", 4), w["layer_00/q_norm"],
                                   1e-6), 1e7)
        k = ref.rotary(ref.rmsnorm(heads("w_k", 2), w["layer_00/k_norm"],
                                   1e-6), 1e7)
        plain = ref_tokenq.attention(q, k, heads("w_v", 2), 0, None)
    assert np.array_equal(np.asarray(keep),
                          np.tril(np.ones((T + 1, T + 1), bool)))
    assert np.array_equal(
        np.asarray(out),
        np.asarray(plain.transpose(1, 0, 2).reshape(T + 1, 64)))


@pytest.mark.parametrize("which", ["td_loss", "index_loss"])
def test_stop_gradient_holds(which):
    """The TD loss gives the indexer's leaves exactly 0; ``L_I`` gives
    every other leaf exactly 0."""
    solver, hp, cfg = solver_for(8)
    tok = jnp.asarray(ref.seeded_windows(2, 0, hp)[0][:2])

    def loss(params):
        hid, counters = tokenq.backbone(params, tok, cfg.net,
                                        interpret=True)
        if which == "index_loss":
            return jnp.sum(counters["dsa_index_loss"])
        return jnp.sum(jnp.square(hid @ params["head"]))

    grads = tokenq.named_leaves(jax.grad(loss)(solver.state.params))
    for k, g in grads.items():
        indexer = k.rsplit("/", 1)[-1] in ref.INDEXER_LEAVES
        if indexer == (which == "index_loss"):
            assert float(jnp.abs(g).max()) > 0, k
        else:
            assert float(jnp.abs(g).max()) == 0.0, k


# ---- the operator's pieces ----------------------------------------------

@pytest.mark.parametrize("rows,cols,topk", [
    (16, 40, 8), (8, 300, 64), (4, 33, 33), (4, 20, 32)])
def test_topk_mask_is_exact_with_ties_to_the_smaller_key(rows, cols, topk):
    rng = np.random.default_rng(rows * cols)
    # few distinct values: many ties, at the threshold too; both signs
    # and zeros of both signs
    scores = rng.integers(-3, 4, (rows, cols)).astype(np.float32) * 0.5
    scores[0, ::3] = -0.0
    valid = np.arange(cols)[None, :] <= rng.integers(
        0, cols, (rows, 1)) + np.arange(rows)[:, None]
    valid[-1] = True
    got = np.asarray(jax.jit(sa.topk_mask, static_argnums=2)(
        jnp.asarray(scores), jnp.asarray(valid), topk))
    want = np.zeros_like(valid)
    for r in range(rows):
        cand = np.flatnonzero(valid[r])
        order = cand[np.argsort(-scores[r, cand], kind="stable")]
        want[r, order[:topk]] = True
    assert np.array_equal(got, want)


def _search_in_jnp(u, topk, chunk, chunks):
    """The search as it ran before the kernel (four bits a pass, 15
    candidates a pass, a read of ``u`` a pass, plain ``jax.numpy``): the
    oracle the kernel's ``(tau, room)`` are held to, bit for bit."""
    cands = jnp.arange(1, 16, dtype=jnp.uint32)

    def count(at_or_above):
        return jax.lax.fori_loop(0, chunks, lambda c, n: n + jnp.sum(
            sa._at(u, c, chunk, 1)[:, None, :] >= at_or_above[:, :, None],
            axis=-1, dtype=jnp.int32),
            jnp.zeros(at_or_above.shape, jnp.int32))

    def one_pass(i, tau):
        shift = (32 - 4 * (i + 1)).astype(jnp.uint32)
        enough = count(tau[:, None] | (cands[None, :] << shift)) >= topk
        return tau | (jnp.sum(enough, axis=-1).astype(jnp.uint32) << shift)

    tau = jax.lax.fori_loop(0, 8, one_pass,
                            jnp.zeros(u.shape[0], jnp.uint32))
    return tau, topk - count(tau[:, None] + jnp.uint32(1))[:, 0]


def _search_case(name):
    """(scores [R, T] float32, valid [R, T] bool, topk, chunk, chunks
    read) of one case of the search."""
    rng = np.random.default_rng(len(name))
    rows, chunk, chunks, read, topk = 2 * sa.ROW_TILE, 128, 3, 3, 64
    if name == "a_block_the_row_tile_does_not_divide":
        rows = sa.ROW_TILE + 8
    if name == "a_chunk_that_is_not_whole_lanes":
        rows, chunk = 40, 96
    if name == "chunks_short_of_the_width_and_garbage_past_them":
        chunks, read = 4, 2
    t = chunk * chunks
    scores = rng.integers(-3, 4, (rows, t)).astype(np.float32) * 0.5
    valid = np.ones((rows, t), bool)
    if name == "rows_with_fewer_valid_keys_than_topk":
        valid = np.arange(t)[None, :] < np.arange(rows)[:, None]
    if name == "zeros_of_both_signs_and_both_signs":
        scores = rng.choice(np.float32(
            [0.0, -0.0, 1e-30, -1e-30, 3e38, -3e38, 1.5, -1.5]), (rows, t))
    if name == "a_row_of_one_repeated_value":
        scores[:] = rng.standard_normal((rows, 1)).astype(np.float32)
        scores[1], scores[2], valid[3] = 0.0, -0.0, False
    if name in ("many_ties_and_the_cut_through_a_tie",
                "a_block_the_row_tile_does_not_divide"):
        valid = rng.random((rows, t)) < 0.9
    return scores, valid, topk, chunk, read


@pytest.mark.parametrize("name", [
    "many_ties_and_the_cut_through_a_tie",
    "rows_with_fewer_valid_keys_than_topk",
    "zeros_of_both_signs_and_both_signs",
    "a_row_of_one_repeated_value",
    "chunks_short_of_the_width_and_garbage_past_them",
    "a_block_the_row_tile_does_not_divide",
    "a_chunk_that_is_not_whole_lanes"])
def test_the_search_kernel_is_the_search_in_jnp_bit_for_bit(name):
    """``threshold`` — one Pallas kernel, the rows resident across its
    passes, here interpreted — returns what the search in ``jax.numpy``
    returns and what a sort says: ``tau`` the ``topk``-th largest key of
    the columns READ (0 where a row has fewer), ``room`` the keys equal
    to it that are kept; the number of chunks read is traced."""
    scores, valid, topk, chunk, read = _search_case(name)
    u = np.array(sa.ordered_keys(jnp.asarray(scores), jnp.asarray(valid)))
    u[:, read * chunk:] = np.random.default_rng(1).integers(
        0, 2 ** 32, u[:, read * chunk:].shape, dtype=np.uint32)
    tau, room = jax.jit(lambda u, n: sa.threshold(
        u, topk, chunk, n, True))(jnp.asarray(u), read)
    want_tau, want_room = jax.jit(lambda u, n: _search_in_jnp(
        u, topk, chunk, n))(jnp.asarray(u), read)
    assert tau.dtype == jnp.uint32 and room.dtype == jnp.int32
    assert np.array_equal(np.asarray(tau), np.asarray(want_tau))
    assert np.array_equal(np.asarray(room), np.asarray(want_room))

    seen = -np.sort(-u[:, :read * chunk].astype(np.int64), axis=-1)
    nth = seen[:, topk - 1]     # 0, an invalid entry's key, where fewer
    assert np.array_equal(np.asarray(tau), nth)
    assert np.array_equal(
        np.asarray(room), topk - (seen > nth[:, None]).sum(-1))
    if "ties" in name:      # the cut does go through a tie
        assert ((seen == nth[:, None]).sum(-1) > np.asarray(room)).any()
    if "fewer" in name:
        assert not np.asarray(tau)[:topk].any()


def test_pack_and_unpack_are_inverse_and_the_host_reads_the_same():
    rng = np.random.default_rng(0)
    keep = rng.random((2 * BLOCK, 50)) < 0.3
    words = jnp.concatenate([sa.pack(jnp.asarray(keep[:BLOCK])),
                             sa.pack(jnp.asarray(keep[BLOCK:]))])
    assert words.shape == (2, 50) and words.dtype == jnp.int32
    assert np.array_equal(np.asarray(sa.unpack(words[:1])), keep[:BLOCK])
    assert np.array_equal(sa.unpack_selection(words, BLOCK), keep)


def _kept_by_hand(scores, topk):
    """Row ``t``'s ``topk`` largest of ``scores[t, :t + 1]``, ties to the
    smaller key."""
    keep = np.zeros(scores.shape, bool)
    for i in range(scores.shape[0]):
        keep[i, np.argsort(-scores[i, :i + 1], kind="stable")[:topk]] = True
    return keep


def _naive(q, k, v, qi, wi, ki, topk):
    """One batch in float64 numpy: outputs, kept pairs, Σ_t KL_t / (B T)."""
    b, h, t, d = q.shape
    hi, di = qi.shape[2:]
    outs, keeps, loss = [], [], 0.0
    for s_ in range(b):
        scores = (hi ** -.5 * di ** -.5) * np.einsum(
            "th,ths->ts", wi[s_], np.maximum(np.einsum(
                "thd,sd->ths", qi[s_], ki[s_]), 0))
        keep = _kept_by_hand(scores, topk)
        rep = h // k.shape[1]
        s = np.einsum("htd,hsd->hts", q[s_], np.repeat(k[s_], rep, 0)
                      ) * d ** -.5
        s = np.where(keep[None], s, -1e30)
        p = np.where(keep[None], np.exp(s - s.max(-1, keepdims=True)), 0)
        p /= p.sum(-1, keepdims=True)
        outs.append(np.einsum("hts,hsd->htd", p, np.repeat(v[s_], rep, 0)))
        pm = p.sum(0) / h
        log_q = np.where(keep, scores, -1e30)
        log_q = log_q - log_q.max(-1, keepdims=True)
        log_q = log_q - np.log(np.exp(log_q).sum(-1, keepdims=True))
        loss += np.where(keep & (pm > 0), pm * (np.log(
            np.where(pm > 0, pm, 1)) - log_q), 0).sum()
        keeps.append(keep)
    return np.stack(outs), np.stack(keeps), loss / (b * t)


@pytest.mark.parametrize("t,topk", [(150, 16), (180, 16), (33, 5), (40, 64)])
def test_sparse_attention_forward_backward_interpret(t, topk):
    """The four kernels, the selection and the loss against numpy, over
    several query blocks and key chunks (keys planted equal, so ties
    occur), forward and by finite-free analytic gradients of the naive
    form in jax."""
    rng = np.random.default_rng(t)
    b, h, hkv, d, hi, di = 2, 4, 2, 16, 4, 8
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), F32)  # noqa: E731
    q, k, v = mk(b, h, t, d), mk(b, hkv, t, d), mk(b, hkv, t, d)
    qi, wi, ki = mk(b, t, hi, di), mk(b, t, hi), mk(b, t, di)
    ki = ki.at[:, 5].set(ki[:, 3]).at[:, t - 2].set(ki[:, 3])
    pad = -t % BLOCK
    pt = lambda x, ax: jnp.pad(x, [  # noqa: E731
        (0, pad) if a == ax else (0, 0) for a in range(x.ndim)])

    def run(q, k, v, qi, wi, ki):
        o, c = sa.sparse_attention(
            pt(q, 2), pt(k, 2), pt(v, 2), pt(qi, 1), pt(wi, 1), pt(ki, 1),
            topk=topk, block=BLOCK, t_real=t, with_loss=True,
            interpret=True)
        return o[:, :t].reshape(b, t, h, d).transpose(0, 2, 1, 3), c

    o, c = jax.jit(run)(q, k, v, qi, wi, ki)
    f64 = [np.asarray(x, np.float64) for x in (q, k, v, qi, wi, ki)]
    want_o, want_keep, want_loss = _naive(*f64, topk)
    got = np.stack([sa.unpack_selection(x, BLOCK)[:t, :t]
                    for x in np.asarray(c["bits"])])
    assert np.array_equal(got, want_keep)
    assert float(c["selected"]) == want_keep.sum()
    np.testing.assert_allclose(np.asarray(o), want_o, atol=2e-6)
    np.testing.assert_allclose(float(c["index_loss"]), want_loss, rtol=1e-5)

    # gradients: the same mathematics in plain jax under the kept pairs
    keep = jnp.asarray(want_keep)

    def plain(q, k, v, qi, wi, ki):
        rep = h // hkv
        s = jnp.einsum("bhtd,bhsd->bhts", q, jnp.repeat(k, rep, 1),
                       precision="highest") * d ** -.5
        p = jax.nn.softmax(jnp.where(keep[:, None], s, -1e30), -1)
        p = jnp.where(keep[:, None], p, 0.0)
        o = jnp.einsum("bhts,bhsd->bhtd", p, jnp.repeat(v, rep, 1),
                       precision="highest")
        sc = (hi ** -.5 * di ** -.5) * jnp.einsum(
            "bth,bths->bts", wi, jax.nn.relu(jnp.einsum(
                "bthd,bsd->bths", qi, ki, precision="highest")),
            precision="highest")
        log_q = jax.nn.log_softmax(jnp.where(keep, sc, -1e30), -1)
        pm = jax.lax.stop_gradient(p.sum(1) / h)
        kl = jnp.where(keep, jax.scipy.special.xlogy(pm, pm) - pm * log_q,
                       0.0).sum() / (b * t)
        return o, kl

    tgt = mk(b, h, t, d)
    both = lambda f: lambda *a: (  # noqa: E731
        lambda o, l: jnp.sum(o * tgt) + l)(*f(*a))
    g = jax.jit(jax.grad(both(lambda *a: (
        lambda o, c: (o, c["index_loss"]))(*run(*a))), range(6)))(
            q, k, v, qi, wi, ki)
    gw = jax.jit(jax.grad(both(plain), range(6)))(q, k, v, qi, wi, ki)
    for name, a, e in zip("q k v qi wi ki".split(), g, gw):
        scale = float(jnp.abs(e).max()) + 1e-12
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(e) / scale, atol=2e-5,
                                   err_msg=name)


def _indexer_case(t, seed, planted):
    """One sequence's indexer inputs padded to whole blocks and the main
    heads' queries (scaled) and keys, float32. The rows past ``t_real``
    are seeded like the rest: what a padded query holds must not matter.
    ``planted``: keys 5, 12, 19, … are key 3 (scores tie along every row,
    at the ``topk``-th too), query 9 is zero (every score of its row 0)
    and key 11 is zero (a column of zeros)."""
    rng = np.random.default_rng(seed)
    tp = t + (-t % BLOCK)
    hkv, group, d, hi, di = 2, 2, 16, 4, 8
    mk = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    qi, wi, ki = mk(tp, hi, di), mk(tp, hi), mk(tp, di)
    if planted:
        ki[5::7] = ki[3]
        ki[11] = 0.0
        qi[9] = 0.0
    q = mk(hkv, group, tp, d) * d ** -0.5
    return tuple(jnp.asarray(x) for x in (qi, wi, ki, q, mk(hkv, tp, d)))


# t, t_real, topk, planted ties and zeros
INDEXER_CASES = pytest.mark.parametrize("t,t_real,topk,planted", [
    (40, 40, 64, False),        # chunk = 2 blocks; no row has topk keys
    (180, 180, 16, True),       # chunk = 3 blocks; ties at the threshold
    (160, 130, 16, False),      # chunk = 1 block; 30 padded queries
    (192, 192, 16, False),      # block 0's one chunk lies 2/3 above it
], ids=["rows_short_of_topk", "ties_at_the_threshold", "t_real_short_of_T",
        "last_chunk_above_the_diagonal"])


def test_the_searchs_keys_give_their_scores_back():
    """``_scores_of`` inverts ``_ordered_bits`` on every normal float and
    zero, -0.0 read as +0.0 (the search orders them as one), and the running
    log-sum-exp over chunks is the rows' over what they kept — a row that
    keeps nothing of its first chunk, equal scores and zeros of both signs
    among them."""
    rng = np.random.default_rng(0)
    x = np.concatenate([
        rng.standard_normal(64) * 10.0 ** rng.integers(-30, 30, 64),
        [0.0, -0.0, 1.2e-38, -1.2e-38, 3.4e38, -3.4e38, 1.0, 1.0, -0.0]]
    ).astype(np.float32).reshape(1, -1)
    u = sa._ordered_bits(jnp.asarray(x))
    back = np.asarray(sa._scores_of(u))
    assert np.array_equal(back, x) and not np.signbit(back[x == 0]).any()
    assert (np.asarray(u) > 0).all()

    x = np.tile(np.clip(x, -80, 80), (3, 1))
    kept = rng.random(x.shape) < 0.5
    kept[1, :40] = False
    kept[2] = x[2] == 0
    norm = (jnp.full(3, sa.MASKED), jnp.zeros(3))
    for lo in (0, 40):
        cols = slice(lo, lo + 40) if lo == 0 else slice(lo, None)
        norm = sa.kept_logsumexp(norm, sa._ordered_bits(
            jnp.asarray(x[:, cols])), jnp.asarray(kept[:, cols]))
    want = jax.scipy.special.logsumexp(jnp.asarray(x), axis=-1,
                                       where=jnp.asarray(kept))
    np.testing.assert_allclose(np.asarray(norm[0] + jnp.log(norm[1])),
                               np.asarray(want), rtol=1e-6)
    assert float(want[2]) == pytest.approx(np.log(3.0), rel=1e-6)


@INDEXER_CASES
def test_select_hands_out_the_log_sum_exp_of_the_kept_scores(
        t, t_real, topk, planted):
    """``select(with_loss=True)``'s normaliser is ``logsumexp`` of
    ``index_scores`` over the keys its bits keep, row by row (equal
    scores among them); without the loss it returns none, and the bits
    are the same bits either way: the exact top-k."""
    qi, wi, ki, _, _ = _indexer_case(t, t, planted)
    run = lambda with_loss: jax.jit(lambda *a: sa.select(  # noqa: E731
        *a, topk=topk, block=BLOCK, t_real=t_real, with_loss=with_loss))(
            qi, wi, ki)
    bits, kept, lse_i = run(True)
    plain_bits, plain_kept, none = run(False)
    assert none is None
    assert np.array_equal(np.asarray(bits), np.asarray(plain_bits))
    assert int(kept) == int(plain_kept)

    scores = np.asarray(sa.index_scores(qi, wi, ki))
    keep = sa.unpack_selection(bits, BLOCK)
    assert np.array_equal(keep, _kept_by_hand(scores, topk))
    assert int(kept) == keep[:t_real].sum()
    if planted:
        assert (scores[9] == 0).all() and (scores[:, 11] == 0).all()
        edge = np.where(keep, scores, np.inf).min(-1, keepdims=True)
        tied = (scores == edge) & np.tri(len(scores), dtype=bool)
        assert (tied & ~keep).any()     # a tie the threshold cuts through
    want = jax.scipy.special.logsumexp(
        jnp.asarray(scores), axis=-1, where=jnp.asarray(keep))
    assert lse_i.shape == (len(scores),) and lse_i.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse_i), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@INDEXER_CASES
def test_index_loss_in_one_loop_is_the_dense_formula_and_its_gradients(
        t, t_real, topk, planted):
    """``index_loss`` — a score product a chunk, the normaliser from
    ``select`` — against autodiff of ``Σ_t KL(p_t ‖ softmax_keep(scores))``
    over the dense scores: the value and the gradients by ``q_i``, ``w_i``
    and ``k_i``, to float32 rounding; a padded query adds nothing."""
    qi, wi, ki, q, k = _indexer_case(t, t + 1, planted)
    tp, (hkv, group) = qi.shape[0], q.shape[:2]
    bits, _, lse_i = jax.jit(lambda *a: sa.select(
        *a, topk=topk, block=BLOCK, t_real=t_real, with_loss=True))(
            qi, wi, ki)
    keep = jnp.asarray(sa.unpack_selection(bits, BLOCK))
    s = jnp.where(keep, jnp.einsum("hgtd,hsd->hgts", q, k,
                                   precision="highest"), -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.sum(jnp.exp(s - lse[..., None]), (0, 1)) / (hkv * group)

    def dense(qi, wi, ki):
        log_q = jax.nn.log_softmax(
            jnp.where(keep, sa.index_scores(qi, wi, ki), -1e30), -1)
        kl = jnp.sum(jnp.where(
            keep, jax.scipy.special.xlogy(p, p) - p * log_q, 0.0), -1)
        return jnp.sum(jnp.where(jnp.arange(tp) < t_real, kl, 0.0))

    want, want_g = jax.jit(jax.value_and_grad(dense, (0, 1, 2)))(qi, wi, ki)
    got, got_g = jax.jit(lambda *a: sa.index_loss(
        *a, block=BLOCK, t_real=t_real, interpret=True))(
            qi, wi, ki, q, k, lse, bits, lse_i)
    assert float(want) > 1.0
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    for name, a, e in zip(("q_i", "w_i", "k_i"), got_g, want_g):
        scale = float(jnp.abs(e).max())
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(e) / scale, atol=2e-6,
                                   err_msg=name)
    for a in got_g[:2]:     # a padded query's own leaves: exactly nothing
        assert not np.asarray(a)[t_real:].any()


# ---- the share test at 128-wide routing ---------------------------------

def test_sixteen_shares_of_a_128_wide_expert_layer_add_up_to_the_uncut():
    """THE share test at the published router width: the partial results
    of all 16 shares (8 experts each, top 8 of 128), with the residual and
    the mixer counted once, are the uncut reference's layer."""
    t1 = 33
    cfg = toy_cfg(8, layers=1, moe_num_primary_experts=128,
                  moe_num_active_primary_experts=8, experts_held=8,
                  moe_ffn_hidden_size=16)
    hp = toy_hp(cfg, experts_held=128)      # the uncut layer: all 128 held
    w = ref.init_weights(SEED, hp)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, t1, 64))
    pre = "layer_00/"
    with jax.default_matmul_precision("highest"):
        whole, share_all, _, _ = ref.layer(x[0], as_jnp(w), 0, hp, None)
    assert float(share_all) == 1.0
    lp = {k[len(pre):]: jnp.asarray(v) for k, v in w.items()
          if k.startswith(pre)}

    def run(p, offset):
        net = dataclasses.replace(cfg.net, tokenq=dataclasses.replace(
            cfg.net.tokenq, expert_offset=offset))
        return tokenq.layer(x, p, net, False, True, True, sparse=True,
                            index_loss=False)

    experts = ("w_gate", "w_up", "w_down")
    zero = {**lp, **{n: lp[n][:8] for n in experts},
            "w_down": jnp.zeros_like(lp["w_down"])[:8]}
    residual, _ = run(zero, 0)
    total, held = residual, 0
    for share in range(16):
        lo = 8 * share
        out, c = run({**lp, **{n: lp[n][lo:lo + 8] for n in experts}}, lo)
        total = total + (out - residual)
        held += int(c["slots_held"])
        assert int(c["overflow"]) == 0
    assert held == t1 * 8           # every token-slot lands on one share
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(whole),
                               atol=2e-5)


# ---- counts, the preset, the configuration ------------------------------

def test_counts_against_a_hand_count():
    """5 tokens a window, 2 windows, 2 layers, top 3, by the formulas
    written out."""
    hp = dict(sequence_length=4, batch_size=2, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=8,
              indexer_num_heads=2, indexer_head_dim=4, topk=3,
              hidden_size=16, moe_intermediate_size=8,
              num_experts_per_tok=2, experts_held=2, router_experts=8,
              vocab_size=32)
    tok = 2 * 5
    assert counts.pairs_causal(hp) == 15            # 1+2+3+4+5
    assert counts.pairs_selected(hp) == 12          # 1+2+3+3+3
    assert counts.pairs_selected_share(hp) == 80.0
    core = 4 * 2 * 2 * (4 * 4 * 8 * 12)
    assert counts.sparse_core_flops(hp) == core
    scores = 2 * 2 * ((2 * 4 + 2) * 2) * (2 * 15 + 2 * 12)
    assert counts.indexer_scores_flops(hp) == scores
    proj = 4 * tok * 2 * (2 * 16 * (4 + 4) * 8 + 2 * 4 * 8 * 16)
    assert counts.attention_projection_flops(hp) == proj
    iproj = 3 * tok * 2 * (2 * 16 * (2 * 4 + 4 + 2))
    assert counts.indexer_projection_flops(hp) == iproj
    slots = tok * 2 * 2 / 8
    experts = 4 * 2 * (6 * 16 * 8) * slots
    assert counts.expert_ffn_flops(hp) == experts
    router = 4 * tok * 2 * (2 * 16 * 8)
    head = 4 * tok * 2 * 16 * 32
    assert counts.train_flops_per_step(hp) == (
        core + scores + proj + iproj + experts + router + head)
    assert abs(sum(counts.train_flop_shares(hp).values()) - 1.0) < 1e-12


def _count(shapes):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))


def test_the_keye_preset_is_the_share_the_configuration_states():
    cfg = PRESETS["keye_tokenq"]()
    shapes = tokenq.param_shapes(cfg.net)
    assert _count(shapes) == 314_395_904
    assert all(k["sparse"] and k["rope"] and not (
        k["conv"] or k["dense"] or k["windowed"])
        for k in tokenq.layer_plan(cfg.net.tokenq))
    assert shapes["layer_03"]["w_iq"] == (2048, 16 * 64)
    assert shapes["layer_03"]["w_ik"] == (2048, 64)
    assert shapes["layer_03"]["w_iw"] == (2048, 16)
    assert shapes["layer_03"]["ik_norm"] == (64,)
    assert shapes["layer_03"]["w_router"] == (2048, 128)
    assert shapes["layer_03"]["w_gate"] == (8, 2048, 768)
    assert shapes["head"] == (2048, 18_992)
    assert cfg.replay.sequence_length == 16_384
    assert cfg.replay.capacity // cfg.replay.sequence_length == 8_192
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye_vl2_30b_tokenq_ep16.json")) as fh:
        conf = json.load(fh)
    from benchmark.families.keye import check
    check.assert_hparams(conf, cfg)
    # a query keeps 2 048 of on average 8 192 earlier keys
    assert abs(counts.pairs_selected_share(conf["hparams"]) - 23.4) < 0.05


@pytest.mark.parametrize("bad", [
    {"layer_types": ("sparse_attention", "sparse")},
    {"sliding_window_layout": (1, 0)}], ids=["unknown_mixer", "a_window"])
def test_layer_plan_refuses_what_a_sparse_layer_cannot_be(bad):
    with pytest.raises(ValueError):
        tokenq.layer_plan(dataclasses.replace(
            toy_cfg().net.tokenq, **bad))


def test_the_other_presets_keep_their_leaves():
    for name in ("smallthinker_tokenq", "lfm2_tokenq", "tokenq"):
        shapes = tokenq.param_shapes(PRESETS[name]().net)
        assert not any("w_iq" in layer for layer in shapes.values()
                       if isinstance(layer, dict)), name


SPARSE_TOY = [
    "mesh.num_fake_devices=1", "train.total_steps=600",
    "replay.learn_start=240", "train.train_every=48", "replay.batch_size=2",
    "net.tokenq.layer_types=sparse_attention,sparse_attention",
    "net.tokenq.num_hidden_layers=2", "net.tokenq.sliding_window_layout=0,0",
    "net.tokenq.rope_layout=1,1", "net.tokenq.qk_norm=true",
    "net.tokenq.hidden_act=silu", "net.tokenq.router_input=ffn_norm",
    "net.tokenq.indexer_topk=8", "net.tokenq.indexer_q_chunk=32"]


def test_main_train_runs_sparse_layers_from_the_command_line():
    """``main train`` with the toy preset and the sparse mixer's keys set
    on the command line: the normal path (``train.train_tokenq``)."""
    cmd = [sys.executable, "-m", "distributed_deep_q_tpu.main", "train",
           "--preset", "tokenq", "--backend", "cpu", "--set", *SPARSE_TOY]
    done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    summary = json.loads(done.stdout.strip().splitlines()[-1])
    assert summary["mode"] == "train" and summary["grad_steps"] >= 4


def test_train_tokenq_logs_the_sparse_layers_counters(tmp_path):
    from distributed_deep_q_tpu.metrics import Metrics
    from distributed_deep_q_tpu.train import train_tokenq

    cfg = PRESETS["tokenq"]()
    cfg.mesh.backend = "cpu"
    apply_overrides(cfg, SPARSE_TOY)
    out = tmp_path / "m.jsonl"
    train_tokenq(cfg, Metrics(str(out)), log_every=2)
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    rows = [r for r in rows if "dsa_pairs_selected_share" in r]
    # 25 tokens a window: 8 + 7 + ... + 1 + 17 x 8 = 172 of 325 pairs
    assert rows and abs(rows[-1]["dsa_pairs_selected_share"]
                        - 172 / 325) < 1e-6
    assert rows[-1]["dsa_index_loss"] > 0
