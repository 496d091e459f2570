"""The comparison that decides ``correct`` for the ``keye`` family:
Keye-VL-2.0-30B-A3B's block (grouped-query attention over the keys a
learned indexer selects, SwiGLU experts behind a softmax router) as a
token-window Q-network on ``DeviceTokenReplay`` under ``SequenceSolver`` +
``FusedStepStream``.

The procedure and the compared numbers are the ``tokenq`` family's
(``families/tokenq/check.py``: ONE solver and ONE token ring, the seed's
weights installed by leaf names, the ring filled with seeded windows that
all differ, the first chunk driven through the window's own call under a
recorder, the reference following it afterwards): its functions take the
reference from the configuration, so they are imported; the indexer's four
leaves a layer are among the leaves ``moment_first_worst_leaf`` and its
kin go through. What is this family's own: ``assert_hparams``,
``build_checked`` around it (it also notes how to ask the program which
pairs its sparse layers keep on the first step's windows at the seed's
weights — ``program.program_selection``, a side program that ``compare``
runs after the window —), the toy sizes, and two numbers of the first
step at the seed's weights:

- ``selection_mismatch_share``: (query, key) pairs kept by exactly one of
  program and reference, over the pairs kept by both sides added up, all
  sparse layers and windows of the step: 0 = the same sets, 1 = disjoint;
- ``index_loss_first_rel``: the indexers' loss ``Σ_layers L_I``.
"""

from __future__ import annotations

import numpy as np

from benchmark.common import emit
from benchmark.families.keye import program
from benchmark.families.tokenq import check as tokenq_check
from benchmark.families.tokenq.check import (  # noqa: F401
    drive_first_chunk, hlo_scope_tables, prefill, rel)
from benchmark.family import load_reference

FOLLOWED_CHUNKS = 1     # the reference follows the first chunk
# what a driver's log rows carry of a step's metrics: the expert layers'
# counters and the sparse layers'
ROW_COUNTERS = (*tokenq_check.ROW_COUNTERS, "dsa_pairs_selected",
                "dsa_pairs_causal", "dsa_index_loss")
# Computed and printed, NOT judged (the configuration's
# ``limits_readings``): mean Q is a signed mean near zero and the fp8
# control reads under the sound largest. ``priority_first_max_rel`` IS
# judged here (unlike ``lfm2``): it is what holds the VALUE written back
# to the PER ring every chunk, and its control reads 10x the sound largest
PRINTED_ONLY = ("q_mean_first_rel",)


def log_row(c: dict[str, float]) -> dict[str, float]:
    """A log row's keys from the step's ``ROW_COUNTERS``."""
    return {**tokenq_check.log_row(c),
            "dsa_pairs_selected_share": 100.0 * c["dsa_pairs_selected"]
            / max(c["dsa_pairs_causal"], 1.0),
            "dsa_index_loss": c["dsa_index_loss"]}


def selection_gap(conf: dict, seed: int, rec: dict, quant) -> dict:
    """The first step at the seed's weights, a window at a time: the
    reference's kept pairs against the program's (with ``quant`` the
    control's against the reference's own), and the indexers' loss."""
    import jax.numpy as jnp

    ref = load_reference(conf)
    hp = conf["hparams"]
    theta = {k: jnp.asarray(v) for k, v in
             ref.init_weights(seed, hp).items()}
    tokens = rec["feed"]["tokens"][0]               # [B, T+1], step 0
    differ = kept = 0
    gold_loss = other_loss = 0.0
    for b in range(tokens.shape[0]):
        tok = jnp.asarray(tokens[b])
        gold, kl = ref.selection(theta, tok, hp)
        gold, gold_loss = np.asarray(gold), gold_loss + float(kl)
        if quant is None:
            if callable(rec["selection"]):      # asked once, when first read
                rec["selection"] = rec["selection"]()
            other = np.unpackbits(rec["selection"][:, b], axis=-1,
                                  count=gold.shape[-1]).astype(bool)
        else:
            other, kl = ref.selection(theta, tok, hp, quant)
            other, other_loss = np.asarray(other), other_loss + float(kl)
        differ += int(np.sum(gold != other))
        kept += int(np.sum(gold)) + int(np.sum(other))
    gold_loss /= tokens.shape[0]
    if quant is None:
        other_loss = float(rec["metrics"]["dsa_index_loss"][0])
    else:
        other_loss /= tokens.shape[0]
    emit(index_loss_first=[other_loss, gold_loss], pairs_differ=differ,
         pairs_kept_both_sides=kept)
    return {"selection_mismatch_share": differ / max(kept, 1),
            "index_loss_first_rel": float(rel(other_loss, gold_loss))}


def compare(conf: dict, seed: int, mirror, rec: dict, *, quant=None) -> dict:
    """The ``tokenq`` family's comparison with the two numbers of the
    selection added and ``PRINTED_ONLY`` moved to the printed ones."""
    got = tokenq_check.compare(conf, seed, mirror, rec, quant=quant)
    got["print"].update({k: got["numbers"].pop(k) for k in PRINTED_ONLY})
    got["numbers"].update(selection_gap(conf, seed, rec, quant))
    return got


def assert_hparams(conf: dict, cfg) -> None:
    """The configuration file states what the reference computes (and its
    top level the published keys); the program's Config must say the same."""
    hp, tq = conf["hparams"], cfg.net.tokenq
    n = tq.num_hidden_layers
    have = {
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
        "experts_held": tq.experts_held,
        "expert_offset": tq.expert_offset,
        "num_experts_per_tok": tq.moe_num_active_primary_experts,
        "qk_norm": tq.qk_norm, "hidden_act": tq.hidden_act,
        "router_input": tq.router_input,
        "vocab_size": cfg.net.num_actions,
        "num_actions": cfg.net.num_actions,
        "compute_dtype": cfg.net.compute_dtype,
        "sequence_length": cfg.replay.sequence_length,
        "batch_size": cfg.replay.batch_size,
        "fused_chain": cfg.replay.fused_chain,
        "capacity_windows": cfg.replay.capacity
        // cfg.replay.sequence_length,
        "priority_alpha": cfg.replay.priority_alpha,
        "priority_beta0": cfg.replay.priority_beta0,
        "priority_eps": cfg.replay.priority_eps,
        "gamma": cfg.train.gamma, "huber_delta": cfg.train.huber_delta,
        "double_dqn": cfg.train.double_dqn,
        "value_rescale": cfg.train.value_rescale,
        "priority_eta": cfg.train.priority_eta, "lr": cfg.train.lr,
        "adam_eps": cfg.train.adam_eps,
        "grad_clip_norm": cfg.train.grad_clip_norm,
        "target_update_period": cfg.train.target_update_period,
        "optimizer": cfg.train.optimizer,
    }
    bad = {k: (hp.get(k), v) for k, v in have.items() if hp.get(k) != v}
    # what the reference computes as facts of the architecture; the
    # program's ``route`` always renormalises, its indexer has one key head
    facts = {
        "a softmax router": (tq.moe_primary_router_apply_softmax, True),
        "no expert bias": (tq.use_expert_bias, False),
        "no dense layer": (tq.num_dense_layers, 0),
        "norm_topk_prob": (hp.get("norm_topk_prob"), True),
        "indexer_num_kv_heads": (hp.get("indexer_num_kv_heads"), 1),
        "rope on every layer": (all(tq.rope_layout[:n]), True),
        "no sliding window": (any(tq.sliding_window_layout[:n]), False),
        "an exact selection": (hp.get("selection", "exact"), "exact")}
    bad.update({k: v for k, v in facts.items() if v[0] != v[1]})
    sa = conf.get("sa_config", {})
    top = {k: (got, hp[h]) for k, got, h in (
        ("num_hidden_layers", conf.get("num_hidden_layers"),
         "num_hidden_layers"),
        ("num_experts", conf.get("num_experts"), "router_experts"),
        ("num_local_experts", conf.get("num_local_experts"),
         "experts_held"),
        ("vocab_size", conf.get("vocab_size"), "vocab_size"),
        ("hidden_size", conf.get("hidden_size"), "hidden_size"),
        ("head_dim", conf.get("head_dim"), "head_dim"),
        ("moe_intermediate_size", conf.get("moe_intermediate_size"),
         "moe_intermediate_size"),
        ("num_attention_heads", conf.get("num_attention_heads"),
         "num_attention_heads"),
        ("num_key_value_heads", conf.get("num_key_value_heads"),
         "num_key_value_heads"),
        ("num_experts_per_tok", conf.get("num_experts_per_tok"),
         "num_experts_per_tok"),
        ("rms_norm_eps", conf.get("rms_norm_eps"), "rms_norm_eps"),
        ("rope_theta", conf.get("rope_theta"), "rope_theta"),
        ("hidden_act", conf.get("hidden_act"), "hidden_act"),
        ("norm_topk_prob", conf.get("norm_topk_prob"), "norm_topk_prob"),
        ("sa_config.topk", sa.get("topk"), "topk"),
        ("sa_config.indexer_num_heads", sa.get("indexer_num_heads"),
         "indexer_num_heads"),
        ("sa_config.indexer_head_dim", sa.get("indexer_head_dim"),
         "indexer_head_dim"),
        ("sa_config.indexer_num_kv_heads", sa.get("indexer_num_kv_heads"),
         "indexer_num_kv_heads"))
        if got is not None and got != hp[h]}
    if bad or top:
        raise SystemExit(f"configuration {conf['name']}: hparams differ "
                         f"from the program's Config (file, program): "
                         f"{bad}; top-level keys differ from hparams: {top}")


def build_checked(conf: dict, cfg, seed: int, rows, episode: int,
                  beta_steps: int | None = None, mark=lambda name: None):
    """The object the window will drive, built and checked once. Returns
    ``(solver, replay, stream, mirror, rec)``."""
    from distributed_deep_q_tpu.solver import FusedStepStream

    del episode             # episode ends are seeded per step, not spaced
    assert_hparams(conf, cfg)
    hp = conf["hparams"]
    hp["priority_beta_steps"] = beta_steps or cfg.replay.priority_beta_steps
    ref = load_reference(conf)
    chain = cfg.replay.fused_chain
    solver = program.make_solver(cfg)
    theta0 = ref.init_weights(seed, hp)
    solver.set_named_weights(theta0, target=True)
    replay = program.make_replay(cfg, solver, beta_steps)
    mark("solver_weights_ring")
    mirror = prefill(replay, seed, rows, hp, ref)
    mark("prefill")
    stream = FusedStepStream(solver, replay, chain)
    rec = drive_first_chunk(solver, stream, replay, chain, theta0)
    rec["driven_steps"] = FOLLOWED_CHUNKS * chain
    mark("first_chunks")
    rec["selection"] = program.program_selection(
        solver, theta0, rec["feed"]["tokens"][0])
    return solver, replay, stream, mirror, rec


# ---- toy sizes: the CPU walk of this family's cells ----

TOY_OVERRIDES = [
    "net.num_actions=64", "env.token_vocab=64", "net.compute_dtype=float32",
    "net.tokenq.hidden_size=64", "net.tokenq.num_attention_heads=4",
    "net.tokenq.num_key_value_heads=2", "net.tokenq.head_dim=16",
    "net.tokenq.indexer_num_heads=4", "net.tokenq.indexer_head_dim=8",
    "net.tokenq.indexer_topk=8", "net.tokenq.indexer_q_chunk=32",
    "net.tokenq.moe_ffn_hidden_size=32",
    "net.tokenq.moe_num_primary_experts=8",
    "net.tokenq.moe_num_active_primary_experts=2",
    "net.tokenq.experts_held=2", "net.tokenq.expert_offset=3",
    "net.tokenq.attn_block=128", "net.tokenq.attn_compute_block=128",
    "net.tokenq.head_block=32", "net.tokenq.moe_tile=8",
    "replay.sequence_length=40", "replay.capacity=10240",
    "replay.batch_size=4", "replay.write_chunk=64",
    "mesh.num_fake_devices=1"]
TOY_HPARAMS = {
    "vocab_size": 64, "num_actions": 64, "compute_dtype": "float32",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "indexer_num_heads": 4, "indexer_head_dim": 8,
    "topk": 8, "moe_intermediate_size": 32, "router_experts": 8,
    "num_experts_per_tok": 2, "experts_held": 2, "expert_offset": 3,
    "sequence_length": 40, "capacity_windows": 256, "batch_size": 4}
TOY_TOP = {"hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "moe_intermediate_size": 32, "num_experts": 8,
           "num_local_experts": 2, "num_experts_per_tok": 2,
           "vocab_size": 64,
           "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                         "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                         "q_chunk_size": 8, "topk": 8}}
TOY_TRAFFIC = {"warmup_steps": 8, "row_every": 4, "trace_start_step": 8,
               "trace_num_steps": 8}
TOY_LIMIT = 0.05    # float32 on both sides at the toy size


def toy(conf: dict, traffic: dict) -> None:
    """This family's toy sizes for a CPU walk (``rehearse.py``): h 64,
    four sparse layers keeping 8 of up to 41 keys (indexer 4 heads of 8,
    two query blocks of 32), 8 experts top 2 of which 2 held, vocabulary
    64, T 40, float32 — so every inexact limit is one small number."""
    conf["limits"] = {k: TOY_LIMIT for k in conf["limits"]}
    conf["overrides"] = [*conf["overrides"], *TOY_OVERRIDES]
    conf["hparams"].update(TOY_HPARAMS)
    conf.update(TOY_TOP)
    traffic.update({k: v for k, v in TOY_TRAFFIC.items() if k in traffic})
