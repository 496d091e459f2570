"""The comparison that decides ``correct`` for the ``nemotron`` family:
NVIDIA-Nemotron-3-Nano-30B-A3B's block (Mamba-2 state-space mixers —
chunked scan, a causal convolution of 4 taps, a gated group norm —,
attention with no positional embedding, two-matrix relu² experts behind a
sigmoid router with a selection bias and scaled gates, a shared expert;
every layer ONE part alone under one norm) as a token-window Q-network on
``DeviceTokenReplay`` under ``SequenceSolver`` + ``FusedStepStream``.

The procedure and every compared number are the ``tokenq`` family's
(``families/tokenq/check.py``: ONE solver and ONE token ring, the seed's
weights installed by leaf names, the ring filled with seeded windows that
all differ, the first chunk driven through the window's own call under a
recorder, the reference following it afterwards): its functions take the
reference from the configuration, so they are imported. What is this
family's own: ``assert_hparams`` (the published keys against the program's
Config: it refuses a file whose pattern, state-space heads, state, groups,
chunk, taps, activation, router, gate scale or share are not what the
program runs), ``build_checked`` around it, the scan's counter in the log
rows, the toy sizes, and which of the compared numbers decide ``correct``
(``PRINTED_ONLY`` are printed and not judged).
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from benchmark.families.nemotron import program
from benchmark.families.tokenq import check as tokenq_check
from benchmark.families.tokenq.check import (  # noqa: F401
    drive_first_chunk, hlo_scope_tables, prefill)
from benchmark.family import load_reference

FOLLOWED_CHUNKS = 1     # the reference follows the first chunk
# what a driver's log rows carry of a step's metrics: the expert layers'
# counters and the state-space mixers' mean Δ
ROW_COUNTERS = (*tokenq_check.ROW_COUNTERS, "ssm_dt_mean")
# Computed and printed, NOT judged: on the chip at this cell's sizes the
# fp8 control's smallest reading lies UNDER the sound program's largest, so
# no limit has room on both sides (the configuration's ``limits_readings``).
# The written priority is an extreme of 8 191 TD errors with a heavy tail,
# as in the LFM2 and Moonlight cells; the first step's loss gap is a signed
# difference near zero that one control seed of eight read smaller than five
# sound seeds of eighteen — ``loss_max_rel`` (the largest of the chunk's
# steps, the first among them) is judged and holds the first loss too
PRINTED_ONLY = ("priority_first_max_rel", "loss_first_rel")


def log_row(c: dict[str, float]) -> dict[str, float]:
    """A log row's keys from the step's ``ROW_COUNTERS``."""
    return {**tokenq_check.log_row(c), "ssm_dt_mean": c["ssm_dt_mean"]}


# the three numbers that are a worst leaf's, in the order the ``tokenq``
# comparison takes them
WORST_LEAF_NUMBERS = ("moment_first_worst_leaf", "moment_norm_worst_leaf",
                      "delta_norm_worst_leaf")


def compare(conf: dict, seed: int, mirror, rec: dict, *, quant=None) -> dict:
    """The ``tokenq`` family's comparison, ``PRINTED_ONLY`` moved from the
    judged numbers to the printed ones, and WHICH leaf each worst-leaf
    number is printed beside them (``worst_leaves``: judged by nothing; a
    reading's size is explained by the leaf it sits on)."""
    gap, leaves = tokenq_check.worst_leaf_gap, []

    def naming_gap(prog: dict, ref: dict) -> float:
        med = float(np.median(list(ref.values())))
        leaves.append(max(ref, key=lambda k: abs(prog[k] - ref[k])
                          / max(ref[k], med, 1e-30)))
        return gap(prog, ref)

    with mock.patch.object(tokenq_check, "worst_leaf_gap", naming_gap):
        got = tokenq_check.compare(conf, seed, mirror, rec, quant=quant)
    got["print"].update({k: got["numbers"].pop(k) for k in PRINTED_ONLY})
    got["print"]["worst_leaves"] = dict(zip(WORST_LEAF_NUMBERS, leaves))
    return got


def assert_hparams(conf: dict, cfg) -> None:
    """The configuration file states what the reference computes (and its
    top level the published keys); the program's Config must say the same."""
    hp, tq = conf["hparams"], cfg.net.tokenq
    n = tq.num_hidden_layers
    have = {
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
        "experts_held": tq.experts_held,
        "expert_offset": tq.expert_offset,
        "num_experts_per_tok": tq.moe_num_active_primary_experts,
        "routed_scaling_factor": tq.routed_scaling_factor,
        "use_expert_bias": tq.use_expert_bias,
        "mlp_hidden_act": tq.hidden_act, "router_input": tq.router_input,
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
    # what the reference computes as facts of the architecture: two-matrix
    # feed-forwards, a sigmoid router that renormalises, one shared expert,
    # attention without a positional embedding or a window, a convolution
    # with a bias, no planted fault
    facts = {
        "two-matrix feed-forwards (no gate)": (tq.ffn_gated, False),
        "a sigmoid router (the nemotron_h router)": (
            tq.moe_primary_router_apply_softmax, False),
        "one shared expert": (
            (tq.n_shared_experts, conf.get("n_shared_experts")), (1, 1)),
        "norm_topk_prob": (hp.get("norm_topk_prob"), True),
        "one routing group": (
            (conf.get("n_group"), conf.get("topk_group")), (1, 1)),
        "no positional embedding, no window": (
            (any(tq.rope_layout[:n]), any(tq.sliding_window_layout[:n])),
            (False, False)),
        "no qk_norm, no gating": ((tq.qk_norm, tq.gating), (False, False)),
        "a convolution with a bias (use_conv_bias)": (
            conf.get("use_conv_bias", True), True),
        "the pattern is the published one's start": (
            str(conf.get("hybrid_override_pattern")).startswith(
                hp.get("pattern", "?")), True),
        "no planted fault": (hp.get("fault"), None)}
    bad.update({k: v for k, v in facts.items() if v[0] != v[1]})
    top = {k: (conf.get(k), hp[h]) for k, h in (
        ("num_hidden_layers", "num_hidden_layers"),
        ("n_routed_experts", "experts_held"), ("vocab_size", "vocab_size"),
        ("hidden_size", "hidden_size"),
        ("mamba_num_heads", "mamba_num_heads"),
        ("mamba_head_dim", "mamba_head_dim"),
        ("ssm_state_size", "ssm_state_size"), ("n_groups", "n_groups"),
        ("conv_kernel", "conv_kernel"), ("chunk_size", "chunk_size"),
        ("num_attention_heads", "num_attention_heads"),
        ("num_key_value_heads", "num_key_value_heads"),
        ("head_dim", "head_dim"),
        ("intermediate_size", "moe_intermediate_size"),
        ("moe_intermediate_size", "moe_intermediate_size"),
        ("moe_shared_expert_intermediate_size",
         "moe_shared_expert_intermediate_size"),
        ("num_experts_per_tok", "num_experts_per_tok"),
        ("routed_scaling_factor", "routed_scaling_factor"),
        ("layer_norm_epsilon", "rms_norm_eps"), ("norm_eps", "rms_norm_eps"),
        ("mlp_hidden_act", "mlp_hidden_act"),
        ("norm_topk_prob", "norm_topk_prob"))
        if k in conf and conf[k] != hp[h]}
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
    return solver, replay, stream, mirror, rec


# ---- toy sizes: the CPU walk of this family's cells ----

TOY_OVERRIDES = [
    "net.num_actions=64", "env.token_vocab=64", "net.compute_dtype=float32",
    "net.tokenq.hidden_size=64", "net.tokenq.mamba_num_heads=4",
    "net.tokenq.mamba_head_dim=16", "net.tokenq.ssm_state_size=16",
    "net.tokenq.n_groups=2", "net.tokenq.chunk_size=8",
    "net.tokenq.ssm_segment=16", "net.tokenq.num_attention_heads=4",
    "net.tokenq.num_key_value_heads=2", "net.tokenq.head_dim=16",
    "net.tokenq.moe_ffn_hidden_size=40",
    "net.tokenq.moe_shared_expert_intermediate_size=48",
    "net.tokenq.moe_num_primary_experts=8",
    "net.tokenq.moe_num_active_primary_experts=2",
    "net.tokenq.experts_held=2", "net.tokenq.expert_offset=3",
    "net.tokenq.attn_block=128", "net.tokenq.attn_compute_block=128",
    "net.tokenq.head_block=32", "net.tokenq.moe_tile=8",
    "replay.sequence_length=24", "replay.capacity=6144",
    "replay.batch_size=4", "replay.write_chunk=64",
    "mesh.num_fake_devices=1"]
TOY_HPARAMS = {
    "vocab_size": 64, "num_actions": 64, "compute_dtype": "float32",
    "hidden_size": 64, "mamba_num_heads": 4, "mamba_head_dim": 16,
    "ssm_state_size": 16, "n_groups": 2, "chunk_size": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 40, "moe_shared_expert_intermediate_size": 48,
    "router_experts": 8, "num_experts_per_tok": 2, "experts_held": 2,
    "expert_offset": 3, "sequence_length": 24, "capacity_windows": 256,
    "batch_size": 4}
TOY_TOP = {"hidden_size": 64, "mamba_num_heads": 4, "mamba_head_dim": 16,
           "ssm_state_size": 16, "n_groups": 2, "chunk_size": 8,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "intermediate_size": 40,
           "moe_intermediate_size": 40,
           "moe_shared_expert_intermediate_size": 48,
           "n_routed_experts": 2, "num_experts_per_tok": 2,
           "vocab_size": 64}
TOY_TRAFFIC = {"warmup_steps": 8, "row_every": 4, "trace_start_step": 8,
               "trace_num_steps": 8}
TOY_LIMIT = 0.05    # float32 on both sides at the toy size


def toy(conf: dict, traffic: dict) -> None:
    """This family's toy sizes for a CPU walk (``rehearse.py``): h 64, the
    cell's own seven layers ``MEMEM*E`` (Mamba-2 mixers of 4 heads of 16,
    state 16, 2 groups, chunks of 8 in segments of 16 — the window of 25
    rows pads both; attention of 4 / 2 heads of 16; 8 two-matrix experts
    of width 40 top 2 of which 2 held, a shared expert of 48), vocabulary
    64, T 24, float32 — so every inexact limit is one small number."""
    conf["limits"] = {k: TOY_LIMIT for k in conf["limits"]}
    conf["overrides"] = [*conf["overrides"], *TOY_OVERRIDES]
    conf["hparams"].update(TOY_HPARAMS)
    conf.update(TOY_TOP)
    traffic.update({k: v for k, v in TOY_TRAFFIC.items() if k in traffic})
