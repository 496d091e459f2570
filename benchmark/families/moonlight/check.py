"""The comparison that decides ``correct`` for the ``moonlight`` family:
Moonlight-16B-A3B's block (latent attention: a full-rank query, keys and
values from one low-rank latent and one shared rotary key head, scores
192 wide over values of 128; a leading dense layer; SwiGLU experts behind a
sigmoid router with a selection bias and scaled gates, a shared expert
beside them) as a token-window Q-network on ``DeviceTokenReplay`` under
``SequenceSolver`` + ``FusedStepStream``.

The procedure and every compared number are the ``tokenq`` family's
(``families/tokenq/check.py``: ONE solver and ONE token ring, the seed's
weights installed by leaf names, the ring filled with seeded windows that
all differ, the first chunk driven through the window's own call under a
recorder, the reference following it afterwards): its functions take the
reference from the configuration, so they are imported. What is this
family's own: ``assert_hparams`` (the published keys against the program's
Config: it refuses a file whose gate scale, shared experts, head widths or
rotary convention are not what the program runs), ``build_checked`` around
it, the toy sizes, and which of the compared numbers decide ``correct``
(``PRINTED_ONLY`` are printed and not judged).
"""

from __future__ import annotations

from benchmark.families.moonlight import program
from benchmark.families.tokenq import check as tokenq_check
from benchmark.families.tokenq.check import (  # noqa: F401
    ROW_COUNTERS, drive_first_chunk, hlo_scope_tables, log_row, prefill)
from benchmark.family import load_reference

FOLLOWED_CHUNKS = 1     # the reference follows the first chunk
# Computed and printed, NOT judged: on the chip at this cell's sizes no
# limit has room on both sides (PERF.md section 2; the configuration's
# ``limits_readings``). The written priority is an extreme of 8 191 TD
# errors with a heavy tail (1 of 16 sound seeds reads 5.3e-3, fourteen at
# most 1.8e-3) and the fp8 control reads 9.5e-3 at its smallest, 1.8x.
# Mean Q IS judged (``q_mean_first_rel``: the control stands 7.3x over the
# sound largest), so the forward Q values are held by two numbers, the
# first step's loss and their mean
PRINTED_ONLY = ("priority_first_max_rel",)


def compare(conf: dict, seed: int, mirror, rec: dict, *, quant=None) -> dict:
    """The ``tokenq`` family's comparison, ``PRINTED_ONLY`` moved from the
    judged numbers to the printed ones."""
    got = tokenq_check.compare(conf, seed, mirror, rec, quant=quant)
    got["print"].update({k: got["numbers"].pop(k) for k in PRINTED_ONLY})
    return got


def assert_hparams(conf: dict, cfg) -> None:
    """The configuration file states what the reference computes (and its
    top level the published keys); the program's Config must say the same."""
    hp, tq = conf["hparams"], cfg.net.tokenq
    n = tq.num_hidden_layers
    have = {
        "hidden_size": tq.hidden_size, "num_hidden_layers": n,
        "layer_types": list(tq.layer_types[:n]),
        "num_dense_layers": tq.num_dense_layers,
        "intermediate_size": tq.intermediate_size,
        "num_attention_heads": tq.num_attention_heads,
        "kv_lora_rank": tq.kv_lora_rank,
        "qk_nope_head_dim": tq.qk_nope_head_dim,
        "qk_rope_head_dim": tq.qk_rope_head_dim,
        "v_head_dim": tq.v_head_dim,
        "rms_norm_eps": tq.rms_norm_eps, "rope_theta": tq.rope_theta,
        "moe_intermediate_size": tq.moe_ffn_hidden_size,
        "router_experts": tq.moe_num_primary_experts,
        "experts_held": tq.experts_held,
        "expert_offset": tq.expert_offset,
        "num_experts_per_tok": tq.moe_num_active_primary_experts,
        "n_shared_experts": tq.n_shared_experts,
        "routed_scaling_factor": tq.routed_scaling_factor,
        "use_expert_bias": tq.use_expert_bias,
        "hidden_act": tq.hidden_act, "router_input": tq.router_input,
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
    # program's ``route`` always renormalises, its latent mixer has a
    # full-rank query, interleaved rotary pairs and no positional scaling
    facts = {
        "a sigmoid router (scoring_func)": (
            (tq.moe_primary_router_apply_softmax,
             conf.get("scoring_func")), (False, "sigmoid")),
        "every layer latent attention": (
            set(tq.layer_types[:n]), {"latent_attention"}),
        "norm_topk_prob": (hp.get("norm_topk_prob"), True),
        "q_lora_rank": (conf.get("q_lora_rank"), None),
        "one routing group": (
            (conf.get("n_group"), conf.get("topk_group")), (1, 1)),
        "interleaved rotary pairs (the latent mixer's only convention)": (
            hp.get("rope_interleave"), True),
        "no qk_norm": (tq.qk_norm, False),
        "rope on every layer": (all(tq.rope_layout[:n]), True),
        "no sliding window": (any(tq.sliding_window_layout[:n]), False),
        "the window inside the published positions": (
            cfg.replay.sequence_length + 1
            <= conf.get("max_position_embeddings", 0), True),
        "no planted fault": (hp.get("fault"), None)}
    bad.update({k: v for k, v in facts.items() if v[0] != v[1]})
    top = {k: (conf.get(k), hp[h]) for k, h in (
        ("num_hidden_layers", "num_hidden_layers"),
        ("first_k_dense_replace", "num_dense_layers"),
        ("n_routed_experts", "experts_held"), ("vocab_size", "vocab_size"),
        ("hidden_size", "hidden_size"),
        ("intermediate_size", "intermediate_size"),
        ("moe_intermediate_size", "moe_intermediate_size"),
        ("num_attention_heads", "num_attention_heads"),
        ("kv_lora_rank", "kv_lora_rank"),
        ("qk_nope_head_dim", "qk_nope_head_dim"),
        ("qk_rope_head_dim", "qk_rope_head_dim"),
        ("v_head_dim", "v_head_dim"),
        ("num_experts_per_tok", "num_experts_per_tok"),
        ("n_shared_experts", "n_shared_experts"),
        ("routed_scaling_factor", "routed_scaling_factor"),
        ("rms_norm_eps", "rms_norm_eps"), ("rope_theta", "rope_theta"),
        ("hidden_act", "hidden_act"), ("norm_topk_prob", "norm_topk_prob"))
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
    "net.tokenq.hidden_size=64", "net.tokenq.num_attention_heads=4",
    "net.tokenq.num_key_value_heads=4", "net.tokenq.kv_lora_rank=32",
    "net.tokenq.qk_nope_head_dim=16", "net.tokenq.qk_rope_head_dim=8",
    "net.tokenq.v_head_dim=16", "net.tokenq.intermediate_size=96",
    "net.tokenq.moe_ffn_hidden_size=32",
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
    "hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "router_experts": 8, "num_experts_per_tok": 2, "experts_held": 2,
    "expert_offset": 3, "sequence_length": 24, "capacity_windows": 256,
    "batch_size": 4}
TOY_TOP = {"hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 32,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "n_routed_experts": 2, "num_experts_per_tok": 2,
           "vocab_size": 64}
TOY_TRAFFIC = {"warmup_steps": 8, "row_every": 4, "trace_start_step": 8,
               "trace_num_steps": 8}
TOY_LIMIT = 0.05    # float32 on both sides at the toy size


def toy(conf: dict, traffic: dict) -> None:
    """This family's toy sizes for a CPU walk (``rehearse.py``): h 64, the
    cell's own five layers (latent attention of 4 heads, scores 16 + 8 wide
    over values of 16, rank 32; a dense layer of width 96, then four expert
    layers: 8 experts top 2 of which 2 held, a shared expert of 2 x 32),
    vocabulary 64, T 24, float32 — so every inexact limit is one small
    number."""
    conf["limits"] = {k: TOY_LIMIT for k in conf["limits"]}
    conf["overrides"] = [*conf["overrides"], *TOY_OVERRIDES]
    conf["hparams"].update(TOY_HPARAMS)
    conf.update(TOY_TOP)
    traffic.update({k: v for k, v in TOY_TRAFFIC.items() if k in traffic})
