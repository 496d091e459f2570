"""The comparison that decides ``correct`` for the ``laguna`` family:
Laguna-XS.2's block (window and full attention mixed, each kind with its
own head count and rotary embedding — YaRN over half of each head on the
full layers —, a sigmoid gate a head on the attention output, a leading
dense layer, SwiGLU experts behind a sigmoid router with scaled gates, a
shared expert beside them) as a token-window Q-network on
``DeviceTokenReplay`` under ``SequenceSolver`` + ``FusedStepStream``.

The procedure and every compared number are the ``tokenq`` family's
(``families/tokenq/check.py``: ONE solver and ONE token ring, the seed's
weights installed by leaf names, the ring filled with seeded windows that
all differ, the first chunk driven through the window's own call under a
recorder, the reference following it afterwards): its functions take the
reference from the configuration, so they are imported. What is this
family's own: ``assert_hparams`` (the published keys against the program's
Config: it refuses a file whose head counts a layer, window, either kind's
rotary parameters, gate, router scoring, gate scale or shared width are
not what the program runs), ``build_checked`` around it, the gate's
counter in the log rows and the toy sizes. Every number the comparison
computes decides ``correct``: each has a limit between this cell's own
readings (the configuration's ``limits_readings``).
"""

from __future__ import annotations

import dataclasses

from benchmark.families.laguna import program
from benchmark.families.tokenq import check as tokenq_check
from benchmark.families.tokenq.check import (  # noqa: F401
    compare, drive_first_chunk, hlo_scope_tables, prefill)
from benchmark.family import load_reference

FOLLOWED_CHUNKS = 1     # the reference follows the first chunk
# what a driver's log rows carry of a step's metrics: the expert layers'
# counters and the attention gates' mean
ROW_COUNTERS = (*tokenq_check.ROW_COUNTERS, "attn_gate_mean")
# a kind's published rotary keys, by ``rope_type``
ROPE_KEYS = {"default": ("rope_type", "rope_theta", "partial_rotary_factor"),
             "yarn": ("rope_type", "rope_theta", "partial_rotary_factor",
                      "factor", "original_max_position_embeddings",
                      "beta_fast", "beta_slow", "attention_factor")}
KINDS = ("full_attention", "sliding_attention")


def log_row(c: dict[str, float]) -> dict[str, float]:
    """A log row's keys from the step's ``ROW_COUNTERS``."""
    return {**tokenq_check.log_row(c), "attn_gate_mean": c["attn_gate_mean"]}


def rope_as_published(rp) -> dict:
    """A kind's ``RopeParameters`` of the program under the published
    keys of its ``rope_type``."""
    have = dataclasses.asdict(rp)
    return {k: have[k] for k in ROPE_KEYS.get(rp.rope_type, ())}


def assert_hparams(conf: dict, cfg) -> None:
    """The configuration file states what the reference computes (and its
    top level the published keys); the program's Config must say the same."""
    hp, tq = conf["hparams"], cfg.net.tokenq
    n = tq.num_hidden_layers
    have = {
        "hidden_size": tq.hidden_size, "num_hidden_layers": n,
        "layer_types": [KINDS[bool(w)]
                        for w in tq.sliding_window_layout[:n]],
        "num_attention_heads_per_layer": list(
            tq.num_attention_heads_per_layer[:n]),
        "num_key_value_heads": tq.num_key_value_heads,
        "head_dim": tq.head_dim, "sliding_window": tq.sliding_window_size,
        "rope_parameters": {k: rope_as_published(
            getattr(tq.rope_parameters, k)) for k in KINDS},
        "gating": tq.gating,
        "num_dense_layers": tq.num_dense_layers,
        "intermediate_size": tq.intermediate_size,
        "rms_norm_eps": tq.rms_norm_eps,
        "moe_intermediate_size": tq.moe_ffn_hidden_size,
        "shared_expert_intermediate_size":
            tq.n_shared_experts * tq.moe_ffn_hidden_size,
        "router_experts": tq.moe_num_primary_experts,
        "experts_held": tq.experts_held,
        "expert_offset": tq.expert_offset,
        "num_experts_per_tok": tq.moe_num_active_primary_experts,
        "routed_scaling_factor": tq.routed_scaling_factor,
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
    # what the reference computes as facts of the architecture: the
    # program's ``route`` always renormalises, its plain attention mixer
    # has no q/k norm unless asked, and positions are the window's own
    facts = {
        "a sigmoid router (assumed.router_scoring)": (
            (tq.moe_primary_router_apply_softmax, hp.get("scoring_func")),
            (False, "sigmoid")),
        "no selection bias": (tq.use_expert_bias, False),
        "norm_topk_prob": (hp.get("norm_topk_prob"), True),
        "gates on the experts' output": (
            conf.get("moe_apply_router_weight_on_input"), False),
        "every layer plain attention": (set(tq.layer_types[:n]), set()),
        "no qk_norm": (tq.qk_norm, False),
        "rope on every layer": (all(tq.rope_layout[:n]), True),
        "the window inside the published positions": (
            cfg.replay.sequence_length + 1
            <= conf.get("max_position_embeddings", 0), True),
        "no planted fault": (hp.get("fault"), None)}
    bad.update({k: v for k, v in facts.items() if v[0] != v[1]})
    # the published keys at the file's top level against hparams; the
    # three lists keep their published length and are read at layers_run
    run = conf.get("layers_run", list(range(n)))
    top = {k: (conf.get(k), hp[h]) for k, h in (
        ("num_hidden_layers", "num_hidden_layers"),
        ("num_experts", "experts_held"), ("vocab_size", "vocab_size"),
        ("hidden_size", "hidden_size"),
        ("intermediate_size", "intermediate_size"),
        ("num_key_value_heads", "num_key_value_heads"),
        ("head_dim", "head_dim"), ("sliding_window", "sliding_window"),
        ("gating", "gating"),
        ("moe_intermediate_size", "moe_intermediate_size"),
        ("shared_expert_intermediate_size",
         "shared_expert_intermediate_size"),
        ("num_experts_per_tok", "num_experts_per_tok"),
        ("moe_routed_scaling_factor", "routed_scaling_factor"),
        ("rms_norm_eps", "rms_norm_eps"))
        if k in conf and conf[k] != hp[h]}
    lists = {
        "layer_types": hp["layer_types"],
        "num_attention_heads_per_layer":
            hp["num_attention_heads_per_layer"],
        "mlp_layer_types": ["dense" if i < hp["num_dense_layers"]
                            else "sparse" for i in range(n)]}
    top.update({k: ([conf[k][i] for i in run], v) for k, v in lists.items()
                if k in conf and [conf[k][i] for i in run] != v})
    top.update({f"rope_parameters.{k}": (conf["rope_parameters"].get(k),
                                         hp["rope_parameters"][k])
                for k in KINDS if "rope_parameters" in conf
                and conf["rope_parameters"].get(k)
                != hp["rope_parameters"][k]})
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

TOY_HEADS = [2, 4, 4, 4, 2]
TOY_OVERRIDES = [
    "net.num_actions=64", "env.token_vocab=64", "net.compute_dtype=float32",
    "net.tokenq.hidden_size=64", "net.tokenq.num_attention_heads=2",
    "net.tokenq.num_attention_heads_per_layer="
    + ",".join(str(h) for h in TOY_HEADS),
    "net.tokenq.num_key_value_heads=2", "net.tokenq.head_dim=16",
    "net.tokenq.sliding_window_size=8", "net.tokenq.intermediate_size=96",
    "net.tokenq.moe_ffn_hidden_size=32",
    "net.tokenq.moe_num_primary_experts=8",
    "net.tokenq.moe_num_active_primary_experts=2",
    "net.tokenq.experts_held=2", "net.tokenq.expert_offset=3",
    "net.tokenq.attn_block=256", "net.tokenq.attn_compute_block=128",
    "net.tokenq.sliding_attn_block=128",
    "net.tokenq.head_block=32", "net.tokenq.moe_tile=8",
    "replay.sequence_length=24", "replay.capacity=6144",
    "replay.fused_chain=2", "replay.write_chunk=64",
    "mesh.num_fake_devices=1"]
TOY_HPARAMS = {
    "vocab_size": 64, "num_actions": 64, "compute_dtype": "float32",
    "hidden_size": 64, "num_attention_heads_per_layer": TOY_HEADS,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 8,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "router_experts": 8,
    "num_experts_per_tok": 2, "experts_held": 2, "expert_offset": 3,
    "sequence_length": 24, "capacity_windows": 256, "fused_chain": 2}
TOY_TOP = {"hidden_size": 64, "num_key_value_heads": 2, "head_dim": 16,
           "sliding_window": 8, "intermediate_size": 96,
           "moe_intermediate_size": 32,
           "shared_expert_intermediate_size": 32, "num_experts": 2,
           "num_experts_per_tok": 2, "vocab_size": 64}
# five layers of interpreted kernels take seconds a step here: chunks of
# two steps, and no warm-up beyond the chunk the comparison drove
TOY_TRAFFIC = {"warmup_steps": 2, "row_every": 2, "trace_start_step": 2,
               "trace_num_steps": 2}
TOY_LIMIT = 0.05    # float32 on both sides at the toy size


def toy(conf: dict, traffic: dict) -> None:
    """This family's toy sizes for a CPU walk (``rehearse.py``): h 64, the
    cell's own five layers (full + dense, sliding x 3, full) with 2 heads
    on the full layers and 4 on the sliding ones over 2 key/value heads of
    16, window 8 on T 24, both kinds' rotary parameters as published (at
    a head of 16 the full layers turn 8 columns, YaRN's ramp runs over
    pairs 0-2), blocks 256 / 128; a dense layer of width 96, then four
    expert layers: 8 experts top 2 of which 2 held, a shared expert of 32;
    vocabulary 64, chain 2, float32 — so every inexact limit is one small
    number."""
    conf["limits"] = {k: TOY_LIMIT for k in conf["limits"]}
    conf["overrides"] = [*conf["overrides"], *TOY_OVERRIDES]
    conf["hparams"].update(TOY_HPARAMS)
    conf.update(TOY_TOP)
    run = conf["layers_run"]
    conf["num_attention_heads_per_layer"] = list(
        conf["num_attention_heads_per_layer"])
    for i, h in zip(run, TOY_HEADS):
        conf["num_attention_heads_per_layer"][i] = h
    traffic.update({k: v for k, v in TOY_TRAFFIC.items() if k in traffic})
