"""The comparison that decides ``correct`` for the ``lfm2`` family: LFM2's
block (gated short convolutions among full attention with q/k norms, a
leading dense layer, SwiGLU experts behind a sigmoid router with a
selection bias) as a token-window Q-network on ``DeviceTokenReplay`` under
``SequenceSolver`` + ``FusedStepStream``.

The procedure and every compared number are the ``tokenq`` family's
(``families/tokenq/check.py``: ONE solver and ONE token ring, the seed's
weights installed by leaf names, the ring filled with seeded windows that
all differ, the first chunk driven through the window's own call under a
recorder, the reference following it afterwards): its functions take the
reference from the configuration, so they are imported. What is this
family's own: ``assert_hparams`` (LFM2's published keys against the
program's Config), ``build_checked`` around it, the toy sizes, and which
of the compared numbers decide ``correct``: two are printed and not judged
(``PRINTED_ONLY``).
"""

from __future__ import annotations

from benchmark.families.lfm2 import program
from benchmark.families.tokenq import check as tokenq_check
from benchmark.families.tokenq.check import (  # noqa: F401
    ROW_COUNTERS, drive_first_chunk, hlo_scope_tables, log_row, prefill)
from benchmark.family import load_reference

FOLLOWED_CHUNKS = 1     # the reference follows the first chunk
# Computed and printed, NOT judged: on the chip at this cell's sizes no
# reading lies far enough above the sound program's to put a limit between
# them (PERF.md section 2; the configuration's ``limits_readings``). The
# written priority is an extreme of 8 192 TD errors with a heavy tail (2
# of 26 sound seeds read 1.1e-2, the rest at most 4.1e-3) and a priority
# of max |TD| alone reads 3.2e-2; mean Q is a signed mean near zero that a
# wrong rotary base moves less than rounding does.
PRINTED_ONLY = ("priority_first_max_rel", "q_mean_first_rel")


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
        "num_key_value_heads": tq.num_key_value_heads,
        "head_dim": tq.head_dim, "norm_eps": tq.rms_norm_eps,
        "rope_theta": tq.rope_theta,
        "moe_intermediate_size": tq.moe_ffn_hidden_size,
        "router_experts": tq.moe_num_primary_experts,
        "experts_held": tq.experts_held,
        "expert_offset": tq.expert_offset,
        "num_experts_per_tok": tq.moe_num_active_primary_experts,
        "use_expert_bias": tq.use_expert_bias,
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
    # what the reference computes as facts of the architecture; the last
    # three are held as constants here (the program's conv layers have 3
    # taps and ``route`` always renormalises; it takes a ``scale`` since
    # PR 38, 1.0 for this family)
    attn = [i for i in range(n) if tq.layer_types[i] != "conv"]
    facts = {
        "moe_primary_router_apply_softmax": (
            tq.moe_primary_router_apply_softmax, False),
        "conv_L_cache": (hp.get("conv_L_cache"), 3),
        "norm_topk_prob": (hp.get("norm_topk_prob"), True),
        "routed_scaling_factor": (hp.get("routed_scaling_factor"), 1.0),
        "rope on every attention layer": (
            all(tq.rope_layout[i] for i in attn), True),
        "a window on an attention layer": (
            any(tq.sliding_window_layout[i] for i in attn), False)}
    bad.update({k: v for k, v in facts.items() if v[0] != v[1]})
    top = {k: (conf.get(k), hp[h]) for k, h in (
        ("num_hidden_layers", "num_hidden_layers"),
        ("num_dense_layers", "num_dense_layers"),
        ("num_experts", "experts_held"), ("vocab_size", "vocab_size"),
        ("hidden_size", "hidden_size"),
        ("intermediate_size", "intermediate_size"),
        ("moe_intermediate_size", "moe_intermediate_size"),
        ("num_attention_heads", "num_attention_heads"),
        ("num_key_value_heads", "num_key_value_heads"),
        ("num_experts_per_tok", "num_experts_per_tok"),
        ("conv_L_cache", "conv_L_cache"), ("norm_eps", "norm_eps"),
        ("use_expert_bias", "use_expert_bias"),
        ("norm_topk_prob", "norm_topk_prob"),
        ("routed_scaling_factor", "routed_scaling_factor"))
        if k in conf and conf[k] != hp[h]}
    ran = conf.get("layers_run")
    if ran is not None and [conf["layer_types"][i] for i in ran] != \
            hp["layer_types"]:
        top["layer_types"] = ([conf["layer_types"][i] for i in ran],
                              hp["layer_types"])
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

TOY_LAYERS = ["conv", "full_attention", "conv", "conv", "conv"]
TOY_OVERRIDES = [
    "net.num_actions=64", "env.token_vocab=64", "net.compute_dtype=float32",
    "net.tokenq.hidden_size=64", "net.tokenq.num_attention_heads=4",
    "net.tokenq.num_key_value_heads=2", "net.tokenq.head_dim=16",
    "net.tokenq.intermediate_size=96",
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
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "router_experts": 8, "num_experts_per_tok": 2, "experts_held": 2,
    "expert_offset": 3, "sequence_length": 24, "capacity_windows": 256,
    "batch_size": 4}
TOY_TOP = {"hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "intermediate_size": 96,
           "moe_intermediate_size": 32, "num_experts": 2,
           "num_experts_per_tok": 2, "vocab_size": 64}
TOY_TRAFFIC = {"warmup_steps": 8, "row_every": 4, "trace_start_step": 8,
               "trace_num_steps": 8}
TOY_LIMIT = 0.05    # float32 on both sides at the toy size


def toy(conf: dict, traffic: dict) -> None:
    """This family's toy sizes for a CPU walk (``rehearse.py``): h 64, the
    cell's own five layers (conv + dense, then full attention and three
    convolutions with experts), 8 experts top 2 of which 2 held, dense
    width 96, vocabulary 64, T 24, float32 — so every inexact limit is
    one small number."""
    conf["limits"] = {k: TOY_LIMIT for k in conf["limits"]}
    conf["overrides"] = [*conf["overrides"], *TOY_OVERRIDES]
    conf["hparams"].update(TOY_HPARAMS)
    conf.update(TOY_TOP)
    traffic.update({k: v for k, v in TOY_TRAFFIC.items() if k in traffic})
