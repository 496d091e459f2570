"""The comparison that decides ``correct`` for the ``sdar`` family:
SDAR-30B-A3B-Chat's block (generation by diffusion over blocks: the window
packed twice — a clean copy and a partly masked one sharing positions —
under the three-part block mask, one decision a block, block-to-block
n-step returns; SwiGLU experts behind a softmax router) as a token-window
Q-network on ``DeviceTokenReplay`` under ``SequenceSolver`` +
``FusedStepStream``.

The procedure is the ``tokenq`` family's (``families/tokenq/check.py``:
ONE solver and ONE token ring, the seed's weights installed by leaf names,
the ring filled with seeded windows that all differ, the first chunk
driven through the window's own call under a recorder, the reference
following it afterwards) and its helpers are imported. What is this
family's own:

- the recorder also keeps the KEYS the sample program was given, and the
  feed carries ``reveal`` (tokens of each block already revealed): the
  reference draws it again from the key (``reveal_mismatch``);
- what the train program makes of the feed, by its own functions
  (``program.packed_feed``), against the reference's Python loops: the
  packed token ids (``noised_id_mismatch``), the decision rows
  (``decision_row_mismatch``), the span returns ``R_b`` / ``Γ_b`` to 1e-6
  and which decisions carry a loss (``span_valid_mismatch``), and the
  step's own count of those (``decisions_valid_mismatch``);
- ``q_sa_first_early_max_rel``: the first step's Q_θ(d, a) DECISION BY
  DECISION (the train step's own ``bd_q_sa``, from the recorded chunk)
  against the reference's, over the first ``EARLY_BLOCKS`` blocks: the
  largest gap over the RMS of the reference's over the window. At the
  cell's length no mean over a window's thousands of decisions sees a few
  keys more or fewer in a row's mask (a noised row that also sees its own
  block's clean rows: read on the chip, PR 44); the first blocks'
  decisions, whose rows see a handful of keys, do — and the largest gap
  over ALL decisions does not serve: in bfloat16 the worst of a window's
  4 095 read 0.04 to 0.1 off on three seeds, anywhere in the window, where
  the leak's first blocks read 0.3 (both on the chip). The profile by
  block range is printed beside it;
- ``compare``: the reference follows the chunk with ITS OWN draw of
  ``reveal``; the numbers of the forward, backward and optimizer path are
  the ``tokenq`` family's. Every number decides ``correct``.
"""

from __future__ import annotations

import contextlib

import numpy as np

from benchmark.check import Recorder, _adam_mu
from benchmark.common import emit
from benchmark.families.sdar import program
from benchmark.families.tokenq import check as tokenq_check
from benchmark.families.tokenq.check import (  # noqa: F401
    first_moment, hlo_scope_tables, leaf_norms, prefill, rel,
    worst_leaf_gap)
from benchmark.family import load_reference

FOLLOWED_CHUNKS = 1     # the reference follows the first chunk
# the decisions ``q_sa_first_early_max_rel`` reads: those of the first
# blocks, whose rows see at most 4 * 16 + 5 keys
EARLY_BLOCKS = 16
BD_COUNTERS = ("bd_decisions_valid", "bd_reveal_mean", "bd_span_mean")
# what a driver's log rows carry of a step's metrics: the expert layers'
# counters and the block mask's
ROW_COUNTERS = (*tokenq_check.ROW_COUNTERS, *BD_COUNTERS)
_GOLD: dict = {}        # the reference's follow of a seed's chunk, kept for
#                         the control's reading of the same seed (control.py)
_MASK_SHARES: dict = {}  # ``program.mask_shares`` of the solver built last


def log_row(c: dict[str, float]) -> dict[str, float]:
    """A log row's keys from the step's ``ROW_COUNTERS``, and beside them
    the block mask's two shares, which no step moves
    (``program.mask_shares``)."""
    return {**tokenq_check.log_row(c), **{k: c[k] for k in BD_COUNTERS},
            **_MASK_SHARES}


def assert_hparams(conf: dict, cfg) -> None:
    """The configuration file states what the reference computes (and its
    top level the published keys); the program's Config must say the same."""
    hp, tq = conf["hparams"], cfg.net.tokenq
    n = tq.num_hidden_layers
    have = {
        "hidden_size": tq.hidden_size, "num_hidden_layers": n,
        "num_attention_heads": tq.num_attention_heads,
        "num_key_value_heads": tq.num_key_value_heads,
        "head_dim": tq.head_dim, "rms_norm_eps": tq.rms_norm_eps,
        "rope_theta": tq.rope_theta, "qk_norm": tq.qk_norm,
        "block_length": tq.block_length,
        "moe_intermediate_size": tq.moe_ffn_hidden_size,
        "router_experts": tq.moe_num_primary_experts,
        "experts_held": tq.experts_held, "expert_offset": tq.expert_offset,
        "num_experts_per_tok": tq.moe_num_active_primary_experts,
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
    # what the reference computes as facts of the architecture
    facts = {
        "a softmax router": (tq.moe_primary_router_apply_softmax, True),
        "no selection bias": (tq.use_expert_bias, False),
        "norm_topk_prob": (hp.get("norm_topk_prob"), True),
        "gates sum to 1": (tq.routed_scaling_factor, 1.0),
        "no shared expert, no dense layer": (
            (tq.n_shared_experts, tq.num_dense_layers), (0, 0)),
        "plain full attention with rope on every layer": (
            (set(tq.layer_types[:n]), any(tq.sliding_window_layout[:n]),
             all(tq.rope_layout[:n]), tq.gating), (set(), False, True,
                                                   False)),
        "[MASK] is the last row held": (hp.get("mask_token_id"),
                                        cfg.net.num_actions - 1),
        "the window inside the published positions": (
            cfg.replay.sequence_length + 1
            <= conf.get("max_position_embeddings", 0), True),
        "no planted fault": (hp.get("fault"), None)}
    bad.update({k: v for k, v in facts.items() if v[0] != v[1]})
    top = {k: (conf.get(k), hp[h]) for k, h in (
        ("num_hidden_layers", "num_hidden_layers"),
        ("num_experts", "experts_held"), ("vocab_size", "vocab_size"),
        ("hidden_size", "hidden_size"), ("head_dim", "head_dim"),
        ("num_attention_heads", "num_attention_heads"),
        ("num_key_value_heads", "num_key_value_heads"),
        ("moe_intermediate_size", "moe_intermediate_size"),
        ("num_experts_per_tok", "num_experts_per_tok"),
        ("norm_topk_prob", "norm_topk_prob"),
        ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps"))
        if k in conf and conf[k] != hp[h]}
    if bad or top:
        raise SystemExit(f"configuration {conf['name']}: hparams differ "
                         f"from the program's Config (file, program): "
                         f"{bad}; top-level keys differ from hparams: {top}")


class KeyedRecorder(Recorder):
    """The sample program's stand-in that also keeps the keys it was
    given (its first argument, ``[shards, chain, 2]`` uint32)."""

    def __init__(self, sample):
        super().__init__(sample)
        self.keys = []

    def __call__(self, keys, *args):
        self.keys.append(np.asarray(keys))
        return super().__call__(keys, *args)


@contextlib.contextmanager
def recording(solver, replay, chain: int):
    learner = solver.learner
    sample, train = learner.token_fused_programs(
        replay, solver.config.replay.batch_size, chain)
    key = next(k for k, v in learner._fused_steps.items()
               if v[0] is sample)
    rec = KeyedRecorder(sample)
    learner._fused_steps[key] = (rec, train)
    try:
        yield rec
    finally:
        learner._fused_steps[key] = (sample, train)


def drive_first_chunk(solver, stream, replay, chain: int, theta0: dict,
                      cfg) -> dict:
    """The first chunk through ``stream.next`` with the recorder in; host
    copies of what the reference will be held against."""
    from distributed_deep_q_tpu.models import tokenq

    per_step = []
    with recording(solver, replay, chain) as rec:
        for _ in range(chain):
            per_step.append(stream.next(10 ** 9))
    batch, idx = rec.calls[0]
    feed = {k: np.asarray(v) for k, v in batch.items()}
    feed["idx"] = np.asarray(idx)
    feed["keys"] = rec.keys[0][0]            # one shard: [chain, 2]
    metrics = {k: np.asarray([np.asarray(m[k], np.float64)
                              for m in per_step])
               for k in per_step[0]}
    return dict(
        feed=feed, metrics=metrics, packed=program.packed_feed(cfg, feed),
        leaf_names=program.leaf_names(solver),
        delta_norm=leaf_norms(tokenq.named_leaves(solver.state.params),
                              theta0),
        m_norm=leaf_norms(tokenq.named_leaves(
            _adam_mu(solver.state.opt_state))),
        prio_after=(prio := np.asarray(replay.dmeta["prio"]))[feed["idx"]],
        prio_rewritten=int(np.sum(prio[:len(replay)] != 1.0)))


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
    rec = drive_first_chunk(solver, stream, replay, chain, theta0, cfg)
    rec["driven_steps"] = FOLLOWED_CHUNKS * chain
    _MASK_SHARES.update(program.mask_shares(solver))
    emit(**_MASK_SHARES)
    mark("first_chunks")
    return solver, replay, stream, mirror, rec


def _follow(ref, hp: dict, seed: int, batch: dict, weights: np.ndarray,
            quant) -> dict:
    """The reference (with ``quant`` the control) over the chunk's steps,
    from the seed's weights."""
    import jax
    import jax.numpy as jnp

    theta0 = ref.init_weights(seed, hp)
    dev = {k: jnp.asarray(v) for k, v in theta0.items()}
    state = ref.init_state(dev, {k: jnp.array(v) for k, v in dev.items()})
    del dev
    step = ref.make_step(hp, quant)
    out = {"loss": [], "q_mean": [], "grad_norm": [], "held_share": [],
           "grad_leaf_norm": [], "priority": [], "decisions_valid": [],
           "q_sa": []}
    for s in range(weights.shape[0]):
        b = {k: batch[k][s] for k in
             ("tokens", "reward", "discount", "mask", "reveal")}
        b["weight"] = weights[s]
        state, m, prio = step(state, b)
        m = jax.device_get(m)
        for k in ("loss", "q_mean", "grad_norm", "decisions_valid"):
            out[k].append(float(m[k]))
        out["held_share"].append(float(np.mean(m["held_share"])))
        out["grad_leaf_norm"].append(
            {k: float(v) for k, v in m["grad_leaf_norm"].items()})
        out["priority"].append(np.asarray(prio))
        out["q_sa"].append(np.asarray(m["q_sa"]))
    out["delta_norm"] = leaf_norms(jax.device_get(state["theta"]), theta0)
    out["m_norm"] = leaf_norms(jax.device_get(state["m"]))
    return out


def feed_numbers(ref, hp: dict, feed: dict, packed: dict,
                 gold_batch: dict) -> dict:
    """What this family's feed adds, program against reference: the draw
    of ``reveal``, and what the train program makes of the feed."""
    chain, b = feed["reveal"].shape[:2]
    nums = {"reveal_mismatch": int((feed["reveal"]
                                    != gold_batch["reveal"]).sum())}
    rows = ids = valid = 0
    ret = gamma = 0.0
    for s in range(chain):
        for w in range(b):
            seq = {k: gold_batch[k][s, w] for k in
                   ("tokens", "reward", "discount", "mask", "reveal")}
            dec = ref.decisions(seq, hp)
            rows += int((packed["dec_rows"][s, w] != dec["dec_rows"]).sum())
            ids += int((packed["packed"][s, w]
                        != ref.pack(seq["tokens"], seq["reveal"], hp)).sum())
            valid += int((packed["valid"][s, w] != dec["valid"]).sum())
            both = dec["valid"] > 0      # an invalid span carries no loss
            ret = max(ret, float(np.abs(
                packed["ret"][s, w] - dec["ret"])[both].max(initial=0.0)))
            gamma = max(gamma, float(np.abs(
                packed["gamma"][s, w] - dec["gamma"])[both].max(
                    initial=0.0)))
    nums.update(decision_row_mismatch=rows, noised_id_mismatch=ids,
                span_valid_mismatch=valid, span_return_max_abs=ret,
                span_discount_max_abs=gamma)
    return nums


def compare(conf: dict, seed: int, mirror, rec: dict, *, quant=None) -> dict:
    ref = load_reference(conf)
    hp = conf["hparams"]
    chain = hp["fused_chain"]
    feed = rec["feed"]
    idx = feed["idx"].astype(np.int64)
    nums: dict[str, float] = {}

    # (a) what the sample program fed, against the seeded ring and the
    # reference's own draw of ``reveal`` from the recorded keys
    legal = (idx >= 0) & (idx < mirror["filled"])
    nums["windows_illegal"] = int((~legal).sum())
    idx = np.where(legal, idx, 0)
    gold_batch = ref.windows_at(mirror["seed"], idx, hp)
    gold_batch["reveal"] = np.stack([
        ref.reveal_draw(feed["keys"][s], idx.shape[1], hp)
        for s in range(chain)])
    nums["token_window_mismatch"] = int(
        (feed["tokens"] != gold_batch["tokens"]).sum())
    nums["validity_mismatch"] = int(
        (feed["mask"] != gold_batch["mask"]).sum())
    nums["reward_max_abs"] = float(
        np.abs(feed["reward"] - gold_batch["reward"]).max())
    nums["discount_max_abs"] = float(
        np.abs(feed["discount"] - gold_batch["discount"]).max())
    nums.update(feed_numbers(ref, hp, feed, rec["packed"], gold_batch))
    betas = ref.betas_for(0, chain, hp)
    w_gold = ref.is_weights(np.ones(mirror["filled"], np.float32),
                            mirror["filled"], idx, betas)
    nums["weight_max_rel"] = float(rel(feed["weight"], w_gold).max())

    # (b) the chunk's steps from the seed's weights
    key = (seed, idx.tobytes(), repr(sorted(hp.items())))
    if key not in _GOLD:
        _GOLD.clear()
        _GOLD[key] = _follow(ref, hp, seed, gold_batch, w_gold, None)
    gold = _GOLD[key]
    if quant is None:
        names = rec["leaf_names"]
        m = rec["metrics"]
        prog = {
            "loss": m["loss"], "q_mean": m["q_mean"],
            "grad_norm": m["grad_norm"],
            "held_share": m["moe_slots_held"] / np.maximum(m["moe_slots"],
                                                           1.0),
            "grad_leaf_norm": [dict(zip(names, row))
                               for row in m["grad_leaf_norm"]],
            "delta_norm": rec["delta_norm"], "m_norm": rec["m_norm"],
            "written": rec["prio_after"],
            "rewritten": rec["prio_rewritten"],
            "overflow": float(np.max(m["moe_overflow"])),
            "decisions_valid": m["bd_decisions_valid"],
            "q_sa": m["bd_q_sa"],
        }
    else:
        prog = _follow(ref, hp, seed, gold_batch, w_gold, quant)
        prog["written"] = ref.written_priority(np.stack(prog["priority"]),
                                               hp)
        prog["overflow"] = 0.0
        prog["rewritten"] = len(np.unique(idx))
    written_gold = ref.written_priority(np.stack(gold["priority"]), hp)
    # a slot drawn twice in the chunk keeps its last write only
    flat = idx.reshape(-1)
    once = np.array([np.sum(flat == s) == 1 for s in flat]).reshape(
        idx.shape)
    prio_rel = np.where(once, rel(prog["written"], written_gold), 0.0)

    for k in ("loss", "grad_norm"):
        r = rel(prog[k], gold[k])
        nums[f"{k}_first_rel"] = float(r[0])
        nums[f"{k}_max_rel"] = float(r.max())
    # mean Q sits near zero: its gap is held against 0.1 at least
    nums["q_mean_first_rel"] = float(rel(prog["q_mean"][0],
                                         gold["q_mean"][0], 0.1))
    # decision by decision: the first step's Q_θ(d, a), the largest gap
    # over the reference's RMS
    q_gap = np.abs(np.asarray(prog["q_sa"][0], np.float64)
                   - gold["q_sa"][0]).max(0) / np.sqrt(
                       np.mean(np.square(gold["q_sa"][0])))
    nums["q_sa_first_early_max_rel"] = float(q_gap[:EARLY_BLOCKS].max())
    nums["priority_first_max_rel"] = float(prio_rel[0].max())
    nums["priority_slots_miswritten"] = abs(
        int(prog["rewritten"]) - len(np.unique(idx)))
    nums["decisions_valid_mismatch"] = float(np.abs(
        np.asarray(prog["decisions_valid"])
        - np.asarray(gold["decisions_valid"])).max())
    nums["moment_first_worst_leaf"] = worst_leaf_gap(
        first_moment(prog["grad_leaf_norm"][0], float(prog["grad_norm"][0]),
                     hp),
        first_moment(gold["grad_leaf_norm"][0], gold["grad_norm"][0], hp))
    nums["moment_norm_worst_leaf"] = worst_leaf_gap(prog["m_norm"],
                                                    gold["m_norm"])
    nums["delta_norm_worst_leaf"] = worst_leaf_gap(prog["delta_norm"],
                                                   gold["delta_norm"])
    nums["held_share_max_abs"] = float(np.abs(
        np.asarray(prog["held_share"]) - np.asarray(gold["held_share"])
    ).max())
    nums["expert_buffer_overflow"] = prog["overflow"]
    emit(held_share=[float(x) for x in np.asarray(prog["held_share"])],
         duplicate_draws=int((~once).sum()),
         priority_max_rel=float(prio_rel.max()),
         decisions_valid=[float(x) for x in gold["decisions_valid"]],
         q_sa_max_rel_by_block_range={
             f"{lo}-{hi}": float(q_gap[lo:hi].max())
             for lo, hi in ((0, EARLY_BLOCKS), (EARLY_BLOCKS, 64), (64, 512),
                            (512, len(q_gap))) if lo < len(q_gap)},
         reveal_mean=float(np.mean(gold_batch["reveal"])))
    steps = {k: [[float(x) for x in prog[k]], gold[k]]
             for k in ("loss", "grad_norm", "q_mean")}
    return dict(numbers=nums, steps=steps, print=dict(
        followed_steps=chain, reference_loss=gold["loss"],
        compared_loss=[float(x) for x in prog["loss"]]))


# ---- toy sizes: the CPU walk of this family's cells ----

TOY_OVERRIDES = [
    "net.num_actions=64", "env.token_vocab=63",
    "net.compute_dtype=float32",
    "net.tokenq.hidden_size=64", "net.tokenq.num_attention_heads=4",
    "net.tokenq.num_key_value_heads=2", "net.tokenq.head_dim=16",
    "net.tokenq.moe_ffn_hidden_size=32",
    "net.tokenq.moe_num_primary_experts=8",
    "net.tokenq.moe_num_active_primary_experts=2",
    "net.tokenq.experts_held=2", "net.tokenq.expert_offset=3",
    "net.tokenq.attn_block=128", "net.tokenq.attn_compute_block=128",
    "net.tokenq.head_block=32", "net.tokenq.moe_tile=8",
    "replay.sequence_length=24", "replay.capacity=6144",
    "replay.fused_chain=2", "replay.write_chunk=64",
    "mesh.num_fake_devices=1"]
TOY_HPARAMS = {
    "vocab_size": 64, "num_actions": 64, "mask_token_id": 63,
    "compute_dtype": "float32", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 32, "router_experts": 8,
    "num_experts_per_tok": 2, "experts_held": 2, "expert_offset": 3,
    "sequence_length": 24, "capacity_windows": 256, "fused_chain": 2}
TOY_TOP = {"hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "moe_intermediate_size": 32, "num_experts": 2,
           "num_experts_per_tok": 2, "vocab_size": 64}
# interpreted kernels take seconds a step here: chunks of two steps, and
# no warm-up beyond the chunk the comparison drove
TOY_TRAFFIC = {"warmup_steps": 2, "row_every": 2, "trace_start_step": 2,
               "trace_num_steps": 2}
TOY_LIMIT = 0.05    # float32 on both sides at the toy size


def toy(conf: dict, traffic: dict) -> None:
    """This family's toy sizes for a CPU walk (``rehearse.py``): h 64, the
    cell's own four layers with 4 heads over 2 key/value heads of 16, T 24
    in 6 blocks of 4 (49 packed rows, kernel block 128), 8 experts top 2
    of which 2 held, vocabulary 64 with ``[MASK]`` its last row, chain 2,
    float32 — so every inexact limit is one small number."""
    conf["limits"] = {k: TOY_LIMIT for k in conf["limits"]}
    conf["overrides"] = [*conf["overrides"], *TOY_OVERRIDES]
    conf["hparams"].update(TOY_HPARAMS)
    conf.update(TOY_TOP)
    traffic.update({k: v for k, v in TOY_TRAFFIC.items() if k in traffic})
