"""The comparison that decides ``correct`` for the token-window Q-network
family (``tokenq``): a transformer Q-network on ``DeviceTokenReplay`` under
``SequenceSolver`` + ``FusedStepStream``.

Set-up builds ONE solver and ONE token ring, installs the seed's weights
under the program's per-path leaf names, fills the ring with seeded windows
that all differ, and drives the first chunk through the window's own call
(``FusedStepStream.next``) with a recorder on the sample program's outputs.
The same solver and ring then go into the window. After the window has
closed ``compare`` lets ``reference/tokenq.py`` follow that chunk from the
seed, a step at a time, and returns the numbers; ``family.verdict`` holds
each to its limit.

What is compared, all of it produced by the timed path itself:

- the feed: drawn slots inside the filled ring, token windows (so actions)
  and validity bit for bit, reward and discount to 1e-5 (the reference's
  ``EXACT_LIMITS``), the IS weights against the reference's own table;
- the forward path at the seed's weights: the FIRST step's loss, mean Q and
  the priorities it wrote back (η max|TD| + (1-η) mean|TD| of each window:
  an extreme of 8 192 TD errors, so it does not average the precision
  away), and that exactly the drawn slots of the table were rewritten;
- the backward and optimizer path: the first step's global gradient norm
  and Adam's first moment after it, ``m1 = (1-b1) clip g0``, by the worst
  leaf (from the step's per-leaf gradient norms); then what the chunk
  gathers: every step's loss and gradient norm,
  Adam's moment and θ's change at the chunk's end by the worst leaf, and
  the share of token-slots the router sent to the held experts.
"""

from __future__ import annotations

import contextlib

import numpy as np

from benchmark.check import Recorder, _adam_mu
from benchmark.common import emit
from benchmark.families.tokenq import program
from benchmark.family import load_reference

FOLLOWED_CHUNKS = 1     # the reference follows the first chunk
# what a driver's log rows carry of a step's metrics (the expert layer's
# counters), and the programs whose HLO scope tables a traced run needs
ROW_COUNTERS = ("moe_slots_held", "moe_slots", "moe_overflow",
                "moe_load_max_over_mean")
ADAM_B1 = 0.9
_GOLD: dict = {}        # the reference's follow of a seed's chunk, kept for
#                         the control's reading of the same seed (control.py)


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
    top = {k: (conf.get(k), hp[h]) for k, h in (
        ("num_hidden_layers", "num_hidden_layers"),
        ("moe_num_primary_experts", "moe_experts_held"),
        ("vocab_size", "vocab_size"), ("hidden_size", "hidden_size"),
        ("head_dim", "head_dim"),
        ("num_attention_heads", "num_attention_heads"),
        ("num_key_value_heads", "num_key_value_heads"),
        ("moe_ffn_hidden_size", "moe_ffn_hidden_size"),
        ("moe_num_active_primary_experts",
         "moe_num_active_primary_experts"),
        ("sliding_window_size", "sliding_window_size"),
        ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps"))
        if k in conf and conf[k] != hp[h]}
    if bad or top:
        raise SystemExit(f"configuration {conf['name']}: hparams differ "
                         f"from the program's Config (file, program): "
                         f"{bad}; top-level keys differ from hparams: {top}")


def prefill(replay, seed: int, rows, hp: dict, ref) -> dict:
    """Fill the ring through the program's own ``add_windows`` with seeded
    windows, a block at a time. ``rows`` is a window count or
    ``"capacity"``. Window i lands in slot i (one shard). The mirror is the
    seed and the count: the reference makes any window again."""
    if replay.num_shards != 1:
        raise SystemExit("the tokenq check fills one shard (slot = window)")
    n = replay.capacity if rows == "capacity" else min(int(rows),
                                                       replay.capacity)
    n = max(n // ref.GEN_BLOCK, 1) * ref.GEN_BLOCK
    if n > replay.capacity:
        raise SystemExit(f"ring of {replay.capacity} windows is smaller "
                         f"than one seeded block ({ref.GEN_BLOCK})")
    for b in range(n // ref.GEN_BLOCK):
        replay.add_windows(*ref.seeded_windows(seed, b, hp))
        replay.flush()
    return {"seed": int(seed), "filled": n}


def log_row(c: dict[str, float]) -> dict[str, float]:
    """A log row's keys from the step's ``ROW_COUNTERS``."""
    return {"moe_slots_held_share": 100.0 * c["moe_slots_held"]
            / max(c["moe_slots"], 1.0),
            "moe_load_max_over_mean": c["moe_load_max_over_mean"],
            "moe_overflow": c["moe_overflow"]}


def hlo_scope_tables(solver, replay, chain: int) -> dict[str, dict]:
    return {"jit_token_train_fn": program.train_program_scopes(
        solver, replay, chain)}


@contextlib.contextmanager
def recording(solver, replay, chain: int):
    learner = solver.learner
    sample, train = learner.token_fused_programs(
        replay, solver.config.replay.batch_size, chain)
    key = next(k for k, v in learner._fused_steps.items()
               if v[0] is sample)
    rec = Recorder(sample)
    learner._fused_steps[key] = (rec, train)
    try:
        yield rec
    finally:
        learner._fused_steps[key] = (sample, train)


def leaf_norms(tree_named: dict, minus: dict | None = None) -> dict:
    """‖leaf‖ (or ‖leaf - minus[leaf]‖) by name, a leaf at a time."""
    out = {}
    for k, v in tree_named.items():
        x = np.asarray(v, np.float32)
        if minus is not None:
            x = x - minus[k]
        out[k] = float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
    return out


def drive_first_chunk(solver, stream, replay, chain: int,
                      theta0: dict) -> dict:
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
    metrics = {k: np.asarray([np.asarray(m[k], np.float64)
                              for m in per_step])
               for k in per_step[0]}
    return dict(
        feed=feed, metrics=metrics, leaf_names=program.leaf_names(solver),
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
    # θ and θ⁻ from the seed, by the program's per-path leaf names; Adam's
    # state stays at the zeros it was built with
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
           "grad_leaf_norm": [], "priority": []}
    for s in range(weights.shape[0]):
        b = {k: jnp.asarray(batch[k][s]) for k in
             ("tokens", "reward", "discount", "mask")}
        b["weight"] = jnp.asarray(weights[s])
        state, m, prio = step(state, b)
        m = jax.device_get(m)
        for k in ("loss", "q_mean", "grad_norm"):
            out[k].append(float(m[k]))
        out["held_share"].append(float(np.mean(m["held_share"])))
        out["grad_leaf_norm"].append(
            {k: float(v) for k, v in m["grad_leaf_norm"].items()})
        out["priority"].append(np.asarray(prio))
    out["delta_norm"] = leaf_norms(jax.device_get(state["theta"]), theta0)
    out["m_norm"] = leaf_norms(jax.device_get(state["m"]))
    return out


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    """max over leaves of |‖prog‖ − ‖ref‖| / max(‖ref‖, median leaf ‖ref‖)."""
    med = float(np.median(list(ref.values())))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in ref)


def first_moment(leaf: dict, gnorm: float, hp: dict) -> dict:
    """‖m1‖ by leaf: (1 - b1) · clip · ‖g0‖."""
    scale = min(1.0, hp["grad_clip_norm"] / max(gnorm, 1e-12))
    return {k: (1.0 - ADAM_B1) * scale * v for k, v in leaf.items()}


def rel(a, g, floor=1e-12):
    a, g = np.asarray(a, np.float64), np.asarray(g, np.float64)
    return np.abs(a - g) / np.maximum(np.abs(g), floor)


def compare(conf: dict, seed: int, mirror, rec: dict, *, quant=None) -> dict:
    ref = load_reference(conf)
    hp = conf["hparams"]
    chain = hp["fused_chain"]
    feed = rec["feed"]
    idx = feed["idx"].astype(np.int64)
    nums: dict[str, float] = {}

    # (a) what the sample program fed, against the seeded ring
    legal = (idx >= 0) & (idx < mirror["filled"])
    nums["windows_illegal"] = int((~legal).sum())
    idx = np.where(legal, idx, 0)
    gold_batch = ref.windows_at(mirror["seed"], idx, hp)
    nums["token_window_mismatch"] = int(
        (feed["tokens"] != gold_batch["tokens"]).sum())
    nums["validity_mismatch"] = int(
        (feed["mask"] != gold_batch["mask"]).sum())
    nums["reward_max_abs"] = float(
        np.abs(feed["reward"] - gold_batch["reward"]).max())
    nums["discount_max_abs"] = float(
        np.abs(feed["discount"] - gold_batch["discount"]).max())
    # IS weights: every window entered at priority 1 and the chunk samples
    # against the table as of its start
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
    nums["priority_first_max_rel"] = float(prio_rel[0].max())
    # the table afterwards: every window entered at priority 1, and
    # exactly the drawn slots were rewritten (the later steps' values run
    # on weights that have drifted apart; their gap has a heavy tail —
    # sound 1.5e-2 against the control's 3.8e-2 — and is printed only)
    nums["priority_slots_miswritten"] = abs(
        int(prog["rewritten"]) - len(np.unique(idx)))
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
         priority_max_rel=float(prio_rel.max()))
    steps = {k: [[float(x) for x in prog[k]], gold[k]]
             for k in ("loss", "grad_norm", "q_mean")}
    return dict(numbers=nums, steps=steps, print=dict(
        followed_steps=chain, reference_loss=gold["loss"],
        compared_loss=[float(x) for x in prog["loss"]]))


# ---- toy sizes: the CPU walk of this family's cells ----

TOY_OVERRIDES = [
    "net.num_actions=64", "env.token_vocab=64", "net.compute_dtype=float32",
    "net.tokenq.hidden_size=64", "net.tokenq.num_attention_heads=4",
    "net.tokenq.num_key_value_heads=2", "net.tokenq.head_dim=16",
    "net.tokenq.sliding_window_size=8", "net.tokenq.moe_ffn_hidden_size=32",
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
    "head_dim": 16, "sliding_window_size": 8, "moe_ffn_hidden_size": 32,
    "moe_router_experts": 8, "moe_num_active_primary_experts": 2,
    "moe_experts_held": 2, "expert_offset": 3, "sequence_length": 24,
    "capacity_windows": 256, "batch_size": 4}
TOY_TOP = {"hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "sliding_window_size": 8, "moe_ffn_hidden_size": 32,
           "moe_num_primary_experts": 2,
           "moe_num_active_primary_experts": 2, "vocab_size": 64}
TOY_TRAFFIC = {"warmup_steps": 8, "row_every": 4, "trace_start_step": 8,
               "trace_num_steps": 8}
TOY_LIMIT = 0.05    # float32 on both sides at the toy size


def toy(conf: dict, traffic: dict) -> None:
    """This family's toy sizes for a CPU walk (``rehearse.py``): h 64,
    4 layers [0,1,1,1], window 8 on T 24, 8 experts top 2 of which 2 held,
    vocabulary 64, float32 — so every inexact limit is one small number."""
    conf["limits"] = {k: TOY_LIMIT for k in conf["limits"]}
    conf["overrides"] = [*conf["overrides"], *TOY_OVERRIDES]
    conf["hparams"].update(TOY_HPARAMS)
    conf.update(TOY_TOP)
    traffic.update({k: v for k, v in TOY_TRAFFIC.items() if k in traffic})
