"""The comparison that decides ``correct`` for the fused-learner cells.

Set-up builds ONE solver with its compiled sample/train pair and ONE ring,
installs the seed's weights, fills the ring to capacity with seeded rows
that all differ, and drives the pair through its first two chunks by the
window's own call (``FusedStepStream.next``); the same solver and ring then
go into the window. ``Recorder`` stands in the
learner's program table for those two chunks only and keeps what the
sample program really fed the train program (drawn rows, windows,
composed metadata, weights). After the window has closed — so neither
``setup_s`` nor ``memory_peak_bytes`` carries it — ``compare`` lets
``reference/dqn.py`` follow the same steps from the seed and hands every
number compared to ``family.verdict``, which holds each to its limit.

This is the comparison of ONE model family: the Nature CNN on the frame
ring (``DevicePERFrameReplay`` + ``FusedStepStream``). A configuration
takes it by ``"check": "check"``; another family brings its own module
with the same two functions, ``build_checked`` and ``compare``
(``family.py``; ``FOLLOWED_CHUNKS`` and ``toy`` are the two optional names
listed there).
"""

from __future__ import annotations

import contextlib

import numpy as np

from benchmark import program
from benchmark.common import emit, load_json
from benchmark.family import load_reference

# reference weight names in the order the program's weight IO lists its
# leaves (``Solver.get_weights``: flax tree, keys sorted)
PROGRAM_LEAF_ORDER = ("q_b", "q_w", "conv1_b", "conv1_w", "conv2_b",
                      "conv2_w", "conv3_b", "conv3_w", "fc4_b", "fc4_w")


# Chunks the reference follows step by step. One more chunk is driven
# after them: its IS weights are held against the reference's priority table
# as the followed chunks left it. More followed chunks separate the program
# from the control WORSE (the two sides drift apart step by step, PERF.md
# §2); ``control.py --follow-chunks`` sets this to read that again.
FOLLOWED_CHUNKS = 1


_STEPS: dict = {}


def reference_step(ref, hp: dict, quant):
    """The reference's jitted step, built once per (sizes, precision)."""
    key = (ref.__name__, repr(sorted(hp.items())), quant)
    if key not in _STEPS:
        _STEPS[key] = ref.make_step(hp, quant)
    return _STEPS[key]


def assert_hparams(conf: dict, cfg) -> None:
    """The configuration file states what the reference computes; the
    program's Config must say the same, or the cell is not what it claims."""
    hp = conf["hparams"]
    have = {
        "lr": cfg.train.lr, "adam_eps": cfg.train.adam_eps,
        "grad_clip_norm": cfg.train.grad_clip_norm,
        "target_update_period": cfg.train.target_update_period,
        "huber_delta": cfg.train.huber_delta, "gamma": cfg.train.gamma,
        "double_dqn": cfg.train.double_dqn, "n_step": cfg.replay.n_step,
        "stack": cfg.env.stack, "frame_shape": list(cfg.env.frame_shape),
        "batch_size": cfg.replay.batch_size,
        "fused_chain": cfg.replay.fused_chain,
        "capacity": cfg.replay.capacity,
        "priority_alpha": cfg.replay.priority_alpha,
        "priority_beta0": cfg.replay.priority_beta0,
        "priority_eps": cfg.replay.priority_eps,
        "compute_dtype": cfg.net.compute_dtype,
        "num_actions": cfg.net.num_actions, "dueling": cfg.net.dueling,
        "optimizer": cfg.train.optimizer, "target_tau": cfg.train.target_tau,
    }
    bad = {k: (hp.get(k), v) for k, v in have.items() if hp.get(k) != v}
    if bad:
        raise SystemExit(f"configuration drift (file, program): {bad}")


GEN_BLOCK = 25_000          # rows per seeded block of frames
GEN_THREADS = 6


def seeded_meta(seed: int, n: int, hp: dict, episode: int):
    """Actions, N(0,1) rewards and an episode end every ``episode`` rows
    for ``n`` transitions, from the seed."""
    rng = np.random.default_rng([seed, 0xD1])
    action = rng.integers(0, hp["num_actions"], n).astype(np.int32)
    reward = rng.standard_normal(n).astype(np.float32)
    done = (np.arange(n) % episode) == episode - 1
    return action, reward, done


def seeded_frames(seed: int, n: int, row_len: int):
    """``n`` uint8 frames that all differ, block ``b`` from the key
    ``(seed, b)``, made by a few threads (numpy's generators release the
    GIL). Returns the array and one future per block, in row order: the
    fill starts on a block as soon as it is there."""
    from concurrent.futures import ThreadPoolExecutor

    frames = np.empty((n, row_len), np.uint8)

    def block(b: int) -> None:
        lo, hi = b * GEN_BLOCK, min((b + 1) * GEN_BLOCK, n)
        rng = np.random.default_rng([seed, 0xD0, b])
        frames[lo:hi] = rng.integers(0, 256, (hi - lo, row_len),
                                     dtype=np.uint8)

    pool = ThreadPoolExecutor(GEN_THREADS)
    futs = [pool.submit(block, b) for b in range(-(-n // GEN_BLOCK))]
    pool.shutdown(wait=False)
    return frames, futs


def prefill(replay, seed: int, rows, hp: dict, ref, *, episode: int = 1000):
    """Fill the ring through the program's own ``add_batch`` and return the
    reference's ``Mirror`` of what was written. ``rows`` is a count or
    ``"capacity"``: a deployment's ring is full, so draws, priorities and
    window DMAs span all of it. Each stream writes whole episodes into its
    own sub-rings, one flush round (``write_chunk`` rows) per call — more
    rows in a call would only be compacted in staging again and again."""
    streams = replay.num_streams
    per = (replay.capacity if rows == "capacity" else int(rows)) // streams
    per = per // episode * episode      # whole episodes: no sub-ring wraps
    n = per * streams
    h, w = hp["frame_shape"]
    action, reward, done = seeded_meta(seed, n, hp, episode)
    frames, blocks = seeded_frames(seed, n, h * w)
    gidx = np.empty(n, np.int64)
    k = replay.write_chunk
    ready = 0                           # rows whose block has been made
    for s in range(streams):
        for p0 in range(s * per, (s + 1) * per, k):
            p1 = min(p0 + k, (s + 1) * per)
            while ready < p1:
                blocks[ready // GEN_BLOCK].result()
                ready = min((ready // GEN_BLOCK + 1) * GEN_BLOCK, n)
            gidx[p0:p1] = replay.add_batch({
                "frame": frames[p0:p1].reshape(-1, h, w),
                "action": action[p0:p1], "reward": reward[p0:p1],
                "done": done[p0:p1]}, stream=s)
    replay.flush()
    # the mirror is kept in RING order per sub-ring so that "the next row"
    # of the reference is the next ring row (written stream by stream, the
    # rows already are in ring order on one shard)
    if np.any(np.diff(gidx) <= 0):
        order = np.argsort(gidx, kind="stable")
        frames, action, reward, done, gidx = (
            frames[order], action[order], reward[order], done[order],
            gidx[order])
    # a run ends where the ring rows stop being consecutive, and at every
    # sub-ring's first row (full sub-rings lie back to back)
    cuts = np.flatnonzero((np.diff(gidx) != 1)
                          | (gidx[1:] % replay.slot_cap == 0)) + 1
    starts = np.concatenate([[0], cuts])
    segments = [(int(gidx[s]), int(e - s)) for s, e in
                zip(starts, np.concatenate([cuts, [n]]))]
    return ref.Mirror(frames, action, reward, done, gidx, segments,
                      replay.capacity)


def install_weights(solver, ref, seed: int, hp: dict):
    """θ and θ⁻ from the seed into the solver (the reference makes the same
    from the same seed); Adam's state stays at the zeros it was built with."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    theta, target = ref.init_weights(seed, hp["num_actions"], hp["stack"],
                                     hp["frame_shape"])
    treedef = jax.tree_util.tree_structure(solver.state.params)
    rep = NamedSharding(solver.mesh, PartitionSpec())

    def tree(w):
        return jax.device_put(jax.tree_util.tree_unflatten(
            treedef, [w[k] for k in PROGRAM_LEAF_ORDER]), rep)

    solver.state = solver.state.replace(params=tree(theta),
                                        target_params=tree(target))


class Recorder:
    """Callable stand-in for the learner's ``sample`` program that calls it
    and keeps its outputs (device arrays; fetched later)."""

    def __init__(self, sample):
        self.sample, self.calls = sample, []

    def __call__(self, *args):
        out = self.sample(*args)
        self.calls.append(out)
        return out


@contextlib.contextmanager
def recording(solver, replay, chain: int):
    """Record the sample program's outputs for the chunks dispatched inside
    the block. The compiled pair is the solver's own, built on first use."""
    spec = solver.device_per_spec(replay)
    sample, train = solver.learner.device_per_programs(spec, chain)
    table = solver.learner._device_per_steps
    rec = Recorder(sample)
    table[(spec, chain)] = (rec, train)
    try:
        yield rec
    finally:
        table[(spec, chain)] = (sample, train)


def drive_first_chunks(solver, stream, replay, chain: int,
                       follow: int) -> dict:
    """The first ``follow + 1`` chunks through ``stream.next`` with the
    recorder in; returns host copies of what the reference will be held
    against: per-step metrics, the state before and after the followed
    chunks, the recorded feed."""
    import jax

    state0 = jax.device_get(solver.state)
    per_step: list[dict] = []
    with recording(solver, replay, chain) as rec:
        for c in range(follow + 1):
            for _ in range(chain):
                per_step.append(stream.next(10 ** 9))
            if c == follow - 1:
                state1 = jax.device_get(solver.state)
    metrics = {k: np.asarray([float(m[k]) for m in per_step])
               for k in ("loss", "grad_norm", "q_mean")}
    feeds = []
    for c, (metas, win, idx) in enumerate(rec.calls):
        feed = {k: np.asarray(v) for k, v in metas.items()}
        feed["idx"] = np.asarray(idx)
        if c == 0:
            feed["win"] = np.asarray(win)
        feeds.append(feed)
    if len(feeds) != follow + 1:
        raise SystemExit(f"recorded {len(feeds)} sample calls, expected "
                         f"{follow + 1}")
    return dict(state0=state0, state1=state1, metrics=metrics, feeds=feeds)


def build_checked(conf: dict, cfg, seed: int, rows, episode: int,
                  beta_steps: int | None = None, mark=lambda name: None):
    """The object the window will drive, built and checked once: a solver
    with the seed's weights, a ring as ``train_distributed`` builds it with
    ``rows`` seeded rows, the stream, and the first chunks driven through
    it. Returns ``(solver, replay, stream, mirror, rec)``;
    ``rec["driven_steps"]`` is what a driver takes off its warm-up."""
    from distributed_deep_q_tpu.solver import FusedStepStream

    assert_hparams(conf, cfg)
    hp = conf["hparams"]
    hp["priority_beta_steps"] = beta_steps or cfg.replay.priority_beta_steps
    ref = load_reference(conf)
    chain = cfg.replay.fused_chain
    solver = program.make_solver(cfg)
    install_weights(solver, ref, seed, hp)
    replay = program.make_replay(cfg, solver, beta_steps)
    mark("solver_weights_ring")
    mirror = prefill(replay, seed, rows, hp, ref, episode=episode)
    mark("prefill")
    stream = FusedStepStream(solver, replay, chain)
    rec = drive_first_chunks(solver, stream, replay, chain,
                             FOLLOWED_CHUNKS)
    rec["driven_steps"] = (FOLLOWED_CHUNKS + 1) * chain
    mark("first_chunks")
    return solver, replay, stream, mirror, rec


def _leaves(state_tree) -> dict[str, np.ndarray]:
    import jax

    return dict(zip(PROGRAM_LEAF_ORDER,
                    (np.asarray(x, np.float32)
                     for x in jax.tree_util.tree_leaves(state_tree))))


def _adam_mu(opt_state):
    """The Adam first-moment tree inside the program's optimizer state
    (bare adam, or chain(clip, adam))."""
    import jax

    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise SystemExit("cannot locate Adam's state in the optimizer state")
    return found[0].mu


def rel_l2(prog: dict, ref: dict) -> float:
    """‖prog − ref‖₂ / ‖ref‖₂ over all leaves as one vector: it gathers
    every element's rounding, so it is steady from seed to seed where a
    gap of two norms (a scalar through zero) is not."""
    num = sum(float(np.sum(np.square(prog[k] - ref[k]))) for k in ref)
    den = sum(float(np.sum(np.square(ref[k]))) for k in ref)
    return float(np.sqrt(num / max(den, 1e-60)))


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    """max over leaves of |‖prog‖ − ‖ref‖| / max(‖ref‖, median leaf ‖ref‖)."""
    rn = {k: float(np.linalg.norm(ref[k])) for k in ref}
    med = float(np.median(list(rn.values())))
    return max(abs(float(np.linalg.norm(prog[k])) - rn[k])
               / max(rn[k], med, 1e-30) for k in ref)


def _follow(ref, hp: dict, seed: int, mirror, idxs, betas, quant):
    """The reference (or, with ``quant``, the control) over the chunks
    drawn at ``idxs`` from the seed: each chunk's batch composed from the
    mirror, its IS weights from the reference's own priority table as of
    the chunk's start, the table rewritten with the chunk's |TD| at its
    end (a later duplicate wins, as a sequential write has it). Returns
    per-step metrics, the weights, θ at the start, the state at the end
    — all on the host — and chunk 0's batch."""
    import jax

    chain = hp["fused_chain"]
    theta, target = ref.init_weights(seed, hp["num_actions"], hp["stack"],
                                     hp["frame_shape"])
    state = ref.init_state(theta, target)
    step = reference_step(ref, hp, quant)
    metrics = {"loss": [], "grad_norm": [], "q_mean": []}
    weights, batch0 = [], None
    mirror.fresh_priorities()
    for c, idx in enumerate(idxs):
        batch = ref.compose(mirror, idx, hp)
        batch0 = batch if c == 0 else batch0
        w = ref.is_weights(mirror, idx, betas[c * chain:(c + 1) * chain], hp)
        weights.append(w)
        td = []
        for s in range(chain):
            b = {k: batch[k][s] for k in
                 ("obs", "next_obs", "action", "reward", "discount")}
            b["weight"] = w[s]
            state, m, td_abs = step(state, b)
            for k in metrics:
                metrics[k].append(float(m[k]))
            td.append(np.asarray(td_abs))
        for s in range(chain):
            ref.update_priorities(mirror, idx[s], td[s], hp)
    state = jax.device_get(state)
    host = lambda t: {k: np.asarray(v) for k, v in t.items()}  # noqa: E731
    return dict(metrics=metrics, weights=weights, batch0=batch0,
                theta0=host(jax.device_get(theta)),
                theta=host(state["theta"]), m=host(state["m"]))


def compare(conf: dict, seed: int, mirror, rec: dict, *, quant=None) -> dict:
    """Let the reference follow the recorded steps and compare. Returns
    ``{"numbers": {name: value}, "steps": per-step metrics of both,
    "print": what to print beside them}``; ``family.verdict`` holds the
    numbers to their limits. With ``quant`` the CONTROL — the reference in
    the next precision down — stands where the program stood, for the
    optimizer numbers (the feed numbers are the program's either way)."""
    ref = load_reference(conf)
    hp = conf["hparams"]
    chain = hp["fused_chain"]
    follow = FOLLOWED_CHUNKS
    feeds = rec["feeds"]
    f0, f_last = feeds[0], feeds[follow]
    nums: dict[str, float] = {}

    # (a) what the sample program fed, against the mirror at its draws
    ok = ref.valid_rows(mirror, hp["stack"], hp["n_step"])
    a_legal_row = mirror.segments[0][0] + hp["stack"]
    idxs, illegal = [], 0
    for f in feeds:
        idx = f["idx"].astype(np.int64)
        illegal += int((~ok[idx]).sum())
        idxs.append(np.where(ok[idx], idx, a_legal_row))
    nums["illegal_draws"] = illegal
    betas = ref.betas_for(0, (follow + 1) * chain, hp)

    # (b) the optimizer steps of the followed chunks, from the seed
    gold = _follow(ref, hp, seed, mirror, idxs[:follow], betas, None)
    batch = gold["batch0"]
    nums["action_mismatch"] = int((f0["action"] != batch["action"]).sum())
    nums["reward_max_abs"] = float(np.abs(f0["reward"]
                                          - batch["reward"]).max())
    nums["discount_max_abs"] = float(np.abs(f0["discount"]
                                            - batch["discount"]).max())
    nums["validity_mismatch"] = int(
        (f0["ovalid"].astype(bool) != batch["ovalid"]).sum()
        + (f0["nvalid"].astype(bool) != batch["nvalid"]).sum())
    # windows: rows idx-stack+1 .. idx+n of the ring, unmasked, padded rows
    row_len = hp["frame_shape"][0] * hp["frame_shape"][1]
    win = f0["win"].view(np.uint8).reshape(f0["win"].shape[:3] + (-1,))
    rows = (mirror.row_of[idxs[0]][..., None]
            + np.arange(-(hp["stack"] - 1), hp["n_step"] + 1))
    nums["window_pixels_mismatch"] = sum(     # a chunk step at a time
        int((win[c, :, :, :row_len] != mirror.frames[rows[c]]).sum())
        for c in range(win.shape[0]))

    # (c) IS weights: the followed chunks' against the reference's own
    # table as it stood at each chunk's start, and the chunk after them
    # against the table the followed chunks left. The per-step
    # normalization is taken out by the median ratio. Rows drawn twice in
    # one step have no defined winner and are left out. A row whose |TD|
    # is near zero turns bf16 noise into a large relative gap, so the
    # rewritten rows are held by their median gap. (The table is the
    # reference's: this runs before the control rewrites it.)
    if quant is None:
        n_rows = len(mirror.row_of)
        dup = np.zeros(n_rows, bool)
        rewritten = np.zeros(n_rows, bool)
        for idx in idxs[:follow]:
            for s in range(chain):
                u, cnt = np.unique(idx[s], return_counts=True)
                dup[u[cnt > 1]] = True
            rewritten[idx.reshape(-1)] = True
        w_last = ref.is_weights(mirror, idxs[follow], betas[follow * chain:],
                                hp)
        ratio = f_last["weight"] / np.maximum(w_last, 1e-12)
        ratio = np.abs(ratio / np.median(ratio, axis=1, keepdims=True) - 1)
        fresh = ~rewritten[idxs[follow]]
        moved = rewritten[idxs[follow]] & ~dup[idxs[follow]]
        w0 = np.abs(f0["weight"] / np.maximum(gold["weights"][0], 1e-12)
                    - 1.0)
        nums["weight_fresh_max_rel"] = float(max(
            w0.max(), ratio[fresh].max() if fresh.any() else 0.0))
        nums["weight_rewritten_median_rel"] = float(
            np.median(ratio[moved]) if moved.any() else 0.0)
        emit(weight_rows_fresh=int(fresh.sum()),
             weight_rows_rewritten=int(moved.sum()))
        prog = dict(
            metrics={k: rec["metrics"][k][:follow * chain]
                     for k in gold["metrics"]},
            theta0=_leaves(rec["state0"].params),
            theta=_leaves(rec["state1"].params),
            m=_leaves(_adam_mu(rec["state1"].opt_state)))
    else:
        prog = _follow(ref, hp, seed, mirror, idxs[:follow], betas, quant)
    for k in gold["metrics"]:
        a, g = np.asarray(prog["metrics"][k]), np.asarray(gold["metrics"][k])
        # q_mean can sit near zero: its gap is held against 0.1 at least
        rel = np.abs(a - g) / np.maximum(np.abs(g),
                                         0.1 if k == "q_mean" else 1e-12)
        nums[f"{k}_max_rel"] = float(rel.max())
        nums[f"{k}_mean_rel"] = float(rel.mean())
        if k == "q_mean":
            # the first step runs on the seed's θ on both sides, so its
            # gap is the forward pass's precision alone; every later step
            # adds the two sides' drifting apart (at batch 32 that drift
            # passes the fp8 control's gap within the chunk)
            nums["q_mean_first_rel"] = float(rel[0])
    nums["moment_norm_worst_leaf"] = worst_leaf_gap(prog["m"], gold["m"])
    nums["moment_diff_rel_l2"] = rel_l2(prog["m"], gold["m"])
    nums["delta_norm_worst_leaf"] = worst_leaf_gap(
        {k: prog["theta"][k] - prog["theta0"][k] for k in gold["theta"]},
        {k: gold["theta"][k] - gold["theta0"][k] for k in gold["theta"]})

    steps = {k: [[float(x) for x in prog["metrics"][k]], gold["metrics"][k]]
             for k in gold["metrics"]}
    return dict(numbers=nums, steps=steps, print=dict(
        followed_steps=follow * chain,
        reference_loss=gold["metrics"]["loss"][:3],
        compared_loss=[float(x) for x in prog["metrics"]["loss"][:3]]))


# ---- toy sizes: the CPU walk of this family's cells ----

TOY_OVERRIDES = ["replay.capacity=8192", "replay.batch_size=32",
                 "env.frame_shape=36,36", "net.frame_shape=36,36",
                 "mesh.num_fake_devices=1"]
TOY_HPARAMS = {"capacity": 8192, "batch_size": 32, "frame_shape": [36, 36]}
TOY_TRAFFIC = {"episode": 256, "warmup_steps": 16, "row_every": 40,
               "num_actors": 2, "learn_start": 300, "trace_start_step": 16,
               "trace_num_steps": 16}
TOY_SLACK = 3.0     # 36x36 frames at batch 32 are noisier than any cell


def toy(conf: dict, traffic: dict) -> None:
    """This family's toy sizes for a CPU walk (``rehearse.py``,
    ``test_control.py``), as a ``conf_patch``. At batch 32 every
    configuration takes the plane path, so the limits start from those
    read for the batch-32 configuration; the numbers that gather the
    steps' drifting apart get ``TOY_SLACK`` times the room, the first
    step's forward gap — the one that separates the fp8 control — keeps
    its limit."""
    limits = load_json("configs", "dqn_b32.json")["limits"]
    conf["limits"] = {k: v if k == "q_mean_first_rel" else TOY_SLACK * v
                      for k, v in limits.items()}
    conf["overrides"] = [*conf["overrides"], *TOY_OVERRIDES]
    conf["hparams"].update(TOY_HPARAMS)
    traffic.update({k: v for k, v in TOY_TRAFFIC.items() if k in traffic})
