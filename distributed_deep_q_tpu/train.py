"""Single-process training loop — config 1 (CartPole smoke) and the
in-process Atari path (SURVEY.md §7.2 step 1: the minimum end-to-end slice).

One process hosts actor + replay + learner; the distributed topology
(actors over RPC → replay service → mesh learner) lives in ``rpc/`` and
``actors/supervisor.py`` and reuses the same Solver and replay components.
"""

from __future__ import annotations

import os

import jax
import numpy as np

from distributed_deep_q_tpu import tracing
from distributed_deep_q_tpu.actors.game import (
    FrameStacker, NStepAccumulator, make_env)
from distributed_deep_q_tpu.config import Config
from distributed_deep_q_tpu.metrics import Metrics, MovingAverage
from distributed_deep_q_tpu.profiling import (
    StepTimer, TraceWindow, start_profiler_server)
from distributed_deep_q_tpu.replay.prioritized import maybe_prioritize
from distributed_deep_q_tpu.replay.replay_memory import FrameStackReplay, ReplayMemory
from distributed_deep_q_tpu.solver import Solver
from distributed_deep_q_tpu.utils.checkpoint import maybe_checkpointer
from distributed_deep_q_tpu.utils.compile_cache import process_clock


def epsilon_at(step: int, cfg) -> float:
    """Linear ε anneal (Nature-DQN style single-actor schedule)."""
    frac = min(step / max(cfg.eps_decay_steps, 1), 1.0)
    return cfg.eps_start + frac * (cfg.eps_end - cfg.eps_start)


def evaluate(solver: Solver, cfg: Config, episodes: int | None = None,
             seed: int = 10_000) -> float:
    """Greedy-policy rollouts (ε=eval_eps) → mean episode return
    (SURVEY §3.5 [M])."""
    env = make_env(cfg.env, seed=seed)
    rng = np.random.default_rng(seed)
    episodes = episodes or cfg.train.eval_episodes
    pixel_env = env.obs_dtype == np.uint8
    stacker = FrameStacker(env.obs_shape, cfg.env.stack) if pixel_env else None
    returns = []
    for _ in range(episodes):
        obs, ep_ret, over = env.reset(), 0.0, False
        if stacker:
            obs = stacker.reset(obs)
        while not over:
            a = solver.act(obs, cfg.actors.eval_eps, rng)
            frame, r, _, over = env.step(a)
            obs = stacker.push(frame) if stacker else frame
            ep_ret += r
        returns.append(ep_ret)
    return float(np.mean(returns))


def evaluate_per_game(solver, cfg: Config, episodes: int | None = None,
                      seed: int = 10_000, recurrent: bool = False,
                      ) -> dict[str, float]:
    """Greedy eval on every configured game (config 4 multi-game fleets):
    ``{game_id: mean return}``; single-game configs return one entry."""
    import dataclasses

    fn = evaluate_recurrent if recurrent else evaluate
    out = {}
    for g in (cfg.env.games or (cfg.env.id,)):
        gcfg = cfg.replace(env=dataclasses.replace(cfg.env, id=g))
        out[g] = fn(solver, gcfg, episodes, seed)
    return out


def log_final_eval(solver, cfg: Config, metrics: Metrics, summary: dict,
                   recurrent: bool = False) -> float:
    """Final greedy eval across all configured games: fills ``summary``
    (``eval_return`` mean, ``eval_per_game`` when multi-game) and logs
    per-game metrics. Shared by the distributed loops."""
    per_game = evaluate_per_game(solver, cfg, recurrent=recurrent)
    summary["eval_return"] = float(np.mean(list(per_game.values())))
    if len(per_game) > 1:
        summary["eval_per_game"] = per_game
        metrics.log(cfg.train.total_steps,
                    **{f"eval_return/{g}": v for g, v in per_game.items()})
    return summary["eval_return"]


def train_single_process(cfg: Config, metrics: Metrics | None = None,
                         log_every: int = 1_000) -> dict:
    """Run config-1-style training; returns final summary metrics.

    Multi-host (config 5, SURVEY §5.8): when the process was connected via
    ``initialize_multihost``, every host runs this same loop — its own env
    (seed-offset per process) feeding its own replay shard, sampling its
    ``batch_size/process_count`` local rows into the global-mesh train step
    whose ``lax.pmean`` spans hosts. The learn gate opens only when every
    host's shard is warm (``all_processes_ready``) so no process enters the
    collective step early.
    """
    if cfg.net.kind == "r2d2":
        return train_recurrent(cfg, metrics, log_every)
    if cfg.net.kind == "tokenq":
        return train_tokenq(cfg, metrics, log_every)
    metrics = metrics or Metrics()
    # NOTE: solver/env construction initializes the JAX backend; only then
    # is process topology safe to query (probing earlier would pre-empt the
    # --backend platform selection).
    env = make_env(cfg.env, seed=cfg.train.seed)
    cfg.net.num_actions = env.num_actions
    obs_dim = int(np.prod(env.obs_shape))
    solver = Solver(cfg, obs_dim=obs_dim)
    pc, pid = jax.process_count(), jax.process_index()
    local_batch = cfg.replay.batch_size
    if pc > 1:
        from distributed_deep_q_tpu.parallel.multihost import (
            all_processes_ready, local_rows)
        if cfg.replay.batch_size % pc:
            raise ValueError(f"replay.batch_size={cfg.replay.batch_size} "
                             f"must divide across {pc} processes")
        local_batch = cfg.replay.batch_size // pc
        # decorrelate the per-host experience streams
        env = make_env(cfg.env, seed=cfg.train.seed + 131 * pid)
        if pid != 0:
            metrics = Metrics()  # file/TB sinks live on process 0 only
    rng = np.random.default_rng(cfg.train.seed + 131 * pid)

    seed = cfg.train.seed + 131 * pid
    pixel_env = env.obs_dtype == np.uint8
    if pixel_env:
        if cfg.replay.device_resident:
            if pc > 1:
                raise ValueError(
                    "replay.device_resident=True is single-controller only "
                    "(the host writes frames into a mesh-sharded HBM ring); "
                    "multi-host pixel runs need replay.device_resident=false")
            # TPU-first data path: frames, metadata and priorities live in
            # HBM and the fused step samples there (zero host round trips
            # a step)
            from distributed_deep_q_tpu.replay.device_per import (
                pixel_device_ring)
            replay = pixel_device_ring(
                cfg.replay, solver.mesh, env.obs_shape, cfg.env.stack,
                cfg.train.gamma, seed=seed)
        else:
            replay = maybe_prioritize(FrameStackReplay(
                cfg.replay.capacity, env.obs_shape, cfg.env.stack,
                cfg.replay.n_step, cfg.train.gamma, seed=seed),
                cfg.replay, seed=seed)
        stacker = FrameStacker(env.obs_shape, cfg.env.stack)
    else:
        replay = maybe_prioritize(ReplayMemory(
            cfg.replay.capacity, env.obs_shape, np.float32,
            seed=seed), cfg.replay, seed=seed)
        nstep = NStepAccumulator(cfg.replay.n_step, cfg.train.gamma)

    frame = env.reset()
    obs = stacker.reset(frame) if pixel_env else frame
    ep_ret, ep_returns = 0.0, MovingAverage(100)
    summary: dict = {}
    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay
    from distributed_deep_q_tpu.solver import FusedStepStream
    fused_per = isinstance(replay, DevicePERFrameReplay)
    writeback = None
    if replay.prioritized and not fused_per:
        from distributed_deep_q_tpu.replay.prioritized import make_writeback
        writeback = make_writeback(replay, cfg.replay,
                                   to_host=None if pc == 1 else local_rows)
    learn_live = False  # latched once warm (all shards warm, multi-host)
    gsteps = 0
    best_eval, best_params = float("-inf"), None
    timer = StepTimer()
    fused_stream = (FusedStepStream(solver, replay, cfg.replay.fused_chain,
                                    timer=timer) if fused_per else None)
    # learning-dynamics plane (ISSUE 16): in-process loop folds the
    # fused chunks' returned planes into learn/* gauges at log cadence
    # (the health-plane registration is the distributed supervisor's job)
    learn_acc = None
    if cfg.train.learn_metrics and fused_per:
        from distributed_deep_q_tpu import learning
        learn_acc = learning.LearnAccumulator()
    trace = TraceWindow(cfg.train.profile_dir, cfg.train.profile_start_step,
                        cfg.train.profile_num_steps)
    if cfg.train.profile_port:
        start_profiler_server(cfg.train.profile_port)
    compile_clock = process_clock()
    ckpt = maybe_checkpointer(cfg.train)
    if ckpt and cfg.train.resume and ckpt.latest_step() is not None:
        solver.state, _ = ckpt.restore(solver.state)
        gsteps = solver.step
    persist = cfg.replay.persist_path
    if persist and pc > 1:
        # per-process shard files: a shared path would race on save and
        # clone one shard's content (and RNG) onto every host on resume
        persist = f"{persist}.proc{pid}"
    if persist and cfg.train.resume and os.path.exists(persist):
        # opt-in replay persistence (SURVEY §5.4): restore the buffer's
        # exact sampling state instead of warm-refilling
        from distributed_deep_q_tpu.replay.persistence import load_replay
        load_replay(replay, persist)

    try:
        for t in range(1, cfg.train.total_steps + 1):
            eps = epsilon_at(t, cfg.actors)
            a = solver.act(obs, eps, rng)
            next_frame, r, done, over = env.step(a)
            ep_ret += r

            if pixel_env:
                # frame (pre-action), action, reward, done; boundary marks any
                # episode end incl. truncation so stacks/windows never cross it
                replay.add(frame, a, r, done, boundary=over)
                frame = next_frame
                obs = stacker.push(frame)
            else:
                for tr in nstep.push(obs, a, r, next_frame, done):
                    replay.add(*tr)
                obs = next_frame
            metrics.count("env_steps")

            if over:
                if not pixel_env and not done:
                    # time-limit truncation: flush the n-step tail with bootstrap
                    # instead of discarding the end-of-episode transitions
                    for tr in nstep.flush_truncated(next_frame):
                        replay.add(*tr)
                ep_returns.add(ep_ret)
                ep_ret = 0.0
                frame = env.reset()
                if pixel_env:
                    obs = stacker.reset(frame)
                else:
                    obs = frame
                    nstep.reset()

            if t % cfg.train.train_every == 0 and not learn_live:
                # the ready latch: single-process = local fill check;
                # multi-host = every process's shard warm (collective AND,
                # called at the same loop point on every host)
                ready = replay.ready(cfg.replay.learn_start)
                learn_live = (ready if pc == 1
                              else all_processes_ready(ready))
            if learn_live and t % cfg.train.train_every == 0:
                # learn phase: j minibatches per k env steps (SURVEY §3.1 [M]).
                # Fused path: chain up to fused_chain of the j steps into one
                # two-program dispatch (lax.scan); per-step bookkeeping below
                # reads its row of the chunk's stacked metrics.
                for j in range(cfg.train.grad_steps_per_train):
                    if fused_per:
                        # sample+train+priority-update fused on device,
                        # up to fused_chain grad steps per dispatch
                        # (FusedStepStream owns the chunk/tail/slicing)
                        m = fused_stream.next(
                            cfg.train.grad_steps_per_train - j)
                    else:
                        with timer.phase("sample"):
                            batch = replay.sample(local_batch)
                        sampled_at = batch.pop("_sampled_at",
                                               replay.steps_added)
                        with timer.phase("dispatch"):
                            m = solver.train_step(batch)
                    gsteps += 1
                    timer.step_done()
                    trace.on_step(gsteps)
                    if replay.prioritized and not fused_per:
                        # pipelined priority write-back: |TD| is async-
                        # copied at dispatch and consumed ``depth`` steps
                        # later, so the learner never blocks on a D2H
                        # fetch. Multi-host: each process writes back only
                        # its own rows, into its own shard (local_rows).
                        writeback.push(m["index"], m["td_abs"], sampled_at)
                    metrics.count("grad_steps")
                    if ckpt and gsteps % cfg.train.checkpoint_every == 0:
                        with tracing.span("learner_checkpoint"):
                            ckpt.save(solver.state, extra={"env_steps": t})
                            if persist:
                                from distributed_deep_q_tpu.replay \
                                    .persistence import save_replay
                                save_replay(replay, persist)
                    # host-side counter: reading solver.step would sync on the
                    # just-dispatched device step every iteration
                    if gsteps % log_every == 0:
                        with tracing.span("learner_log"):
                            timer.measure_device(m["loss"])
                            summary = {
                                "loss": float(m["loss"]),
                                "q_mean": float(m["q_mean"]),
                                "return_avg100": ep_returns.value,
                                "epsilon": eps,
                                "grad_steps_per_s": metrics.rate("grad_steps"),
                                "env_steps_per_s": metrics.rate("env_steps"),
                            }
                            metrics.gauge("queue/replay_size", len(replay))
                            pending = getattr(replay, "pending_rows", None)
                            if pending is not None:
                                metrics.gauge("queue/staged_rows", pending())
                            if learn_acc is not None:
                                # D2H of the window's planes happens here, at
                                # log cadence — never on the step path
                                for plane in fused_stream.drain_planes():
                                    learn_acc.ingest(plane)
                                for lk, lv in learn_acc.gauges().items():
                                    metrics.gauge(lk, lv)
                                for lk, lv in learn_acc.hist_snapshot(
                                        ).summary(
                                        prefix="learn/td_error").items():
                                    metrics.gauge(lk, lv)
                            metrics.log(solver.step, **summary,
                                        **timer.summary(),
                                        **metrics.telemetry(),
                                        **solver.fused_gauges(),
                                        **compile_clock.row())

            if (cfg.train.eval_every and t % cfg.train.eval_every == 0):
                ret = evaluate(solver, cfg)
                metrics.log(solver.step, eval_return=ret)
                if cfg.train.keep_best_eval and ret > best_eval:
                    best_eval = ret
                    best_params = jax.device_get(solver.state.params)

    finally:
        trace.close()
    if writeback:
        writeback.drain()  # apply the depth-queued priority tail
    summary["final_return_avg100"] = ep_returns.value
    final_ret = evaluate(solver, cfg)
    if best_params is not None and best_eval > final_ret:
        # model selection: the best-eval snapshot beats the final params;
        # restore BEFORE the final checkpoint so what's on disk is what
        # eval_return reports
        solver.state = solver.state.replace(params=jax.device_put(
            best_params, solver.learner._replicated))
        final_ret = evaluate(solver, cfg)
    if ckpt:
        ckpt.save(solver.state, extra={"env_steps": cfg.train.total_steps},
                  wait=True)
    if persist:
        from distributed_deep_q_tpu.replay.persistence import save_replay
        save_replay(replay, persist)
    summary["eval_return"] = final_ret
    summary["train_unpack_planes"] = solver.learner.unpack_planes
    summary["solver"] = solver
    return summary


# ---------------------------------------------------------------------------
# Recurrent (R2D2) single-process loop — config 5 [M]
# ---------------------------------------------------------------------------


def evaluate_recurrent(solver, cfg: Config, episodes: int | None = None,
                       seed: int = 10_000) -> float:
    """Greedy rollouts threading LSTM state through the episode."""
    env = make_env(cfg.env, seed=seed)
    rng = np.random.default_rng(seed)
    episodes = episodes or cfg.train.eval_episodes
    pixel = env.obs_dtype == np.uint8
    stacker = FrameStacker(env.obs_shape, cfg.env.stack) if pixel else None
    returns = []
    for _ in range(episodes):
        obs, ep_ret, over = env.reset(), 0.0, False
        if stacker:
            obs = stacker.reset(obs)
        carry = solver.initial_state(1)
        while not over:
            a, carry = solver.act(np.asarray(obs), carry,
                                  cfg.actors.eval_eps, rng)
            frame, r, _, over = env.step(a)
            obs = stacker.push(frame) if stacker else frame
            ep_ret += r
        returns.append(ep_ret)
    return float(np.mean(returns))


def train_recurrent(cfg: Config, metrics: Metrics | None = None,
                    log_every: int = 1_000) -> dict:
    """R2D2 loop: recurrent actor → SequenceBuilder → SequenceReplay →
    SequenceLearner. Sequence counts derive from transition-denominated
    config fields (capacity/learn_start ÷ seq_len)."""
    from distributed_deep_q_tpu.parallel.sequence_learner import SequenceSolver
    from distributed_deep_q_tpu.replay.sequence import (
        SequenceBuilder, SequenceReplay)

    metrics = metrics or Metrics()
    env = make_env(cfg.env, seed=cfg.train.seed)
    cfg.net.num_actions = env.num_actions
    obs_dim = int(np.prod(env.obs_shape))
    solver = SequenceSolver(cfg, obs_dim=obs_dim)
    rng = np.random.default_rng(cfg.train.seed)

    pixel = env.obs_dtype == np.uint8
    stacker = FrameStacker(env.obs_shape, cfg.env.stack) if pixel else None
    obs_shape = (tuple(env.obs_shape) + (cfg.env.stack,)) if pixel \
        else tuple(env.obs_shape)
    obs_dtype = np.uint8 if pixel else np.float32

    seq_len = cfg.replay.sequence_length
    seq_capacity = max(cfg.replay.capacity // seq_len, 64)
    device_seq = pixel and cfg.replay.device_resident
    if device_seq:
        # R2D2 pixel plane in HBM: frames stored once (unstacked streams),
        # [B, T+1, H, W, S] windows composed on device — kills the
        # ~36 MB/step host→device sequence-minibatch transfer
        # (replay/device_sequence.py)
        from distributed_deep_q_tpu.replay.device_sequence import (
            DeviceSequenceReplay)
        replay = DeviceSequenceReplay(
            seq_capacity, seq_len, obs_shape, solver.mesh,
            cfg.net.lstm_size, prioritized=cfg.replay.prioritized,
            alpha=cfg.replay.priority_alpha, beta0=cfg.replay.priority_beta0,
            beta_steps=cfg.replay.priority_beta_steps,
            eps=cfg.replay.priority_eps, seed=cfg.train.seed)
    else:
        replay = SequenceReplay(
            seq_capacity, seq_len, obs_shape,
            obs_dtype, cfg.net.lstm_size, prioritized=cfg.replay.prioritized,
            alpha=cfg.replay.priority_alpha, beta0=cfg.replay.priority_beta0,
            beta_steps=cfg.replay.priority_beta_steps,
            eps=cfg.replay.priority_eps, seed=cfg.train.seed)
    builder = SequenceBuilder(seq_len, cfg.replay.burn_in, obs_shape,
                              obs_dtype, cfg.net.lstm_size, cfg.train.gamma)
    learn_start_seqs = max(cfg.replay.learn_start // seq_len, 2)

    # fused chained sequence path: sampling/meta/pixels/priorities all on
    # device, chain grad steps per dispatch (sequence twin of the
    # transition path's FusedStepStream loop). Prioritized-only: the
    # device sampler draws from the priority row, so a uniform config
    # keeps the per-step path here (the transition loops refuse one:
    # replay/device_per.pixel_device_ring).
    fused_seq = (device_seq and cfg.replay.device_per
                 and cfg.replay.prioritized)
    stream = None
    if fused_seq:
        from distributed_deep_q_tpu.solver import FusedStepStream
        stream = FusedStepStream(solver, replay,
                                 max(int(cfg.replay.fused_chain), 1))

    frame = env.reset()
    obs = stacker.reset(frame) if pixel else frame
    carry = solver.initial_state(1)
    ep_ret, ep_returns = 0.0, MovingAverage(100)
    summary: dict = {}
    writeback = None
    if replay.prioritized and not fused_seq:
        from distributed_deep_q_tpu.replay.prioritized import make_writeback
        writeback = make_writeback(replay, cfg.replay)
    gsteps = 0
    ckpt = maybe_checkpointer(cfg.train)
    if ckpt and cfg.train.resume and ckpt.latest_step() is not None:
        solver.state, _ = ckpt.restore(solver.state)
        gsteps = solver.step
    persist = cfg.replay.persist_path
    if persist and jax.process_count() > 1:
        if device_seq:
            # the device sequence ring is a GLOBAL mesh array: each
            # process's shard file would hold only its addressable slice,
            # and resume would reassemble a buffer whose sampling state no
            # longer matches the mesh — silent corruption. Refuse loudly.
            raise ValueError(
                "replay.persist_path is not supported with a device-"
                "resident DeviceSequenceReplay under multi-process "
                f"(process_count={jax.process_count()}); set "
                "replay.device_resident=false or drop persist_path")
        # per-process shard files (same rule as train_single_process): a
        # shared path would race on save and clone one process's state
        # onto every host on resume
        persist = f"{persist}.proc{jax.process_index()}"
    if persist and cfg.train.resume and os.path.exists(persist):
        # opt-in replay persistence (SURVEY §5.4), sequence edition:
        # restore the buffer's exact sampling state (host store or device
        # ring + device meta/priorities) instead of warm-refilling
        from distributed_deep_q_tpu.replay.persistence import load_replay
        load_replay(replay, persist)

    for t in range(1, cfg.train.total_steps + 1):
        eps = epsilon_at(t, cfg.actors)
        carry_before = carry
        a, carry = solver.act(np.asarray(obs), carry, eps, rng)
        next_frame, r, done, over = env.step(a)
        next_obs = stacker.push(next_frame) if pixel else next_frame
        ep_ret += r
        for seq in builder.on_step(obs, a, r, done,
                                   (carry_before[0][0], carry_before[1][0]),
                                   next_obs):
            replay.add_sequence(seq)
        obs = next_obs
        metrics.count("env_steps")

        if over:
            if not done:
                # time-limit truncation: emit the pending window with its
                # bootstrap intact instead of discarding the episode tail
                for seq in builder.flush_truncated(next_obs):
                    replay.add_sequence(seq)
            ep_returns.add(ep_ret)
            ep_ret = 0.0
            builder.reset()
            frame = env.reset()
            obs = stacker.reset(frame) if pixel else frame
            carry = solver.initial_state(1)

        if (replay.ready(learn_start_seqs)
                and t % cfg.train.train_every == 0):
            if fused_seq:
                remaining = ((cfg.train.total_steps - t)
                             // cfg.train.train_every + 1)
                m = stream.next(remaining)
            else:
                batch = replay.sample(cfg.replay.batch_size)
                sampled_at = batch.pop("_sampled_at")
                if device_seq:
                    m = solver.train_step_from_ring(replay, batch)
                else:
                    m = solver.train_step(batch)
            gsteps += 1
            if writeback is not None:
                writeback.push(m["index"], m["td_abs"], sampled_at)
            metrics.count("grad_steps")
            if ckpt and gsteps % cfg.train.checkpoint_every == 0:
                ckpt.save(solver.state, extra={"env_steps": t})
                if persist:
                    from distributed_deep_q_tpu.replay.persistence import (
                        save_replay)
                    save_replay(replay, persist)
            if gsteps % log_every == 0:
                summary = {
                    "loss": float(m["loss"]), "q_mean": float(m["q_mean"]),
                    "return_avg100": ep_returns.value, "epsilon": eps,
                    "grad_steps_per_s": metrics.rate("grad_steps"),
                    "env_steps_per_s": metrics.rate("env_steps"),
                }
                metrics.gauge("queue/replay_size", len(replay))
                pending = getattr(replay, "pending_rows", None)
                if pending is not None:
                    metrics.gauge("queue/staged_rows", pending())
                metrics.log(gsteps, **summary, **metrics.telemetry())

    if writeback:
        writeback.drain()
    if ckpt:
        ckpt.save(solver.state, extra={"env_steps": cfg.train.total_steps},
                  wait=True)
    if persist:
        # unconditional end-of-run save (mirrors train_single_process):
        # without it, persist without checkpointing is silently inert and
        # with checkpointing the buffer goes stale vs the final θ
        from distributed_deep_q_tpu.replay.persistence import save_replay
        save_replay(replay, persist)
    summary["final_return_avg100"] = ep_returns.value
    summary["eval_return"] = evaluate_recurrent(solver, cfg)
    summary["solver"] = solver
    summary["replay"] = replay
    return summary


# -- token-window Q-network (net.kind = "tokenq") ---------------------------


def token_rows(cfg: Config, env) -> int:
    """Rows of the embedding and of the head for this env: its tokens, and
    where the net generates by diffusion over blocks
    (``net.tokenq.block_length`` > 0) one more — the mask token's, the
    LAST row (``models/tokenq.mask_token``): the env never emits it."""
    return env.num_actions + bool(cfg.net.tokenq.block_length)


def make_token_replay(cfg: Config, mesh):
    """The token ring for this Config: ``replay.capacity`` counts steps
    (as for the other sequence rings), a window holds
    ``replay.sequence_length`` of them."""
    from distributed_deep_q_tpu.replay.device_tokens import DeviceTokenReplay

    seq_len = cfg.replay.sequence_length
    return DeviceTokenReplay(
        max(cfg.replay.capacity // seq_len, 2), seq_len, mesh,
        cfg.train.gamma, alpha=cfg.replay.priority_alpha,
        beta0=cfg.replay.priority_beta0,
        beta_steps=cfg.replay.priority_beta_steps,
        eps=cfg.replay.priority_eps, write_chunk=cfg.replay.write_chunk)


class TokenWindowBuilder:
    """Cuts an actor's token stream into the ring's windows: ``seq_len``
    steps (+1 token), back to back inside an episode; an episode's last
    window is padded with invalid steps."""

    def __init__(self, seq_len: int):
        self.seq_len = int(seq_len)
        self.reset(0)

    def reset(self, first_token: int) -> None:
        self._tok = [int(first_token)]
        self._rew: list[float] = []
        self._done: list[bool] = []

    def on_step(self, token: int, reward: float, done: bool, over: bool):
        """One env step: ``token`` was emitted. Returns a finished window
        ``(tokens, reward, done, valid)`` or None."""
        self._tok.append(int(token))
        self._rew.append(float(reward))
        self._done.append(bool(done))
        n, t = len(self._rew), self.seq_len
        if n < t and not over:
            return None
        tokens = np.zeros(t + 1, np.int32)
        tokens[:n + 1] = self._tok
        reward_, done_ = np.zeros(t, np.float32), np.zeros(t, bool)
        reward_[:n], done_[:n] = self._rew, self._done
        self.reset(token)       # the next window starts at this token
        return tokens, reward_, done_, np.arange(t) < n


def evaluate_tokenq(solver, cfg: Config, episodes: int | None = None,
                    seed: int = 10_000) -> float:
    """ε=eval_eps rollouts of the token env, the episode so far as the
    prefix (capped at the training window)."""
    env = make_env(cfg.env, seed=seed)
    rng = np.random.default_rng(seed)
    returns = []
    for _ in range(episodes or cfg.train.eval_episodes):
        prefix, ep_ret, over = [int(env.reset()[0])], 0.0, False
        while not over:
            a = solver.token_act(solver.acting_prefix(prefix),
                                 cfg.actors.eval_eps, rng)
            obs, r, _, over = env.step(a)
            prefix.append(int(obs[0]))
            ep_ret += r
        returns.append(ep_ret)
    return float(np.mean(returns))


def train_tokenq(cfg: Config, metrics: Metrics | None = None,
                 log_every: int = 1_000) -> dict:
    """Token-level Q-learning: ε-greedy token actor → ``TokenWindowBuilder``
    → ``DeviceTokenReplay`` → the fused sequence step
    (``SequenceSolver.train_steps_device_per`` through ``FusedStepStream``:
    sample program + train program, priorities written back on device).
    Every ``train.train_every`` env steps the learner takes
    ``train.grad_steps_per_train`` grad steps. The actor runs the whole
    prefix per token (no cache): the recipe is for high replay ratios."""
    from distributed_deep_q_tpu.parallel.sequence_learner import SequenceSolver
    from distributed_deep_q_tpu.solver import FusedStepStream

    metrics = metrics or Metrics()
    env = make_env(cfg.env, seed=cfg.train.seed)
    cfg.net.num_actions = token_rows(cfg, env)
    solver = SequenceSolver(cfg)
    replay = make_token_replay(cfg, solver.mesh)
    seq_len = cfg.replay.sequence_length
    stream = FusedStepStream(solver, replay,
                             max(int(cfg.replay.fused_chain), 1))
    builder = TokenWindowBuilder(seq_len)
    rng = np.random.default_rng(cfg.train.seed)
    learn_start = max(cfg.replay.learn_start // seq_len,
                      cfg.replay.batch_size)
    trace = TraceWindow(cfg.train.profile_dir, cfg.train.profile_start_step,
                        cfg.train.profile_num_steps)
    ckpt = maybe_checkpointer(cfg.train)
    if ckpt and cfg.train.resume and ckpt.latest_step() is not None:
        solver.state, _ = ckpt.restore(solver.state)

    prefix = [int(env.reset()[0])]
    builder.reset(prefix[0])
    ep_ret, ep_returns = 0.0, MovingAverage(100)
    gsteps, summary = 0, {}
    for t in range(1, cfg.train.total_steps + 1):
        eps = epsilon_at(t, cfg.actors)
        a = solver.token_act(solver.acting_prefix(prefix), eps, rng)
        obs, r, done, over = env.step(a)
        prefix.append(int(obs[0]))
        ep_ret += r
        window = builder.on_step(int(obs[0]), r, done, over)
        if window is not None:
            replay.add_window(*window)
        metrics.count("env_steps")
        if over:
            ep_returns.add(ep_ret)
            ep_ret = 0.0
            prefix = [int(env.reset()[0])]
            builder.reset(prefix[0])
        if not (replay.ready(learn_start)
                and t % cfg.train.train_every == 0):
            continue
        for _ in range(max(cfg.train.grad_steps_per_train, 1)):
            trace.on_step(gsteps)
            remaining = ((cfg.train.total_steps - t)
                         // cfg.train.train_every + 1) * max(
                cfg.train.grad_steps_per_train, 1)
            m = stream.next(remaining)
            gsteps += 1
            metrics.count("grad_steps")
            if ckpt and gsteps % cfg.train.checkpoint_every == 0:
                with tracing.span("learner_checkpoint"):
                    ckpt.save(solver.state, extra={"env_steps": t})
            if gsteps % log_every == 0:
                with tracing.span("learner_log"):
                    summary = {
                        "loss": float(m["loss"]),
                        "q_mean": float(m["q_mean"]),
                        "return_avg100": ep_returns.value, "epsilon": eps,
                        "grad_steps_per_s": metrics.rate("grad_steps"),
                        "env_steps_per_s": metrics.rate("env_steps"),
                        # the expert layer's counters (ops/moe.py)
                        "moe_slots_held_share": float(m["moe_slots_held"])
                        / max(float(m["moe_slots"]), 1.0),
                        "moe_rows_run_over_held": float(m["moe_rows_run"])
                        / max(float(m["moe_slots_held"]), 1.0),
                        "moe_load_max_over_mean": float(
                            m["moe_load_max_over_mean"]),
                        "moe_overflow": float(m["moe_overflow"]),
                    }
                    if "dsa_pairs_selected" in m:   # sparse layers only
                        summary.update({
                            "dsa_pairs_selected_share": float(
                                m["dsa_pairs_selected"])
                            / max(float(m["dsa_pairs_causal"]), 1.0),
                            "dsa_index_loss": float(m["dsa_index_loss"])})
                    if "attn_gate_mean" in m:       # gated attention only
                        summary["attn_gate_mean"] = float(
                            m["attn_gate_mean"])
                    if "ssm_dt_mean" in m:          # state-space layers only
                        summary["ssm_dt_mean"] = float(m["ssm_dt_mean"])
                    if "bd_decisions_valid" in m:   # block diffusion only
                        summary.update({k: float(m[k]) for k in (
                            "bd_decisions_valid", "bd_reveal_mean",
                            "bd_span_mean")})
                    metrics.gauge("queue/replay_size", len(replay))
                    metrics.log(gsteps, **summary, **solver.fused_gauges(),
                                **metrics.telemetry())
    trace.close()
    if ckpt:
        ckpt.save(solver.state, extra={"env_steps": cfg.train.total_steps},
                  wait=True)
    summary["final_return_avg100"] = ep_returns.value
    summary["grad_steps"] = gsteps
    summary["train_rotary_fused"] = solver.learner.rotary_fused
    summary["eval_return"] = evaluate_tokenq(solver, cfg)
    summary["solver"] = solver
    summary["replay"] = replay
    return summary
