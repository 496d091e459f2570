"""CLI entry point (SURVEY.md §1 L6, §2 "Entry/CLI" [M]).

Reference surface kept: a ``main.py`` with ``--backend`` and train / eval /
play modes plus hyperparameter flags. Presets mirror the BASELINE.json
config matrix; any field is overridable with ``--set path=value``.

Examples:
    python -m distributed_deep_q_tpu.main train --preset cartpole --backend cpu
    python -m distributed_deep_q_tpu.main train --preset pong --backend tpu
    python -m distributed_deep_q_tpu.main eval --preset cartpole --backend cpu
"""

from __future__ import annotations

import argparse
import json
import sys

from distributed_deep_q_tpu.config import add_config_flags, config_from_args


def _maybe_restore(solver, cfg) -> int | None:
    """Load the newest Orbax snapshot into ``solver`` when a checkpoint dir
    is configured; returns the restored step (None if nothing to restore)."""
    if not cfg.train.checkpoint_dir:
        return None
    from distributed_deep_q_tpu.utils.checkpoint import Checkpointer
    ckpt = Checkpointer(cfg.train.checkpoint_dir)
    if ckpt.latest_step() is None:
        return None
    solver.state, _ = ckpt.restore(solver.state)
    return solver.step


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="distributed_deep_q_tpu")
    parser.add_argument("mode", choices=["train", "eval", "play"],
                        help="train: run the training loop; eval: greedy "
                             "rollouts; play: single greedy episode with "
                             "per-step printout")
    add_config_flags(parser)
    parser.add_argument("--metrics-jsonl", default="",
                        help="write structured metrics to this JSONL file")
    parser.add_argument("--distributed", action="store_true",
                        help="run the actor/learner RPC topology instead of "
                             "the single-process loop")
    args = parser.parse_args(argv)
    cfg = config_from_args(args)

    # Multi-host bring-up (config 5): when --set mesh.num_processes=N (+
    # mesh.coordinator, mesh.process_id) is given, every process runs this
    # same CLI command and connects here, before any backend init. No-op in
    # the default single-process case.
    from distributed_deep_q_tpu.parallel.multihost import initialize_multihost
    initialize_multihost(cfg.mesh)

    # persistent compile cache, before first backend use: without it every
    # cold run recompiles each fused program pair
    from distributed_deep_q_tpu.utils.compile_cache import (
        place_compile_cache)
    place_compile_cache()

    # Import past flag parsing so --help never initializes JAX backends.
    from distributed_deep_q_tpu.metrics import Metrics
    from distributed_deep_q_tpu.train import evaluate, train_single_process

    if args.mode == "train":
        if args.distributed:
            try:
                from distributed_deep_q_tpu.actors.supervisor import (
                    train_distributed)
            except ImportError as e:
                print(f"error: distributed topology unavailable: {e}",
                      file=sys.stderr)
                return 2
            summary = train_distributed(cfg, metrics=Metrics(
                args.metrics_jsonl or None))
        else:
            summary = train_single_process(cfg, metrics=Metrics(
                args.metrics_jsonl or None))
        summary.pop("solver", None)
        print(json.dumps({"mode": "train", **{
            k: v for k, v in summary.items()
            if isinstance(v, (int, float, str))}}))
        return 0

    if args.mode == "eval":
        import numpy as np
        from distributed_deep_q_tpu.actors.game import make_env
        env = make_env(cfg.env, seed=cfg.train.seed)
        cfg.net.num_actions = _num_actions(cfg, env)
        solver = _build_solver(cfg, env)
        restored = _maybe_restore(solver, cfg)
        if cfg.net.kind == "r2d2":
            from distributed_deep_q_tpu.train import evaluate_recurrent
            ret = evaluate_recurrent(solver, cfg)
        elif cfg.net.kind == "tokenq":
            from distributed_deep_q_tpu.train import evaluate_tokenq
            ret = evaluate_tokenq(solver, cfg)
        else:
            ret = evaluate(solver, cfg)
        print(json.dumps({"mode": "eval", "eval_return": ret,
                          "episodes": cfg.train.eval_episodes,
                          "restored_step": restored}))
        return 0

    if args.mode == "play":
        import numpy as np
        from distributed_deep_q_tpu.actors.game import FrameStacker, make_env
        env = make_env(cfg.env, seed=cfg.train.seed)
        cfg.net.num_actions = _num_actions(cfg, env)
        solver = _build_solver(cfg, env)
        _maybe_restore(solver, cfg)
        rng = np.random.default_rng(cfg.train.seed)
        recurrent = cfg.net.kind == "r2d2"
        tokens = cfg.net.kind == "tokenq"   # the state is the token prefix
        carry = solver.initial_state(1) if recurrent else None
        stacker = (FrameStacker(env.obs_shape, cfg.env.stack)
                   if env.obs_dtype == np.uint8 else None)
        obs, over, t, ep_ret = env.reset(), False, 0, 0.0
        if stacker:
            obs = stacker.reset(obs)
        prefix = [int(obs[0])] if tokens else []
        while not over:
            if tokens:
                a = solver.token_act(solver.acting_prefix(prefix),
                                     cfg.actors.eval_eps, rng)
            elif recurrent:
                a, carry = solver.act(np.asarray(obs), carry,
                                      cfg.actors.eval_eps, rng)
            else:
                a = solver.act(obs, cfg.actors.eval_eps, rng)
            frame, r, _, over = env.step(a)
            obs = stacker.push(frame) if stacker else frame
            if tokens:
                prefix.append(int(frame[0]))
            ep_ret += r
            t += 1
            print(f"t={t} a={a} r={r:+.1f} R={ep_ret:.1f}")
        print(json.dumps({"mode": "play", "steps": t, "return": ep_ret}))
        return 0

    return 2


def _num_actions(cfg, env) -> int:
    """The env's actions — for a token-window net its rows, the mask
    token's among them (``train.token_rows``)."""
    if cfg.net.kind != "tokenq":
        return env.num_actions
    from distributed_deep_q_tpu.train import token_rows
    return token_rows(cfg, env)


def _build_solver(cfg, env):
    """Solver for eval/play: SequenceSolver for recurrent (r2d2) and
    token-window (tokenq) nets, the feed-forward Solver otherwise — a
    train-mode checkpoint of either must be evaluable/playable from the
    CLI."""
    import numpy as np
    obs_dim = int(np.prod(env.obs_shape))
    if cfg.net.kind in ("r2d2", "tokenq"):
        from distributed_deep_q_tpu.parallel.sequence_learner import (
            SequenceSolver)
        return SequenceSolver(cfg, obs_dim=obs_dim)
    from distributed_deep_q_tpu.solver import Solver
    return Solver(cfg, obs_dim=obs_dim)


if __name__ == "__main__":
    sys.exit(main())
