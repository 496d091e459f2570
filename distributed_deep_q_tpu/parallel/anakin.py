"""Anakin mode — acting, replay insert, and training in ONE jitted program.

The Podracer paper's Anakin endpoint (PAPERS.md arXiv:2104.06272) puts the
environment ON the accelerator: when the env's step function is expressible
in ``jax.numpy`` (the ``signal_atari`` family — ``ops/jax_envs.py``), the
whole act→insert→learn loop compiles into a single ``shard_map``ped XLA
program and the host's only steady-state job is re-dispatching it. This is
a MODE of the existing system, not a fork:

- the replay ring is the SAME ``DevicePERFrameReplay`` allocation the
  distributed path trains from (padded frame plane, ghost rows, metadata/
  priority rows, Pallas row-DMA insert via ``insert_meta_pack`` +
  ``scatter_rows``) — only the cursor/size bookkeeping moves from host
  slot objects into the device carry;
- the train phase is the learner's plane-carry body (``plane_train_fn``,
  PERF.md §3) recomposed from the same primitives — ``fused_sample_prep``
  → ``build_meta_pack`` → ``fused_sample_draw_packed`` →
  ``gather_windows`` → ``stacked_q_apply`` → ``q_step_loss`` →
  ``fused_plane_adam_target_step`` → ``scatter_priorities`` — with θ/θ⁻
  and the Adam moments living PERMANENTLY as flat planes in the donated
  carry (the distributed path converts tree↔plane at every chunk
  boundary; here the conversion happens once at construction and once at
  ``sync_solver``);
- sampling keys and β stay host-generated per dispatch
  (``sample_key_schedule`` — same schedule, same anchoring as the
  distributed fused path), so a fold_in-keyed program never touches the
  ring gather (measured ~200× slower, learner.py r3 note). They ride in
  as tiny arguments; nothing is read back.

Superstep layout (one dispatch, donated carry)::

    act scan (T ticks):   vmapped jax env step + batched ε-greedy forward
                          through the online half of the parameter plane
    ring insert:          T·E staged rows per shard → one meta-pack +
                          row-DMA scatter (ghost mirroring, device cursors)
    sample (hoisted):     chunk CDF + pack + all-chain draws + window DMA
    train scan (chain):   the plane-carry grad step + priority scatter

Env↔slot identity: with ``num_envs == num_slots`` every env owns exactly
ONE sub-ring, so the stream→slot advance of the host path degenerates to
the identity and the device cursor math is ``cursor = (cursor + T) %
slot_cap``. Env at plane position ``p`` of shard ``d`` is global stream
``gid = sub·D + d`` — the SAME routing ``DeviceFrameReplay._slot_base``
gives ``add_batch(stream=gid)``, which is what makes the Anakin ring
bitwise-comparable to a host loop feeding the same transitions
(tests/test_anakin.py).

Zero steady-state host transfers: the compiled superstep contains no
infeed/outfeed/send/recv/host-copy ops (pinned via ``profiling.py``'s HLO
census in tests/test_op_count.py, alongside the scheduled-op ratchet).
Episode returns and train metrics come back as replicated device scalars
the caller may read at its OWN cadence — reading is the only D2H, and it
is optional.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_deep_q_tpu import learning, tracing
from distributed_deep_q_tpu.config import Config
from distributed_deep_q_tpu.models.qnet import stacked_q_apply
from distributed_deep_q_tpu.ops.jax_envs import make_jax_env
from distributed_deep_q_tpu.ops.ring_gather import (
    gather_windows, scatter_rows)
from distributed_deep_q_tpu.parallel.learner import (
    TrainState, _locate_adam_state, fused_plane_adam_target_step,
    params_to_plane, plane_meta, plane_stacked_views, plane_to_param_trees,
    plane_to_tree, q_step_loss, tree_to_plane)
from distributed_deep_q_tpu.parallel.mesh import AXIS_DP, AXIS_MODEL
from distributed_deep_q_tpu.replay.device_per import (
    DeviceReplayState, build_meta_pack, fused_sample_draw_packed,
    fused_sample_prep, insert_meta_pack, scatter_priorities,
    stack_rows_to_obs)


def act_tick(apply_fn, step_fn, frame_shape, params, eps, env_state, buf,
             akeys):
    """One vectorized ε-greedy acting tick over ``n`` co-resident envs.

    THE single copy of the per-tick acting math, shared verbatim by the
    Anakin superstep's act scan and the host reference driver in
    tests/test_anakin.py — the bitwise ring pin compares two drivers of
    this exact function, so acting semantics can never fork between them.

    ``buf`` is the batched frame stacker ``[n, stack, H·W]`` u8 (newest
    frame last — the device twin of ``FrameStacker``/
    ``VectorFrameStacker``); ``akeys`` per-env action keys ``[n, 2]``;
    ``eps`` the per-env ε ladder ``[n]``. Episode boundaries fold into the
    tick exactly like the host loops: the env auto-resets inside ``step``
    (``ops/jax_envs.py``) and the stacker row restarts from the new
    episode's first frame (zeros + that frame — ``FrameStacker.reset``).

    Returns ``(env_state, buf, akeys, record)`` where ``record`` holds the
    transition row the host actor would flush: the PRE-step frame, the
    action, reward, and the done flag (the signal envs terminate on their
    step cap, so done doubles as the episode boundary — the same value the
    numpy envs return for both).
    """
    n, stack = buf.shape[0], buf.shape[1]
    h, w = frame_shape
    obs = jnp.moveaxis(buf.reshape(n, stack, h, w), 1, -1)
    q = apply_fn(params, obs)
    num_actions = q.shape[-1]
    greedy = jnp.argmax(q, axis=-1).astype(jnp.int32)
    k3 = jax.vmap(lambda k: jax.random.split(k, 3))(akeys)     # [n, 3, 2]
    akeys, ku, kr = k3[:, 0], k3[:, 1], k3[:, 2]
    u = jax.vmap(jax.random.uniform)(ku)
    ra = jax.vmap(
        lambda k: jax.random.randint(k, (), 0, num_actions, jnp.int32))(kr)
    action = jnp.where(u < eps, ra, greedy)
    env_state, frame, reward, done = jax.vmap(step_fn)(env_state, action)
    frow = frame.reshape(n, -1)
    pushed = jnp.concatenate([buf[:, 1:], frow[:, None]], axis=1)
    fresh = jnp.concatenate(
        [jnp.zeros_like(buf[:, 1:]), frow[:, None]], axis=1)
    record = {"frame": buf[:, -1], "action": action,
              "reward": reward.astype(jnp.float32), "done": done}
    buf = jnp.where(done[:, None, None], fresh, pushed)
    return env_state, buf, akeys, record


class AnakinRunner:
    """Owner of the Anakin superstep: carry allocation, dispatch, and the
    tree↔plane seams back into the ``Solver``.

    Construction derives everything from the SAME config the distributed
    path reads: ``cfg.actors.anakin_envs`` co-resident envs (must divide
    over the dp mesh; 0 = one per shard), ``cfg.actors.anakin_ticks`` env
    ticks per superstep, ``cfg.replay.fused_chain`` grad steps per
    superstep, the Ape-X ε ladder from ``eps_base``/``eps_alpha`` keyed by
    global stream id. All envs run ``cfg.env`` (one jax step function is
    vmapped — multi-game fleets stay on the host acting planes).

    The donated device carry holds: the ``DeviceReplayState`` ring twin,
    vmapped env states, the batched stacker buffer, per-env action keys,
    per-sub cursors/sizes, and the θ/θ⁻ + Adam planes. ``superstep()``
    dispatches one act+insert+train program; ``sync_solver()`` folds the
    planes back into ``solver.state`` so checkpoints, ``q_values``, and
    weight publishing keep working unchanged — the mode seam.
    """

    def __init__(self, cfg: Config, solver=None, replay=None):
        from distributed_deep_q_tpu.actors.supervisor import actor_epsilon
        from distributed_deep_q_tpu.replay.device_per import (
            DevicePERFrameReplay)
        from distributed_deep_q_tpu.solver import Solver

        self.cfg = cfg
        h, w = cfg.env.frame_shape
        stack = int(cfg.env.stack)
        self.frame_shape = (h, w)
        self.solver = solver or Solver(cfg, obs_dim=h * w * stack)
        mesh = self.solver.mesh
        assert cfg.train.optimizer == "adam" and \
            mesh.shape[AXIS_MODEL] <= 1, (
                "Anakin reuses the plane-carry train body, which requires "
                "adam and no model-parallel axis (learner.py use_plane)")
        d = mesh.shape[AXIS_DP]
        n = int(cfg.actors.anakin_envs) or d
        assert n % d == 0, f"anakin_envs={n} must divide over {d} dp shards"
        self.num_envs, self.num_shards = n, d
        self.envs_per_shard = n // d
        self.replay = replay or DevicePERFrameReplay(
            cfg.replay, mesh, self.frame_shape, stack, cfg.train.gamma,
            seed=cfg.train.seed, write_chunk=cfg.replay.write_chunk,
            num_streams=n)
        rp = self.replay
        assert rp.num_slots == n and rp.subs_per_shard == n // d, (
            "env↔slot identity needs one slot per env: raise anakin_envs "
            "to a multiple of the dp shard count")
        self.ticks = int(cfg.actors.anakin_ticks)
        assert 0 < self.ticks <= rp.slot_cap, (
            f"anakin_ticks={self.ticks} must stay within one sub-ring "
            f"(slot_cap={rp.slot_cap}) so a superstep's row targets are "
            "distinct")
        self.chain = max(int(cfg.replay.fused_chain), 1)
        assert cfg.replay.batch_size % d == 0

        # env at plane position p = shard·E + e is global stream e·D + d —
        # DeviceFrameReplay's slot s ↔ (shard s % D, sub s // D) routing,
        # which add_batch(stream=gid) follows when num_streams == num_slots
        e_per = self.envs_per_shard
        self.stream_ids = np.array(
            [(p % e_per) * d + (p // e_per) for p in range(n)], np.int64)
        eps = np.array(
            [actor_epsilon(int(g), n, cfg.actors.eps_base,
                           cfg.actors.eps_alpha) for g in self.stream_ids],
            np.float32)

        sharded = NamedSharding(mesh, P(AXIS_DP))
        self._eps = jax.device_put(eps, sharded)
        self._reset_fn, self._step_fn = make_jax_env(cfg.env)

        # per-env key streams echo the numpy fleet's seed-offset discipline
        # (env 1000·(gid+1), ε 7777·(gid+1)) in the jax.random family —
        # deterministic and collision-free, but deliberately NOT numpy-rng
        # parity (ops/jax_envs.py docstring)
        base = jax.random.PRNGKey(cfg.train.seed)
        env_keys = jax.vmap(
            lambda g: jax.random.fold_in(base, 1000 * (g + 1)))(
                jnp.asarray(self.stream_ids, jnp.int32))
        self.act_keys0 = jax.vmap(
            lambda g: jax.random.fold_in(base, 7777 * (g + 1)))(
                jnp.asarray(self.stream_ids, jnp.int32))

        row_len = rp._row_len
        reset_fn = self._reset_fn

        def _init(ekeys, akeys):
            st, frame = jax.vmap(reset_fn)(ekeys)
            buf = jnp.zeros((n, stack, row_len), jnp.uint8)
            buf = buf.at[:, -1].set(frame.reshape(n, -1))
            return st, buf, akeys

        shapes = jax.eval_shape(_init, env_keys, self.act_keys0)
        env_state, buf, akeys = jax.jit(
            _init, out_shardings=jax.tree.map(lambda _: sharded, shapes))(
                env_keys, self.act_keys0)
        self._env_spec = jax.tree.map(lambda _: P(AXIS_DP), shapes[0])

        # θ/θ⁻ + Adam moments as persistent planes (the distributed path
        # pays this conversion per chunk; Anakin pays it here and at sync)
        state = self.solver.state
        self._meta = plane_meta(state.params)
        adam_state, _ = _locate_adam_state(state.opt_state)
        repl = NamedSharding(mesh, P())
        pt, m, v = jax.jit(
            lambda s, a: (params_to_plane(self._meta, s.params,
                                          s.target_params),
                          tree_to_plane(a.mu), tree_to_plane(a.nu)),
            out_shardings=(repl, repl, repl))(state, adam_state)
        cursors = jax.device_put(np.zeros(n, np.int32), sharded)
        sizes = jax.device_put(np.zeros(n, np.int32), sharded)
        self._carry = (rp.dstate, env_state, buf, akeys, cursors, sizes,
                       pt, m, v, adam_state.count, state.step)
        rp.dstate = None  # single owner: the ring lives in the carry now
        self._fn = self._build_superstep(mesh)
        self.last_metrics: dict[str, Any] | None = None
        self.last_act_reward: Any = None
        self.supersteps_run = 0

    # -- the program ---------------------------------------------------------

    def _build_superstep(self, mesh):
        cfg_t = self.cfg.train
        rp = self.replay
        slot_cap, slot_pad = rp.slot_cap, rp.slot_pad
        rowb, row_len, rowp = rp.rowb, rp._row_len, rp.rowb // 4
        stack, n_step, gamma = rp.stack, rp.n_step, rp.gamma
        window = stack + n_step
        scratch = rp.cap_local_pad
        interpret = rp._interpret
        d, e_per, t_len = self.num_shards, self.envs_per_shard, self.ticks
        k = t_len * e_per
        chain = self.chain
        per_b = self.cfg.replay.batch_size // d
        alpha = float(self.cfg.replay.priority_alpha)
        p_eps = float(self.cfg.replay.priority_eps)
        n_win = chain * per_b
        apply_fn = self.solver.apply_fn
        meta = self._meta
        step_fn = self._step_fn
        frame_shape = self.frame_shape
        double = cfg_t.double_dqn

        def superstep_body(carry, eps, keys, betas):
            (ds, env_st, buf, akeys, cursors, sizes,
             pt, m, v, cnt, gstep) = carry

            # -- act scan: T ticks against this superstep's frozen θ ------
            params = jax.tree_util.tree_unflatten(
                meta.treedef, [x[0] for x in plane_stacked_views(meta, pt)])

            def act_body(c, _):
                env_st, buf, akeys = c
                env_st, buf, akeys, rec = act_tick(
                    apply_fn, step_fn, frame_shape, params, eps, env_st,
                    buf, akeys)
                return (env_st, buf, akeys), rec

            (env_st, buf, akeys), recs = lax.scan(
                act_body, (env_st, buf, akeys), None, length=t_len)

            # -- ring insert: one meta pack + row-DMA scatter per shard ---
            # (the device twin of _apply_write's main/ghost/scratch didx)
            t_i = jnp.arange(t_len, dtype=jnp.int32)[:, None]
            e_i = jnp.arange(e_per, dtype=jnp.int32)[None, :]
            local = (cursors[None, :] + t_i) % slot_cap          # [T, E]
            midx = (e_i * slot_cap + local).reshape(-1)
            main = e_i * slot_pad + local
            ghost = jnp.where(local < window - 1,
                              e_i * slot_pad + slot_cap + local, scratch)
            sidx = jnp.concatenate(
                [jnp.arange(k, dtype=jnp.int32)] * 2)
            didx = jnp.concatenate([main.reshape(-1), ghost.reshape(-1)])
            packed, new_p = insert_meta_pack(
                recs["frame"].reshape(-1), ds.maxp, k=k, row_len=row_len,
                rowb=rowb, alpha=alpha)
            frames = scatter_rows(sidx, didx, packed, ds.frames, n=2 * k,
                                  rowb=rowb, interpret=interpret)
            dn = recs["done"].reshape(-1).astype(jnp.uint8)
            action = ds.action.at[midx].set(
                recs["action"].reshape(-1).astype(jnp.int32))
            reward = ds.reward.at[midx].set(recs["reward"].reshape(-1))
            done = ds.done.at[midx].set(dn)
            boundary = ds.boundary.at[midx].set(dn)
            prio = ds.prio.at[midx].set(new_p)
            cursors = (cursors + t_len) % slot_cap
            sizes = jnp.minimum(sizes + t_len, slot_cap)

            # -- sample, hoisted per chunk (learner.py sample_fn twin) ----
            shard_rows = {"action": action, "reward": reward, "done": done,
                          "boundary": boundary, "prio": prio}
            pm, cdf, mass, n_glob = fused_sample_prep(
                shard_rows, cursors, sizes, slot_cap, stack, n_step)
            pack = build_meta_pack(action, reward, done, boundary,
                                   slot_cap, stack, n_step, gamma)
            metas, ws, idxs = fused_sample_draw_packed(
                keys[0], pack, pm, cdf, mass, n_glob, per_b, slot_cap,
                slot_pad, stack, n_step, betas, d)
            win = gather_windows(ws.reshape(-1), frames, n=n_win, w=window,
                                 rowb=rowb, interpret=interpret)
            win = win.reshape(chain, per_b, window, rowp)

            # -- train scan: the plane-carry body (plane_train_fn twin) ---
            def train_body(c, xs):
                if cfg_t.learn_metrics:
                    pt, m, v, cnt, gstep, prio, maxp, lmp = c
                else:
                    pt, m, v, cnt, gstep, prio, maxp = c
                batch, w_, idx = xs
                batch = dict(batch)
                ovalid = batch.pop("ovalid")
                nvalid = batch.pop("nvalid")
                pix = lax.bitcast_convert_type(w_, jnp.uint8)
                pix = pix.reshape(w_.shape[:2] + (rowp * 4,))[:, :, :row_len]
                obs = pix[:, :stack] * ovalid[..., None]
                nobs = pix[:, n_step:n_step + stack] * nvalid[..., None]
                batch["obs"] = stack_rows_to_obs(obs, frame_shape)
                batch["next_obs"] = stack_rows_to_obs(nobs, frame_shape)
                step2 = gstep + 1

                def loss_fn(views):
                    stacked = jax.tree_util.tree_unflatten(
                        meta.treedef, list(views))
                    q, q_next_o, q_next_t = stacked_q_apply(
                        apply_fn, stacked, batch["obs"], batch["next_obs"],
                        double)
                    loss, td_abs = q_step_loss(cfg_t, q, q_next_o,
                                               q_next_t, batch, interpret)
                    return loss, (td_abs, q)

                (loss, (td_abs, q)), gv = jax.value_and_grad(
                    loss_fn, has_aux=True)(plane_stacked_views(meta, pt))
                with jax.named_scope("ddq.grad_plane"):
                    g = jnp.concatenate([x[0].reshape(-1) for x in gv])
                g = lax.pmean(g, AXIS_DP)
                loss = lax.pmean(loss, AXIS_DP)
                q_mean = lax.pmean(jnp.mean(q), AXIS_DP)
                gnorm = jnp.sqrt(jnp.sum(jnp.square(g)))
                m, v, pt, cnt = fused_plane_adam_target_step(
                    cfg_t, meta, g, m, v, cnt, pt, step2, gnorm)
                prio, maxp = scatter_priorities(prio, maxp, idx, td_abs,
                                                alpha, p_eps)
                metrics = {"loss": loss, "q_mean": q_mean,
                           "grad_norm": gnorm}
                if cfg_t.learn_metrics:
                    # learning-dynamics plane (learning.py): jnp-only
                    # accumulation, so the zero-host-comm census pin
                    # holds with the gate on (test_op_count)
                    lmp = learning.lm_update(
                        lmp, cfg=cfg_t, td_abs=td_abs,
                        weight=batch["weight"], loss=loss, q=q,
                        q_mean=q_mean, gnorm=gnorm, step=step2,
                        alpha=alpha, eps=p_eps)
                    return (pt, m, v, cnt, step2, prio, maxp, lmp), \
                        metrics
                return (pt, m, v, cnt, step2, prio, maxp), metrics

            carry0 = (pt, m, v, cnt, gstep, prio, ds.maxp)
            if cfg_t.learn_metrics:
                carry0 = carry0 + (learning.lm_init(),)
                (pt, m, v, cnt, gstep, prio, maxp, lmp), metrics = \
                    lax.scan(train_body, carry0, (metas, win, idxs))
                metrics = dict(metrics)
                metrics["learn_plane"] = learning.lm_finalize(
                    lmp, AXIS_DP)
            else:
                (pt, m, v, cnt, gstep, prio, maxp), metrics = lax.scan(
                    train_body, carry0, (metas, win, idxs))

            ds = DeviceReplayState(
                frames=frames, action=action, reward=reward, done=done,
                boundary=boundary, prio=prio, maxp=maxp)
            act_reward = lax.pmean(jnp.mean(recs["reward"]), AXIS_DP)
            return ((ds, env_st, buf, akeys, cursors, sizes,
                     pt, m, v, cnt, gstep), metrics, act_reward)

        S = P(AXIS_DP)
        state_spec = DeviceReplayState(
            frames=S, action=S, reward=S, done=S, boundary=S, prio=S,
            maxp=P())
        carry_spec = (state_spec, self._env_spec, S, S, S, S,
                      P(), P(), P(), P(), P())
        metric_spec = {"loss": P(), "q_mean": P(), "grad_norm": P()}
        if cfg_t.learn_metrics:
            # the finalized plane is replicated (lm_finalize's psums)
            metric_spec["learn_plane"] = P()
        return jax.jit(
            shard_map(superstep_body, mesh=mesh,
                      in_specs=(carry_spec, S, S, P()),
                      out_specs=(carry_spec, metric_spec, P()),
                      check_vma=False),
            donate_argnums=(0,))

    # -- dispatch ------------------------------------------------------------

    def superstep(self) -> dict[str, Any]:
        """One act+insert+train dispatch. Keys/β are the distributed fused
        path's exact schedules (``next_fused_keys`` anchoring via the
        solver, ``next_betas`` via the replay), so an Anakin run and a
        host-driven run of the same config draw identical samples. The
        span times host DISPATCH only — nothing blocks, nothing reads
        back; returned metrics are ``[chain]`` device arrays."""
        keys = self.solver._next_sample_keys(self.num_shards, self.chain)
        betas = np.asarray(self.replay.next_betas(self.chain), np.float32)
        with tracing.span("anakin_superstep"):
            self._carry, metrics, act_r = self._fn(
                self._carry, self._eps, keys, betas)
        self.last_metrics, self.last_act_reward = metrics, act_r
        self.supersteps_run += 1
        return metrics

    def run(self, supersteps: int) -> dict[str, Any]:
        """Drive ``supersteps`` dispatches back-to-back, then sync the
        trained state into the solver. Returns the final chunk's metrics
        (host numpy — the ONE deliberate readback, at the very end)."""
        for _ in range(int(supersteps)):
            self.superstep()
        self.sync_solver()
        return {kk: np.asarray(vv) for kk, vv in
                (self.last_metrics or {}).items()}

    @property
    def dstate(self) -> DeviceReplayState:
        """The live ring twin (it rides the donated carry)."""
        return self._carry[0]

    @property
    def env_steps(self) -> int:
        return self.supersteps_run * self.ticks * self.num_envs

    @property
    def grad_steps(self) -> int:
        return self.supersteps_run * self.chain

    def sync_solver(self) -> TrainState:
        """Fold the planes back into ``solver.state`` (and the ring twin
        back into the replay object) — the seam that keeps Anakin a mode:
        checkpoints, ``q_values``, ``get_weights`` all read the solver."""
        (ds, _env, _buf, _ak, _cur, _siz, pt, m, v, cnt, gstep) = \
            self._carry
        state = self.solver.state
        adam_state, rebuild = _locate_adam_state(state.opt_state)
        params, target = plane_to_param_trees(
            self._meta, pt, state.params, state.target_params)
        new_opt = rebuild(adam_state._replace(
            count=cnt, mu=plane_to_tree(self._meta, m, adam_state.mu),
            nu=plane_to_tree(self._meta, v, adam_state.nu)))
        self.solver.state = TrainState(params, target, new_opt, gstep)
        self.replay.dstate = ds
        return self.solver.state


def run_anakin(cfg: Config, supersteps: int) -> dict[str, Any]:
    """Entry point: build a runner, train, return final metrics (with the
    episode-reward scalar folded in). The distributed path's
    ``train_distributed`` stays untouched — Anakin is selected explicitly
    (``cfg.actors.anakin_envs > 0``), not inferred."""
    runner = AnakinRunner(cfg)
    out = runner.run(supersteps)
    out["act_reward"] = float(np.asarray(runner.last_act_reward))
    return out
