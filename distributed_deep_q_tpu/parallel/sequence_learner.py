"""Recurrent (R2D2) sequence learner — config 5 [M].

Same synchronous-DP shape as ``parallel/learner.py`` (shard_map over the
``dp`` mesh axis, ``lax.pmean`` gradient allreduce over ICI, replicated
on-device target refresh), with the R2D2 sequence step inside one XLA
program:

1. **Burn-in**: the LSTM runs over the first ``burn_in`` steps from the
   *stored* carry to refresh recurrent state; ``stop_gradient`` on the
   resulting carry keeps burn-in out of the backward pass (SURVEY §7.3
   item 3). The unroll is a flax ``nn.RNN`` = lifted ``lax.scan`` — one
   fused scan body, compiler-friendly, no Python unrolling.
2. **Train window**: online and target nets unroll over the remaining
   ``T+1`` observations; per-step Double-DQN targets with R2D2 invertible
   value rescaling (``ops/losses.sequence_bellman_targets``).
3. **Masked loss + priority**: ``sequence_dqn_loss`` masks padding and
   burn-in, and returns the mixed max/mean |TD| per-sequence priority for
   PER write-back.

Batch sequences are sharded over ``dp`` on the batch axis — the scope
decision recorded in SURVEY §5.7: the RECURRENT path's sequence length
stays ≤ O(100) steps, so sequence-axis parallelism (ring attention /
Ulysses-style CP) is not applied; scale comes from sharding the batch of
sequences.

The TOKEN path (``net.kind = "tokenq"``, ``models/tokenq.py``) shares the
learner, the loss and the optimizer step but has no carry and no burn-in:
θ and θ⁻ each run ONE causal forward over a window of T+1 tokens — T in
the thousands — and the Q head and the TD loss run blockwise over tokens
(``_token_step_core``), because ``[tokens, vocabulary]`` Q-values cannot
be materialised at that length. Its ring is ``replay/device_tokens.py``.
Where the backbone generates by diffusion over blocks
(``TokenQConfig.block_length`` > 0) a forward pass gives ONE decision a
block, not one a position: the sample program also draws how much of each
block is revealed, the SAME step body packs the window twice, gathers the
decision rows before the head and takes its targets from one block's
decision to the next's (``_block_decisions_loss``,
``ops/losses.span_returns``); optimizer, counters and priorities are the
one path's.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_deep_q_tpu import learning, tracing
from distributed_deep_q_tpu.config import ReplayConfig, TrainConfig
from distributed_deep_q_tpu.models.qnet import (
    r2d2_burn_carry, r2d2_param_split, r2d2_recur, stacked_r2d2_features)
from distributed_deep_q_tpu.ops.losses import (
    sequence_bellman_targets, sequence_dqn_loss, span_returns)
from distributed_deep_q_tpu.parallel.learner import (
    TrainState, clip_grads, fused_adam_target_step, make_optimizer,
    refresh_target)
from distributed_deep_q_tpu.parallel.mesh import AXIS_DP
from distributed_deep_q_tpu.profiling import (
    outputs_as_avals, ran_executable)
from distributed_deep_q_tpu.parallel.multihost import (
    global_batch, put_replicated)


def token_q_select(hid_on: jax.Array, hid_tg: jax.Array, head_on: jax.Array,
                   head_tg: jax.Array, actions: jax.Array, *, block: int,
                   dtype, double: bool, skip: int = -1):
    """The Q head over token positions, BLOCKWISE: ``[positions,
    vocabulary]`` Q-values exist only one block of ``block`` positions at
    a time (at 32 768 positions x 18 992 rows they are 2.5 GB, three times
    over for θ, θ⁻ and the cotangent), and each block is rematerialised in
    the backward pass.

    ``hid_*`` [P, h] final-normed hidden states of θ and θ⁻, ``head_*``
    [h, V], ``actions`` [P] the token taken at each position. Per position
    p: ``q_sa`` = Q_θ(p, actions[p]), ``q_boot`` = Q_θ⁻(p, a*) with a* the
    argmax of Q_θ(p, ·) (Double-DQN) or of Q_θ⁻(p, ·), and ``q_row`` =
    Σ_a Q_θ(p, a). Only ``q_sa`` carries a gradient. ``skip`` >= 0 is a
    head column that is no action (a mask token's): no argmax picks it and
    ``q_row`` leaves it out."""
    n, h = hid_on.shape
    nb = -(-n // block)
    pad = nb * block - n

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((nb, block) + x.shape[1:])

    w_on, w_tg = head_on.astype(dtype), head_tg.astype(dtype)

    @jax.checkpoint
    def one(ho, ht, a):
        q_on = jnp.dot(ho.astype(dtype), w_on,
                       preferred_element_type=jnp.float32)
        q_tg = jnp.dot(ht.astype(dtype), w_tg,
                       preferred_element_type=jnp.float32)
        pick = lax.stop_gradient(q_on) if double else q_tg
        if skip >= 0:
            pick = pick.at[:, skip].set(-jnp.inf)
        a_star = jnp.argmax(pick, axis=-1)
        q_sa = jnp.take_along_axis(q_on, a[:, None], axis=-1)[:, 0]
        q_boot = jnp.take_along_axis(q_tg, a_star[:, None], axis=-1)[:, 0]
        q_row = jnp.sum(lax.stop_gradient(q_on), axis=-1)
        if skip >= 0:
            q_row = q_row - lax.stop_gradient(q_on[:, skip])
        return q_sa, q_boot, q_row

    _, (q_sa, q_boot, q_row) = lax.scan(
        lambda c, xs: (c, one(*xs)), None,
        (blocks(hid_on), blocks(hid_tg), blocks(actions)))
    return (q_sa.reshape(-1)[:n], lax.stop_gradient(q_boot.reshape(-1)[:n]),
            q_row.reshape(-1)[:n])


class SequenceLearner:
    """Owns the sharded sequence train step: R2D2 for recurrent Q-nets,
    and the token path for the token-window Q-network."""

    def __init__(self, module, cfg: TrainConfig, replay_cfg: ReplayConfig,
                 mesh, net_cfg=None):
        self.module = module
        self.net_cfg = net_cfg      # the token path's NetConfig
        self.cfg = cfg
        self.burn_in = int(replay_cfg.burn_in)
        self.mesh = mesh
        self.opt = make_optimizer(cfg)
        self._replicated = NamedSharding(mesh, P())
        self._batch_sharding = NamedSharding(mesh, P(AXIS_DP))
        self._train_step = self._build_train_step()
        # device-sequence-ring steps, keyed on ring geometry
        self._ring_steps: dict[tuple, Any] = {}
        # fused chained sequence steps, keyed on (spec, chain)
        self._fused_steps: dict[tuple, Any] = {}
        # static gauge ``train/rotary_fused`` of the last token train
        # program built (``models/tokenq.rotary_fused``); None before
        self.rotary_fused: int | None = None

    def init_state(self, params: Any) -> TrainState:
        state = TrainState(
            params=params,
            target_params=jax.tree.map(jnp.copy, params),
            opt_state=self.opt.init(params),
            step=jnp.zeros((), jnp.int32),
        )
        return put_replicated(state, self._replicated)

    def _step_core(self, state: TrainState, batch: dict[str, jax.Array]):
        """Burn-in + train-window unroll + masked loss + optimizer — the
        per-shard R2D2 step body, shared by the host-batch program and the
        device-sequence-ring train program."""
        cfg, burn = self.cfg, self.burn_in
        module, opt = self.module, self.opt

        def apply_seq(params, obs, carry):
            return module.apply({"params": params}, obs, carry)

        def step_fn(state: TrainState, batch: dict[str, jax.Array]):
            obs = batch["obs"]                    # [B, T_total+1, ...]
            carry0 = (batch["init_c"], batch["init_h"])
            # static gate, same policy as Learner._step_core: the stacked
            # time-batched torso wins whenever the step is op-count-bound
            use_stacked = (cfg.stack_forwards == "on"
                           or (cfg.stack_forwards == "auto"
                               and obs.shape[0] <= 128))

            def loss_fn(params):
                if use_stacked:
                    # Op-count surgery (PERF.md §4): the conv torso runs
                    # ONCE, time-batched over ALL [B·(T_total+1)] frames —
                    # burn-in included — for θ AND θ⁻ together (stacked
                    # weights, models/qnet.py); only the LSTM recurs. The
                    # scheduled conv count is therefore independent of
                    # both the sequence length and the number of nets,
                    # where the module-apply path pays four separate conv
                    # chains (on/target × burn/window). Gradients still
                    # cut at the burn-in seam: the burn features only
                    # reach the loss through the stop-gradded carry.
                    feats = stacked_r2d2_features(
                        module, params, state.target_params, obs)
                    _, l_on, h_on = r2d2_param_split(params)
                    _, l_tg, h_tg = r2d2_param_split(state.target_params)
                    f_on, f_tg = feats[0], feats[1]
                    if burn > 0:
                        carry_on = lax.stop_gradient(r2d2_burn_carry(
                            module, l_on, f_on[:, :burn], carry0))
                        carry_tg = r2d2_burn_carry(
                            module, l_tg, f_tg[:, :burn], carry0)
                    else:
                        carry_on = carry_tg = carry0
                    q_all, _ = r2d2_recur(module, l_on, h_on,
                                          f_on[:, burn:], carry_on)
                    q_tgt_all, _ = r2d2_recur(module, l_tg, h_tg,
                                              f_tg[:, burn:], carry_tg)
                else:
                    # burn-in from the stored carry; grads cut at the seam
                    if burn > 0:
                        _, carry_on = apply_seq(params, obs[:, :burn],
                                                carry0)
                        carry_on = lax.stop_gradient(carry_on)
                        _, carry_tg = apply_seq(state.target_params,
                                                obs[:, :burn], carry0)
                    else:
                        carry_on = carry_tg = carry0

                    # train window: T+1 obs → q for steps and bootstraps
                    q_all, _ = apply_seq(params, obs[:, burn:], carry_on)
                    q_tgt_all, _ = apply_seq(state.target_params,
                                             obs[:, burn:], carry_tg)
                q = q_all[:, :-1]                           # [B, T, A]
                q_next_online = lax.stop_gradient(q_all[:, 1:])
                q_next_target = q_tgt_all[:, 1:]

                targets = sequence_bellman_targets(
                    batch["reward"][:, burn:], batch["discount"][:, burn:],
                    q_next_target, q_next_online,
                    double=cfg.double_dqn, rescale=cfg.value_rescale)
                loss, priority = sequence_dqn_loss(
                    q, batch["action"][:, burn:], targets,
                    batch["mask"][:, burn:], batch["weight"],
                    cfg.huber_delta, eta=cfg.priority_eta)
                return loss, (priority, q)

            (loss, (priority, q)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)

            grads = lax.pmean(grads, AXIS_DP)
            loss = lax.pmean(loss, AXIS_DP)
            q_mean = lax.pmean(jnp.mean(q), AXIS_DP)

            gnorm = optax.global_norm(grads)
            step = state.step + 1
            if cfg.optimizer == "adam":
                # clip + Adam + target refresh in the one fused tree pass
                # (the lax.cond refresh scheduled a whole-tree copy per
                # step — see fused_adam_target_step)
                opt_state, params, target_params = fused_adam_target_step(
                    cfg, grads, state.opt_state, state.params,
                    state.target_params, gnorm, step)
            else:
                grads, gnorm = clip_grads(cfg, grads, gnorm)
                updates, opt_state = opt.update(grads, state.opt_state,
                                                state.params)
                params = optax.apply_updates(state.params, updates)
                target_params = refresh_target(cfg, params,
                                               state.target_params, step)
            new_state = TrainState(params, target_params, opt_state, step)
            metrics = {
                "loss": loss,
                "q_mean": q_mean,
                "grad_norm": gnorm,
            }
            if cfg.learn_metrics:
                # learning-dynamics plane (learning.py): the recurrent
                # step's Q extreme, reduced here so the fused chain's
                # plane sees a replicated scalar (lm_finalize's pmax is
                # then idempotent). Static gate — off traces nothing.
                metrics["q_max"] = lax.pmax(jnp.max(q), AXIS_DP)
            return new_state, metrics, priority

        return step_fn(state, batch)

    def _build_train_step(self):
        sharded = shard_map(
            lambda state, batch: self._step_core(state, batch),
            mesh=self.mesh,
            in_specs=(P(), P(AXIS_DP)),
            out_specs=(P(), P(), P(AXIS_DP)),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=0)

    def _build_ring_step(self, geom: tuple):
        """Per-step R2D2 ring path (host-sampled indices): the SAMPLE
        program DMA-copies each drawn sequence's contiguous W-row block
        out of the flat padded ring (``ops/ring_gather.py`` — one DMA per
        sequence, no gather lowering); the TRAIN program slices the
        stacked observations out of the blocks (static slices,
        ``compose_sequence_block``) and runs the recurrent step. Pixels
        never cross the host boundary per step — only KB-scale metadata
        does. The CHAINED path (``_build_fused_steps``) is the
        throughput mode; this one serves host-tree PER and the
        RPC-driven per-step loops."""
        (seq_len, stack, frame_shape, W, rowb, row_len, per_shard,
         interpret) = geom
        from distributed_deep_q_tpu.ops.ring_gather import gather_windows
        from distributed_deep_q_tpu.replay.device_sequence import (
            compose_sequence_block)

        S = P(AXIS_DP)
        rowp = rowb // 4

        def sample_fn(ring, seq_local):
            win = gather_windows(seq_local * W, ring, n=per_shard, w=W,
                                 rowb=rowb, interpret=interpret)
            return win.reshape(per_shard, W, rowp)

        sample = jax.jit(shard_map(
            sample_fn, mesh=self.mesh, in_specs=(S, S), out_specs=S,
            check_vma=False))

        def train_fn(state: TrainState, block, batch):
            h, w = frame_shape
            batch = dict(batch)
            obs = compose_sequence_block(block, batch["mask"], seq_len,
                                         stack, row_len)
            obs = obs.reshape(obs.shape[:3] + (h, w))
            batch["obs"] = jnp.moveaxis(obs, 2, -1)  # [b, T+1, H, W, S]
            return self._step_core(state, batch)

        # donate the state tree: params/target/opt alias their updated
        # outputs, so the optimizer writes in place. The pixel block has
        # no same-shaped output to alias — donating it would be a no-op.
        train = jax.jit(shard_map(
            train_fn, mesh=self.mesh,
            in_specs=(P(), S, S),
            out_specs=(P(), P(), S),
            check_vma=False), donate_argnums=0)
        return sample, train

    def train_step_from_ring(self, state: TrainState, replay, batch):
        """One DP step composing sequence pixels from the HBM ring; returns
        (state, metrics, per-sequence priority [B])."""
        b = len(batch["seq_local"])
        geom = (replay.seq_len, replay.stack, tuple(replay.frame_shape),
                replay.W, replay.rowb, replay._row_len,
                b // replay.num_shards, replay._interpret)
        if geom not in self._ring_steps:
            self._ring_steps[geom] = self._build_ring_step(geom)
        sample, train = self._ring_steps[geom]
        rows = sample(replay.ring, np.asarray(batch["seq_local"], np.int32))
        meta = {k: v for k, v in batch.items()
                if k not in ("seq_local", "n_valid")}
        return train(state, rows, meta)

    def _build_fused_steps(self, spec: tuple, chain: int):
        """Chained fused sequence steps — the transition path's two-program
        structure (``Learner._build_device_per_step``) on the sequence
        ring: the SAMPLE program draws all ``chain`` sequence batches
        against chunk-start priorities (inverse-CDF over the device
        priority row), row-gathers their metadata, and DMA-copies each
        sequence's contiguous W-row pixel block; the TRAIN program scans
        the ``chain`` recurrent steps with same-step per-sequence
        priority scatters. Per chunk the host ships per-shard sizes, βs,
        and keys — nothing reads back. Per-step dispatch caps at ~133/s
        on this runtime (PERF §2, measured 50.6/s for the r4 sequence
        path); chaining is what lifts the R2D2 device path past it."""
        (caps_local, seq_len, stack, W, rowb, row_len, frame_shape,
         per_shard, alpha, eps, num_shards, interpret) = spec
        from distributed_deep_q_tpu.ops.ring_gather import gather_windows
        from distributed_deep_q_tpu.replay.device_per import (
            build_cdf, draw_from_cdf, scatter_priorities,
            stratified_is_weights)
        from distributed_deep_q_tpu.replay.device_sequence import (
            compose_sequence_block)

        S = P(AXIS_DP)
        SK = P(None, AXIS_DP)
        SK3 = P(None, AXIS_DP, None)
        SWIN = P(None, AXIS_DP, None, None)
        rowp = rowb // 4
        n_win = chain * per_shard

        def sample_fn(keys, ring, dmeta, sizes, betas):
            filled = (jnp.arange(caps_local) < sizes[0]).astype(
                jnp.float32)
            pm = dmeta["prio"] * filled
            cdf, mass = build_cdf(pm)
            n_glob = lax.psum(jnp.sum(filled), AXIS_DP)
            idx, p = jax.vmap(
                lambda k: draw_from_cdf(k, cdf, pm, mass, per_shard))(
                keys[0])                               # [chain, b]
            flat = idx.reshape(-1)
            metas = {key: dmeta[key][flat].reshape(
                (chain, per_shard) + dmeta[key].shape[1:])
                for key in ("action", "reward", "discount", "mask",
                            "init_c", "init_h")}
            metas["weight"] = stratified_is_weights(p, mass, n_glob,
                                                    betas, num_shards)
            win = gather_windows(flat * W, ring, n=n_win, w=W, rowb=rowb,
                                 interpret=interpret)
            idx = jnp.where(mass > 0, idx, caps_local)
            return (metas, win.reshape(chain, per_shard, W, rowp),
                    idx.astype(jnp.int32))

        meta_spec = {"action": SK3, "reward": SK3, "discount": SK3,
                     "mask": SK3, "init_c": SK3, "init_h": SK3,
                     "weight": SK}
        dmeta_spec = {k: S for k in ("action", "reward", "discount",
                                     "mask", "init_c", "init_h", "prio")}
        sample = jax.jit(shard_map(
            sample_fn, mesh=self.mesh,
            in_specs=(S, S, dmeta_spec, S, P()),
            out_specs=(meta_spec, SWIN, SK),
            check_vma=False))

        def train_fn(state: TrainState, metas, win, idxs, prio, maxp):
            h, wd = frame_shape
            lm = bool(self.cfg.learn_metrics)  # static trace-time gate

            def body(carry, xs):
                if lm:
                    state, prio, maxp, lmp = carry
                else:
                    state, prio, maxp = carry
                batch, block, idx = xs
                batch = dict(batch)
                obs = compose_sequence_block(block, batch["mask"],
                                             seq_len, stack, row_len)
                obs = obs.reshape(obs.shape[:3] + (h, wd))
                batch["obs"] = jnp.moveaxis(obs, 2, -1)
                state, metrics, priority = self._step_core(state, batch)
                prio, maxp = scatter_priorities(prio, maxp, idx, priority,
                                                alpha, eps)
                if lm:
                    # per-sequence mixed max/mean |TD| (the PER priority
                    # statistic of record on the R2D2 path) feeds the TD
                    # histogram; loss/q_mean/gnorm arrive pmean'd from
                    # _step_core, q_max already pmax'd (idempotent under
                    # lm_finalize's pmax)
                    lmp = learning.lm_update(
                        lmp, cfg=self.cfg, td_abs=priority,
                        weight=batch["weight"], loss=metrics["loss"],
                        q=metrics["q_max"], q_mean=metrics["q_mean"],
                        gnorm=metrics["grad_norm"], step=state.step,
                        alpha=alpha, eps=eps)
                    return (state, prio, maxp, lmp), metrics
                return (state, prio, maxp), metrics

            if lm:
                (state, prio, maxp, lmp), metrics = lax.scan(
                    body, (state, prio, maxp, learning.lm_init()),
                    (metas, win, idxs))
                metrics = dict(metrics)
                metrics["learn_plane"] = learning.lm_finalize(lmp, AXIS_DP)
            else:
                (state, prio, maxp), metrics = lax.scan(
                    body, (state, prio, maxp), (metas, win, idxs))
            return state, prio, maxp, metrics

        # donate every input with an updated output to alias (transition
        # path's discipline): the state tree (0) and prio/maxp (4, 5) are
        # rewritten in place instead of through defensive copies. metas/
        # win/idxs have no same-shaped output, so donating them is a no-op
        # (XLA donation is strictly output aliasing).
        train = jax.jit(shard_map(
            train_fn, mesh=self.mesh,
            in_specs=(P(), meta_spec, SWIN, SK, S, P()),
            out_specs=(P(), S, P(), P()),
            check_vma=False), donate_argnums=(0, 4, 5))
        return sample, train

    def _token_step_core(self, state: TrainState,
                         batch: dict[str, jax.Array]):
        """The token-window step body (per shard): θ and θ⁻ forward over
        the SAME window of T+1 tokens, the Q head and the Double-DQN
        selection blockwise over tokens, the repo's sequence loss over the
        selected values, then the shared clip + Adam + target step.
        ``batch``: ``tokens`` [b, T+1] int32, ``reward`` / ``discount`` /
        ``mask`` [b, T], ``weight`` [b]."""
        from distributed_deep_q_tpu.models import tokenq
        from distributed_deep_q_tpu.parallel.mesh import pallas_interpret

        cfg, net = self.cfg, self.net_cfg
        assert not cfg.learn_metrics, "learn_metrics: not on the token path"
        interpret = pallas_interpret(self.mesh)
        dtype = jnp.dtype(net.compute_dtype)
        tokens = batch["tokens"]
        b, t1 = tokens.shape
        # generation by diffusion over blocks: the sample program's draw
        # of how much of each block is revealed (absent: one token a
        # position under the causal mask)
        reveal = batch.get("reveal")
        # the action at position p is the next token; the last position
        # only bootstraps
        actions = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
        hid_tg, _ = tokenq.backbone(state.target_params, tokens, net,
                                    interpret, index_loss=False,
                                    reveal=reveal)

        def loss_fn(params):
            hid_on, counters = tokenq.backbone(params, tokens, net,
                                               interpret, reveal=reveal)
            if reveal is not None:
                loss, priority, q_mean, bd = self._block_decisions_loss(
                    hid_on, hid_tg, params["head"],
                    state.target_params["head"], batch)
                return loss, (loss, priority, q_mean, {**counters, **bd})
            with jax.named_scope("ddq.q_head_loss"):
                q_sa, q_boot, q_row = token_q_select(
                    hid_on.reshape(b * t1, -1), hid_tg.reshape(b * t1, -1),
                    params["head"], state.target_params["head"],
                    actions.reshape(-1), block=net.tokenq.head_block,
                    dtype=dtype, double=cfg.double_dqn)
                q_sa = q_sa.reshape(b, t1)[:, :-1, None]
                q_boot = q_boot.reshape(b, t1)[:, 1:, None]
                # the selection is done: the repo's loss sees one action
                targets = sequence_bellman_targets(
                    batch["reward"], batch["discount"], q_boot, q_boot,
                    double=cfg.double_dqn, rescale=cfg.value_rescale)
                loss, priority = sequence_dqn_loss(
                    q_sa, jnp.zeros((b, t1 - 1), jnp.int32), targets,
                    batch["mask"], batch["weight"], cfg.huber_delta,
                    eta=cfg.priority_eta)
                q_mean = jnp.mean(q_row.reshape(b, t1)[:, :-1]) / \
                    params["head"].shape[1]
            # a sparse layer's indexer learns from its own loss (no other
            # gradient reaches it): the step minimises the sum, and
            # reports the TD loss as ``loss``
            total = loss
            if "dsa_index_loss" in counters:
                total = loss + jnp.sum(counters["dsa_index_loss"])
            return total, (loss, priority, q_mean, counters)

        (_, (loss, priority, q_mean, counters)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        grads = lax.pmean(grads, AXIS_DP)
        with jax.named_scope("ddq.optimizer"):
            # per-leaf norms (in ``tokenq.named_leaves`` order): which
            # layer's gradient moved, and the global norm from them
            leaf_sq = jnp.stack([jnp.sum(jnp.square(g))
                                 for g in jax.tree.leaves(grads)])
            gnorm = jnp.sqrt(jnp.sum(leaf_sq))
            step = state.step + 1
            if cfg.optimizer == "adam":
                opt_state, params, target_params = fused_adam_target_step(
                    cfg, grads, state.opt_state, state.params,
                    state.target_params, gnorm, step)
            else:
                grads, gnorm = clip_grads(cfg, grads, gnorm)
                updates, opt_state = self.opt.update(
                    grads, state.opt_state, state.params)
                params = optax.apply_updates(state.params, updates)
                target_params = refresh_target(cfg, params,
                                               state.target_params, step)
        load = counters["load"].astype(jnp.float32)        # [layers, held]
        metrics = {
            "loss": lax.pmean(loss, AXIS_DP),
            "q_mean": lax.pmean(q_mean, AXIS_DP),
            "grad_norm": gnorm,
            "grad_leaf_norm": jnp.sqrt(leaf_sq),
            # the expert layer's counters, over layers and shards
            "moe_slots_held": lax.psum(jnp.sum(counters["slots_held"]),
                                       AXIS_DP),
            "moe_slots": lax.psum(jnp.sum(counters["slots"]), AXIS_DP),
            # rows of the blocks the walk ran: over ``moe_slots_held`` it
            # says how well the blocks fit the load (1.0: no row for
            # nothing)
            "moe_rows_run": lax.psum(jnp.sum(counters["rows_run"]),
                                     AXIS_DP),
            "moe_overflow": lax.psum(jnp.sum(counters["overflow"]),
                                     AXIS_DP),
            "moe_load_max_over_mean": lax.pmean(jnp.mean(
                jnp.max(load, -1) / jnp.maximum(jnp.mean(load, -1), 1.0)),
                AXIS_DP),
        }
        if "dsa_selected" in counters:
            # the sparse layers' counters: (query, key) pairs attended and
            # inside the causal mask, over layers and shards, and the
            # indexers' loss summed over layers
            metrics.update({
                "dsa_pairs_selected": lax.psum(
                    jnp.sum(counters["dsa_selected"]), AXIS_DP),
                "dsa_pairs_causal": lax.psum(
                    jnp.sum(counters["dsa_causal"]), AXIS_DP),
                "dsa_index_loss": lax.pmean(
                    jnp.sum(counters["dsa_index_loss"]), AXIS_DP)})
        if "attn_gate_mean" in counters:
            # the attention output gates: their mean over tokens, heads
            # and the gated layers (0.5 at zero weights; where training
            # closes heads it falls)
            metrics["attn_gate_mean"] = lax.pmean(
                jnp.mean(counters["attn_gate_mean"]), AXIS_DP)
        if "ssm_dt_mean" in counters:
            # the state-space mixers' step Δ: its mean over tokens, heads
            # and the Mamba layers (a scan that forgets everything reads
            # large, one that forgets nothing near 0)
            metrics["ssm_dt_mean"] = lax.pmean(
                jnp.mean(counters["ssm_dt_mean"]), AXIS_DP)
        if reveal is not None:
            # block diffusion: decisions that carried a loss, tokens of a
            # block already revealed (mean: (B - 1) / 2), env steps from
            # one decision to the next (mean: B), and Q_θ(d, a) decision
            # by decision [windows, G - 1] (a mean over thousands of
            # decisions hides a few keys more or fewer in a row's mask;
            # one decision's Q does not)
            metrics.update({
                "bd_decisions_valid": lax.psum(counters["bd_valid"],
                                               AXIS_DP),
                "bd_reveal_mean": lax.pmean(
                    jnp.mean(reveal.astype(jnp.float32)), AXIS_DP),
                "bd_span_mean": lax.pmean(counters["bd_span"], AXIS_DP),
                "bd_q_sa": lax.all_gather(counters["bd_q_sa"], AXIS_DP,
                                          tiled=True)})
        return (TrainState(params, target_params, opt_state, step), metrics,
                priority)

    def _block_decisions_loss(self, hid_on: jax.Array, hid_tg: jax.Array,
                              head_on: jax.Array, head_tg: jax.Array,
                              batch: dict[str, jax.Array]):
        """The TD loss of a block-diffusion step: ONE decision a block.
        ``hid_*`` [b, T + 1 + G·B, h] are θ's and θ⁻'s hidden states over
        the packed rows. Block g's decision row is the first masked row of
        its noised copy (``tokenq.bd_decision_rows``): its state the
        prefix ``tok[0..p_g]``, ``p_g = gB + reveal[g]``, its action
        ``tok[p_g + 1]``. The decision rows are gathered BEFORE the head
        (G rows a window through it, not all the packed rows); the target
        of decision g is the uncorrected n-step return over the ``p_{g+1}
        - p_g`` env steps to the next block's decision
        (``ops/losses.span_returns``), bootstrapped at that decision's row
        of the same θ⁻ pass with the Double-DQN argmax of the same θ
        pass; the last block only bootstraps. → (loss, priority [b], mean
        Q over decisions and actions, counters: among them ``bd_q_sa``
        [b, G - 1], Q_θ(d_g, a_g) decision by decision)."""
        from distributed_deep_q_tpu.models import tokenq

        cfg, tq = self.cfg, self.net_cfg.tokenq
        tokens, reveal = batch["tokens"], batch["reveal"]
        b, t1 = tokens.shape
        g = reveal.shape[1]
        with jax.named_scope("ddq.bd_gather"):
            rows, step = tokenq.bd_decision_rows(reveal, t1 - 1,
                                                 tq.block_length)
            at = rows[..., None]
            dec_on = jnp.take_along_axis(hid_on, at, axis=1)    # [b, G, h]
            dec_tg = jnp.take_along_axis(hid_tg, at, axis=1)
            actions = jnp.take_along_axis(
                tokens, jnp.minimum(step + 1, t1 - 1), axis=1)
        with jax.named_scope("ddq.q_head_loss"):
            q_sa, q_boot, q_row = token_q_select(
                dec_on.reshape(b * g, -1), dec_tg.reshape(b * g, -1),
                head_on, head_tg, actions.reshape(-1), block=tq.head_block,
                dtype=jnp.dtype(self.net_cfg.compute_dtype),
                double=cfg.double_dqn, skip=tokenq.mask_token(self.net_cfg))
            q_sa = q_sa.reshape(b, g)[:, :-1, None]
            q_boot = q_boot.reshape(b, g)[:, 1:, None]
            with jax.named_scope("ddq.block_return"):
                span = step[:, 1:] - step[:, :-1]
                ret, gamma, valid = span_returns(
                    batch["reward"], batch["discount"], batch["mask"],
                    step[:, :-1], span, 2 * tq.block_length - 1)
            targets = sequence_bellman_targets(
                ret, gamma, q_boot, q_boot, double=cfg.double_dqn,
                rescale=cfg.value_rescale)
            loss, priority = sequence_dqn_loss(
                q_sa, jnp.zeros((b, g - 1), jnp.int32), targets, valid,
                batch["weight"], cfg.huber_delta, eta=cfg.priority_eta)
            q_mean = jnp.mean(q_row.reshape(b, g)[:, :-1]) / (
                head_on.shape[1] - 1)
        return loss, priority, q_mean, {
            "bd_valid": jnp.sum(valid),
            "bd_span": jnp.mean(span.astype(jnp.float32)),
            "bd_q_sa": lax.stop_gradient(q_sa[..., 0])}

    def _build_token_fused_steps(self, spec: tuple, chain: int):
        """The fused two-program step on the token ring
        (``replay/device_tokens.py``): the SAMPLE program draws ``chain``
        batches of windows against chunk-start priorities and gathers
        whole windows (tokens, rewards, flags) and their IS weights; the
        TRAIN program scans ``chain`` token steps with same-step priority
        scatters. Nothing reads back."""
        (caps_local, seq_len, per_shard, alpha, eps, num_shards,
         gamma) = spec
        from distributed_deep_q_tpu.replay.device_per import (
            build_cdf, draw_from_cdf, scatter_priorities,
            stratified_is_weights)
        from distributed_deep_q_tpu.replay.device_tokens import unpack_flags

        S = P(AXIS_DP)
        SK = P(None, AXIS_DP)
        SK3 = P(None, AXIS_DP, None)
        ring_spec = {"tokens": S, "reward": S, "flags": S}
        batch_spec = {"tokens": SK3, "reward": SK3, "discount": SK3,
                      "mask": SK3, "weight": SK}
        # generation by diffusion over blocks: how many tokens of each
        # block of each drawn window are already revealed, from the step's
        # key (its own stream, beside the draw of the windows)
        bd = self.net_cfg.tokenq.block_length if self.net_cfg else 0
        if bd:
            batch_spec["reveal"] = SK3
        if self.net_cfg:
            from distributed_deep_q_tpu.models.tokenq import rotary_fused
            self.rotary_fused = rotary_fused(self.net_cfg.tokenq)

        def token_sample_fn(keys, ring, prio, sizes, betas):
            filled = (jnp.arange(caps_local) < sizes[0]).astype(
                jnp.float32)
            pm = prio * filled
            cdf, mass = build_cdf(pm)
            n_glob = lax.psum(jnp.sum(filled), AXIS_DP)
            idx, p = jax.vmap(
                lambda k: draw_from_cdf(k, cdf, pm, mass, per_shard))(
                keys[0])                               # [chain, b]
            flat = idx.reshape(-1)

            def rows(x):
                return x[flat].reshape((chain, per_shard) + x.shape[1:])

            discount, mask = unpack_flags(rows(ring["flags"]), gamma)
            batch = {"tokens": rows(ring["tokens"]),
                     "reward": rows(ring["reward"]),
                     "discount": discount, "mask": mask,
                     "weight": stratified_is_weights(p, mass, n_glob,
                                                     betas, num_shards)}
            if bd:
                batch["reveal"] = jax.vmap(lambda k: jax.random.randint(
                    jax.random.fold_in(k, 1),
                    (per_shard, -(-seq_len // bd)), 0, bd))(keys[0])
            idx = jnp.where(mass > 0, idx, caps_local)
            return batch, idx.astype(jnp.int32)

        sample = jax.jit(shard_map(
            jax.named_scope("ddq.sample")(token_sample_fn), mesh=self.mesh,
            in_specs=(S, ring_spec, S, S, P()),
            out_specs=(batch_spec, SK), check_vma=False))

        def token_train_fn(state: TrainState, batch, idxs, prio, maxp):
            def body(carry, xs):
                state, prio, maxp = carry
                one, idx = xs
                state, metrics, priority = self._token_step_core(state, one)
                prio, maxp = scatter_priorities(prio, maxp, idx, priority,
                                                alpha, eps)
                return (state, prio, maxp), metrics

            (state, prio, maxp), metrics = lax.scan(
                body, (state, prio, maxp), (batch, idxs))
            return state, prio, maxp, metrics

        train = jax.jit(shard_map(
            jax.named_scope("ddq.train")(token_train_fn), mesh=self.mesh,
            in_specs=(P(), batch_spec, SK, S, P()),
            out_specs=(P(), S, P(), P()),
            check_vma=False), donate_argnums=(0, 3, 4))
        return sample, train

    def token_fused_programs(self, replay, batch_size: int, chain: int):
        """(sample, train) of the token ring's fused step, built once per
        ring geometry and chain."""
        spec = (replay.caps_local, replay.seq_len,
                batch_size // replay.num_shards, replay.alpha, replay.eps,
                replay.num_shards, replay.gamma)
        cache_key = ("tokens", spec, chain)
        if cache_key not in self._fused_steps:
            self._fused_steps[cache_key] = self._build_token_fused_steps(
                spec, chain)
        return self._fused_steps[cache_key]

    def train_steps_fused(self, state: TrainState, replay, batch_size: int,
                          sizes, betas: np.ndarray, keys: np.ndarray):
        """``len(betas)`` fused sequence steps in one two-program dispatch.
        Returns (state, new prio, new maxp, metrics stacked [chain])."""
        chain = len(betas)
        if getattr(replay, "window_kind", "frames") == "tokens":
            sample, train = self.token_fused_programs(replay, batch_size,
                                                      chain)
            with tracing.span("sample"):
                batch, idx = sample(keys, replay.ring, replay.dmeta["prio"],
                                    np.asarray(sizes),
                                    np.asarray(betas, np.float32))
            with tracing.span("train_step"):
                return train(state, batch, idx, replay.dmeta["prio"],
                             replay.dmaxp)
        spec = (replay.caps_local, replay.seq_len, replay.stack, replay.W,
                replay.rowb, replay._row_len, tuple(replay.frame_shape),
                batch_size // replay.num_shards,
                replay.alpha, replay.eps, replay.num_shards,
                replay._interpret)
        cache_key = (spec, chain)
        if cache_key not in self._fused_steps:
            self._fused_steps[cache_key] = self._build_fused_steps(
                spec, chain)
        sample, train = self._fused_steps[cache_key]

        def feed(x, dtype=None):
            # multi-host global arrays pass through untouched
            return x if isinstance(x, jax.Array) else np.asarray(x, dtype)

        metas, win, idx = sample(keys, replay.ring, replay.dmeta,
                                 feed(sizes), feed(betas, np.float32))
        return train(state, metas, win, idx, replay.dmeta["prio"],
                     replay.dmaxp)

    def train_step(self, state: TrainState, batch: dict[str, Any]):
        """One synchronous DP step over a [B, T_total(+1)] sequence batch;
        returns (state, metrics, per-sequence priority [B]). In multi-host
        mode each process passes its local B/process_count sequences (same
        contract as ``Learner.train_step``)."""
        return self._train_step(state, global_batch(self._batch_sharding,
                                                    batch))


class SequenceSolver:
    """Reference ``Solver`` surface for the sequence pipelines.

    Mirrors ``solver.Solver`` (train_step / q_values / act / weight IO [M])
    with recurrent state threading for the actor path (``r2d2``), or — for
    the token-window Q-network (``tokenq``) — the token prefix as the
    state: ``token_q_values`` / ``token_act`` run the whole prefix, and
    weights also go by per-path leaf names (``get_named_weights``).
    """

    def __init__(self, config, obs_dim: int = 4, backend: str | None = None):
        import dataclasses

        from distributed_deep_q_tpu.models.qnet import (
            QNet, build_qnet, init_params)
        from distributed_deep_q_tpu.parallel.mesh import make_mesh
        from distributed_deep_q_tpu.solver import _strip_host_keys

        assert config.net.kind in ("r2d2", "tokenq"), (
            "SequenceSolver is for r2d2 and tokenq nets")
        self.tokenq = config.net.kind == "tokenq"
        if backend is not None:
            config = dataclasses.replace(
                config, mesh=dataclasses.replace(config.mesh, backend=backend))
        self.config = config
        self.backend = config.mesh.backend
        self.mesh = make_mesh(config.mesh)
        if self.tokenq:
            from distributed_deep_q_tpu.models import tokenq
            from distributed_deep_q_tpu.parallel.mesh import (
                pallas_interpret)

            self.module = None
            params = tokenq.init_params(config.net, config.train.seed)
            interpret = pallas_interpret(self.mesh)
            self._fwd = jax.jit(lambda p, tok, pos: tokenq.q_at(
                p, tok, pos, config.net, interpret))
        else:
            self.module = build_qnet(config.net)
            params = init_params(self.module, config.net,
                                 config.train.seed, obs_dim)
            self._fwd = jax.jit(
                lambda p, o, c: self.module.apply({"params": p}, o, c))
        self.learner = SequenceLearner(self.module, config.train,
                                       config.replay, self.mesh,
                                       net_cfg=config.net)
        self.state: TrainState = self.learner.init_state(params)
        self._treedef = jax.tree_util.tree_structure(params)
        self._strip = _strip_host_keys
        # fused chained-path key bookkeeping (Solver's scheme)
        self._fused_key_base: int | None = None
        self._fused_steps_issued = 0

    @property
    def step(self) -> int:
        return int(self.state.step)

    def train_step(self, batch: dict[str, Any]) -> dict[str, Any]:
        self.state, metrics, priority = self.learner.train_step(
            self.state, self._strip(batch))
        out: dict[str, Any] = dict(metrics)
        out["td_abs"] = priority  # per-sequence priority for PER write-back
        if "index" in batch:
            out["index"] = batch["index"]
        return out

    def train_step_from_ring(self, replay, batch: dict[str, Any],
                             ) -> dict[str, Any]:
        """One R2D2 step with pixels composed from the device-resident
        sequence ring (``DeviceSequenceReplay``): ``batch`` carries only
        sequence metadata + shard-local slot indices."""
        self.state, metrics, priority = self.learner.train_step_from_ring(
            self.state, replay, self._strip(batch))
        out: dict[str, Any] = dict(metrics)
        out["td_abs"] = priority
        if "index" in batch:
            out["index"] = batch["index"]
        return out

    def train_steps_device_per(self, replay,
                               chain: int | None = None) -> dict[str, Any]:
        """``chain`` fused sequence steps in ONE two-program dispatch
        (sampling, metadata, pixels, and per-sequence priority updates all
        on device — ``SequenceLearner._build_fused_steps``). Same protocol
        as ``Solver.train_steps_device_per`` so ``FusedStepStream`` drives
        either. Returns metrics stacked [chain]."""
        from distributed_deep_q_tpu.solver import next_fused_keys

        chain = chain or max(int(self.config.replay.fused_chain), 1)
        # the same spans as Solver.train_steps_device_per, so the device's
        # idle time has an owner in this loop too (tracing.STAGES)
        with tracing.span("learner_flush"):
            if replay.pending_rows() or replay.defer_flush:
                # multi-host the flush is a lockstep collective with an
                # agreed round count — every process calls it here
                replay.flush()
        with tracing.span("learner_feed"):
            sizes = replay.device_inputs()
            betas = replay.next_betas(chain)
            keys = next_fused_keys(self, replay.num_shards, chain)
            if replay._pc > 1:
                keys = replay.to_global(
                    np.ascontiguousarray(keys[replay.local_shards]))
                sizes = replay.to_global(np.asarray(sizes))
                betas = replay.to_replicated(np.asarray(betas, np.float32))
        state, prio, maxp, metrics = self.learner.train_steps_fused(
            self.state, replay, self.config.replay.batch_size, sizes,
            betas, keys)
        with tracing.span("learner_adopt"):
            self.state = state
            replay.dmeta = dict(replay.dmeta)
            replay.dmeta["prio"] = prio
            replay.dmaxp = maxp
        return dict(metrics)

    def fused_gauges(self) -> dict[str, int]:
        """Static gauges of the fused token step for the train loop's log
        rows: ``train/rotary_fused`` (``SequenceLearner.rotary_fused``);
        nothing before a token train program was built."""
        fused = self.learner.rotary_fused
        return {} if fused is None else {"train/rotary_fused": fused}

    def fused_executables(self, replay, chain: int) -> dict[str, Any]:
        """``Solver.fused_executables`` for the token ring's fused step:
        the two executables the loop ran, found again from the types and
        shardings of its arguments (nothing executes, no key is drawn)."""
        if getattr(replay, "window_kind", "frames") != "tokens":
            raise NotImplementedError(
                "only the token ring's fused programs are found again")
        sample, train = self.learner.token_fused_programs(
            replay, self.config.replay.batch_size, chain)
        sampled = ran_executable(
            sample, np.zeros((replay.num_shards, chain, 2), np.uint32),
            replay.ring, replay.dmeta["prio"],
            np.asarray(replay.device_inputs()),
            np.full(chain, 0.5, np.float32))
        batch, idx = outputs_as_avals(sampled)
        return {"sample": sampled, "train": ran_executable(
            train, self.state, batch, idx, replay.dmeta["prio"],
            replay.dmaxp)}

    # -- recurrent actor path ----------------------------------------------

    def initial_state(self, batch_size: int = 1):
        from distributed_deep_q_tpu.models.qnet import R2d2QNet
        return R2d2QNet(self.config.net.num_actions,
                        self.config.net.lstm_size).initial_state(batch_size)

    def q_values(self, obs: np.ndarray, carry):
        """obs [B, ...] single step → (q [B, A], next carry)."""
        q, carry = self._fwd(self.state.params, np.asarray(obs)[:, None],
                             carry)
        return np.asarray(q[:, 0]), carry

    def act(self, obs: np.ndarray, carry, epsilon: float,
            rng: np.random.Generator):
        """ε-greedy with recurrent state; returns (action, next carry).

        The carry ALWAYS advances (even on random actions) so stored actor
        state matches what the policy network saw — required for the
        stored-state burn-in strategy to be meaningful."""
        q, carry = self.q_values(obs[None], carry)
        if rng.random() < epsilon:
            return int(rng.integers(self.config.net.num_actions)), carry
        return int(np.argmax(q[0])), carry

    # -- token actor path ---------------------------------------------------

    def acting_prefix(self, prefix) -> np.ndarray:
        """The tail of an episode's token prefix that the acting path
        runs: its last T + 1 tokens. With ``block_length`` > 0 the tail
        starts a whole number of blocks into the episode (the ring's
        windows do: they lie back to back, T a multiple of the block) and
        holds at most T tokens, so that the first masked position lies in
        the window."""
        t = self.config.replay.sequence_length
        bl = self.config.net.tokenq.block_length
        if not bl:
            return np.asarray(prefix[-(t + 1):])
        drop = -(-max(len(prefix) - t, 0) // bl) * bl
        return np.asarray(prefix[drop:])

    def token_q_values(self, prefix: np.ndarray) -> np.ndarray:
        """Q(prefix, ·) [V] for one token prefix of at most a window's
        T+1 tokens (T with ``block_length`` > 0, where Q is read at the
        position AFTER the prefix). There is no cache: the prefix is
        padded to the window (ONE compiled shape) and the whole window is
        run. Under the causal mask the padding reaches no real position;
        under the block mask the rest of the prefix's block IS part of the
        state, so the padding is the mask token (``tokenq.q_at``)."""
        from distributed_deep_q_tpu.models import tokenq

        n = len(prefix)
        blocks = self.config.net.tokenq.block_length
        window = np.full((1, self.config.replay.sequence_length + 1),
                         tokenq.mask_token(self.config.net) if blocks else 0,
                         np.int32)
        if blocks and n >= window.shape[1]:
            raise ValueError(
                f"a prefix of {n} tokens leaves no masked position in a "
                f"window of {window.shape[1]} (acting_prefix cuts it)")
        window[0, :n] = prefix
        return np.asarray(self._fwd(self.state.params, window,
                                    np.int32(n - 1))[0])

    def token_act(self, prefix: np.ndarray, epsilon: float,
                  rng: np.random.Generator) -> int:
        """ε-greedy next token; a mask token is no action (never drawn,
        its column skipped by the argmax)."""
        from distributed_deep_q_tpu.models import tokenq

        mask_id = tokenq.mask_token(self.config.net)
        if rng.random() < epsilon:
            if mask_id < 0:
                return int(rng.integers(self.config.net.num_actions))
            a = int(rng.integers(self.config.net.num_actions - 1))
            return a + (a >= mask_id)
        q = np.array(self.token_q_values(prefix))
        if mask_id >= 0:
            q[mask_id] = -np.inf
        return int(np.argmax(q))

    # -- weight IO ----------------------------------------------------------

    def get_named_weights(self) -> dict[str, np.ndarray]:
        """θ by per-path leaf names (``layer_00/w_q``): the token path's
        weight IO, independent of the tree's flattening order."""
        from distributed_deep_q_tpu.models import tokenq
        return {k: np.asarray(v) for k, v in tokenq.named_leaves(
            self.state.params).items()}

    def set_named_weights(self, named: dict[str, np.ndarray],
                          target: bool = True) -> None:
        """Install θ (and θ⁻ with ``target``) from per-path leaf names."""
        from distributed_deep_q_tpu.models import tokenq
        params = jax.device_put(
            tokenq.from_named(self.state.params, named),
            self.learner._replicated)
        self.state = self.state.replace(params=params)
        if target:
            self.state = self.state.replace(
                target_params=jax.tree.map(jnp.copy, params))

    def get_weights(self) -> list[np.ndarray]:
        return [np.asarray(x)
                for x in jax.tree_util.tree_leaves(self.state.params)]

    def update(self, weights: list[np.ndarray]) -> None:
        params = jax.tree_util.tree_unflatten(self._treedef, list(weights))
        params = jax.device_put(params, self.learner._replicated)
        self.state = self.state.replace(params=params)

    set_weights = update
