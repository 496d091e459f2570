"""The synchronous data-parallel learner — core of the TPU rebuild.

Replaces the reference's Spark/parameter-server asynchronous gradient
push/pull (SURVEY.md §2.2, §3.4 [M][P]) with the north-star-mandated design:
one jitted ``train_step`` wrapped in ``shard_map`` over a ``dp`` device
mesh; per-device gradients are allreduced with ``lax.pmean`` (psum/n) over
ICI; parameters, optimizer state, and the target network stay replicated so
the periodic target refresh ("every C pulls: θ⁻ ← θ", SURVEY §3.1 [M]) is a
branchless on-device copy — the moral equivalent of "broadcast θ⁻ from
chip 0" with zero comms, since replicated updates are bitwise identical on
every chip.

Everything — Bellman targets, forward, backward, optimizer, target refresh —
compiles into ONE XLA program per step. The reference crosses the Python↔
Caffe boundary multiple times per minibatch (SURVEY §3.1 hot loop); here the
host only feeds batches and reads back scalar metrics.

TrainState buffers are donated (``donate_argnums=0``), so parameters and
optimizer state are updated in place in HBM with no per-step allocation churn.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from optax import safe_increment

from distributed_deep_q_tpu import learning, tracing

from distributed_deep_q_tpu.config import TrainConfig
from distributed_deep_q_tpu.models.qnet import (
    stacked_q_apply, stacked_q_forwards)
from distributed_deep_q_tpu.ops.losses import bellman_targets, dqn_loss
from distributed_deep_q_tpu.parallel.mesh import (
    AXIS_DP, AXIS_MODEL, pallas_interpret, tree_shardings)
from distributed_deep_q_tpu.parallel.multihost import (
    global_batch, put_replicated)


LANES = 128     # a TPU vector register's minor dimension

# Adam moment decays, shared by ``make_optimizer`` (the state-structure
# builder) and ``fused_adam_step`` (the hot path) so the two can never
# drift apart — their bitwise equivalence is load-bearing for checkpoints.
ADAM_B1, ADAM_B2 = 0.9, 0.999


class TrainState(flax.struct.PyTreeNode):
    params: Any
    target_params: Any
    opt_state: Any
    step: jax.Array  # int32 scalar


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    """Optimizer chain. The reference PS applied RMSProp/AdaGrad-style
    updates (SURVEY §3.4 [P]); we default to Adam with the same switch.

    For adam the returned transform's ``init`` defines the opt_state
    STRUCTURE (kept exactly as optax builds it, chain included, so
    checkpoints resume across versions) but its ``update`` is NOT on the
    hot path — the train steps run ``fused_adam_step``, which performs
    the same clip+adam math in one tree pass (the optax stack costs
    ~0.05 ms/step at batch 32 in separate passes — the step is
    op-count-bound there). rmsprop keeps the optax update path with
    ``clip_grads``."""
    if cfg.optimizer == "adam":
        opt = optax.adam(cfg.lr, b1=ADAM_B1, b2=ADAM_B2, eps=cfg.adam_eps,
                         mu_dtype=jnp.dtype(cfg.adam_mu_dtype))
    elif cfg.optimizer == "rmsprop":
        opt = optax.rmsprop(cfg.lr, decay=0.95, eps=1e-2, centered=True)
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    if cfg.grad_clip_norm > 0:
        return optax.chain(optax.clip_by_global_norm(cfg.grad_clip_norm),
                           opt)
    return opt


def clip_grads(cfg: TrainConfig, grads: Any,
               gnorm: jax.Array) -> tuple[Any, jax.Array]:
    """Global-norm clip using the ALREADY-computed norm — identical math
    to ``optax.clip_by_global_norm`` (scale by min(1, clip/norm)), one
    tree pass instead of three (its norm + its scale + the metric's
    norm). Returns (clipped grads, the norm for the metric)."""
    if cfg.grad_clip_norm <= 0:
        return grads, gnorm
    scale = jnp.minimum(1.0, cfg.grad_clip_norm
                        / jnp.maximum(gnorm, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads), gnorm


def _locate_adam_state(opt_state: Any):
    """Locate the ScaleByAdamState inside whichever structure
    ``make_optimizer`` built — bare adam (clip off) or
    ``chain(clip_by_global_norm, adam)`` — preserving it exactly so
    checkpoints stay resumable across both. Returns (adam_state,
    rebuild) where ``rebuild(new_adam_state)`` reassembles the full
    opt_state."""
    if isinstance(opt_state[0], optax.ScaleByAdamState):
        adam_state = opt_state[0]

        def rebuild(s):
            return (s,) + tuple(opt_state[1:])
    else:
        inner = opt_state[1]
        adam_state = inner[0]

        def rebuild(s):
            return (opt_state[0], (s,) + tuple(inner[1:])) \
                + tuple(opt_state[2:])
    return adam_state, rebuild


def fused_adam_step(cfg: TrainConfig, grads: Any, opt_state: Any,
                    params: Any, gnorm: jax.Array) -> tuple[Any, Any]:
    """Clip + Adam + parameter update in ONE multi-output fusion per leaf.

    Bitwise-compatible math and state structure with
    ``optax.chain(clip_by_global_norm, adam)`` (the state is the tuple
    ``optax.adam().init`` builds, so checkpoints are interchangeable —
    tests/test_losses.py holds the equivalence). Exists because the step
    is op-count-bound at small batch on this chip (~1.5-4.5 µs fixed
    cost per scheduled fusion, measured): optax runs ~5 tree passes ×
    13 leaves where one pass suffices — the fold measured ~0.05 ms/step
    at batch 32, ~18% of the whole train step.

    Returns (new opt_state, new params).
    """
    opt_state, params, _ = fused_adam_target_step(
        cfg, grads, opt_state, params, None, gnorm, None)
    return opt_state, params


@jax.named_scope("ddq.optimizer")
def fused_adam_target_step(
    cfg: TrainConfig, grads: Any, opt_state: Any, params: Any,
    target_params: Any, gnorm: jax.Array, step: jax.Array | None,
) -> tuple[Any, Any, Any]:
    """``fused_adam_step`` with the target refresh folded into the SAME
    per-leaf multi-output fusion.

    The ``lax.cond``-based ``refresh_target`` schedules a whole-tree COPY
    of whichever branch it takes — 13 scheduled copies per step on the
    13-leaf Nature net, pure per-op overhead on the op-count-bound small
    batch step. Folded here the refresh is one extra elementwise output
    per leaf fusion: Polyak ``τ·p₂ + (1−τ)·t`` when ``target_tau`` > 0,
    else ``where(step % C == 0, p₂, t)`` — a select, bitwise-identical
    to the cond's chosen branch. ``step`` is the ALREADY-incremented
    step (the refresh condition matches ``refresh_target``'s).

    With ``target_params=None`` this is plain ``fused_adam_step``
    (returned target tree is ``None``).

    Returns (new opt_state, new params, new target_params).
    """
    adam_state, rebuild = _locate_adam_state(opt_state)
    b1, b2 = ADAM_B1, ADAM_B2
    count = safe_increment(adam_state.count)
    c = count.astype(jnp.float32)
    bc1 = 1.0 - b1 ** c
    bc2 = 1.0 - b2 ** c
    scale = (jnp.minimum(1.0, cfg.grad_clip_norm
                         / jnp.maximum(gnorm, 1e-12))
             if cfg.grad_clip_norm > 0 else jnp.float32(1.0))
    lr, eps = cfg.lr, cfg.adam_eps
    mu_dtype = jnp.dtype(cfg.adam_mu_dtype)
    with_target = target_params is not None
    if with_target:
        if cfg.target_tau > 0:
            tau = cfg.target_tau

            def tleaf(p2, t):
                return tau * p2 + (1.0 - tau) * t
        else:
            do_refresh = step % cfg.target_update_period == 0

            def tleaf(p2, t):
                return jnp.where(do_refresh, p2, t)

    def leaf(g, m, v, p, *rest):
        g = g * scale
        m2 = b1 * m.astype(jnp.float32) + (1.0 - b1) * g
        v2 = b2 * v + (1.0 - b2) * jnp.square(g)
        upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
        p2 = p - lr * upd
        if with_target:
            return m2.astype(mu_dtype), v2, p2, tleaf(p2, rest[0])
        return m2.astype(mu_dtype), v2, p2

    trees = (grads, adam_state.mu, adam_state.nu, params)
    if with_target:
        trees += (target_params,)
    out = jax.tree.map(leaf, *trees)
    treedef = jax.tree_util.tree_structure(grads)
    parts = [jax.tree_util.tree_unflatten(
        treedef, [t[i] for t in jax.tree_util.tree_leaves(
            out, is_leaf=lambda x: isinstance(x, tuple))])
        for i in range(4 if with_target else 3)]
    new_opt = rebuild(adam_state._replace(count=count, mu=parts[0],
                                          nu=parts[1]))
    return new_opt, parts[2], (parts[3] if with_target else None)


def refresh_target(cfg: TrainConfig, params: Any, target_params: Any,
                   step: jax.Array) -> Any:
    """θ⁻ update, shared by both learners: Polyak θ⁻ ← τθ + (1−τ)θ⁻ every
    step when ``target_tau`` > 0, else the hard copy every C steps
    ("every C pulls: θ⁻ ← θ", SURVEY §3.1 [M]) via lax.cond so the copy
    stays off the hot path on non-refresh steps."""
    if cfg.target_tau > 0:
        tau = cfg.target_tau
        return jax.tree.map(lambda p, t: tau * p + (1.0 - tau) * t,
                            params, target_params)
    return lax.cond(
        step % cfg.target_update_period == 0,
        lambda: params,
        lambda: target_params,
    )


# -- flat parameter/moment planes (op-count surgery, PERF.md §3) -----------
#
# The chained device-PER program's scan body used to pay the optimizer as
# per-leaf kernels: on a backend without multi-output fusion (CPU XLA — the
# ratchet's measurement platform) the "one fusion per leaf" fused update
# decomposes into ~5 scheduled fusions PER LEAF, plus a per-leaf stack
# concat feeding the stacked forward and a per-leaf gnorm partial — ~85 of
# the body's ~125 scheduled ops for a 12-leaf Nature net. The fix: carry
# θ/θ⁻ as ONE flat f32 plane and the Adam moments as two more, so clip,
# moments and the Adam update are plane-wide kernels independent of leaf
# count. Layout of the PT plane ([2N], N = total param count): per leaf
# the online and target blocks sit ADJACENT ([θ_i; θ⁻_i] at offset
# 2·off_i), so the stacked ``[2, shape]`` leaf view the vmapped forward
# wants is a contiguous slice — free, where a [P; T] split layout would
# pay a concat per leaf per step. The layout is STATIC STRUCTURE only:
# every block is addressed by a Python-int offset and size, so the step
# reads and writes it with static slices and one concatenate, which any
# backend lowers to contiguous copies. Never address it through index
# arrays: a ``jnp.take`` over the plane is one op in the CPU census and
# an element-by-element gather of 3.37M scalars on the TPU (two of them
# were 86 of the batch-32 step's 86.8 ms, PERF.md §6 PR 25).
# Tree↔plane conversion happens once per chunk at the scan boundary,
# amortized over ``chain`` grad steps.
# "Contiguous copies" holds for the slices, not for what the TPU compiler
# makes of ``plane[off:off + size].reshape(shape)``: it commutes the two
# into a slice of a RESHAPED PLANE, and for a leaf whose minor dimension
# is under the 128 lanes (the head's [512, 4] kernel) that is the whole
# plane laid out 4 wide, each (8, 128) tile padded 32x — 431 MB written
# to cut 8 KB out of the [2N] plane, 215 MB more a moment plane: three
# reshapes that were 0.319 of the batch-32 step's 0.808 ms (PERF.md §6
# PR 41). XLA:CPU never commutes them, so no CPU census saw it. Hence
# ``_plane_blocks``: every leaf's 1-D block is cut first, ONE
# ``optimization_barrier`` over the blocks of a plane holds the compiler
# from moving a reshape across the cut, and each block is reshaped alone.

class PlaneMeta(NamedTuple):
    """Static layout of the flat planes, derived from the param treedef:
    leaf ``i`` is ``[off_i, off_i + size_i)`` of an [N] plane and
    ``[2·off_i, 2·off_i + 2·size_i)`` (online block, then target block)
    of the [2N] PT plane. Python ints and shapes only — nothing here is
    an array, so nothing of it is baked into a program as a constant."""
    treedef: Any
    shapes: tuple
    sizes: tuple
    offsets: tuple
    n: int


def plane_meta(params: Any) -> PlaneMeta:
    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = tuple(leaf.shape for leaf in leaves)
    sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
    offsets = tuple(int(o) for o in np.cumsum((0,) + sizes[:-1]))
    return PlaneMeta(treedef, shapes, sizes, offsets, int(sum(sizes)))


@jax.named_scope("ddq.plane_pack")
def params_to_plane(meta: PlaneMeta, params: Any,
                    target_params: Any) -> jax.Array:
    """Interleave θ/θ⁻ into the [2N] PT plane (leaf blocks adjacent)."""
    blocks = []
    for p, t in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(target_params)):
        blocks.append(p.reshape(-1).astype(jnp.float32))
        blocks.append(t.reshape(-1).astype(jnp.float32))
    return jnp.concatenate(blocks)


@jax.named_scope("ddq.plane_pack")
def tree_to_plane(tree: Any) -> jax.Array:
    """Ravel-and-concat a tree into its [N] plane (moment planes keep
    their storage dtype so per-step round trips stay bitwise)."""
    return jnp.concatenate(
        [leaf.reshape(-1) for leaf in jax.tree_util.tree_leaves(tree)])


def plane_stacked_views(meta: PlaneMeta, pt: jax.Array) -> tuple:
    """The [2, shape] stacked leaf views of the PT plane — contiguous
    slices (the layout's whole point), fed to ``stacked_q_apply``."""
    return tuple(
        pt[2 * off:2 * off + 2 * size].reshape((2,) + shape)
        for off, size, shape in zip(meta.offsets, meta.sizes, meta.shapes))


def _plane_blocks(plane: jax.Array, bounds) -> tuple:
    """The 1-D blocks ``plane[lo:hi]``, cut BEFORE anything reshapes them:
    one barrier over them all, so each later reshape moves its own block
    and never the plane (the comment over ``PlaneMeta`` has the numbers)."""
    return lax.optimization_barrier(
        tuple(plane[lo:hi] for lo, hi in bounds))


@jax.named_scope("ddq.plane_unpack")
def plane_to_param_trees(meta: PlaneMeta, pt: jax.Array,
                         params: Any, target_params: Any) -> tuple:
    """Inverse of ``params_to_plane`` — dtypes restored per template."""
    halves = _plane_blocks(pt, [
        (2 * off + h * size, 2 * off + (h + 1) * size)
        for off, size in zip(meta.offsets, meta.sizes) for h in (0, 1)])
    new_p, new_t = [], []
    for i, (shape, tmpl) in enumerate(zip(
            meta.shapes, jax.tree_util.tree_leaves(params))):
        new_p.append(halves[2 * i].reshape(shape).astype(tmpl.dtype))
        new_t.append(halves[2 * i + 1].reshape(shape).astype(tmpl.dtype))
    return (jax.tree_util.tree_unflatten(meta.treedef, new_p),
            jax.tree_util.tree_unflatten(meta.treedef, new_t))


@jax.named_scope("ddq.plane_unpack")
def plane_to_tree(meta: PlaneMeta, plane: jax.Array,
                  template: Any) -> Any:
    """Slice an [N] plane back into ``template``'s tree structure."""
    blocks = _plane_blocks(plane, [
        (off, off + size) for off, size in zip(meta.offsets, meta.sizes)])
    leaves = [
        block.reshape(shape).astype(tmpl.dtype)
        for block, shape, tmpl in zip(
            blocks, meta.shapes, jax.tree_util.tree_leaves(template))]
    return jax.tree_util.tree_unflatten(meta.treedef, leaves)


@jax.named_scope("ddq.optimizer")
def fused_plane_adam_target_step(
    cfg: TrainConfig, meta: PlaneMeta, g: jax.Array, m: jax.Array,
    v: jax.Array, count: jax.Array, pt: jax.Array, step: jax.Array,
    gnorm: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``fused_adam_target_step`` on the flat planes: clip + Adam as
    plane-wide kernels on the [N] online layout, then the [2N] PT plane
    assembled from static per-leaf slices: leaf ``i``'s online block is
    ``θ_i − upd_i``, its target block the refresh rule applied to that
    fresh value — no index gather, no plane-sized constant. Per-element
    arithmetic is identical to the per-leaf version (only positions
    differ), so the hard refresh stays a bitwise select of the
    freshly-updated online value. ``g`` is the [N] online-layout gradient
    plane (already allreduced); ``step`` the already-incremented step.
    Returns (m2, v2, pt2, count2).
    """
    b1, b2 = ADAM_B1, ADAM_B2
    count2 = safe_increment(count)
    c = count2.astype(jnp.float32)
    bc1 = 1.0 - b1 ** c
    bc2 = 1.0 - b2 ** c
    scale = (jnp.minimum(1.0, cfg.grad_clip_norm
                         / jnp.maximum(gnorm, 1e-12))
             if cfg.grad_clip_norm > 0 else jnp.float32(1.0))
    g = g * scale
    m2 = b1 * m.astype(jnp.float32) + (1.0 - b1) * g
    v2 = b2 * v + (1.0 - b2) * jnp.square(g)
    # lr folded into the denominator: the final update must be a
    # SUBTRACT-OF-A-DIVISION, not subtract-of-a-multiply — a mul feeding
    # the sub is FMA-contractible, and LLVM contracts it in one unroll
    # context but not the other, breaking the chain=k ≡ k × chain=1
    # bitwise guarantee (measured: ~300 one-ulp params diffs per step)
    upd = (m2 / bc1) / ((jnp.sqrt(v2 / bc2) + cfg.adam_eps)
                        * np.float32(1.0 / cfg.lr))
    if cfg.target_tau > 0:
        # Polyak weights rounded to f32 first, 1 − τ formed in f32. The
        # lerp is FMA-contractible either way round; this addend order is
        # the one LLVM contracts like the [2N] weight-plane lerp it
        # replaced (pinned bitwise in tests/test_op_surgery.py)
        tau = np.float32(cfg.target_tau)
        keep = np.float32(1.0) - tau

        def target_block(p2, t):
            return keep * t + tau * p2
    else:
        refresh = step % cfg.target_update_period == 0

        def target_block(p2, t):
            return jnp.where(refresh, p2, t)

    blocks = []
    for off, size in zip(meta.offsets, meta.sizes):
        o2 = 2 * off
        p2 = pt[o2:o2 + size] - upd[off:off + size]
        blocks += [p2, target_block(p2, pt[o2 + size:o2 + 2 * size])]
    pt2 = jnp.concatenate(blocks)
    return m2.astype(jnp.dtype(cfg.adam_mu_dtype)), v2, pt2, count2


@jax.named_scope("ddq.loss")
def q_step_loss(cfg: TrainConfig, q: jax.Array, q_next_o: jax.Array | None,
                q_next_t: jax.Array, batch: dict[str, jax.Array],
                interpret: bool):
    """Bellman targets + (Pallas or XLA) weighted Huber — the loss tail
    shared by the tree-carry and plane-carry step cores, so the two paths
    can never drift numerically. ``interpret`` is the mesh's
    ``pallas_interpret`` verdict (read only when ``use_pallas_loss``).
    Returns (loss, |TD|)."""
    targets = bellman_targets(batch["reward"], batch["discount"],
                              q_next_t, q_next_o, cfg.double_dqn)
    if cfg.use_pallas_loss:
        from distributed_deep_q_tpu.ops.pallas_kernels import (
            fused_dqn_loss)
        return fused_dqn_loss(q, batch["action"],
                              lax.stop_gradient(targets),
                              batch["weight"], cfg.huber_delta, interpret)
    return dqn_loss(q, batch["action"], targets, batch["weight"],
                    cfg.huber_delta)


class Learner:
    """Owns the sharded train step for feed-forward Q-nets.

    ``apply_fn(params, obs) -> q`` is the Flax module apply; the sequence
    (R2D2) learner lives in ``parallel/sequence_learner.py``.
    """

    def __init__(
        self,
        apply_fn: Callable[[Any, jax.Array], jax.Array],
        cfg: TrainConfig,
        mesh: Mesh,
    ):
        self.apply_fn = apply_fn
        self.cfg = cfg
        self.mesh = mesh
        # static gauge ``train/unpack_planes``: 1 = the last fused step
        # built unpacks its pixel windows by byte planes, 0 = by a bitcast
        # to uint8, None = none built yet
        self.unpack_planes: int | None = None
        self._interpret = pallas_interpret(mesh)
        self.opt = make_optimizer(cfg)
        self._replicated = NamedSharding(mesh, P())
        self._batch_sharding = NamedSharding(mesh, P(AXIS_DP))
        self._train_step = self._build_train_step()
        # fused device-PER steps, keyed on the replay's static geometry
        self._device_per_steps: dict[tuple, Any] = {}

    # -- state -------------------------------------------------------------

    def init_state(self, params: Any) -> TrainState:
        """Build the TrainState on the mesh. With ``model=1`` (every
        current config) everything replicates — the historical, bitwise
        path. A real model axis places each leaf by the declarative
        partition rules instead (``parallel.mesh.DEFAULT_PARTITION_RULES``,
        ISSUE 10): optimizer moments inherit their parameter's spec
        because the rules match tree paths, not leaf names."""
        state = TrainState(
            params=params,
            target_params=jax.tree.map(jnp.copy, params),
            opt_state=self.opt.init(params),
            step=jnp.zeros((), jnp.int32),
        )
        if self.mesh.shape[AXIS_MODEL] <= 1:
            return put_replicated(state, self._replicated)
        return put_replicated(state, tree_shardings(self.mesh, state))

    # -- train step --------------------------------------------------------

    def _step_core(self, state: TrainState, batch: dict[str, jax.Array]):
        """Loss + allreduce + optimizer + target refresh — shared by the
        host-batch step and the fused step's tree body (``tree_train_fn``).
        ``batch`` holds per-device local arrays with ``obs``/``next_obs``
        already composed."""
        cfg, apply_fn, opt = self.cfg, self.apply_fn, self.opt
        # static at trace time: per-shard batch decides the auto gate
        use_stacked = (cfg.stack_forwards == "on"
                       or (cfg.stack_forwards == "auto"
                           and batch["obs"].shape[0] <= 128))

        def loss_fn(params):
            if use_stacked:
                # ALL the step's forwards — θ(s), θ(s') when double, and
                # θ⁻(s') — as one stacked-weight application: the conv
                # batching rule lowers the whole thing to a single conv
                # chain (models/qnet.py, stacked_q_forwards)
                q, q_next_o, q_next_t = stacked_q_forwards(
                    apply_fn, params, state.target_params,
                    batch["obs"], batch["next_obs"], cfg.double_dqn)
            else:
                q = apply_fn(params, batch["obs"])
                q_next_o = (apply_fn(params, batch["next_obs"])
                            if cfg.double_dqn else None)
                # action selection must not backprop into the online net
                if q_next_o is not None:
                    q_next_o = lax.stop_gradient(q_next_o)
                q_next_t = apply_fn(state.target_params,
                                    batch["next_obs"])
            loss, td_abs = q_step_loss(cfg, q, q_next_o, q_next_t, batch,
                                       self._interpret)
            return loss, (td_abs, q)

        (loss, (td_abs, q)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)

        # THE collective: gradient allreduce over ICI — replaces the
        # reference's PS push/pull (north star [M]).
        grads = lax.pmean(grads, AXIS_DP)
        loss = lax.pmean(loss, AXIS_DP)
        q_mean = lax.pmean(jnp.mean(q), AXIS_DP)

        gnorm = optax.global_norm(grads)
        step = state.step + 1
        if cfg.optimizer == "adam":
            # clip AND target refresh folded into the one-pass fused
            # update (op-count-bound step — see fused_adam_target_step)
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
        return new_state, metrics, td_abs

    def _build_train_step(self):
        def step_fn(state: TrainState, batch: dict[str, jax.Array]):
            return self._step_core(state, batch)

        sharded = shard_map(
            step_fn,
            mesh=self.mesh,
            in_specs=(P(), P(AXIS_DP)),
            out_specs=(P(), P(), P(AXIS_DP)),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=0)

    def _build_device_per_step(self, spec: tuple, chain: int,
                               donate: bool = True):
        """Fused prioritized step (replay/device_per.py): per shard —
        validity mask → inverse-CDF prioritized draw → on-device stack +
        n-step composition → DQN step → same-step priority scatter. The
        host ships per-slot cursors/sizes, β, and sampling keys; NOTHING
        is read back (the per-sample |TD| never leaves the device).

        ``chain`` > 1 amortizes dispatch: the SAMPLE program draws all
        ``chain`` batches against chunk-start priorities in ONE
        straight-line vectorized block (no scan — per-step draws have no
        carry, and scanned bodies re-touch capacity-sized arrays per
        iteration), while the TRAIN program ``lax.scan``s the ``chain``
        optimizer steps and priority scatters strictly in order.
        Within-chunk priority staleness ≤ chain steps — the same bound
        the host path's ``DelayedPriorityWriteback(depth=8)`` accepts.
        Across chunks everything is fresh.

        Data plane (round 5): the sample program composes metadata from
        the per-row ``build_meta_pack`` (two row gathers per sample) and
        copies each sample's combined obs+next-obs pixel window with the
        Pallas row-DMA kernel (``ops/ring_gather.py`` — 3 ms vs 44 ms
        for the tiled XLA gathers it replaced at the 1M-ring shape). The
        windows cross to the train program as
        ``[chain, per_shard, window, rowp // 128, 128]``
        (``ring_gather.tile_rows``): a row's words fill the two tiled
        dims, so the view is a bitcast of what the kernel wrote. With the
        window's 7 (or 5) rows in the sublanes, ``[..., window, rowp]``,
        the chip pads each window to 8 and copies the whole chunk twice
        (PERF.md §6, PR 35). The train program scans over the chunk,
        flattens a step's rows back (``flat_rows``), takes obs/next-obs
        out of the windows under the scope ``ddq.unpack``, applies the
        validity masks, and runs the DQN step. Where the frame width is a multiple of 4 and a shard's
        batch fills the lanes the packed int32 words are split into their
        four BYTE PLANES by shift and mask and the planes laid side by
        side (``window_to_obs``); anything else is bitcast to uint8 and
        restacked. Both hand the model the same
        ``u8 [B, H, W, stack]``; ``self.unpack_planes`` says which was
        built (gauge ``train/unpack_planes``). Keys stay
        host-generated (a fold_in-keyed program executed the ring gather
        ~200× slower — measured minimal pair, r3)."""
        (slot_cap, slot_pad, rowb, row_len, stack, n_step, gamma,
         frame_shape, per_shard, alpha, eps, num_shards, interpret) = spec
        from distributed_deep_q_tpu.ops.ring_gather import (
            flat_rows, gather_windows, tile_rows)
        from distributed_deep_q_tpu.replay.device_per import (
            build_meta_pack, fused_sample_draw_packed, fused_sample_prep,
            scatter_priorities, stack_rows_to_obs, window_to_obs)

        S = P(AXIS_DP)
        SK = P(None, AXIS_DP)   # [chain, B]-stacked outputs, batch-sharded
        SK3 = P(None, AXIS_DP, None)
        SWIN = P(None, AXIS_DP, None, None, None)
        window = stack + n_step
        n_win = chain * per_shard

        def sample_fn(keys, frames, action, reward, done, boundary, prio,
                      cursors, sizes, betas):
            with jax.named_scope("ddq.sample"):
                shard_rows = {
                    "action": action, "reward": reward,
                    "done": done, "boundary": boundary, "prio": prio,
                }
                pm, cdf, mass, n_glob = fused_sample_prep(
                    shard_rows, cursors, sizes, slot_cap, stack, n_step)
                pack = build_meta_pack(action, reward, done, boundary,
                                       slot_cap, stack, n_step, gamma)
                # keys arrives [1, chain, 2] per shard (sharded over 0)
                metas, ws, idxs = fused_sample_draw_packed(
                    keys[0], pack, pm, cdf, mass, n_glob, per_shard,
                    slot_cap, slot_pad, stack, n_step, betas, num_shards)
            # outside any scope: XLA names a Mosaic custom-call after the
            # innermost scope around it, and the benchmark finds this
            # kernel as ``%sample_fn.N`` (PERF.md §7)
            win = gather_windows(ws.reshape(-1), frames, n=n_win,
                                 w=window, rowb=rowb, interpret=interpret)
            # the view that is a bitcast of the kernel's tiles, whatever
            # the batch and the window are (ring_gather.tile_rows)
            return metas, tile_rows(win, chain, per_shard, window), idxs

        meta_spec = {"action": SK, "reward": SK, "discount": SK,
                     "weight": SK, "ovalid": SK3, "nvalid": SK3}
        sample = jax.jit(shard_map(
            sample_fn, mesh=self.mesh,
            in_specs=(S, S, S, S, S, S, S, S, S, P()),
            out_specs=(meta_spec, SWIN, SK),
            check_vma=False))

        cfg = self.cfg
        # static gates (spec's per_shard is the in-shard batch, the same
        # quantity _step_core's auto gate reads off the traced batch)
        use_stacked = (cfg.stack_forwards == "on"
                       or (cfg.stack_forwards == "auto"
                           and per_shard <= 128))
        # the flat plane-carry layout concatenates every leaf into one
        # replicated f32 plane, which is incompatible with per-leaf
        # model-axis partition rules (parallel.mesh) — a real model axis
        # keeps the per-leaf tree path where rule shardings apply
        use_plane = (use_stacked and cfg.optimizer == "adam"
                     and self.mesh.shape[AXIS_MODEL] <= 1)

        # a frame row packs four pixels to a word, so a width that is a
        # multiple of 4 starts every image row on a word: the bytes then
        # come out of the words as four planes, no bitcast (window_to_obs).
        # It pays where the compiler keeps the batch in the 128 lanes —
        # from 128 rows a shard: the planes then stack without moving a
        # pixel (b512: 0.51 -> 0.16 ms a step). Below it lays the window
        # out pixel-minor and has to interleave them, slower than the
        # bitcast (b32: +0.02 ms a step; PERF.md §6, PR 32)
        by_planes = frame_shape[1] % 4 == 0 and per_shard >= LANES
        self.unpack_planes = int(by_planes)

        def unpack_batch(batch, w):
            batch = dict(batch)
            ovalid = batch.pop("ovalid")
            nvalid = batch.pop("nvalid")
            with jax.named_scope("ddq.unpack"):
                w = flat_rows(w)    # one step's [per_shard, window, rowp]
                if by_planes:
                    obs = window_to_obs(w, 0, ovalid, row_len, frame_shape)
                    nobs = window_to_obs(w, n_step, nvalid, row_len,
                                         frame_shape)
                else:
                    # int32 → pixel bytes (little-endian round trip with
                    # the host's uint8.view(int32), verified both
                    # platforms), drop the DMA row padding
                    pix = lax.bitcast_convert_type(w, jnp.uint8)
                    pix = pix.reshape(
                        w.shape[:2] + (-1,))[:, :, :row_len]
                    obs = stack_rows_to_obs(
                        pix[:, :stack] * ovalid[..., None], frame_shape)
                    nobs = stack_rows_to_obs(
                        pix[:, n_step:n_step + stack] * nvalid[..., None],
                        frame_shape)
            batch["obs"], batch["next_obs"] = obs, nobs
            return batch

        def tree_train_fn(state: TrainState, metas, win, idxs, prio, maxp):
            def body(carry, xs):
                state, prio, maxp = carry
                batch, w, idx = xs
                batch = unpack_batch(batch, w)
                state, metrics, td_abs = self._step_core(state, batch)
                prio, maxp = scatter_priorities(prio, maxp, idx, td_abs,
                                                alpha, eps)
                return (state, prio, maxp), metrics

            (state, prio, maxp), metrics = lax.scan(
                body, (state, prio, maxp), (metas, win, idxs))
            return state, prio, maxp, metrics

        def plane_train_fn(state: TrainState, metas, win, idxs, prio,
                           maxp):
            # The op-count-surgery body (PERF.md §3): θ/θ⁻ ride the scan
            # carry as ONE flat plane (moments as two more), so the whole
            # optimizer + target refresh is a fixed handful of plane-wide
            # kernels instead of ~5 scheduled fusions per leaf, and every
            # stacked leaf view feeding the vmapped forward is a free
            # contiguous slice. Tree↔plane conversion sits OUTSIDE the
            # scan, amortized over the chain. Per-step math is the same
            # fused clip+Adam+refresh (see fused_plane_adam_target_step);
            # the one deliberate deviation is the gradient norm, computed
            # as a single flat reduce over the g-plane rather than
            # optax.global_norm's per-leaf partial sums — same value to
            # f32 ulp, one kernel instead of thirteen.
            meta = plane_meta(state.params)
            adam_state, rebuild = _locate_adam_state(state.opt_state)
            pt = params_to_plane(meta, state.params, state.target_params)
            m = tree_to_plane(adam_state.mu)
            v = tree_to_plane(adam_state.nu)

            def body(carry, xs):
                if cfg.learn_metrics:
                    pt, m, v, cnt, step, prio, maxp, lmp = carry
                else:
                    pt, m, v, cnt, step, prio, maxp = carry
                batch, w, idx = xs
                batch = unpack_batch(batch, w)
                step2 = step + 1

                def loss_fn(views):
                    stacked = jax.tree_util.tree_unflatten(
                        meta.treedef, list(views))
                    q, q_next_o, q_next_t = stacked_q_apply(
                        self.apply_fn, stacked, batch["obs"],
                        batch["next_obs"], cfg.double_dqn)
                    loss, td_abs = q_step_loss(cfg, q, q_next_o,
                                               q_next_t, batch, interpret)
                    return loss, (td_abs, q)

                (loss, (td_abs, q)), gv = jax.value_and_grad(
                    loss_fn, has_aux=True)(plane_stacked_views(meta, pt))
                # online halves only — the target halves carry zero
                # cotangents (targets are stop-gradded in the loss)
                with jax.named_scope("ddq.grad_plane"):
                    g = jnp.concatenate([x[0].reshape(-1) for x in gv])
                g = lax.pmean(g, AXIS_DP)
                loss = lax.pmean(loss, AXIS_DP)
                q_mean = lax.pmean(jnp.mean(q), AXIS_DP)
                gnorm = jnp.sqrt(jnp.sum(jnp.square(g)))
                m, v, pt, cnt = fused_plane_adam_target_step(
                    cfg, meta, g, m, v, cnt, pt, step2, gnorm)
                prio, maxp = scatter_priorities(prio, maxp, idx, td_abs,
                                                alpha, eps)
                metrics = {"loss": loss, "q_mean": q_mean,
                           "grad_norm": gnorm}
                if cfg.learn_metrics:
                    # learning-dynamics plane (learning.py): pure-jnp
                    # accumulation into the carry — the training math
                    # above is untouched, so the gate-off path stays
                    # bitwise identical (test_learning_metrics)
                    lmp = learning.lm_update(
                        lmp, cfg=cfg, td_abs=td_abs,
                        weight=batch["weight"], loss=loss, q=q,
                        q_mean=q_mean, gnorm=gnorm, step=step2,
                        alpha=alpha, eps=eps)
                    return (pt, m, v, cnt, step2, prio, maxp, lmp), \
                        metrics
                return (pt, m, v, cnt, step2, prio, maxp), metrics

            carry0 = (pt, m, v, adam_state.count, state.step, prio, maxp)
            if cfg.learn_metrics:
                carry0 = carry0 + (learning.lm_init(),)
                (pt, m, v, cnt, step, prio, maxp, lmp), metrics = \
                    lax.scan(body, carry0, (metas, win, idxs))
                metrics = dict(metrics)
                # ONE cross-shard reduction per dispatch, outside the
                # scan; replicated, so the trailing P() out-spec covers
                # the new dict leaf unchanged
                metrics["learn_plane"] = learning.lm_finalize(
                    lmp, AXIS_DP)
            else:
                (pt, m, v, cnt, step, prio, maxp), metrics = lax.scan(
                    body, carry0, (metas, win, idxs))
            params, target_params = plane_to_param_trees(
                meta, pt, state.params, state.target_params)
            new_opt = rebuild(adam_state._replace(
                count=cnt, mu=plane_to_tree(meta, m, adam_state.mu),
                nu=plane_to_tree(meta, v, adam_state.nu)))
            new_state = TrainState(params, target_params, new_opt, step)
            return new_state, prio, maxp, metrics

        # the decorator keeps the function's name, so the program is still
        # ``jit_tree_train_fn`` / ``jit_plane_train_fn`` in a trace
        train_fn = jax.named_scope("ddq.train")(
            plane_train_fn if use_plane else tree_train_fn)

        # donate every input that aliases an updated output: the state
        # tree (0) and the priority plane/max (4, 5) are rewritten each
        # call, so XLA writes the new values in place instead of
        # scheduling defensive copies of the (large) param/priority
        # buffers. metas/win/idxs are consumed exactly once but have no
        # same-shaped output to alias, so donating them buys nothing.
        train = jax.jit(shard_map(
            train_fn, mesh=self.mesh,
            in_specs=(P(), meta_spec, SWIN, SK, S, P()),
            out_specs=(P(), S, P(), P()),
            check_vma=False),
            donate_argnums=(0, 4, 5) if donate else ())
        return sample, train

    def device_per_programs(self, spec: tuple, chain: int):
        """The jitted (sample, train) pair for ``(spec, chain)``, built on
        first use — by the train loop or by a census taken before it."""
        key = (spec, chain)
        if key not in self._device_per_steps:
            self._device_per_steps[key] = \
                self._build_device_per_step(spec, chain)
        return self._device_per_steps[key]

    def train_steps_device_per(self, state: TrainState, rows, cursors,
                               sizes, betas: np.ndarray, keys: np.ndarray,
                               spec: tuple):
        """``len(betas)`` fused sample+train+priority-update steps on
        device PER in ONE two-program dispatch (zero reads back). ``keys``
        is host-generated ``[D, chain, 2]`` uint32 (the caller owns key
        derivation — see ``Solver.train_steps_device_per``). Returns
        (state, new_prio, new_maxp, metrics with a leading [chain] axis).
        """
        sample, train = self.device_per_programs(spec, len(betas))

        def feed(x, dtype=None):
            # host numpy feeds pass through asarray; multi-host global
            # jax arrays (assembled by the solver) must not be copied
            return x if isinstance(x, jax.Array) else np.asarray(x, dtype)

        # spans time the host-side DISPATCH of the two async device
        # programs, not device execution (no block_until_ready here — the
        # zero-readback contract holds); both calls stay outside jit so
        # the tracer's host side effects never enter a traced function
        with tracing.span("learner_feed"):
            cursors, sizes = feed(cursors), feed(sizes)
            betas = feed(betas, np.float32)
        with tracing.span("sample"):
            metas, win, idx = sample(keys, rows.frames, rows.action,
                                     rows.reward, rows.done, rows.boundary,
                                     rows.prio, cursors, sizes, betas)
        with tracing.span("train_step"):
            return train(state, metas, win, idx, rows.prio, rows.maxp)

    def train_step(self, state: TrainState, batch: dict[str, Any]):
        """One synchronous DP gradient step.

        Single-process: ``batch`` arrays have global leading dim B
        (divisible by mesh dp size). Multi-host (multi-controller JAX,
        SURVEY §5.8): each process passes its LOCAL B/process_count rows —
        its own replay shard's sample — and the global array is assembled
        here. Returns (new_state, metrics dict of replicated scalars,
        |TD| [B] batch-sharded, for PER priority updates).
        """
        with tracing.span("train_step"):  # host dispatch, outside the jit
            return self._train_step(state, global_batch(
                self._batch_sharding, batch))
