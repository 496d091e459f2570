"""Numerical pins for the op-count surgery (PERF.md §3/§4).

Every rewritten program is pinned against the program it replaced:

- the stacked-weight triple Q-forward vs three separate module applies
- the donated fused step vs the same step compiled without donation
  (donation is an aliasing contract — it must never change values)
- the plane-carry fused chain body vs the tree-carry body, and its
  static-slice optimizer step vs the index-gather step it replaced
- the time-batched R2D2 torso (burn-in included) vs the module-apply
  in-scan reference, at the CPU bench shapes

Bitwise where the two programs are the same math in the same order
(donation); tight-atol where a rewrite legitimately reorders conv/reduce
lanes (stacked batching changes the batch shape XLA reduces over).
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_deep_q_tpu.config import (
    Config, NetConfig, ReplayConfig, TrainConfig)
from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay


def _filled_dev_replay(solver, cfg, seed=0, n=300):
    dev = DevicePERFrameReplay(cfg.replay, solver.mesh, (36, 36), stack=4,
                               gamma=0.99, seed=seed, write_chunk=16)
    rng = np.random.default_rng(seed)
    for i in range(n):
        dev.add(rng.integers(0, 255, (36, 36), dtype=np.uint8),
                int(rng.integers(4)), float(rng.standard_normal()),
                done=(i % 9 == 8))
    dev.flush()
    return dev


def _transition_cfg(stack_forwards="auto", alpha=0.0):
    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 2
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36))
    cfg.train.stack_forwards = stack_forwards
    cfg.replay = ReplayConfig(capacity=512, batch_size=16, n_step=2,
                              prioritized=True, priority_alpha=alpha,
                              device_per=True, write_chunk=16,
                              fused_chain=2)
    return cfg


@pytest.mark.parametrize("double", [True, False])
def test_stacked_triple_forward_matches_separate_applies(double):
    """``stacked_q_forwards`` == the three module applies it replaces.
    The stacked path batches both nets (and both obs sets) through one
    conv stack, which changes the shapes XLA reduces over — tight atol,
    not bitwise."""
    from distributed_deep_q_tpu.models.qnet import (
        build_qnet, init_params, stacked_q_forwards)

    net = NetConfig(kind="nature_cnn", num_actions=4, frame_shape=(36, 36),
                    dueling=True)
    module = build_qnet(net)
    params = init_params(module, net, 0)
    target = init_params(module, net, 1)

    def apply_fn(p, o):
        return module.apply({"params": p}, o)

    rng = np.random.default_rng(2)
    obs = jnp.asarray(rng.integers(0, 255, (16, 36, 36, 4), np.uint8))
    nobs = jnp.asarray(rng.integers(0, 255, (16, 36, 36, 4), np.uint8))

    q, q_no, q_nt = stacked_q_forwards(apply_fn, params, target, obs,
                                       nobs, double)
    ref_q = apply_fn(params, obs)
    ref_nt = apply_fn(target, nobs)
    np.testing.assert_allclose(q, ref_q, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(q_nt, ref_nt, rtol=1e-5, atol=1e-5)
    if double:
        ref_no = apply_fn(params, nobs)
        np.testing.assert_allclose(q_no, ref_no, rtol=1e-5, atol=1e-5)
    else:
        assert q_no is None


def test_donated_step_matches_undonated():
    """Donation is a buffer-aliasing contract, not a program change: the
    fused chained step must produce bit-identical states and priorities
    with donation disabled."""
    from distributed_deep_q_tpu.solver import Solver

    def build(donate):
        cfg = _transition_cfg()
        solver = Solver(cfg)
        replay = _filled_dev_replay(solver, cfg)
        spec = (replay.slot_cap, replay.slot_pad, replay.rowb,
                replay._row_len, replay.stack, replay.n_step, replay.gamma,
                tuple(replay.frame_shape),
                cfg.replay.batch_size // replay.num_shards,
                float(cfg.replay.priority_alpha),
                float(cfg.replay.priority_eps),
                replay.num_shards, replay._interpret)
        solver.learner._device_per_steps[(spec, 2)] = \
            solver.learner._build_device_per_step(spec, 2, donate=donate)
        return solver, replay

    sa, da = build(donate=True)
    sb, db = build(donate=False)
    for _ in range(2):
        sa.train_steps_device_per(da, chain=2)
        sb.train_steps_device_per(db, chain=2)
    jax.block_until_ready(sa.state.params)
    jax.block_until_ready(sb.state.params)
    for xa, xb in zip(jax.tree.leaves(sa.state), jax.tree.leaves(sb.state)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    np.testing.assert_array_equal(np.asarray(da.dstate.prio),
                                  np.asarray(db.dstate.prio))


def test_plane_body_matches_tree_body():
    """The plane-carry scan body (stacked forward + flat fused Adam +
    in-plane target refresh) vs the tree-carry body it replaced. α=0
    keeps sampling independent of the ulp-level priority differences the
    reordered reductions introduce; the states then agree to tight atol
    (flat vs per-leaf grad-norm reduction, lr folded into the Adam
    denominator — both sub-ulp per step)."""
    from distributed_deep_q_tpu.solver import Solver

    def build(stack_forwards):
        cfg = _transition_cfg(stack_forwards=stack_forwards)
        solver = Solver(cfg)
        return solver, _filled_dev_replay(solver, cfg)

    sa, da = build("on")    # plane body
    sb, db = build("off")   # tree body (reference)
    for _ in range(2):
        sa.train_steps_device_per(da, chain=2)
        sb.train_steps_device_per(db, chain=2)
    jax.block_until_ready(sa.state.params)
    jax.block_until_ready(sb.state.params)
    leaves_a = jax.tree.leaves(sa.state)
    leaves_b = jax.tree.leaves(sb.state)
    assert len(leaves_a) == len(leaves_b)
    for xa, xb in zip(leaves_a, leaves_b):
        np.testing.assert_allclose(np.asarray(xa, np.float32),
                                   np.asarray(xb, np.float32),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(da.dstate.prio),
                               np.asarray(db.dstate.prio),
                               rtol=1e-4, atol=1e-5)


def _raw_bits(x):
    """An array's storage, so -0.0 and NaN payloads count too."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.parametrize("mu_dtype", ["float32", "bfloat16"])
def test_plane_tree_conversions_return_their_inputs_bitwise(mu_dtype):
    """``plane_to_param_trees(params_to_plane(θ, θ⁻))`` and
    ``plane_to_tree(tree_to_plane(m))`` are the identity, bit for bit and
    dtype for dtype, on the Nature net's ten leaves at 84x84 with the
    [512, 4] head (the leaf whose reshape the TPU compiler used to carry
    across its slice, PERF.md §6 PR 41) and Adam's first moment in its
    storage dtype: the conversions cut, hold and reshape — they move
    bytes and compute nothing."""
    from distributed_deep_q_tpu.models.qnet import build_qnet, init_params
    from distributed_deep_q_tpu.parallel.learner import (
        params_to_plane, plane_meta, plane_to_param_trees, plane_to_tree,
        tree_to_plane)

    net = NetConfig(kind="nature_cnn", num_actions=4)
    module = build_qnet(net)
    params = init_params(module, net, 0)
    target = init_params(module, net, 1)
    meta = plane_meta(params)
    assert len(meta.shapes) == 10 and (512, 4) in meta.shapes
    assert meta.n == 1_686_180
    rng = np.random.default_rng(41)

    def moment(x):
        m = jnp.asarray(rng.standard_normal(x.shape) * 1e-3,
                        jnp.dtype(mu_dtype))
        # bits no arithmetic would hand back: -0.0 first, a NaN last
        return m.reshape(-1).at[0].set(-0.0).at[-1].set(jnp.nan) \
            .reshape(x.shape)

    mu = jax.tree.map(moment, params)

    @jax.jit
    def round_trip(params, target, mu):
        pt = params_to_plane(meta, params, target)
        return (plane_to_param_trees(meta, pt, params, target),
                plane_to_tree(meta, tree_to_plane(mu), mu))

    (p2, t2), mu2 = round_trip(params, target, mu)
    for want, got in ((params, p2), (target, t2), (mu, mu2)):
        assert jax.tree.structure(want) == jax.tree.structure(got)
        for x, y in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            assert x.dtype == y.dtype and x.shape == y.shape
            np.testing.assert_array_equal(_raw_bits(x), _raw_bits(y))


def _gather_plane_step(cfg, meta, g, m, v, count, pt, step, gnorm):
    """The formulation ``fused_plane_adam_target_step`` replaced (PR 4 to
    PR 24), kept here as its reference: [2N] position maps baked in as
    constants and two ``jnp.take`` gathers over the plane. Same Adam
    arithmetic, operand for operand."""
    from distributed_deep_q_tpu.parallel.learner import (
        ADAM_B1, ADAM_B2, safe_increment)

    upd_map = np.empty(2 * meta.n, np.int32)
    src_map = np.empty(2 * meta.n, np.int32)
    onl = np.zeros(2 * meta.n, bool)
    for off, size in zip(meta.offsets, meta.sizes):
        o2 = 2 * off
        upd_map[o2:o2 + size] = upd_map[o2 + size:o2 + 2 * size] = \
            np.arange(off, off + size, dtype=np.int32)
        src_map[o2:o2 + size] = src_map[o2 + size:o2 + 2 * size] = \
            np.arange(o2, o2 + size, dtype=np.int32)
        onl[o2:o2 + size] = True
    count2 = safe_increment(count)
    c = count2.astype(jnp.float32)
    bc1, bc2 = 1.0 - ADAM_B1 ** c, 1.0 - ADAM_B2 ** c
    g = g * jnp.minimum(1.0, cfg.grad_clip_norm / jnp.maximum(gnorm, 1e-12))
    m2 = ADAM_B1 * m.astype(jnp.float32) + (1.0 - ADAM_B1) * g
    v2 = ADAM_B2 * v + (1.0 - ADAM_B2) * jnp.square(g)
    upd = (m2 / bc1) / ((jnp.sqrt(v2 / bc2) + cfg.adam_eps)
                        * np.float32(1.0 / cfg.lr))
    p2t = jnp.take(pt, src_map) - jnp.take(upd, upd_map)
    if cfg.target_tau > 0:
        w = jnp.asarray(np.where(onl, 1.0, cfg.target_tau), jnp.float32)
        pt2 = w * p2t + (1.0 - w) * pt
    else:
        pt2 = jnp.where(
            jnp.asarray(onl) | (step % cfg.target_update_period == 0),
            p2t, pt)
    return m2.astype(jnp.dtype(cfg.adam_mu_dtype)), v2, pt2, count2


@pytest.mark.parametrize("target_tau,period,step", [
    (0.0, 4, 8),      # hard refresh, on a refresh step
    (0.0, 4, 7),      # hard refresh, off a refresh step
    (0.005, 4, 7),    # Polyak lerp every step
], ids=["hard-refresh-step", "hard-off-step", "polyak"])
def test_plane_step_static_slices_match_gather_reference(
        target_tau, period, step):
    """The static-slice plane step == the index-gather formulation it
    replaced, BITWISE on every output: the rewrite moves the same values
    by contiguous copies instead of scalar fetches and touches no
    arithmetic. Leaves of unequal, tile-unaligned sizes."""
    from distributed_deep_q_tpu.parallel.learner import (
        fused_plane_adam_target_step, params_to_plane, plane_meta,
        tree_to_plane)

    rng = np.random.default_rng(7)

    def tree(scale):
        return {"a": {"kernel": rng.standard_normal((5, 7, 3)) * scale,
                      "bias": rng.standard_normal((3,)) * scale},
                "b": {"kernel": rng.standard_normal((129, 11)) * scale,
                      "bias": rng.standard_normal((11,)) * scale},
                "c": rng.standard_normal((1,)) * scale}

    as_f32 = lambda t: jax.tree.map(
        lambda x: jnp.asarray(x, jnp.float32), t)
    params, target = as_f32(tree(1.0)), as_f32(tree(1.0))
    meta = plane_meta(params)
    assert len(set(meta.sizes)) >= 3 and len(meta.sizes) == 5
    cfg = TrainConfig(target_tau=target_tau, target_update_period=period,
                      grad_clip_norm=1.0, lr=6.25e-5, adam_eps=1.5e-4)
    g = tree_to_plane(as_f32(tree(0.5)))
    args = (g, tree_to_plane(as_f32(tree(0.1))),
            jnp.square(tree_to_plane(as_f32(tree(0.1)))),
            jnp.asarray(41, jnp.int32),
            params_to_plane(meta, params, target),
            jnp.asarray(step, jnp.int32), jnp.sqrt(jnp.sum(jnp.square(g))))
    got = jax.jit(
        lambda *a: fused_plane_adam_target_step(cfg, meta, *a))(*args)
    want = jax.jit(lambda *a: _gather_plane_step(cfg, meta, *a))(*args)
    for name, x, y in zip(("m2", "v2", "pt2", "count2"), got, want):
        assert x.dtype == y.dtype and x.shape == y.shape, name
        # raw bits: -0.0 and NaN payloads count too
        np.testing.assert_array_equal(
            np.asarray(x).view(np.uint32), np.asarray(y).view(np.uint32),
            err_msg=name)
    # the refresh rule did what the case says, not merely the same thing
    pt, pt2 = np.asarray(args[4]), np.asarray(got[2])
    for off, size in zip(meta.offsets, meta.sizes):
        onl, tgt = pt2[2 * off:2 * off + size], \
            pt2[2 * off + size:2 * off + 2 * size]
        assert not np.array_equal(onl, pt[2 * off:2 * off + size])
        old_tgt = pt[2 * off + size:2 * off + 2 * size]
        if target_tau > 0:
            assert not np.array_equal(tgt, old_tgt)
            assert not np.array_equal(tgt, onl)
        elif step % period == 0:
            np.testing.assert_array_equal(tgt, onl)
        else:
            np.testing.assert_array_equal(tgt, old_tgt)


def _plane_index_ops(text, n):
    """(gathers with an operand or result of >= n elements, s32 constants
    of >= 2n elements) in a compiled program's HLO text."""
    def elems(shape):
        return int(np.prod([int(x) for x in shape.split(",") if x] or [1]))

    gathers = [
        line.strip()[:160] for line in text.splitlines()
        if re.search(r"= \S+ gather\(", line)
        and any(elems(s) >= n
                for s in re.findall(r"[a-z]\w*\[([\d,]*)\]", line))]
    constants = [
        line.strip()[:160] for line in text.splitlines()
        if (m := re.search(r"= s32\[([\d,]*)\]\S* constant\(", line))
        and elems(m.group(1)) >= 2 * n]
    return gathers, constants


def test_plane_train_program_has_no_plane_sized_gather():
    """The compiled plane train program holds no gather over the
    parameter plane and no [2N] int32 constant: the position maps cannot
    come back through a refactor that is only ever timed on CPU, where
    two gathers are just two ops (on the TPU they were 86 of the batch-32
    step's 86.8 ms, PERF.md §6 PR 25)."""
    from distributed_deep_q_tpu.parallel.learner import plane_meta
    from distributed_deep_q_tpu.profiling import compile_fused_train
    from distributed_deep_q_tpu.solver import Solver

    cfg = _transition_cfg(stack_forwards="on")
    solver = Solver(cfg)
    meta = plane_meta(solver.state.params)
    text = compile_fused_train(
        solver, _filled_dev_replay(solver, cfg), 2).as_text()
    assert "plane_train_fn" in text     # the plane body, not the tree's
    assert _plane_index_ops(text, meta.n) == ([], [])
    # ... and the census does see the formulation it guards against
    plane = jnp.zeros((meta.n,), jnp.float32)
    ref = jax.jit(lambda g, m, v, pt: _gather_plane_step(
        cfg.train, meta, g, m, v, jnp.int32(0), pt, jnp.int32(1),
        jnp.float32(1.0))).lower(
            plane, plane, plane, jnp.zeros((2 * meta.n,), jnp.float32))
    gathers, constants = _plane_index_ops(ref.compile().as_text(), meta.n)
    assert gathers and constants


def _r2d2_solver(stack_forwards):
    from distributed_deep_q_tpu.parallel.sequence_learner import (
        SequenceSolver)

    hw, stack, lstm = (36, 36), 4, 16
    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.net = NetConfig(kind="r2d2", num_actions=6, frame_shape=hw,
                        stack=stack, lstm_size=lstm,
                        compute_dtype="float32")
    cfg.replay = ReplayConfig(batch_size=8, sequence_length=16, burn_in=4)
    cfg.train.stack_forwards = stack_forwards
    return SequenceSolver(cfg, obs_dim=int(np.prod(hw)))


def test_r2d2_time_batched_torso_matches_in_scan_reference():
    """The time-batched stacked torso path (one conv pass over all
    [B·(T+1)] frames, burn-in included, both nets) vs the module-apply
    reference (four conv chains) — same batch, same init, one full train
    step each, at the CPU bench shapes. Pins loss, per-sequence
    priorities, and the post-step parameters."""
    b, seq, burn, lstm = 8, 16, 4, 16
    T = seq + burn
    sa = _r2d2_solver("on")
    sb = _r2d2_solver("off")

    rng = np.random.default_rng(5)
    mask = np.ones((b, T), np.float32)
    mask[0, -6:] = 0.0          # one truncated sequence
    discount = np.full((b, T), 0.99, np.float32)
    discount[1, 7] = 0.0        # one episode cut inside the window
    batch = {
        "obs": rng.integers(0, 255, (b, T + 1, 36, 36, 4), np.uint8),
        "action": rng.integers(0, 6, (b, T)).astype(np.int32),
        "reward": rng.standard_normal((b, T)).astype(np.float32),
        "discount": discount,
        "mask": mask,
        "weight": np.linspace(0.5, 1.0, b).astype(np.float32),
        "init_c": rng.standard_normal((b, lstm)).astype(np.float32) * 0.1,
        "init_h": rng.standard_normal((b, lstm)).astype(np.float32) * 0.1,
    }
    ma = sa.train_step(dict(batch))
    mb = sb.train_step(dict(batch))
    np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ma["td_abs"]),
                               np.asarray(mb["td_abs"]),
                               rtol=1e-4, atol=1e-5)
    for xa, xb in zip(jax.tree.leaves(sa.state.params),
                      jax.tree.leaves(sb.state.params)):
        np.testing.assert_allclose(np.asarray(xa), np.asarray(xb),
                                   rtol=1e-5, atol=1e-5)
