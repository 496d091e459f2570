#!/usr/bin/env python
"""Quickest proof that the system still starts on the chip (ISSUE 21).

    python chip_smoke.py             # one TPU chip — what the driver runs
    python chip_smoke.py --chips 4   # the dp=4 mesh path and its dp=1
                                     # comparison, and no other phase

One chip: drives the main path once through the normal entry point —
``distributed_deep_q_tpu.main.main(["train", "--preset", "breakout",
"--backend", "tpu", "--distributed", ...])``: CPU actor processes →
ReplayFeedServer → columnar staging → the 1M-frame HBM ring → fused
chained sample+train programs → θ published back — at the preset's full
widths (Nature-DQN CNN, 84×84×4 uint8, bf16 torso, Double-DQN, n_step 3,
PER α=0.6, batch 512, fused_chain 8). Then the same with the inference
plane on (actors send observations, the learner process answers from the
chip while it trains), then ``main(["eval", ...])``. Before them: the three
Pallas kernels, Mosaic-compiled, against plain references; the train
program's byte-plane unpack against the bitcast it replaced, at batch 512
(PERF.md §6, PR 32); and a check that ``block_until_ready`` really waits
for the device.

This process is the ONE that holds the chip; the actor children it starts
pin themselves to the CPU. Every line but the last is one JSON object of
things worth knowing; the last line is the contract's
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed on a TPU. No accelerator → non-zero exit, no result line.

``--rehearse-cpu`` runs the same control flow at a toy size on the CPU
(interpret-mode kernels) to find wrong paths before chip time is spent;
it always exits non-zero and never prints ``"ok": true``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import math
import os
import signal
import sys
import tempfile
import time

from distributed_deep_q_tpu.utils.compile_cache import (
    CompileClock, place_compile_cache)

DEADLINE_S = 1150           # the contract allows 1200 s, compilation included
F32_RTOL = 2e-4             # tests/test_solver.py's dp=N vs dp=1 bound
# bf16 keeps 8 significand bits (eps = 2^-8 ≈ 3.9e-3). dp=4 and dp=1 take
# different forward schedules (the per-shard-batch ≤128 gate stacks the
# three Q-forwards) and sum the batch in a different order, so each
# Q-value differs by a few eps; the loss (a 512-sample mean of Huber(TD))
# is held to ~13 eps, and the 3-step weight movement to half its own norm.
BF16_LOSS_RTOL = 5e-2
BF16_DELTA_REL_L2 = 0.5


def emit(**kv) -> None:
    print(json.dumps(kv), flush=True)


class PhaseClock(CompileClock):
    """Splits a phase's wall time into compile vs steady, and counts
    persistent-cache hits, from JAX's own monitoring events."""

    @contextlib.contextmanager
    def phase(self, name: str, **extra):
        c0, h0, m0 = self.compile_s, self.hits, self.misses
        t0 = time.perf_counter()
        out: dict = {}
        yield out
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        emit(phase=name, wall_s=round(wall, 3), compile_s=round(comp, 3),
             steady_s=round(max(wall - comp, 0.0), 3),
             cache_hits=self.hits - h0, cache_misses=self.misses - m0,
             **extra, **out)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def hbm(dev) -> dict:
    s = dev.memory_stats() or {}
    return {k: s.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit")}


def live_bytes() -> int:
    import jax
    gc.collect()
    return int(sum(a.nbytes for a in jax.live_arrays()))


# ---------------------------------------------------------------------------
# phase: kernels — Mosaic-compiled, against plain references
# ---------------------------------------------------------------------------


def phase_kernels(seed: int, interpret: bool) -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_deep_q_tpu.ops.losses import dqn_loss
    from distributed_deep_q_tpu.ops.pallas_kernels import fused_dqn_loss
    from distributed_deep_q_tpu.ops.ring_gather import (
        gather_windows, padded_row_bytes, scatter_rows)

    rng = np.random.default_rng(seed)
    rowb = padded_row_bytes(84 * 84)
    rowp = rowb // 4
    rows, n, w, k = 4096, 512, 7, 64
    ring = rng.integers(-2**31, 2**31 - 1, (rows, rowp), dtype=np.int32)
    idx = rng.integers(0, rows - w, n).astype(np.int32)
    staged = rng.integers(-2**31, 2**31 - 1, (k, rowp), dtype=np.int32)
    sidx = np.tile(np.arange(k, dtype=np.int32), 2)
    didx = rng.permutation(rows)[:2 * k].astype(np.int32)
    compiled: dict = {}

    def run(name, fn, *args):
        jfn = jax.jit(fn)
        if not interpret:
            text = jfn.lower(*args).compile().as_text()
            compiled[name] = "tpu_custom_call" in text
            check(compiled[name], f"{name}: no tpu_custom_call in the "
                                  "compiled program")
        else:
            compiled[name] = False
        return jax.block_until_ready(jfn(*args))

    got = run("gather_windows", functools.partial(
        gather_windows, n=n, w=w, rowb=rowb, interpret=interpret),
        idx, ring.reshape(-1))
    want = np.stack([ring[i:i + w] for i in idx]).reshape(-1)
    check(np.array_equal(np.asarray(got), want), "gather_windows != numpy")

    got = run("scatter_rows", functools.partial(
        scatter_rows, n=2 * k, rowb=rowb, interpret=interpret),
        sidx, didx, staged.reshape(-1), jnp.asarray(ring.reshape(-1)))
    want = ring.copy()
    want[didx] = staged[sidx]
    check(np.array_equal(np.asarray(got).reshape(rows, rowp), want),
          "scatter_rows != numpy")

    b, a = 512, 4
    q = rng.normal(size=(b, a)).astype(np.float32)
    act = rng.integers(0, a, b).astype(np.int32)
    tgt = rng.normal(size=b).astype(np.float32)
    wts = rng.uniform(0.2, 1.0, b).astype(np.float32)

    def pallas(q, act, tgt, wts):
        return jax.value_and_grad(lambda qq: fused_dqn_loss(
            qq, act, tgt, wts, 1.0, interpret), has_aux=True)(q)

    def plain(q, act, tgt, wts):
        return jax.value_and_grad(lambda qq: dqn_loss(
            qq, act, tgt, wts, 1.0), has_aux=True)(q)

    (lp, tdp), gp = run("fused_dqn_loss", pallas, q, act, tgt, wts)
    (lj, tdj), gj = jax.jit(plain)(q, act, tgt, wts)
    np.testing.assert_allclose(lp, lj, rtol=1e-5)
    np.testing.assert_allclose(tdp, tdj, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gp, gj, rtol=1e-5, atol=1e-8)
    return {"interpret": interpret, "mosaic_compiled": compiled,
            "agree_with_reference": True}


# ---------------------------------------------------------------------------
# phase: unpack_planes — the train program's byte-plane unpack against the
# bitcast it replaced, at the published sizes
# ---------------------------------------------------------------------------


def phase_unpack_planes(cfg, seed: int) -> dict:
    """Seeded packed windows (the preset's batch, 84x84, window stack +
    n_step, older frames cut by the mask) through ``window_to_obs`` and
    through the bitcast to uint8: the pixels bit for bit, and through the
    preset's network in its compute dtype Q-values and conv-1 gradients
    that agree to the dtype's rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from distributed_deep_q_tpu.models.qnet import build_qnet, init_params
    from distributed_deep_q_tpu.ops.ring_gather import padded_row_bytes
    from distributed_deep_q_tpu.replay.device_per import (
        stack_rows_to_obs, window_to_obs)

    frame, stack = (84, 84), cfg.net.stack
    first, batch = cfg.replay.n_step, cfg.replay.batch_size
    row_len = frame[0] * frame[1]
    rng = np.random.default_rng(seed)
    rows = np.zeros((batch, stack + first, padded_row_bytes(row_len)),
                    np.uint8)
    rows[..., :row_len] = rng.integers(
        0, 256, (batch, stack + first, row_len), dtype=np.uint8)
    win = jnp.asarray(rows.view(np.int32))
    valid = jnp.asarray(np.triu(np.ones((stack, stack), np.uint8))[
        rng.integers(0, stack, batch)])       # older frames cut, as drawn
    module = build_qnet(cfg.net)
    params = init_params(module, cfg.net, seed)

    def by_bitcast(win):
        pix = lax.bitcast_convert_type(win, jnp.uint8)
        pix = pix.reshape(win.shape[:2] + (-1,))[:, :, :row_len]
        return stack_rows_to_obs(
            pix[:, first:first + stack] * valid[..., None], frame)

    def by_planes(win):
        return window_to_obs(win, first, valid, row_len, frame)

    def q_and_conv1_grads(unpack):
        obs = unpack(win)

        def loss(p):
            q = module.apply({"params": p}, obs)
            return jnp.mean(jnp.square(q)), q
        (_, q), g = jax.value_and_grad(loss, has_aux=True)(params)
        return obs, q, g["torso"]["conv1"]

    got, want = (jax.jit(q_and_conv1_grads, static_argnums=0)(u)
                 for u in (by_planes, by_bitcast))
    check(np.array_equal(np.asarray(got[0]), np.asarray(want[0])),
          "unpack_planes: pixels differ from the bitcast path's")

    def rel_l2(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    # the same pixels into two separately compiled programs: the compiler
    # may lay conv-1's input out differently in each, so the rest is held
    # to a few roundings of the compute dtype (bf16 eps 2^-8), not to bits
    out = {"compute_dtype": cfg.net.compute_dtype, "batch": batch,
           "pixels_equal": True,
           "q_rel_l2": rel_l2(got[1], want[1]),
           "conv1_bias_grad_rel_l2": rel_l2(got[2]["bias"], want[2]["bias"]),
           "conv1_kernel_grad_rel_l2": rel_l2(got[2]["kernel"],
                                              want[2]["kernel"])}
    tol = 2e-2 if cfg.net.compute_dtype == "bfloat16" else 1e-5
    for key in ("q_rel_l2", "conv1_bias_grad_rel_l2",
                "conv1_kernel_grad_rel_l2"):
        check(out[key] < tol, f"unpack_planes: {key} = {out[key]} >= {tol}")
    return out


# ---------------------------------------------------------------------------
# phase: fence — does block_until_ready wait for the device?
# ---------------------------------------------------------------------------


def phase_fence(peak_flops: float) -> dict:
    import jax
    import jax.numpy as jnp

    n, reps = 8192, 16
    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def chain(x):
        y = x
        for _ in range(reps):
            y = (y @ x).astype(jnp.bfloat16)
        # the sum needs EVERY element of every product: a sliced result
        # would let XLA shrink the chain to vector-matrix products
        return y, jnp.sum(y.astype(jnp.float32))

    jax.block_until_ready(chain(x))         # compile + warm
    t0 = time.perf_counter()
    y, total = chain(x)
    t_enqueue = time.perf_counter() - t0
    jax.block_until_ready((y, total))
    t_ready = time.perf_counter() - t0
    float(jax.device_get(total))
    t_read = time.perf_counter() - t0
    flops = reps * 2 * n ** 3
    implied = flops / t_ready
    # a fence that only acknowledged the enqueue would "finish" faster
    # than the chip's peak allows
    check(implied <= peak_flops, "block_until_ready returned before the "
          f"device could have finished ({implied / 1e12:.0f} TFLOP/s "
          f"implied > {peak_flops / 1e12:.0f} peak)")
    return {"enqueue_ms": round(1e3 * t_enqueue, 3),
            "block_until_ready_ms": round(1e3 * t_ready, 3),
            "then_d2h_read_ms": round(1e3 * (t_read - t_ready), 3),
            "implied_tflops": round(implied / 1e12, 1),
            "peak_tflops": peak_flops / 1e12,
            "block_until_ready_fences": True}


# ---------------------------------------------------------------------------
# phases: train / train+inference / eval — through main.main
# ---------------------------------------------------------------------------


def run_main(argv: list[str]) -> dict:
    """``main.main(argv)`` with its one-line JSON summary captured."""
    from distributed_deep_q_tpu.main import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    check(rc == 0, f"main.main({argv[:1]}) returned {rc}")
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def read_rows(path: str) -> list[dict]:
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def phase_train(base: list[str], sets: list[str], tmp: str, name: str,
                inference: bool) -> dict:
    jsonl = os.path.join(tmp, f"{name}.jsonl")
    extra = ["inference.enabled=true"] if inference else []
    summary = run_main(["train", *base, "--distributed", "--metrics-jsonl",
                        jsonl, "--set", *sets, *extra])
    rows = [r for r in read_rows(jsonl) if "loss" in r]
    need = 1 if inference else 2
    check(len(rows) >= need, f"{name}: {len(rows)} metrics rows < {need}")
    for r in rows:
        check(math.isfinite(r["loss"]), f"{name}: loss {r['loss']}")
        check(r["grad_steps_per_s"] > 0, f"{name}: grad_steps_per_s")
    check(summary["env_steps"] > 0, f"{name}: env_steps == 0")
    for key in ("rpc_dispatch_errors", "rpc_checksum_errors",
                "actor_restarts"):
        check(summary[key] == 0, f"{name}: {key} = {summary[key]}")
    check(math.isfinite(summary["eval_return"]), f"{name}: eval_return")
    if inference:
        check(summary["inference_requests"] > 0,
              f"{name}: inference_requests == 0")
    return {"summary": summary,
            "log_rows": [{k: r.get(k) for k in (
                "step", "t", "loss", "q_mean", "grad_steps_per_s",
                "env_steps", "replay_size", "time_dispatch_ms",
                "time_device_ms", "time_step_ms")} for r in rows]}


def phase_census(cfg, capacity: int) -> dict:
    """``profiling.fused_train_flops`` on the main path's train program
    (same batch, chain and widths; a small ring — the train program takes
    the gathered windows, not the ring) and the peak it is divided by."""
    import dataclasses

    import jax

    from distributed_deep_q_tpu.profiling import (
        fused_train_flops, peak_flops_for)
    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay
    from distributed_deep_q_tpu.solver import Solver

    solver = Solver(cfg, obs_dim=84 * 84)
    replay = DevicePERFrameReplay(
        dataclasses.replace(cfg.replay, capacity=capacity), solver.mesh,
        (84, 84), cfg.env.stack, cfg.train.gamma, seed=cfg.train.seed,
        write_chunk=cfg.replay.write_chunk,
        num_streams=cfg.actors.num_actors)
    flops = fused_train_flops(solver, replay, cfg.replay.fused_chain)
    peak = peak_flops_for(jax.devices()[0], backend=cfg.mesh.backend)
    return {"flops_per_grad_step": flops, "peak_flops_bf16": peak,
            "census_ring_capacity": replay.capacity,
            "seconds_per_step_at_peak": (flops / peak) if flops and peak
            else None}


def preset_cfg(rehearse: bool, seed: int, sets: list[str]):
    from distributed_deep_q_tpu.config import PRESETS, apply_overrides

    cfg = PRESETS["breakout"]()
    cfg.mesh.backend = "cpu" if rehearse else "tpu"
    apply_overrides(cfg, sets + [f"train.seed={seed}"])
    cfg.net.num_actions = 4         # SignalAtari's action count
    return cfg


def one_chip(args, clock: PhaseClock, dev) -> None:
    import jax

    from distributed_deep_q_tpu import native
    from distributed_deep_q_tpu.parallel.mesh import (
        make_mesh, pallas_interpret)
    from distributed_deep_q_tpu.profiling import peak_flops_for

    rehearse = args.rehearse_cpu
    backend = "cpu" if rehearse else "tpu"
    actors = max(2, min(4, (os.cpu_count() or 2) // 3))
    # the cuts ISSUE 21 allows, and nothing else: ALE is not installed, so
    # the seeded signal env at 84x84 stands in; actors to what the host's
    # cores carry; learn_start / total_steps / eval episodes shortened.
    # total_steps must pass two log rows (the entry point logs every 500)
    def cuts_for(total_steps: int) -> list[str]:
        toy = ["replay.capacity=8192", "replay.batch_size=8",
               "replay.learn_start=300", "mesh.num_fake_devices=1",
               "net.compute_dtype=float32"] if rehearse else []
        return ["env.kind=signal_atari", "env.id=signal",
                f"actors.num_actors={actors}", "replay.learn_start=4000",
                f"train.total_steps={total_steps}", "train.eval_episodes=2",
                f"train.seed={args.seed}", *toy]

    cuts, infer_cuts = cuts_for(1024), cuts_for(512)
    cfg = preset_cfg(rehearse, args.seed, cuts)
    emit(cuts=cuts, inference_phase_cuts=infer_cuts,
         kept={"preset": "breakout", "net": cfg.net.kind,
               "frame": "84x84x4 uint8",
               "compute_dtype": cfg.net.compute_dtype,
               "double_dqn": cfg.train.double_dqn,
               "n_step": cfg.replay.n_step,
               "priority_alpha": cfg.replay.priority_alpha,
               "batch_size": cfg.replay.batch_size,
               "fused_chain": cfg.replay.fused_chain,
               "write_chunk": cfg.replay.write_chunk,
               "staging_columnar": cfg.replay.staging_columnar,
               "ring_capacity": cfg.replay.capacity})

    mesh = make_mesh(cfg.mesh)      # raises unless every device is a TPU
    interpret = pallas_interpret(mesh)
    check(rehearse or not interpret, "Pallas kernels would be interpreted")
    emit(pallas_interpret=interpret, staging_backend=native.backend())

    with clock.phase("kernels") as out:
        out.update(phase_kernels(args.seed, interpret))
    with clock.phase("unpack_planes") as out:
        out.update(phase_unpack_planes(cfg, args.seed))
    if not rehearse:
        with clock.phase("fence") as out:
            out.update(phase_fence(peak_flops_for(dev, backend="tpu")))

    base = ["--preset", "breakout", "--backend", backend]
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        with clock.phase("train") as out:
            out.update(phase_train(base, cuts, tmp, "train", False))
        leaked = live_bytes()
        emit(after="train", hbm=hbm(dev), live_array_bytes=leaked)
        # the next phase allocates its own ring: the last one must be gone
        check(leaked < 1 << 30, f"{leaked} B of device arrays still live")
        with clock.phase("train_inference") as out:
            out.update(phase_train(base, infer_cuts, tmp, "train_inference",
                                   True))
        leaked = live_bytes()       # collects first, then reads the stats
        emit(after="train_inference", hbm=hbm(dev), live_array_bytes=leaked)
    with clock.phase("eval") as out:
        res = run_main(["eval", *base, "--set", *cuts])
        check(math.isfinite(res["eval_return"]), "eval_return not finite")
        out.update(res)
    with clock.phase("census") as out:
        out.update(phase_census(cfg, 8192 if rehearse else 65_536))
    emit(after="all", hbm=hbm(dev))


# ---------------------------------------------------------------------------
# --chips 4: the dp mesh, and what it is compared with
# ---------------------------------------------------------------------------


def dp_vs_single(cfg, dtype: str, steps: int = 3) -> dict:
    """Explicit-batch sync-DP step on dp=4 vs dp=1 over one of the four
    devices: same seed, same global batch — the chip form of
    tests/test_solver.py::test_multi_device_matches_single_device."""
    import copy

    import numpy as np

    from distributed_deep_q_tpu.solver import Solver

    b = cfg.replay.batch_size
    rng = np.random.default_rng(cfg.train.seed)
    batches = [{
        "obs": rng.integers(0, 255, (b, 84, 84, 4), dtype=np.uint8),
        "next_obs": rng.integers(0, 255, (b, 84, 84, 4), dtype=np.uint8),
        "action": rng.integers(0, cfg.net.num_actions, b).astype(np.int32),
        "reward": rng.normal(size=b).astype(np.float32),
        "discount": np.full(b, 0.99 ** 3, np.float32),
        "weight": rng.uniform(0.2, 1.0, b).astype(np.float32),
    } for _ in range(steps)]

    def run(dp):
        c = copy.deepcopy(cfg)
        c.net.compute_dtype = dtype
        c.mesh.dp = dp
        s = Solver(c, obs_dim=84 * 84)
        w0 = s.get_weights()
        losses = [float(s.train_step(dict(bt))["loss"]) for bt in batches]
        return losses, w0, s.get_weights()

    l4, w0, w4 = run(4)
    l1, _, w1 = run(1)
    loss_rel = max(abs(a - c) / max(abs(c), 1e-12) for a, c in zip(l4, l1))
    d4 = np.concatenate([(a - z).ravel() for a, z in zip(w4, w0)])
    d1 = np.concatenate([(a - z).ravel() for a, z in zip(w1, w0)])
    delta_rel = float(np.linalg.norm(d4 - d1)
                      / max(np.linalg.norm(d1), 1e-30))
    out = {"dtype": dtype, "loss_dp4": l4, "loss_dp1": l1,
           "loss_max_rel": loss_rel, "weight_delta_rel_l2": delta_rel}
    check(all(math.isfinite(x) for x in l4 + l1), f"{dtype}: loss")
    if dtype == "float32":
        out["bound"] = {"loss_rtol": F32_RTOL, "weights_rtol": F32_RTOL,
                        "weights_atol": 1e-6}
        check(loss_rel <= F32_RTOL, f"f32 loss rel {loss_rel}")
        for a, c in zip(w4, w1):
            np.testing.assert_allclose(a, c, rtol=F32_RTOL, atol=1e-6)
    else:
        out["bound"] = {"loss_rtol": BF16_LOSS_RTOL,
                        "weight_delta_rel_l2": BF16_DELTA_REL_L2}
        check(loss_rel <= BF16_LOSS_RTOL, f"bf16 loss rel {loss_rel}")
        check(delta_rel <= BF16_DELTA_REL_L2,
              f"bf16 weight-delta rel L2 {delta_rel}")
    return out


def sharded_ring(cfg, chunks: int = 3) -> dict:
    """A few chained chunks of the fused sharded-ring path on dp=4, fed
    through add_batch/flush — and where everything was PLACED."""
    import copy

    import numpy as np

    from distributed_deep_q_tpu.profiling import compile_fused_train
    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay
    from distributed_deep_q_tpu.solver import Solver

    c = copy.deepcopy(cfg)
    c.mesh.dp = 4
    solver = Solver(c, obs_dim=84 * 84)
    streams = 4
    replay = DevicePERFrameReplay(
        c.replay, solver.mesh, (84, 84), c.env.stack, c.train.gamma,
        seed=c.train.seed, write_chunk=c.replay.write_chunk,
        num_streams=streams)
    rng = np.random.default_rng(c.train.seed)
    n = 512
    for rnd in range(4):            # 4 rounds x 4 streams x 512 rows
        for s in range(streams):
            done = np.zeros(n, bool)
            done[-1] = True         # advance the stream's slot cycle
            replay.add_batch({
                "frame": rng.integers(0, 255, (n, 84, 84), dtype=np.uint8),
                "action": rng.integers(0, 4, n).astype(np.int32),
                "reward": rng.normal(size=n).astype(np.float32),
                "done": done}, stream=s)
    replay.flush()
    check(replay.ready(1), "sharded ring not sampleable on every shard")
    chain = c.replay.fused_chain
    losses = []
    for _ in range(chunks):
        m = solver.train_steps_device_per(replay, chain=chain)
        losses += [float(x) for x in np.asarray(m["loss"])]
    check(all(math.isfinite(x) for x in losses), f"loss {losses}")

    ring = replay.dstate.frames
    shards = ring.addressable_shards
    devs = {s.device.id for s in shards}
    check(len(shards) == 4 and len(devs) == 4,
          f"ring shards on devices {sorted(devs)}")
    quarter = ring.nbytes // 4
    in_use = {}
    for s in shards:
        check(s.data.nbytes == quarter,
              f"shard on device {s.device.id}: {s.data.nbytes} != {quarter}")
        stats = s.device.memory_stats()
        if stats is not None:       # the CPU rehearsal reports none
            in_use[s.device.id] = stats["bytes_in_use"]
            check(in_use[s.device.id] > quarter,
                  f"device {s.device.id} holds {in_use[s.device.id]} B "
                  f"<= its ring shard {quarter} B")
    text = compile_fused_train(solver, replay, chain).as_text()
    check("all-reduce" in text, "no all-reduce in the dp=4 train program")
    return {"ring_capacity": replay.capacity, "ring_bytes": ring.nbytes,
            "shard_bytes": quarter, "shard_devices": sorted(devs),
            "bytes_in_use_per_device": in_use,
            "all_reduce_ops_in_train": text.count("all-reduce("),
            "grad_steps": len(losses), "loss_first": losses[0],
            "loss_last": losses[-1]}


def four_chips(args, clock: PhaseClock) -> None:
    rehearse = args.rehearse_cpu
    sets = []
    if rehearse:
        sets = ["replay.capacity=16384", "replay.batch_size=64",
                "mesh.num_fake_devices=4"]
    cfg = preset_cfg(rehearse, args.seed, sets)
    emit(chips=4, cuts=sets, batch_size=cfg.replay.batch_size,
         ring_capacity=cfg.replay.capacity,
         fused_chain=cfg.replay.fused_chain)
    with clock.phase("dp4_vs_dp1_f32") as out:
        out.update(dp_vs_single(cfg, "float32"))
    with clock.phase("dp4_vs_dp1_bf16") as out:
        out.update(dp_vs_single(cfg, "bfloat16"))
    with clock.phase("dp4_sharded_ring") as out:
        out.update(sharded_ring(cfg))


# ---------------------------------------------------------------------------


def _deadline(signum, frame):
    raise TimeoutError(f"chip_smoke exceeded its {DEADLINE_S} s deadline")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy-size control-flow rehearsal on the CPU; "
                         "always exits non-zero")
    args = ap.parse_args(argv)
    signal.signal(signal.SIGALRM, _deadline)
    signal.alarm(DEADLINE_S)

    # before first backend use; JAX_COMPILATION_CACHE_DIR wins when set
    cache_dir = place_compile_cache()

    import jax
    import jaxlib

    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.chips)
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — reporting only
        libtpu = None
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    # no accelerator: say so on stderr only — no line on stdout at all
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke: JAX found platform {dev.platform!r}, not a TPU "
              "— nothing was run", file=sys.stderr)
        return 1
    if len(devs) != args.chips and not args.rehearse_cpu:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 1
    emit(jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
         device=device, argv=sys.argv[1:])
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    emit(compile_cache_dir=cache_dir, entries_at_start=entries,
         from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))

    clock = PhaseClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, clock)
    else:
        one_chip(args, clock, dev)
    emit(total_wall_s=round(time.perf_counter() - t0, 1),
         compile_s=round(clock.compile_s, 1), cache_hits=clock.hits,
         cache_misses=clock.misses, compile_cache_dir=cache_dir,
         entries_at_end=len(os.listdir(cache_dir))
         if os.path.isdir(cache_dir) else 0)
    signal.alarm(0)
    if args.rehearse_cpu:
        emit(ok=False, rehearsal=True, device=device)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
