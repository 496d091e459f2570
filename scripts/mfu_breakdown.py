"""Decompose the bench MFU (VERDICT r3 next #4): where does the non-MXU
2/3 of the idle train step go?

``bench.py`` reports one MFU number (flops/step × steps/s ÷ peak) with no
attribution. This script splits the idle_uniform step into separately
jitted, separately timed component programs on the REAL chip, and pairs
each with XLA's own cost analysis (flops + bytes accessed) so every
component gets a roofline verdict — compute-bound (time ≈ flops/peak) or
HBM-bound (time ≈ bytes/bandwidth):

- ``fwd``        — one online-net forward (the pure-MXU lower bound)
- ``loss_grad``  — value_and_grad of the DQN loss: online fwd+bwd, target
                   fwd, Double-DQN selection fwd (≈5× fwd FLOPs)
- ``full_hostb`` — the complete train step (loss_grad + Adam + Polyak θ⁻)
                   on a pre-composed device batch (no ring gather)
- ``full_ring``  — the production step: ring gather/stack + full_hostb
                   (bench.py's idle_uniform program)

Deltas attribute wall time: gather = full_ring − full_hostb; optimizer +
target tail = full_hostb − loss_grad. A batch sweep (256→2048) shows how
MFU scales when the fixed per-step costs amortize. Results + analysis are
recorded in PERF.md.

Run on the TPU box:  python scripts/mfu_breakdown.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REPS = 5
HBM_GBPS = {  # public per-chip HBM bandwidth, GB/s (keys match
    #           bench.PEAK_FLOPS — the flops side lives there)
    "TPU v6 lite": 1640.0, "TPU v5 lite": 819.0, "TPU v5": 2765.0,
    "TPU v4": 1228.0, "TPU v3": 900.0,
}


def lookup(table: dict, kind: str):
    for prefix, v in sorted(table.items(), key=lambda kv: -len(kv[0])):
        if kind.startswith(prefix):
            return v
    return None


def _fence(out):
    """Device sync: D2H-read the smallest output leaf (data-depends on
    the whole call chain)."""
    import jax

    leaf = min(jax.tree_util.tree_leaves(out), key=lambda x: x.size)
    return np.asarray(jax.device_get(leaf))


def time_program(fn, args, iters: int, donate_state: bool = False):
    """Median seconds/call of a compiled program, fenced by D2H readback.
    ``donate_state`` reuses the returned state as the next call's first
    arg (train-step style)."""
    out = fn(*args)
    _fence(out)
    if donate_state:
        args = (out[0],) + args[1:]
    rates = []
    for _ in range(REPS):
        a = args
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*a)
            if donate_state:
                a = (out[0],) + a[1:]
        _fence(out)
        rates.append(max(time.perf_counter() - t0, 1e-9) / iters)
        if donate_state:
            args = (out[0],) + args[1:]
    return float(np.median(rates)), args


def cost_of(lowered) -> dict:
    """flops + bytes-accessed from XLA's compiled cost model."""
    try:
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return {"flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0))}
    except Exception:
        return {"flops": 0.0, "bytes": 0.0}


def build(batch: int, capacity: int = 65_536):
    import jax

    from bench import build as bench_build
    from distributed_deep_q_tpu import config as cfg_mod

    solver, replay = bench_build(
        cfg_mod, capacity=capacity, batch=batch, prioritized=False,
        pallas=False, prefill=min(40_000, capacity // 2) if
        jax.devices()[0].platform != "cpu" else 8192)
    return solver, replay


def main() -> None:
    import os

    import jax

    if os.environ.get("DDQ_PLATFORM") == "cpu":
        # jax is already imported, so JAX_PLATFORMS set now would be too
        # late — override via config
        jax.config.update("jax_platforms", "cpu")
        from distributed_deep_q_tpu.parallel.mesh import set_cpu_device_count
        set_cpu_device_count(8)
    import jax.numpy as jnp

    from bench import peak_flops_for

    on_cpu = jax.devices()[0].platform == "cpu"
    iters = 20 if on_cpu else 400
    out: dict = {"device_kind": getattr(jax.devices()[0], "device_kind",
                                        jax.devices()[0].platform)}
    peak = peak_flops_for(jax.devices()[0])
    hbm = lookup(HBM_GBPS, out["device_kind"])

    solver, replay = build(512)
    learner = solver.learner
    state = solver.state
    batch = replay.sample(512)
    batch.pop("_sampled_at", None)
    clean = {k: np.asarray(v) for k, v in batch.items() if k != "index"}

    # -- full_ring: the production idle program ---------------------------
    ring_fn = None
    fs = tuple(solver.config.net.frame_shape)
    if fs not in learner._ring_steps:
        solver.train_step_from_ring(replay.ring, dict(batch))
        state = solver.state
    ring_fn = learner._ring_steps[fs]
    t_ring, (state, *_) = time_program(
        ring_fn, (state, replay.ring, clean), iters, donate_state=True)
    out["full_ring_ms"] = round(1e3 * t_ring, 4)
    out["full_ring_cost"] = cost_of(
        ring_fn.lower(state, replay.ring, clean))

    # -- full_hostb: same step, batch pre-composed on device --------------
    from distributed_deep_q_tpu.replay.device_ring import compose_stacks
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map
    from distributed_deep_q_tpu.parallel.mesh import AXIS_DP

    compose = jax.jit(shard_map(
        lambda ring, oidx, valid: compose_stacks(ring, oidx, valid, fs),
        mesh=learner.mesh, in_specs=(P(AXIS_DP), P(AXIS_DP), P(AXIS_DP)),
        out_specs=P(AXIS_DP), check_vma=False))
    composed = {
        "obs": compose(replay.ring, clean["oidx"], clean["valid"]),
        "next_obs": compose(replay.ring, clean["noidx"], clean["nvalid"]),
        "action": jnp.asarray(clean["action"]),
        "reward": jnp.asarray(clean["reward"]),
        "discount": jnp.asarray(clean["discount"]),
        "weight": jnp.asarray(clean["weight"]),
    }
    composed = {k: jax.device_put(v, NamedSharding(learner.mesh, P(AXIS_DP)))
                for k, v in composed.items()}
    full_fn = learner._train_step
    t_hostb, (state, *_) = time_program(
        full_fn, (state, composed), iters, donate_state=True)
    out["full_hostb_ms"] = round(1e3 * t_hostb, 4)
    out["full_hostb_cost"] = cost_of(full_fn.lower(state, composed))

    # -- loss_grad: fwd+bwd only (no optimizer, no θ⁻ refresh) ------------
    cfg = solver.config.train
    from distributed_deep_q_tpu.ops.losses import bellman_targets, dqn_loss

    def loss_fn(params, target_params, b):
        q = solver.apply_fn(params, b["obs"])
        q_next_t = solver.apply_fn(target_params, b["next_obs"])
        q_next_o = jax.lax.stop_gradient(
            solver.apply_fn(params, b["next_obs"]))
        targets = bellman_targets(b["reward"], b["discount"], q_next_t,
                                  q_next_o, True)
        loss, _ = dqn_loss(q, b["action"], targets, b["weight"],
                           cfg.huber_delta)
        return loss

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    t_grad, _ = time_program(
        grad_fn, (state.params, state.target_params, composed), iters)
    out["loss_grad_ms"] = round(1e3 * t_grad, 4)
    out["loss_grad_cost"] = cost_of(
        grad_fn.lower(state.params, state.target_params, composed))

    # -- fwd: one online forward ------------------------------------------
    fwd_fn = jax.jit(solver.apply_fn)
    t_fwd, _ = time_program(fwd_fn, (state.params, composed["obs"]), iters)
    out["fwd_ms"] = round(1e3 * t_fwd, 4)
    out["fwd_cost"] = cost_of(fwd_fn.lower(state.params, composed["obs"]))

    # -- dispatch floor: tiny program, same runtime -----------------------
    tiny = jnp.zeros(8, jnp.float32)
    tiny_fn = jax.jit(lambda x: x + 1.0)
    t_disp, _ = time_program(tiny_fn, (tiny,), iters)
    out["dispatch_floor_ms"] = round(1e3 * t_disp, 4)

    # -- attribution + rooflines ------------------------------------------
    out["gather_ms"] = round(out["full_ring_ms"] - out["full_hostb_ms"], 4)
    out["opt_tail_ms"] = round(out["full_hostb_ms"] - out["loss_grad_ms"], 4)
    if peak and hbm:
        for key in ("full_ring", "full_hostb", "loss_grad", "fwd"):
            c = out[f"{key}_cost"]
            out[f"{key}_roofline_ms"] = {
                "compute": round(1e3 * c["flops"] / peak, 4),
                "hbm": round(1e3 * c["bytes"] / (hbm * 1e9), 4),
            }
        out["mfu_full_ring"] = round(
            out["full_ring_cost"]["flops"] / peak / t_ring, 4)

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    out["param_count"] = int(n_params)
    del solver, replay, state, composed, clean

    # -- batch sweep: does MFU climb as fixed costs amortize? -------------
    sweep = {}
    for b in ((256,) if on_cpu else (256, 1024, 2048)):
        s, r = build(b)
        bt = r.sample(b)
        bt.pop("_sampled_at", None)
        bt = {k: np.asarray(v) for k, v in bt.items() if k != "index"}
        s.train_step_from_ring(r.ring, dict(bt))
        fn = s.learner._ring_steps[fs]
        t, _ = time_program(fn, (s.state, r.ring, bt), max(iters // 2, 5),
                            donate_state=True)
        c = cost_of(fn.lower(s.state, r.ring, bt))
        sweep[b] = {"ms": round(1e3 * t, 4),
                    "steps_per_s": round(1.0 / t, 1),
                    "mfu": round(c["flops"] / peak / t, 4) if peak else None}
        del s, r
    out["batch_sweep"] = sweep

    print(json.dumps(out))


if __name__ == "__main__":
    main()
