"""Time the fused device-PER program pair at 65k vs 1M ring capacity.

Round-5 diagnostic that drove the flat-ring redesign: before it, the
sample program went 28 → 105 ms/chunk between capacities (tile-amplified
meta element-gathers ~42 ms + frame row-gathers ~44 ms, searchsorted
~2 ms — recorded in PERF.md); after the Pallas row-DMA ring + meta pack
both capacities sit near the small-ring cost (not measured on today's
code). Re-run on the chip to re-attribute if the shape of the programs
changes.

Every timed window ends with a D2H read of a data-dependent scalar.
"""

from __future__ import annotations

import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from bench import build  # noqa: E402
from distributed_deep_q_tpu import config as cfg_mod  # noqa: E402

CHAIN = 32
BATCH = 512


def note(msg: str) -> None:
    print(f"[probe] {msg}", file=sys.stderr, flush=True)


def probe_capacity(cap: int, prefill: int) -> None:
    note(f"build cap={cap}")
    solver, replay = build(cfg_mod, capacity=cap, batch=BATCH,
                           prioritized=True, pallas=False, device_per=True,
                           prefill=prefill)
    solver.train_steps_device_per(replay, chain=CHAIN)
    sample, train = solver.learner._device_per_steps[
        (solver._dp_spec, CHAIN)]
    cursors, sizes = replay.device_inputs()
    betas = np.full(CHAIN, 0.5, np.float32)
    keys = solver._next_sample_keys(replay.num_shards, CHAIN)
    rows = replay.dstate

    def one_sample():
        out = sample(keys, rows.frames, rows.action, rows.reward,
                     rows.done, rows.boundary, rows.prio,
                     np.asarray(cursors), np.asarray(sizes), betas)
        int(jax.device_get(out[2][0, 0]))
        return out

    note("time sample program")
    metas, win, idx = one_sample()
    ts = []
    for _ in range(7):
        del metas, win, idx
        t0 = time.perf_counter()
        metas, win, idx = one_sample()
        ts.append(time.perf_counter() - t0)
    t_sample = float(np.median(ts))

    note("time train program")
    state, prio, maxp = solver.state, rows.prio, rows.maxp
    reps = 4

    def run_train(state, prio, maxp):
        for _ in range(reps):
            state, prio, maxp, m = train(state, metas, win, idx, prio,
                                         maxp)
        int(jax.device_get(state.step))
        return state, prio, maxp

    state, prio, maxp = run_train(state, prio, maxp)
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        state, prio, maxp = run_train(state, prio, maxp)
        ts.append((time.perf_counter() - t0) / reps)
    t_train = float(np.median(ts))
    total = t_sample + t_train
    print(f"cap {cap:>9}: sample {t_sample*1e3:8.2f} ms/chunk | "
          f"train {t_train*1e3:8.2f} ms/chunk | per-step "
          f"{1e3*total/CHAIN:6.3f} ms | {CHAIN/total:7.1f} steps/s",
          flush=True)
    del solver, replay


def main() -> None:
    print(f"device: {jax.devices()[0].device_kind}  chain={CHAIN} "
          f"batch={BATCH}")
    probe_capacity(65_536, 40_000)
    probe_capacity(1_000_000, 60_000)


if __name__ == "__main__":
    main()
