#!/usr/bin/env python3
"""CPU rehearsal of both drivers at a toy size, before chip time is spent.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--workload <cell>] [--trace 1]

Walks the real control flow — data-file loading, prefill and mirror, the
recorder and the reference check, the sink and its raise-to-stop teardown
— with ``backend=cpu``, capacity 8 192, batch 32, 36x36 frames, 2 actors,
Pallas interpreted, 3 s. It prints under ``rehearsal_*`` names, never the
contract's line, and always exits non-zero: nothing it prints is a device
number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TOY_OVERRIDES = ["replay.capacity=8192", "replay.batch_size=32",
                 "env.frame_shape=36,36", "net.frame_shape=36,36",
                 "mesh.num_fake_devices=1"]
TOY_HPARAMS = {"capacity": 8192, "batch_size": 32, "frame_shape": [36, 36]}
TOY_TRAFFIC = {"episode": 256, "warmup_steps": 16, "row_every": 40,
               "num_actors": 2, "learn_start": 300, "trace_start_step": 16,
               "trace_num_steps": 16}


TOY_SLACK = 3.0     # 36x36 frames at batch 32 are noisier than any cell


def toy(conf: dict, traffic: dict) -> None:
    """Toy sizes. At batch 32 every configuration takes the plane path, so
    the limits start from those read for the batch-32 configuration; the
    numbers that gather the steps' drifting apart get ``TOY_SLACK`` times
    the room, the first step's forward gap — the one that separates the
    fp8 control — keeps its limit."""
    from benchmark.common import load_json

    limits = load_json("configs", "dqn_b32.json")["limits"]
    conf["limits"] = {k: v if k == "q_mean_first_rel" else TOY_SLACK * v
                      for k, v in limits.items()}
    conf["overrides"] = [*conf["overrides"], *TOY_OVERRIDES]
    conf["hparams"].update(TOY_HPARAMS)
    traffic.update({k: v for k, v in TOY_TRAFFIC.items() if k in traffic})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 17)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    from benchmark import run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        cells = [w["name"] for w in json.load(fh)["workloads"]]
    for cell in args.workload or cells:
        ns = argparse.Namespace(workload=cell, seed=args.seed,
                                seconds=args.seconds, trace=args.trace)
        line = run.run_cell(ns, backend="cpu", conf_patch=toy)
        print(json.dumps({f"rehearsal_{k}": v for k, v in line.items()}),
              flush=True)
    return 3


if __name__ == "__main__":
    sys.exit(main())
