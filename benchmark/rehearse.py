#!/usr/bin/env python3
"""CPU rehearsal of the drivers at a toy size, before chip time is spent.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--workload <cell>] [--trace 1]

Walks the real control flow — data-file loading, prefill and mirror, the
recorder and the reference check, the sink and its raise-to-stop teardown
— with ``backend=cpu`` at the toy sizes the cell's family states (its
comparison module's ``toy``, ``family.py``; for the frame ring: capacity
8 192, batch 32, 36x36 frames, 2 actors, Pallas interpreted), 3 s. A cell
whose family states none is passed over. It prints under ``rehearsal_*``
names, never the contract's line, and always exits non-zero: nothing it
prints is a device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def toy(conf: dict, traffic: dict) -> None:
    """The toy sizes of the cell's own family (a ``conf_patch``)."""
    from benchmark import family

    family.load_check(conf).toy(conf, traffic)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 17)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    from benchmark import family, run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        cells = [w["name"] for w in json.load(fh)["workloads"]]
    cells = [c for c in cells if hasattr(
        family.load_check(run.load_cell(c)[2]["conf"]), "toy")]
    for cell in args.workload or cells:
        ns = argparse.Namespace(workload=cell, seed=args.seed,
                                seconds=args.seconds, trace=args.trace)
        line = run.run_cell(ns, backend="cpu", conf_patch=toy)
        print(json.dumps({f"rehearsal_{k}": v for k, v in line.items()}),
              flush=True)
    return 3


if __name__ == "__main__":
    sys.exit(main())
