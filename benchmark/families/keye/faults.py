#!/usr/bin/env python3
"""Readings of PLANTED FAULTS of the selection, which ``control.py``'s fp8
control cannot plant (the indexer is float32 on both sides, so the control
selects what the reference selects), on the chip at a cell's own sizes:

    python3 -m benchmark.families.keye.faults --workload <cell> --seeds 1,2

The procedure is ``families/lfm2/faults.py``'s by import: per seed the
program's first chunk is driven once (``build_checked``); then for each
fault the reference follows the same chunk with ONE hyper-parameter wrong
(``hparams.selection``: ``reference/keye.py`` ``select``), and the
family's comparison reads the sound program against it — the distance a
program with that fault would show, from the other side. A limit belongs
under the smallest reading of the fault it is held against and over the
largest sound one. PR 33's readings are in the configuration's
``limits_readings.fault_min``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families.lfm2 import faults as lfm2_faults  # noqa: E402

# the selection computed another way, and the number each was planted to
# move
FAULTS = {
    # the 2 048 MOST RECENT keys: a sliding window in place of the indexer
    "selection_recent": (lambda hp: {"selection": "recent"},
                         "selection_mismatch_share"),
    # ``lax.approx_max_k`` in place of the exact top-k
    "selection_approx": (lambda hp: {"selection": "approx"},
                         "selection_mismatch_share"),
}


def readings(workload: str, seeds, faults=FAULTS, **kw):
    return lfm2_faults.readings(workload, seeds, faults=faults, **kw)


def summarize(rs, faults=FAULTS) -> dict:
    return lfm2_faults.summarize(rs, faults)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--prefill", type=int, default=None)
    ap.add_argument("--raw", default=None)
    ap.add_argument("--only", default=None,
                    help="comma-separated names of FAULTS (all of them)")
    args = ap.parse_args(argv)
    faults = {k: FAULTS[k] for k in args.only.split(",")} \
        if args.only else FAULTS
    from benchmark import program

    program.place_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("faults: no TPU — nothing was run", file=sys.stderr)
        return 1
    rs = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                  faults=faults, prefill=args.prefill)
    if args.raw:
        os.makedirs(os.path.dirname(args.raw) or ".", exist_ok=True)
        with open(args.raw, "w") as fh:
            json.dump(rs, fh)
    print(json.dumps({"workload": args.workload, **summarize(rs, faults)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
