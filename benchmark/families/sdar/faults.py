#!/usr/bin/env python3
"""Readings of PLANTED FAULTS of what this family adds (the three-part
block mask, the positions the two copies share, the block-to-block n-step
target), which ``control.py``'s fp8 control does not plant, on the chip at
a cell's own sizes:

    python3 -m benchmark.families.sdar.faults --workload <cell> --seeds 1,2

The procedure is ``families/lfm2/faults.py``'s by import: per seed the
program's first chunk is driven once (``build_checked``); then for each
fault the reference follows the same chunk with ONE thing wrong
(``reference/sdar.py``: ``hparams.fault``), and the family's comparison
reads the sound program against it — the distance a program with that
fault would show, from the other side. Each must read ``correct: false``
on every seed. PR 44's readings are in the configuration's
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

# one thing wrong in the reference each, and a number it was planted to
# move
FAULTS = {
    # (a) the program without the mechanism: plain causal attention over
    # the packed rows' positions
    "causal": (lambda hp: {"fault": "causal"}, "loss_first_rel"),
    # (b) a noised row also sees the clean rows of its OWN block: the
    # answer leaks (the classic fault of this layout)
    "own_block_leak": (lambda hp: {"fault": "own_block_leak"},
                       "q_sa_first_early_max_rel"),
    # (c) the noised copy's positions continue after the clean copy's
    "positions_continue": (lambda hp: {"fault": "positions_continue"},
                           "moment_first_worst_leaf"),
    # (d) one-step targets, bootstrapped at the next row
    "one_step_targets": (lambda hp: {"fault": "one_step_targets"},
                         "loss_first_rel"),
    # (e) the span's discount is one step's
    "gamma_one_step": (lambda hp: {"fault": "gamma_one_step"},
                       "loss_first_rel"),
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
    from benchmark import family, program, run

    program.place_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("faults: no TPU — nothing was run", file=sys.stderr)
        return 1
    rs = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                  faults=faults, prefill=args.prefill)
    conf = run.load_cell(args.workload)[2]["conf"]
    verdicts = {name: [family.judge(conf, r[name])[0] for r in rs]
                for name in faults}
    if args.raw:
        os.makedirs(os.path.dirname(args.raw) or ".", exist_ok=True)
        with open(args.raw, "w") as fh:
            json.dump(rs, fh)
    print(json.dumps({"workload": args.workload, "correct": verdicts,
                      **summarize(rs, faults)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
