#!/usr/bin/env python3
"""Readings of PLANTED FAULTS of what this family adds (the gate a head,
the full layers' partial rotary under YaRN, the 512-key window), which
``control.py``'s fp8 control does not plant, on the chip at a cell's own
sizes:

    python3 -m benchmark.families.laguna.faults --workload <cell> \
        --seeds 1,2

The procedure is ``families/lfm2/faults.py``'s by import: per seed the
program's first chunk is driven once (``build_checked``); then for each
fault the reference follows the same chunk with ONE thing wrong
(``reference/laguna.py``: ``hparams.fault`` or a plain key), and the
family's comparison reads the sound program against it — the distance a
program with that fault would show, from the other side. Each must read
``correct: false``: a limit belongs under the smallest reading of the
fault it is held against and over the largest sound one. PR 40's readings
are in the configuration's ``limits_readings.fault_min``.
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


def full_rope(hp: dict, **wrong) -> dict:
    """``rope_parameters`` with the full layers' kind changed."""
    rp = hp["rope_parameters"]
    return {"rope_parameters": {
        **rp, "full_attention": {**rp["full_attention"], **wrong}}}


# one thing wrong in the reference each, and a number it was planted to
# move
FAULTS = {
    # the gate left out: gamma = 1 on every head
    "no_gate": (lambda hp: {"fault": "no_gate"}, "moment_first_worst_leaf"),
    # the rotary a program without this family's mechanisms would run on
    # the full layers: all 128 columns at their base, no YaRN
    "full_rope_whole_head_no_yarn": (
        lambda hp: full_rope(hp, rope_type="default",
                             partial_rotary_factor=1.0),
        "moment_first_worst_leaf"),
    # YaRN's frequencies without its factor on cos and sin
    "attention_factor_1": (lambda hp: full_rope(hp, attention_factor=1.0),
                           "loss_first_rel"),
    # a window of twice the published keys
    "sliding_window_x2": (
        lambda hp: {"sliding_window": 2 * hp["sliding_window"]},
        "moment_first_worst_leaf"),
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
