#!/usr/bin/env python3
"""Readings of PLANTED FAULTS of what this family adds (the gated norm's
groups, which group a state-space head reads, the convolution's bias, the
experts' activation, the gates' scale), which ``control.py``'s fp8 control
does not plant, on the chip at a cell's own sizes:

    python3 -m benchmark.families.nemotron.faults --workload <cell> \
        --seeds 1,2

The procedure is ``families/lfm2/faults.py``'s by import: per seed the
program's first chunk is driven once (``build_checked``); then for each
fault the reference follows the same chunk with ONE hyper-parameter wrong
(``reference/nemotron.py``: ``hparams.fault`` or a plain key), and the
family's comparison reads the sound program against it — the distance a
program with that fault would show, from the other side. Each must read
``correct: false``: a limit belongs under the smallest reading of the
fault it is held against and over the largest sound one. PR 48's readings
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

# one wrong hyper-parameter of the reference each, and a number it was
# planted to move
FAULTS = {
    # the gated RMSNorm over all d_inner channels as ONE group (8 published)
    "gate_norm_whole": (lambda hp: {"fault": "gate_norm_whole"},
                        "loss_first_rel"),
    # head j reads the B and C of group j % G where the model reads j // 8
    "head_group_mod": (lambda hp: {"fault": "head_group_mod"},
                       "moment_first_worst_leaf"),
    # the causal convolution without its bias (use_conv_bias true)
    "no_conv_bias": (lambda hp: {"fault": "no_conv_bias"},
                     "loss_first_rel"),
    # relu where the model squares it (mlp_hidden_act relu2)
    "mlp_act_relu": (lambda hp: {"mlp_hidden_act": "relu"},
                     "moment_first_worst_leaf"),
    # the renormalised gates not scaled (2.5 published)
    "routed_scaling_factor_1": (lambda hp: {"routed_scaling_factor": 1.0},
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
    verdicts = {name: [family.judge(conf, {
        k: v for k, v in r[name].items()
        if k not in getattr(family.load_check(conf), "PRINTED_ONLY", ())})[0]
        for r in rs] for name in faults}
    if args.raw:
        os.makedirs(os.path.dirname(args.raw) or ".", exist_ok=True)
        with open(args.raw, "w") as fh:
            json.dump(rs, fh)
    print(json.dumps({"workload": args.workload, "correct": verdicts,
                      **summarize(rs, faults)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
