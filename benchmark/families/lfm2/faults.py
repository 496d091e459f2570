#!/usr/bin/env python3
"""Readings of PLANTED FAULTS, for what ``control.py``'s fp8 control does
not separate (it PASSES ``priority_first_max_rel``, ``q_mean_first_rel``
and ``held_share_max_abs``), on the chip at a cell's own sizes:

    python3 -m benchmark.families.lfm2.faults --workload <cell> --seeds 1,2

Per seed the program's first chunk is driven once (``build_checked``); then
for each PLANTED FAULT the reference follows the same chunk with ONE
hyper-parameter wrong, and the family's comparison reads the sound program
against it — the distance a program with that fault would show, from the
other side. A limit belongs under the smallest reading of the fault it is
held against and over the largest sound one (``control.py``'s); a number
no fault lifts clear of the sound readings is printed and not judged
(``check.PRINTED_ONLY``: read here all the same). Prints, per fault,
every compared number's smallest reading over the seeds. PR 31's readings
are in the configuration's ``limits_readings.fault_min``.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# one wrong hyper-parameter of the reference each (from the right ones),
# and the number it was planted to move
FAULTS = {
    # the priority written back is max |TD| alone
    "priority_eta_1": (lambda hp: {"priority_eta": 1.0},
                       "priority_first_max_rel"),
    # the neighbouring group's experts: the held-share counter (and the
    # forward path with it)
    "expert_offset_next": (
        lambda hp: {"expert_offset": hp["expert_offset"]
                    + hp["experts_held"]}, "held_share_max_abs"),
    # a rotary base 100 times smaller: a fault of the forward path alone
    "rope_theta_over_100": (lambda hp: {"rope_theta": hp["rope_theta"]
                                        / 100.0}, "q_mean_first_rel"),
}


def readings(workload: str, seeds, backend: str = "tpu", conf_patch=None,
             prefill=None, faults=FAULTS):
    """``[{"seed": s, fault: {number: value}}]``, the numbers the
    family prints without judging among them."""
    from benchmark import family, program, run
    from benchmark.common import emit

    out = []
    for seed in seeds:
        _, _, files = run.load_cell(workload)
        conf, traffic = files["conf"], files["traffic"]
        if conf_patch:
            conf_patch(conf, traffic)
        cfg = program.make_cfg(conf, seed, backend,
                               traffic.get("overrides", []))
        check = family.load_check(conf)
        solver, replay, stream, mirror, rec = check.build_checked(
            conf, cfg, seed, prefill or traffic["prefill"],
            traffic["episode"])
        del stream, replay, solver
        gc.collect()
        row = {"seed": seed}
        for name, (wrong, _) in faults.items():
            faulty = copy.deepcopy(conf)
            faulty["hparams"].update(wrong(conf["hparams"]))
            got = check.compare(faulty, seed, mirror, rec)
            row[name] = {**got["numbers"], **{
                k: got["print"][k]
                for k in getattr(check, "PRINTED_ONLY", ())}}
            emit(seed=seed, fault=name, numbers=row[name])
        out.append(row)
    return out


def summarize(rs, faults=FAULTS) -> dict:
    return {name: {"planted_for": number, "smallest": {
        k: min(r[name][k] for r in rs) for k in rs[0][name]}}
        for name, (_, number) in faults.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--prefill", type=int, default=None)
    ap.add_argument("--raw", default=None)
    args = ap.parse_args(argv)
    from benchmark import program

    program.place_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("faults: no TPU — nothing was run", file=sys.stderr)
        return 1
    rs = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                  prefill=args.prefill)
    if args.raw:
        os.makedirs(os.path.dirname(args.raw) or ".", exist_ok=True)
        with open(args.raw, "w") as fh:
            json.dump(rs, fh)
    print(json.dumps({"workload": args.workload, **summarize(rs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
