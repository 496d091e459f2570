#!/usr/bin/env python3
"""Readings a configuration's ``limits`` are set from, on the chip at a
cell's own size, several seeds in one process:

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3[,...]

For each seed: the program's first chunks against the reference (the SOUND
reading) and the CONTROL — the reference computed in float8_e4m3, the
nearest precision below the configuration's bfloat16 — held against the
same reference, both through the comparison the configuration names
(``family.py``). No measured window is needed: the comparison reads the
program's first chunks only. Prints, per compared number, the largest
sound reading, the smallest control reading and the limit; the benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(workload: str, seeds, backend: str = "tpu", conf_patch=None,
             prefill=None):
    from benchmark import family, program, run

    out = []
    for seed in seeds:
        _, _, files = run.load_cell(workload)
        conf, traffic = files["conf"], files["traffic"]
        if conf_patch:
            conf_patch(conf, traffic)
        cfg = program.make_cfg(conf, seed, backend,
                               traffic.get("overrides", []))
        solver, replay, stream, mirror, rec = family.load_check(
            conf).build_checked(conf, cfg, seed,
                                prefill or traffic["prefill"],
                                traffic["episode"])
        del stream, replay, solver
        gc.collect()
        sound = family.verdict(conf, seed, mirror, rec, label="sound")
        ctrl = family.verdict(conf, seed, mirror, rec, quant="fp8",
                              label="control")
        out.append({"seed": seed, "sound": sound, "control": ctrl})
    return out


def summarize(rs) -> dict:
    names = rs[0]["sound"]["numbers"]
    table = {}
    for k, (_, limit) in names.items():
        row = {"sound_max": max(r["sound"]["numbers"][k][0] for r in rs),
               "limit": limit}
        if k in rs[0]["control"]["numbers"]:
            row["control_min"] = min(r["control"]["numbers"][k][0]
                                     for r in rs)
        table[k] = row
    return {"numbers": table,
            "sound_all_correct": all(r["sound"]["correct"] for r in rs),
            "control_all_not_correct": not any(r["control"]["correct"]
                                               for r in rs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--prefill", type=int, default=None,
                    help="rows to fill instead of the traffic's (the "
                         "compared numbers do not depend on the fill, and "
                         "a dozen full fills cost minutes)")
    ap.add_argument("--follow-chunks", type=int, default=None,
                    help="follow this many chunks instead of one (to read "
                         "what more steps buy: PERF.md section 2)")
    ap.add_argument("--raw", default=None,
                    help="write every seed's per-step numbers here")
    args = ap.parse_args(argv)
    from benchmark import program

    program.place_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU — nothing was run", file=sys.stderr)
        return 1
    if args.follow_chunks:      # the limits were read at the default
        from benchmark import family, run
        check = family.load_check(run.load_cell(args.workload)[2]["conf"])
        if not hasattr(check, "FOLLOWED_CHUNKS"):
            raise SystemExit(f"{check.__name__} states no FOLLOWED_CHUNKS "
                             "(family.py): --follow-chunks is not for it")
        check.FOLLOWED_CHUNKS = args.follow_chunks
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
